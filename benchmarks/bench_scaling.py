"""Communication-vs-network-size curve (paper Fig. 3 analogue).

Measures — via :mod:`repro.dist.commstats`, i.e. by counting the collectives
each compiled plan actually traces to — the messages per application of
Phi~ / Phi~* / Phi~*Phi~ on graphs of growing size, and compares them
against the paper's closed forms (2K|E| / 2K|E| / 4K|E|, Section IV-B/C).
The acceptance gate is that the measured count stays within 10% of the
prediction at every size; a faithful Algorithm 1 implementation lands on
it exactly, and ``--check`` tightens the gate to *exact* equality plus a
bytes-per-round == wire-model assert.

Two graph families:

* ``--graph sensor`` (default) — the banded spatially-sorted sensor graphs
  the ring partition handles, N in the hundreds (dense P).
* ``--graph community`` — synthetic community graphs at N up to 1e6,
  sharded by the edge-cut `GeneralPartition` (``--partition general``).
  P stays a CSR closure end to end (never densified) and the measurement
  is trace-only (`jax.make_jaxpr`), so the N=1e6 point needs no
  million-vertex execution.

Also reports the device-level byte curve of the sharded backends: the
boundary-rows-only exchange payload per round (the systems-level payoff of
halo-aware tiling — boundary-proportional, not N-proportional).

    PYTHONPATH=src python -m benchmarks.bench_scaling [--json-dir DIR]
        [--backend pallas_halo,halo] [--sizes 150,300,600] [--shards 8]
        [--graph sensor|community] [--partition banded|general]
        [--block 8x8] [--check]

Measurement needs >= 2 mesh shards (1-shard plans skip collectives); when
the current process has a single device the module re-execs itself in a
subprocess with forced host devices, so it works from `benchmarks.run`
and standalone alike.
"""
import argparse
import os
import subprocess
import sys

DEFAULT_SIZES = (150, 300, 600)
DEFAULT_COMMUNITY_SIZES = (10_000, 100_000, 1_000_000)
DEFAULT_BACKENDS = ("pallas_halo", "halo")
DEFAULT_SHARDS = 8


def _auto_block(n):
    """Block-ELL tile for the general partition: the lane-wide (8, 128)
    column block until the per-shard dense-column padding starts to bite,
    then (8, 8) so million-vertex Block-ELL storage stays O(nnz)."""
    return (8, 128) if n <= 20_000 else (8, 8)


def _build_point(graph, n, n_shards, K, J, partition, block, seed=0):
    """One curve point: (op, E, partition-or-None, graph metadata)."""
    from repro.core.wavelets import sgwt_multipliers
    from repro.dist import GraphOperator
    from repro.dist.partition import (community_graph_csr, csr_matvec_fn,
                                      partition_general)

    if graph == "community":
        if partition != "general":
            raise SystemExit(
                "--graph community needs --partition general: the banded "
                "ring partition only covers bandwidth-limited graphs")
        csr, meta = community_graph_csr(n, seed=seed)
        parts = partition_general(csr, n_shards,
                                  block=block or _auto_block(n))
        op = GraphOperator(P=csr_matvec_fn(csr),
                           multipliers=sgwt_multipliers(meta["lmax"], J),
                           lmax=meta["lmax"], K=K)
        return op, csr.n_edges, parts, {"graph": "community",
                                        "edge_cut": parts.edge_cut}

    from .common import seeded_sensor_graph

    gs, _ = seeded_sensor_graph(n, sort=True)
    lmax = gs.lambda_max_bound()
    op = GraphOperator(P=gs.laplacian(),
                       multipliers=sgwt_multipliers(lmax, J),
                       lmax=lmax, K=K)
    parts = None
    if partition == "general":
        parts = partition_general(gs.laplacian(), n_shards,
                                  block=block or _auto_block(n))
    return op, gs.n_edges, parts, {"graph": "sensor"}


def _measure(backends, sizes, n_shards, json_dir, K=15, J=3,
             graph="sensor", partition="banded", block=None, check=False):
    import jax

    from repro.dist import verify_message_scaling

    from .common import row, write_json

    mesh = jax.make_mesh((n_shards,), ("graph",))
    curve = []
    for n in sizes:
        op, E, parts, meta = _build_point(graph, n, n_shards, K, J,
                                          partition, block)
        point = {"n": n, "E": E, "K": K, "eta": op.eta,
                 "partition": partition, **meta,
                 "predicted": op.message_counts(E), "backends": {}}
        for backend in backends:
            if parts is not None:
                plan = op.plan(backend, mesh=mesh, partition=parts)
            else:
                plan = op.plan(backend, mesh=mesh, allow_leak=True)
            v = verify_message_scaling(plan, E, n=n)
            apply_stats = v["stats"]["apply"]
            rec = {
                "measured": v["measured"],
                "rel_dev": v["rel_dev"],
                "bytes_per_apply": apply_stats["total_bytes"],
                "rounds_per_apply": apply_stats["exchange_rounds"],
                "bytes_per_round": (apply_stats["bytes_per_shard"]
                                    / apply_stats["exchange_rounds"]),
                "plan_info": {k: val for k, val in plan.info.items()
                              if isinstance(val, (int, float, str))},
            }
            point["backends"][backend] = rec
            row(f"scaling_{graph}_{backend}_N{n}", 0.0,
                f"E={E};measured_apply={v['measured']['apply']};"
                f"predicted_apply={v['predicted']['apply']};"
                f"max_rel_dev={v['max_rel_dev']:.3f};"
                f"bytes_per_round={rec['bytes_per_round']:.0f}")
            assert v["max_rel_dev"] <= 0.10, (
                f"{backend} N={n}: measured messages deviate "
                f">10% from 2K|E| ({v['rel_dev']})")
            if check:
                # Exact-equality gates (the ISSUE's acceptance bar): a
                # faithful Algorithm 1 lands on 2K|E| exactly, and each
                # round ships exactly the boundary tiles' wire bytes —
                # boundary-proportional, never N-proportional.
                assert v["max_rel_dev"] == 0.0, (
                    f"{backend} N={n}: measured != 2K|E| exactly "
                    f"({v['measured']} vs {v['predicted']})")
                if parts is not None:
                    dt = plan.info.get("exchange_dtype", "f32")
                    want = parts.wire_bytes_per_round(dt)
                    got = rec["bytes_per_round"]
                    assert got == want, (
                        f"{backend} N={n}: bytes/round {got} != wire "
                        f"model {want} (boundary {parts.halo} rows x "
                        f"{dt})")
        curve.append(point)

    write_json(json_dir, f"bench_scaling_{graph}", {
        "bench": "scaling",
        "graph": graph,
        "partition": partition,
        "n_shards": n_shards,
        "sizes": list(sizes),
        "backends": list(backends),
        "curve": curve,
    })
    return curve


def run(backends=None, json_dir=".", sizes=None, n_shards=DEFAULT_SHARDS,
        graph="sensor", partition="banded", block=None, check=False):
    """Entry point used by `benchmarks.run`.

    Spawns a forced-host-device subprocess when this process cannot build
    an `n_shards`-wide mesh (collectives vanish on 1-shard meshes, so the
    measurement would be vacuous).
    """
    if backends is None:
        backends = ("pallas_halo",) if graph == "community" \
            else DEFAULT_BACKENDS
    backends = tuple(backends)
    if sizes is None:
        sizes = DEFAULT_COMMUNITY_SIZES if graph == "community" \
            else DEFAULT_SIZES
    sizes = tuple(sizes)

    import jax

    if len(jax.devices()) >= n_shards:
        return _measure(backends, sizes, n_shards, json_dir,
                        graph=graph, partition=partition, block=block,
                        check=check)

    env = dict(os.environ)
    # a CPU count run: the child must never contend for this process's chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_shards} "
        + env.get("XLA_FLAGS", ""))
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env["PYTHONPATH"] = (src + os.pathsep + root + os.pathsep
                         + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "benchmarks.bench_scaling",
           "--json-dir", json_dir, "--backend", ",".join(backends),
           "--sizes", ",".join(str(s) for s in sizes),
           "--shards", str(n_shards),
           "--graph", graph, "--partition", partition]
    if block is not None:
        cmd += ["--block", f"{block[0]}x{block[1]}"]
    if check:
        cmd += ["--check"]
    proc = subprocess.run(cmd, env=env, cwd=root)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_scaling subprocess failed (rc={proc.returncode})")
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json-dir", default=".")
    ap.add_argument("--backend", default=None,
                    help="comma list; default pallas_halo,halo (sensor) "
                         "or pallas_halo (community)")
    ap.add_argument("--sizes", default=None,
                    help="comma list; default 150,300,600 (sensor) or "
                         "10000,100000,1000000 (community)")
    ap.add_argument("--shards", type=int, default=DEFAULT_SHARDS)
    ap.add_argument("--graph", choices=("sensor", "community"),
                    default="sensor")
    ap.add_argument("--partition", choices=("banded", "general"),
                    default=None,
                    help="default banded (sensor) / general (community)")
    ap.add_argument("--block", default=None,
                    help="Block-ELL tile RxC for --partition general "
                         "(default: auto by size)")
    ap.add_argument("--check", action="store_true",
                    help="gate measured == 2K|E| EXACTLY and bytes/round "
                         "== the boundary wire model")
    args = ap.parse_args()
    backends = tuple(args.backend.split(",")) if args.backend else None
    sizes = (tuple(int(s) for s in args.sizes.split(","))
             if args.sizes else None)
    partition = args.partition or (
        "general" if args.graph == "community" else "banded")
    block = None
    if args.block:
        r, c = args.block.lower().split("x")
        block = (int(r), int(c))

    import jax

    if len(jax.devices()) >= args.shards:
        print("name,us_per_call,derived")
        _measure(backends or (("pallas_halo",) if args.graph == "community"
                              else DEFAULT_BACKENDS),
                 sizes or (DEFAULT_COMMUNITY_SIZES
                           if args.graph == "community" else DEFAULT_SIZES),
                 args.shards, args.json_dir, graph=args.graph,
                 partition=partition, block=block, check=args.check)
    else:
        run(backends, args.json_dir, sizes, args.shards, graph=args.graph,
            partition=partition, block=block, check=args.check)


if __name__ == "__main__":
    main()

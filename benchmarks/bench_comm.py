"""Communication-scaling table (Sections IV-B, IV-C, VI).

The paper's central systems claim: distributed application costs 2K|E|
messages of length 1 (Phi~ f), 2K|E| of length eta (Phi~* a), 4K|E| of
length 1 (Phi~*Phi~ f), and one lasso ISTA iteration costs 2K|E| x (J+1)
+ 2K|E| — scaling with |E| only, independent of N otherwise. Verified by
counting on random graphs of increasing size, plus the ADMM distributed-
lasso alternative's 2|E| x N(J+1) per iteration for contrast (Section VI).
Also reports the TPU halo-byte analog of the sharded path.

`dtype_sweep` is the compressed-exchange acceptance benchmark: it runs the
sharded backends at every ``exchange_dtype`` on a bandwidth-24 banded
Laplacian (the int8 wire row is ``h + 4`` bytes, so the <= 0.3x ratio only
means anything at realistic halo widths), records measured bytes-per-round
ratios and accuracy vs the dense reference, and writes the repo-root
``BENCH_comm.json``.  ``--check`` gates: rounds stay exactly K for every
dtype (compression must ride the SAME two ppermutes per order), bf16
<= 0.5x and int8 <= 0.3x f32 bytes, and the accuracy ladder
f32 < 1e-5 / bf16 < 5e-3 / int8 <= 10x bf16.

    PYTHONPATH=src python -m benchmarks.bench_comm \
        [--n 512] [--bw 24] [--k 20] [--shards 8] \
        [--backends halo,pallas_halo] [--json-path BENCH_comm.json] \
        [--check] [--legacy]
"""
import argparse
import os
import subprocess
import sys

import jax
import numpy as np

from repro.core.wavelets import sgwt_multipliers
from repro.dist import GraphOperator
from repro.dist.backends import halo as dist

from .common import make_backend_plan, row, seeded_sensor_graph, write_json

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT_JSON = os.path.join(REPO_ROOT, "BENCH_comm.json")
DEFAULT_DTYPES = ("f32", "bf16", "int8")
DEFAULT_DTYPE_BACKENDS = ("halo", "pallas_halo")
DEFAULT_SHARDS = 8


def sweep_backends(backends, json_dir=".", K=20, J=6):
    """Per-backend communication model through the plan API: the paper's
    scalar-message accounting plus each backend's collective-byte model."""
    gs, _ = seeded_sensor_graph(600, sort=True)
    g = gs
    lmax = gs.lambda_max_bound()
    op = GraphOperator(P=gs.laplacian(),
                       multipliers=sgwt_multipliers(lmax, J),
                       lmax=lmax, K=K)
    mc = op.message_counts(g.n_edges)
    for backend in backends:
        plan = make_backend_plan(op, backend)
        bytes_model = {k: v for k, v in plan.info.items()
                       if "bytes" in k or k in ("n_shards", "mesh_axis")}
        # measured collective counts (vacuous on a 1-shard mesh, where the
        # sharded backends skip their ppermutes — see bench_scaling for the
        # forced-multi-device measurement)
        from repro.dist import plan_comm_stats

        measured = {k: s.summary() for k, s in plan_comm_stats(plan).items()}
        row(f"comm_plan_{backend}", 0.0,
            f"E={g.n_edges};apply_msgs={mc['apply_messages']};"
            + ";".join(f"{k}={v}" for k, v in bytes_model.items()))
        write_json(json_dir, f"bench_comm_{backend}", {
            "bench": "comm",
            "backend": backend,
            "n": g.n_vertices,
            "E": g.n_edges,
            "K": K,
            "eta": op.eta,
            "device_count": len(jax.devices()),
            "paper_message_counts": mc,
            "plan_info": dict(plan.info),
            "measured_commstats": measured,
        })


def run(backends=None, json_dir="."):
    if backends:
        sweep_backends(backends, json_dir)
    K, J = 20, 6
    for n in (125, 250, 500, 1000):
        g, _ = seeded_sensor_graph(n)
        E = g.n_edges
        lmax = g.lambda_max_bound()
        op = GraphOperator(P=g.laplacian(),
                             multipliers=sgwt_multipliers(lmax, J),
                             lmax=lmax, K=K)
        mc = op.message_counts(E)
        ista_scalars = (mc["gram_messages"] * 1
                        + mc["adjoint_messages"] * (J + 1))
        admm_scalars = 2 * E * n * (J + 1)  # ADMM lasso [29,30] per iteration
        row(f"comm_N{n}", 0.0,
            f"E={E};apply={mc['apply_messages']};gram={mc['gram_messages']};"
            f"ista_scalars={ista_scalars};admm_scalars={admm_scalars};"
            f"ratio={admm_scalars / max(ista_scalars, 1):.1f}x")

    # sharded halo-byte analog (DESIGN.md §3)
    gs, _ = seeded_sensor_graph(600, sort=True)
    parts, leak = dist.partition_banded(np.asarray(gs.laplacian()), 8)
    row("comm_halo_8shards", 0.0,
        f"leak={leak};bytes_per_apply={dist.halo_bytes_per_apply(parts, K)};"
        f"bytes_per_ista_iter={dist.halo_bytes_per_apply(parts, K, eta=J + 1) + dist.halo_bytes_per_apply(parts, K)}")

    # Chebyshev gossip vs fabric all-reduce traffic model (DESIGN.md §4.1):
    # exact ring consensus needs K = ceil(n/2) rounds x 2 neighbour sends of
    # the gradient (G bytes fp32); ring all-reduce moves ~2G. int8 messages
    # (ref [31] extension) close most of the gap while tolerating link loss.
    from repro.dist import gossip

    for n_dev in (8, 16):
        Kg = len(gossip.consensus_coeffs(n_dev)) - 1
        err = gossip.consensus_error(n_dev, gossip.consensus_coeffs(n_dev))
        fp32 = 2 * Kg            # sends per device, units of G bytes
        int8 = 2 * Kg / 4.0
        row(f"comm_gossip_ring{n_dev}", 0.0,
            f"rounds={Kg};consensus_err={err:.1e};"
            f"gossip_fp32={fp32:.0f}G;gossip_int8={int8:.0f}G;allreduce=2G;"
            f"note=int8 gossip ~ all-reduce parity + straggler tolerance")


def _banded_operator(n, bw, K, seed=0):
    """Banded Laplacian operator + test signal: halo width == bw on both
    sharded backends, wide enough that the int8 scale row is amortized."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    B = np.zeros((n, n), dtype=np.float32)
    for i in range(n):
        lo, hi = max(0, i - bw), min(n, i + bw + 1)
        B[i, lo:hi] = rng.standard_normal(hi - lo) * 0.1
    B = np.abs(B + B.T) / 2
    L = np.diag(B.sum(1)) - B
    lmax = float(2 * B.sum(1).max())
    op = GraphOperator(P=jnp.asarray(L),
                       multipliers=[lambda lam: jnp.exp(-lam)],
                       lmax=lmax, K=K)
    x = jnp.asarray(rng.standard_normal((4, n)).astype(np.float32))
    return op, x


def _dtype_measure(n, bw, K, n_shards, backends, dtypes, json_path, check):
    import json

    import jax.numpy as jnp

    from repro.dist import plan_comm_stats

    op, x = _banded_operator(n, bw, K)
    mesh = jax.make_mesh((n_shards,), ("graph",))
    ref = op.plan("dense").apply(x)
    refmax = float(jnp.abs(ref).max())
    table = {}
    for backend in backends:
        table[backend] = {}
        base_bpr = None
        for dt in dtypes:
            plan = op.plan(backend, mesh=mesh, exchange_dtype=dt)
            st = plan_comm_stats(plan)["apply"]
            if base_bpr is None:      # dtypes start with f32
                base_bpr = st.bytes_per_round
            ratio = st.bytes_per_round / base_bpr
            rel = float(jnp.abs(plan.apply(x) - ref).max()) / refmax
            table[backend][dt] = {
                "exchange_rounds": int(st.exchange_rounds),
                "bytes_per_round": float(st.bytes_per_round),
                "bytes_per_apply": float(st.total_bytes),
                "bytes_ratio_vs_f32": float(ratio),
                "rel_err_vs_dense": rel,
            }
            row(f"comm_dtype_{backend}_{dt}", 0.0,
                f"rounds={st.exchange_rounds};"
                f"bytes_per_round={st.bytes_per_round:.0f};"
                f"ratio_vs_f32={ratio:.3f};rel_err={rel:.2e}")
    payload = {
        "bench": "comm_dtype",
        "n": n, "halo_width": bw, "K": K, "n_shards": n_shards,
        "backends": list(backends),
        "dtypes": list(dtypes),
        "table": table,
    }
    if json_path:
        parent = os.path.dirname(os.path.abspath(json_path))
        os.makedirs(parent, exist_ok=True)
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"# wrote {json_path}", flush=True)
    if check:
        assert bw >= 20, "int8 <= 0.3x gate needs halo width >= 20"
        for backend, per_dt in table.items():
            errs = {dt: e["rel_err_vs_dense"] for dt, e in per_dt.items()}
            for dt, e in per_dt.items():
                assert e["exchange_rounds"] == K, (
                    f"{backend}/{dt}: {e['exchange_rounds']} rounds != K={K}"
                    " — compression must not add exchange rounds")
            assert per_dt["f32"]["bytes_ratio_vs_f32"] == 1.0
            assert per_dt["bf16"]["bytes_ratio_vs_f32"] <= 0.5, (backend,
                                                                 per_dt)
            assert per_dt["int8"]["bytes_ratio_vs_f32"] <= 0.3, (backend,
                                                                 per_dt)
            assert errs["f32"] < 1e-5, (backend, errs)
            assert errs["bf16"] < 5e-3, (backend, errs)
            assert errs["int8"] <= 10 * errs["bf16"], (backend, errs)
        print("# comm dtype gates OK: bytes bf16<=0.5x int8<=0.3x, "
              "rounds==K, accuracy ladder holds", flush=True)
    return payload


def dtype_sweep(n=512, bw=24, K=20, n_shards=DEFAULT_SHARDS, backends=None,
                dtypes=DEFAULT_DTYPES, json_path=DEFAULT_JSON, check=False):
    """Entry point used by `benchmarks.run`.

    Spawns a forced-host-device subprocess when this process cannot build
    an `n_shards`-wide mesh (1-shard plans skip their ppermutes, so the
    byte measurement would be vacuous) — same idiom as bench_scaling.
    """
    backends = tuple(backends or DEFAULT_DTYPE_BACKENDS)
    if len(jax.devices()) >= n_shards:
        return _dtype_measure(n, bw, K, n_shards, backends, dtypes,
                              json_path, check)

    env = dict(os.environ)
    # a CPU count run: the child must never contend for this process's chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_shards} "
        + env.get("XLA_FLAGS", ""))
    src = os.path.join(REPO_ROOT, "src")
    env["PYTHONPATH"] = (src + os.pathsep + REPO_ROOT + os.pathsep
                         + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "benchmarks.bench_comm",
           "--n", str(n), "--bw", str(bw), "--k", str(K),
           "--shards", str(n_shards), "--backends", ",".join(backends),
           "--json-path", json_path or ""]
    if check:
        cmd.append("--check")
    proc = subprocess.run(cmd, env=env, cwd=REPO_ROOT)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_comm dtype subprocess failed (rc={proc.returncode})")
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--bw", type=int, default=24,
                    help="Laplacian coupling bandwidth == halo width")
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--shards", type=int, default=DEFAULT_SHARDS)
    ap.add_argument("--backends", default=",".join(DEFAULT_DTYPE_BACKENDS))
    ap.add_argument("--json-path", default=DEFAULT_JSON,
                    help="output JSON; '' disables writing")
    ap.add_argument("--check", action="store_true",
                    help="fail unless the byte ratios, round counts and "
                    "accuracy ladder hold (see module docstring)")
    ap.add_argument("--legacy", action="store_true",
                    help="also print the paper's scalar-message CSV table")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.legacy:
        run()
    backends = tuple(args.backends.split(","))
    if len(jax.devices()) >= args.shards:
        _dtype_measure(args.n, args.bw, args.k, args.shards, backends,
                       DEFAULT_DTYPES, args.json_path, args.check)
    else:
        dtype_sweep(args.n, args.bw, args.k, args.shards, backends,
                    DEFAULT_DTYPES, args.json_path, args.check)


if __name__ == "__main__":
    main()

"""Graceful degradation under injected link faults (the robustness table).

Two legs, one tracked artifact (repo-root ``BENCH_faults.json``):

**Filter/solve ladder** (8 shards, banded Laplacian, halo exchange):
for every ``exchange_dtype`` (f32 / bf16 / int8+error-feedback), both
degradation policies and drop probability p in {0, 0.01, 0.05, 0.2},
measure (a) the relative error of ``plan.apply`` and (b) the relative
error of a ``plan.solve(..., "jacobi")`` against the same plan's clean
run, plus the measured exchange rounds — which must stay exactly K (the
paper's 2K|E| messages) under every fault configuration, because
injection is receiver-side substitution after the ppermute, never a
retry or an extra round.

The two policies split by workload, and the table records both sides:
on the *forward apply* the Chebyshev iterates oscillate (the shifted
operator has eigenvalues near -1), so re-serving last round's tile
(``hold_last``) is roughly a sign error and ``zero_fill`` wins; on the
*converging Jacobi solve* consecutive iterates approach the fixed point,
the carried tile is nearly current, and ``hold_last`` wins by orders of
magnitude.  The ``--check`` policy gate therefore anchors on the solve
leg (see :func:`check`).

**Serving leg** (virtual clock, deterministic): replay a seeded Poisson
stream through a hardened :class:`repro.serve.ServeEngine` (per-request
deadlines, bounded queue + loadgen retry/backoff) twice — clean, and
with injected stragglers (every k-th dispatch stalls the clock) — and
record p99 latency, goodput (served/sec; expired answers do not count),
and the failure-outcome tallies.

``--check`` gates (CI smoke):
  * p=0 rides the clean plan bitwise (``p0_bitwise_identical``) and
    ``exchange_rounds == K`` for every (dtype, policy, p);
  * apply error is monotone nondecreasing in p (f32, both policies);
  * ``hold_last`` solve error <= ``zero_fill`` solve error at p=0.05
    (f32 — the graceful-degradation claim, on the leg where it holds);
  * serving: every admitted request answered exactly once under
    stragglers, finite p99, and straggler goodput <= clean goodput.

    PYTHONPATH=src python -m benchmarks.bench_faults \
        [--n 256] [--bw 8] [--k 10] [--shards 8] [--solve-iters 12] \
        [--drop-probs 0,0.01,0.05,0.2] [--json-path BENCH_faults.json] \
        [--check]
"""
import argparse
import json
import os
import subprocess
import sys

import jax
import numpy as np

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT_JSON = os.path.join(REPO_ROOT, "BENCH_faults.json")
DEFAULT_PROBS = (0.0, 0.01, 0.05, 0.2)
DEFAULT_DTYPES = ("f32", "bf16", "int8")
DEFAULT_BACKEND = "halo"
DEFAULT_SHARDS = 8
TAU = 0.5


# ---------------------------------------------------------------------------
# Filter/solve ladder
# ---------------------------------------------------------------------------
def _rel_err(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def fault_ladder(n, bw, K, n_shards, backend, dtypes, probs, solve_iters):
    """The (dtype x policy x p) error table on one sharded backend."""
    import jax.numpy as jnp

    from repro.dist import FaultSpec, plan_comm_stats
    from repro.dist.faults import DEGRADATIONS

    from .bench_comm import _banded_operator

    op, x = _banded_operator(n, bw, K)
    mesh = jax.make_mesh((n_shards,), ("graph",))
    y = x[0]
    table = {}
    for dt in dtypes:
        clean = op.plan(backend, mesh=mesh, exchange_dtype=dt)
        apply_ref = np.asarray(clean.apply(x))
        solve_ref = np.asarray(
            clean.solve(y, "jacobi", tau=TAU, n_iters=solve_iters).x)
        table[dt] = {}
        for degr in DEGRADATIONS:
            col = {}
            for p in probs:
                spec = FaultSpec(drop_prob=p, seed=0)
                plan = op.plan(backend, mesh=mesh, exchange_dtype=dt,
                               fault_spec=spec, degradation=degr)
                out = np.asarray(plan.apply(x))
                res = plan.solve(y, "jacobi", tau=TAU, n_iters=solve_iters,
                                 check_every=solve_iters)
                st = plan_comm_stats(plan)["apply"]
                col[f"{p:g}"] = {
                    "apply_rel_err": _rel_err(out, apply_ref),
                    "solve_rel_err": _rel_err(res.x, solve_ref),
                    "solve_residual": (None if res.info["residual"] is None
                                       else float(res.info["residual"])),
                    "solve_diverged": bool(res.info["diverged"]),
                    "exchange_rounds": int(st.exchange_rounds),
                    "p0_bitwise_identical": (
                        bool(np.array_equal(out, apply_ref))
                        if p == 0.0 else None),
                    "fault_key": plan.info["fault_key"],
                }
                print(f"faults,{backend},{dt},{degr},p={p:g},"
                      f"apply={col[f'{p:g}']['apply_rel_err']:.3e},"
                      f"solve={col[f'{p:g}']['solve_rel_err']:.3e},"
                      f"rounds={st.exchange_rounds}", flush=True)
            table[dt][degr] = col
    return {
        "backend": backend, "n": n, "halo_width": bw, "K": K,
        "n_shards": n_shards, "solve_iters": solve_iters, "tau": TAU,
        "drop_probs": [float(p) for p in probs],
        "table": table,
    }


# ---------------------------------------------------------------------------
# Serving leg (virtual clock — deterministic, single device)
# ---------------------------------------------------------------------------
def serving_leg(n, bw, K, n_requests=200, rate=2000.0,
                deadline=0.05, max_queue_depth=32,
                straggle_every=5, straggle_s=0.06, seed=0):
    """Clean vs straggler-injected replay through the hardened engine.

    Stragglers stall the virtual clock by `straggle_s` on every
    `straggle_every`-th dispatch — a deterministic stand-in for a slow
    device holding its whole batch.  Queued requests whose deadline
    passes during a stall complete with ``expired`` error Responses; the
    loadgen retry hook resubmits queue-full rejections.
    """
    from repro.serve import (RetryPolicy, ServeEngine, VirtualClock,
                             poisson_arrivals, replay_virtual)

    from .bench_comm import _banded_operator

    op, _x = _banded_operator(n, bw, K)
    events = poisson_arrivals(rate=rate, n_requests=n_requests, seed=seed)
    out = {}
    for label, straggle in (("clean", False), ("stragglers", True)):
        eng = ServeEngine(op.plan("dense"), buckets=(1, 8, 32),
                          max_wait=0.002, clock=VirtualClock(),
                          sync_results=False,
                          max_queue_depth=max_queue_depth)
        if straggle:
            orig, count = eng._callable, {"i": 0}

            def straggling(key, group, _orig=orig, _count=count,
                           _clock=eng.clock):
                fn = _orig(key, group)

                def wrapped(batch):
                    _count["i"] += 1
                    if _count["i"] % straggle_every == 0:
                        _clock.advance(straggle_s)
                    return fn(batch)

                return wrapped

            eng._callable = straggling
        futures = replay_virtual(eng, events, n=n, deadline=deadline,
                                 retry=RetryPolicy())
        s = eng.metrics.summary()
        out[label] = {
            "n_events": len(events),
            "all_futures_answered": all(f.done() for f in futures.values()),
            "p99_latency_ms": s["latency_ms"]["p99"],
            "goodput_signals_per_sec": s["signals_per_sec"],
            "n_served": s["n_served"], "n_failed": s["n_failed"],
            "n_expired": s["n_expired"], "n_rejected": s["n_rejected"],
            "served_exactly_once": s["served_exactly_once"],
        }
        print(f"faults,serving,{label},p99_ms={s['latency_ms']['p99']:.3f},"
              f"goodput={s['signals_per_sec']:.0f},"
              f"expired={s['n_expired']},rejected={s['n_rejected']}",
              flush=True)
    return {
        "n_requests": n_requests, "rate": rate, "deadline_s": deadline,
        "max_queue_depth": max_queue_depth,
        "straggle_every": straggle_every, "straggle_s": straggle_s,
        "runs": out,
    }


# ---------------------------------------------------------------------------
# Gates
# ---------------------------------------------------------------------------
def check(payload) -> None:
    probs = payload["ladder"]["drop_probs"]
    K = payload["ladder"]["K"]
    for dt, per_degr in payload["ladder"]["table"].items():
        for degr, col in per_degr.items():
            for p, e in col.items():
                assert e["exchange_rounds"] == K, (
                    f"{dt}/{degr}/p={p}: {e['exchange_rounds']} rounds "
                    f"!= K={K} — faults must not add exchange rounds")
            p0 = col["0"]
            assert p0["p0_bitwise_identical"], (
                f"{dt}/{degr}: p=0 is not the bitwise clean path")
            assert p0["fault_key"] == "none", (dt, degr, p0["fault_key"])
    for degr in ("zero_fill", "hold_last"):
        errs = [payload["ladder"]["table"]["f32"][degr][f"{p:g}"]
                ["apply_rel_err"] for p in probs]
        assert all(a <= b + 1e-12 for a, b in zip(errs, errs[1:])), (
            f"f32/{degr}: apply error not monotone in p: {errs}")
        assert errs[-1] > 0, (degr, errs)
    hl = payload["ladder"]["table"]["f32"]["hold_last"]["0.05"]
    zf = payload["ladder"]["table"]["f32"]["zero_fill"]["0.05"]
    assert hl["solve_rel_err"] <= zf["solve_rel_err"], (
        "hold_last must beat zero_fill on the converging solve at p=0.05: "
        f"hold_last={hl['solve_rel_err']:.3e} "
        f"zero_fill={zf['solve_rel_err']:.3e}")
    for label, run in payload["serving"]["runs"].items():
        assert run["served_exactly_once"], (label, run)
        assert run["all_futures_answered"], (label, run)
        assert run["p99_latency_ms"] is not None and np.isfinite(
            run["p99_latency_ms"]), (label, run)
    clean = payload["serving"]["runs"]["clean"]
    strag = payload["serving"]["runs"]["stragglers"]
    assert (strag["goodput_signals_per_sec"]
            <= clean["goodput_signals_per_sec"] + 1e-9), (clean, strag)
    print("# fault gates OK: rounds==K everywhere, p=0 bitwise clean, "
          "apply error monotone in p, hold_last<=zero_fill on the solve "
          "at p=0.05, serving exactly-once under stragglers", flush=True)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------
def _measure(n, bw, K, n_shards, backend, probs, solve_iters, json_path,
             do_check):
    payload = {
        "bench": "faults",
        "ladder": fault_ladder(n, bw, K, n_shards, backend,
                               DEFAULT_DTYPES, probs, solve_iters),
        "serving": serving_leg(n, bw, K),
    }
    if json_path:
        os.makedirs(os.path.dirname(os.path.abspath(json_path)),
                    exist_ok=True)
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"# wrote {json_path}", flush=True)
    if do_check:
        check(payload)
    return payload


def run(n=256, bw=8, K=10, n_shards=DEFAULT_SHARDS, backend=DEFAULT_BACKEND,
        probs=DEFAULT_PROBS, solve_iters=12, json_path=DEFAULT_JSON,
        do_check=False):
    """Entry point used by `benchmarks.run`.

    Spawns a forced-host-device subprocess when this process cannot build
    an `n_shards`-wide mesh (same idiom as bench_comm.dtype_sweep —
    1-shard plans skip their ppermutes, so fault injection is vacuous).
    """
    if len(jax.devices()) >= n_shards:
        return _measure(n, bw, K, n_shards, backend, probs, solve_iters,
                        json_path, do_check)
    env = dict(os.environ)
    # a CPU count run: the child must never contend for this process's chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_shards} "
        + env.get("XLA_FLAGS", ""))
    src = os.path.join(REPO_ROOT, "src")
    env["PYTHONPATH"] = (src + os.pathsep + REPO_ROOT + os.pathsep
                         + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "benchmarks.bench_faults",
           "--n", str(n), "--bw", str(bw), "--k", str(K),
           "--shards", str(n_shards), "--backend", backend,
           "--solve-iters", str(solve_iters),
           "--drop-probs", ",".join(f"{p:g}" for p in probs),
           "--json-path", json_path or ""]
    if do_check:
        cmd.append("--check")
    proc = subprocess.run(cmd, env=env, cwd=REPO_ROOT)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_faults subprocess failed (rc={proc.returncode})")
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--bw", type=int, default=8,
                    help="Laplacian coupling bandwidth == halo width")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--shards", type=int, default=DEFAULT_SHARDS)
    ap.add_argument("--backend", default=DEFAULT_BACKEND)
    ap.add_argument("--solve-iters", type=int, default=12)
    ap.add_argument("--drop-probs", default=",".join(
        f"{p:g}" for p in DEFAULT_PROBS))
    ap.add_argument("--json-path", default=DEFAULT_JSON,
                    help="output JSON; '' disables writing")
    ap.add_argument("--check", action="store_true",
                    help="fail unless the degradation gates hold "
                    "(see module docstring)")
    args = ap.parse_args()
    probs = tuple(float(p) for p in args.drop_probs.split(","))
    run(args.n, args.bw, args.k, args.shards, args.backend, probs,
        args.solve_iters, args.json_path or None, args.check)


if __name__ == "__main__":
    main()

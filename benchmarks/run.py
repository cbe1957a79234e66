"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]
    PYTHONPATH=src python -m benchmarks.run --only kernels,comm,scaling \
        --backend dense,pallas,halo,pallas_halo,allgather [--json-dir bench-out]

Prints ``name,us_per_call,derived`` CSV rows.  --full uses paper-scale trial
counts (slow on CPU); the default is a reduced but statistically meaningful
configuration.  --backend sweeps bench_kernels/bench_comm through the
`GraphOperator.plan()` API for each named backend and writes one comparable
JSON file per backend to --json-dir.  The `kernels` benchmark additionally
runs the single-launch-sweep microbenchmark (`bench_kernels.sweep_vs_step`)
and writes the repo-root ``BENCH_kernels.json`` with its
``speedup_sweep_vs_step`` gate value.  The `scaling` benchmark
(bench_scaling) measures messages-per-apply with repro.dist.commstats and
checks them against the paper's 2K|E| closed form across graph sizes.
The `comm` benchmark additionally runs the compressed-exchange dtype sweep
(`bench_comm.dtype_sweep`: measured bytes-per-round and accuracy per
``exchange_dtype`` at 8 shards) and writes the repo-root BENCH_comm.json.
The `throughput` benchmark (bench_throughput) sweeps batch sizes
B in {1, 8, 64} through every backend's batched apply and writes the
repo-root BENCH_throughput.json signals/sec trajectory.  The `fig2`
benchmark drives the Section-V solvers (chebyshev/jacobi/cheb_jacobi/arma)
through the sharded `plan.solve` path and writes the repo-root
BENCH_fig2.json error-vs-measured-communication table.  The `serving`
benchmark (bench_serving) replays seeded Poisson request streams through
the repro.serve continuous-batching engine at several offered loads and
writes the repo-root BENCH_serving.json latency/throughput table.  The
`faults` benchmark (bench_faults) measures graceful degradation under
seeded link faults — the (exchange_dtype x degradation policy x drop
probability) error ladder at 8 shards plus a straggler-injected serving
replay — and writes the repo-root BENCH_faults.json.
"""
import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale trial counts")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig1,fig2,lasso,comm,"
                    "kernels,scaling,throughput,serving,faults")
    ap.add_argument("--backend", default=None,
                    help="comma-separated execution backends to sweep "
                    "(dense,pallas,halo,pallas_halo,allgather) through the "
                    "plan API; one JSON per backend is written to --json-dir")
    ap.add_argument("--json-dir", default=".",
                    help="directory for per-backend JSON results")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import (bench_comm, bench_faults, bench_fig1_denoising,
                   bench_fig2_methods, bench_kernels, bench_lasso,
                   bench_scaling, bench_serving, bench_throughput)

    backends = args.backend.split(",") if args.backend else None
    wanted = set((args.only or
                  "fig1,fig2,lasso,comm,kernels,throughput,serving,faults")
                 .split(","))
    print("name,us_per_call,derived")
    if "fig1" in wanted:
        bench_fig1_denoising.run(n_trials=1000 if args.full else 20)
    if "fig2" in wanted:
        # Section-V method comparison through the distributed plan.solve
        # path; the tracked repo-root BENCH_fig2.json is only rewritten by
        # a default sweep (like BENCH_throughput.json below)
        import os

        if backends is None and args.json_dir == ".":
            fig2_json = bench_fig2_methods.DEFAULT_JSON
        else:
            fig2_json = os.path.join(args.json_dir, "BENCH_fig2.json")
        fig2_backend = (backends[0] if backends
                        else bench_fig2_methods.DEFAULT_BACKEND)
        bench_fig2_methods.run(budget=20, backend=fig2_backend,
                               json_path=fig2_json)
    if "lasso" in wanted:
        bench_lasso.run(n_trials=20 if args.full else 4,
                        n_iters=300 if args.full else 120)
    if "comm" in wanted:
        bench_comm.run(backends=backends, json_dir=args.json_dir)
        # compressed-exchange dtype sweep (8-shard subprocess when the
        # current process is single-device); the tracked repo-root
        # BENCH_comm.json is only rewritten by a default run, and the
        # sweep only makes sense for the halo-exchange backends
        import os

        sharded = [b for b in (backends or bench_comm.DEFAULT_DTYPE_BACKENDS)
                   if b in bench_comm.DEFAULT_DTYPE_BACKENDS]
        if sharded:
            if backends is None and args.json_dir == ".":
                comm_json = bench_comm.DEFAULT_JSON
            else:
                comm_json = os.path.join(args.json_dir, "BENCH_comm.json")
            bench_comm.dtype_sweep(backends=sharded, json_path=comm_json)
        else:
            print("# comm dtype sweep skipped: --backend lists no "
                  "halo-exchange backend (halo, pallas_halo)", flush=True)
    if "kernels" in wanted:
        bench_kernels.run(backends=backends, json_dir=args.json_dir)
        # single-launch sweep vs per-order microbenchmark; the tracked
        # repo-root BENCH_kernels.json is only rewritten by a default run
        import os

        if backends is None and args.json_dir == ".":
            kernels_json = bench_kernels.DEFAULT_JSON
        else:
            kernels_json = os.path.join(args.json_dir, "BENCH_kernels.json")
        bench_kernels.sweep_vs_step(json_path=kernels_json,
                                    iters=10 if args.full else 5)
    if "throughput" in wanted:
        # B-sweep of the batched (..., N) contract.  The tracked repo-root
        # BENCH_throughput.json (the full 5-backend trajectory) is only
        # rewritten by a default full sweep; --backend subsets or an
        # explicit --json-dir write next to the other bench JSONs instead.
        import os

        if backends is None and args.json_dir == ".":
            json_path = bench_throughput.DEFAULT_JSON
        else:
            json_path = os.path.join(args.json_dir, "BENCH_throughput.json")
        bench_throughput.run(backends=backends, json_path=json_path,
                             iters=20 if args.full else 5)
    if "serving" in wanted:
        # Offered-load replay through the continuous-batching engine.
        # The tracked repo-root BENCH_serving.json is only rewritten by a
        # default run (same gating as the other tracked bench JSONs).
        import os

        if backends is None and args.json_dir == ".":
            serving_json = bench_serving.DEFAULT_JSON
        else:
            serving_json = os.path.join(args.json_dir, "BENCH_serving.json")
        bench_serving.run(
            backends=(tuple(backends) if backends
                      else bench_serving.DEFAULT_BACKENDS),
            n_requests=300 if args.full else 150,
            json_path=serving_json)
    if "faults" in wanted:
        # Fault-injection degradation ladder + straggler serving replay
        # (8-shard subprocess when the current process is single-device).
        # The tracked repo-root BENCH_faults.json is only rewritten by a
        # default run; the ladder only runs on halo-exchange backends.
        import os

        fault_backend = bench_faults.DEFAULT_BACKEND
        if backends is not None:
            sharded = [b for b in backends if b in ("halo", "pallas_halo")]
            fault_backend = sharded[0] if sharded else None
        if fault_backend is None:
            print("# faults skipped: --backend lists no halo-exchange "
                  "backend (halo, pallas_halo)", flush=True)
        else:
            if backends is None and args.json_dir == ".":
                faults_json = bench_faults.DEFAULT_JSON
            else:
                faults_json = os.path.join(args.json_dir,
                                           "BENCH_faults.json")
            bench_faults.run(backend=fault_backend, json_path=faults_json)
    if "scaling" in wanted:
        if backends is None:
            bench_scaling.run(backends=None, json_dir=args.json_dir)
        else:
            sharded = [b for b in backends
                       if b in ("pallas_halo", "halo", "allgather")]
            if sharded:
                bench_scaling.run(backends=sharded, json_dir=args.json_dir)
            else:
                print("# scaling skipped: --backend lists no sharded "
                      "backend (pallas_halo, halo, allgather)", flush=True)


if __name__ == "__main__":
    main()

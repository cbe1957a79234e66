"""Kernel micro-benchmarks: jitted reference path wall-time on CPU (TPU
kernels are validated in interpret mode — timing them interpreted is
meaningless, so the CSV times the jnp oracle the kernels must beat and
reports roofline-model bytes/flops per call as `derived`).

`sweep_vs_step` is the single-launch-sweep acceptance microbenchmark: it
times the whole K-order Chebyshev application through the per-order path
(`ops.fused_cheb_apply(..., sweep=False)`: one SpMV + one cheb_step per
order) against the sweep path (`ops.fused_cheb_sweep`: the recurrence as
one fused trace / one kernel launch) over K in {5, 20, 50}, eta in {1, 3}
and B in {1, 64}, and writes the repo-root ``BENCH_kernels.json`` whose
top-level ``speedup_sweep_vs_step`` (geometric mean over configs) the CI
smoke step gates at >= 1.0 via ``--check``.  Each config also records the
mixed-precision sweep's VMEM footprint model (``vmem_bytes_f32`` /
``vmem_bytes_bf16``, tiled bytes with the batch on 128 lanes) and their
config-geomean ``vmem_bf16_capacity_ratio``.  bf16 halves the x operand,
the t_k pair and the blocks but not the eta f32 accumulator planes, so
the ratio sits well under 2x and falls as eta grows; it is recorded, not
gated (``tests/test_sweep.py`` pins the exact halving of each
scratch-width term, and wall-time for the bf16 kernel is a TPU effect).

    PYTHONPATH=src python -m benchmarks.bench_kernels \
        [--n 500] [--ks 5,20,50] [--etas 1,3] [--batches 1,64] \
        [--json-path BENCH_kernels.json] [--check] [--check-min 1.0]
"""
import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import chebyshev as cheb
from repro.core import filters, graph
from repro.dist import GraphOperator
from repro.kernels import ops, ref

from .common import make_backend_plan, row, time_fn, write_json

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT_JSON = os.path.join(REPO_ROOT, "BENCH_kernels.json")
DEFAULT_KS = (5, 20, 50)
DEFAULT_ETAS = (1, 3)
DEFAULT_BATCHES = (1, 64)


def sweep_vs_step(n=500, Ks=DEFAULT_KS, etas=DEFAULT_ETAS,
                  batches=DEFAULT_BATCHES, iters=10, json_path=DEFAULT_JSON):
    """Time the single-launch sweep against the per-order path.

    Both arms run the jnp reference dispatch (`use_pallas=False`: the
    interpret/ref CI path — interpret-mode kernel timings are
    meaningless); the sweep arm is the same recurrence as ONE unrolled
    fused trace, which is exactly what the sweep kernel does on TPU minus
    the launch/HBM effects the CPU cannot model.  Writes `json_path` with
    per-config us/call and a top-level geomean ``speedup_sweep_vs_step``;
    returns the payload.
    """
    from .common import seeded_sensor_graph

    import time

    def time_pair(fa, fb, x, iters):
        """Interleaved min-of-N timing (us) for two arms of a comparison.

        Alternating the arms cancels machine-load drift between them, and
        the minimum is the robust per-call estimator under interference
        (any slowdown is additive noise); medians of separated runs flap
        on shared runners.
        """
        for _ in range(2):
            jax.block_until_ready(fa(x))
            jax.block_until_ready(fb(x))
        best_a = best_b = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fa(x))
            t1 = time.perf_counter()
            jax.block_until_ready(fb(x))
            t2 = time.perf_counter()
            best_a = min(best_a, t1 - t0)
            best_b = min(best_b, t2 - t1)
        return best_a * 1e6, best_b * 1e6

    gs, key = seeded_sensor_graph(n, sort=True)
    L = np.asarray(gs.laplacian())
    A = graph.to_block_ell(L, (8, 128))
    lmax = gs.lambda_max_bound()
    configs = {}
    speedups = []
    for K in Ks:
        for eta in etas:
            coeffs = cheb.cheb_coeffs_stack(
                [filters.tikhonov(1.0 + j) for j in range(eta)], K,
                lmax).astype(np.float32)
            per_order = jax.jit(lambda v, c=coeffs, K=K: ops.fused_cheb_apply(
                A, v, c, lmax, use_pallas=False, sweep=False))
            sweep = jax.jit(lambda v, c=coeffs, K=K: ops.fused_cheb_apply(
                A, v, c, lmax, use_pallas=False))
            for B in batches:
                x = jax.random.normal(jax.random.PRNGKey(B), (B, A.padded_n))
                us_step, us_sweep = time_pair(per_order, sweep, x, iters)
                ratio = us_step / us_sweep
                speedups.append(ratio)
                # mixed-precision capacity: the bf16-scratch kernel's VMEM
                # footprint model vs f32 (wall-time is a TPU effect the CPU
                # cannot measure; the footprint ratio is what decides which
                # problems fit under the sweep guard at all)
                shape = A.blocks.shape
                v32 = ops.cheb_sweep_vmem_bytes(shape, A.padded_n, eta, B)
                v16 = ops.cheb_sweep_vmem_bytes(shape, A.padded_n, eta, B,
                                                scratch_dtype="bf16")
                configs[f"K{K}_eta{eta}_B{B}"] = {
                    "per_order_us": us_step,
                    "sweep_us": us_sweep,
                    "speedup": ratio,
                    "vmem_bytes_f32": v32,
                    "vmem_bytes_bf16": v16,
                    "vmem_capacity_ratio": v32 / v16,
                }
                row(f"cheb_sweep_K{K}_eta{eta}_B{B}", us_sweep,
                    f"per_order_us={us_step:.1f};speedup={ratio:.2f};"
                    f"vmem_bf16_ratio={v32 / v16:.2f}")
    geomean = float(np.exp(np.mean(np.log(speedups))))
    vmem_ratios = [c["vmem_capacity_ratio"] for c in configs.values()]
    payload = {
        "bench": "kernels_sweep",
        "n": int(gs.n_vertices),
        "padded_n": int(A.padded_n),
        "path": "ref",
        "configs": configs,
        "speedup_sweep_vs_step": geomean,
        # geomean over configs: under 2x, since the deliberately-f32
        # accumulator planes (eta * n * B128 * 4) do not shrink
        "vmem_bf16_capacity_ratio": float(
            np.exp(np.mean(np.log(vmem_ratios)))),
    }
    if json_path:
        import json

        parent = os.path.dirname(os.path.abspath(json_path))
        os.makedirs(parent, exist_ok=True)
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"# wrote {json_path}", flush=True)
    return payload


def sweep_backends(backends, json_dir="."):
    """Time plan.apply/apply_adjoint/apply_gram per backend through the one
    GraphOperator.plan() entry point; one comparable JSON per backend."""
    key = jax.random.PRNGKey(0)
    g, key = graph.connected_sensor_graph(key, n=500, theta=0.075,
                                          kappa=0.075)
    gs, _ = graph.spatial_sort(g)  # banded order so 'halo' is exact
    lmax = gs.lambda_max_bound()
    op = GraphOperator(P=gs.laplacian(),
                       multipliers=[filters.tikhonov(1.0), filters.heat(0.5)],
                       lmax=lmax, K=20)
    f = jax.random.normal(key, (g.n_vertices,))
    a = jax.random.normal(key, (op.eta, g.n_vertices))
    for backend in backends:
        plan = make_backend_plan(op, backend)
        results = {}
        for fn_name, fn, arg in (("apply", plan.apply, f),
                                 ("apply_adjoint", plan.apply_adjoint, a),
                                 ("apply_gram", plan.apply_gram, f)):
            us = time_fn(jax.jit(fn), arg)
            results[f"{fn_name}_us"] = us
            row(f"plan_{fn_name}_{backend}", us, f"n=500;K={op.K};eta={op.eta}")
        write_json(json_dir, f"bench_kernels_{backend}", {
            "bench": "kernels",
            "backend": backend,
            "n": g.n_vertices,
            "K": op.K,
            "eta": op.eta,
            "device_count": len(jax.devices()),
            "results": results,
            "plan_info": dict(plan.info),
        })


def run(backends=None, json_dir="."):
    if backends:
        sweep_backends(backends, json_dir)
    key = jax.random.PRNGKey(0)
    g, key = graph.connected_sensor_graph(key, n=500)
    L = np.asarray(g.laplacian())
    A = graph.to_block_ell(L, (8, 128))
    x = jax.random.normal(key, (A.padded_n,))

    spmv = jax.jit(lambda v: ref.block_ell_spmv_ref(A.blocks, A.indices, v))
    us = time_fn(spmv, x)
    nnz_blocks = int(np.asarray(A.mask).sum())
    row("spmv_blockell_n500", us,
        f"slots={A.blocks.shape[1]};nnz_blocks={nnz_blocks};"
        f"flops={nnz_blocks * 2 * 8 * 128}")

    lmax = g.lambda_max_bound()
    coeffs = cheb.cheb_coeffs_stack(
        [filters.tikhonov(1.0), filters.heat(0.5)], 20, lmax)
    fused = jax.jit(lambda v: ops.fused_cheb_apply(A, v, coeffs, lmax,
                                                   use_pallas=False))
    us = time_fn(fused, x)
    row("fused_cheb_apply_K20", us, f"eta=2;matvecs=20")

    B, Hq, Hkv, S, D = 1, 8, 2, 1024, 64
    q = jax.random.normal(key, (B, Hq, S, D))
    k = jax.random.normal(key, (B, Hkv, S, D))
    v = jax.random.normal(key, (B, Hkv, S, D))
    att = jax.jit(lambda a, b, c: ref.attention_ref(a, b, c, causal=True))
    us = time_fn(att, q, k, v)
    row("attention_ref_1k", us, f"flops~{4 * B * Hq * S * S * D}")

    from repro.models.layers import attention_chunked
    attc = jax.jit(lambda a, b, c: attention_chunked(a, b, c, causal=True,
                                                     chunk=256))
    us = time_fn(attc, q, k, v)
    row("attention_chunked_1k", us, "chunk=256")

    eta, n = 7, 1 << 16
    a = jax.random.normal(key, (eta, n))
    th = jnp.full((eta, 1), 0.2)
    shr = jax.jit(lambda z: ref.ista_shrink_ref(z, z * 0.5, z * 0.1, th,
                                                gamma=0.2))
    us = time_fn(shr, a)
    row("ista_shrink_64k", us, f"eta={eta}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=500)
    ap.add_argument("--ks", default="5,20,50")
    ap.add_argument("--etas", default="1,3")
    ap.add_argument("--batches", default="1,64")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--json-path", default=DEFAULT_JSON,
                    help="output JSON; '' disables writing")
    ap.add_argument("--full", action="store_true",
                    help="also run the legacy kernel CSV sweep")
    ap.add_argument("--check", action="store_true",
                    help="fail unless the sweep path's geomean speedup over "
                    "the per-order path is >= --check-min")
    ap.add_argument("--check-min", type=float, default=1.0,
                    help="minimum speedup_sweep_vs_step for --check (the "
                    "sweep must at least not regress the per-order path)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.full:
        run()
    payload = sweep_vs_step(
        n=args.n,
        Ks=tuple(int(k) for k in args.ks.split(",")),
        etas=tuple(int(e) for e in args.etas.split(",")),
        batches=tuple(int(b) for b in args.batches.split(",")),
        iters=args.iters, json_path=args.json_path)
    if args.check:
        speedup = payload["speedup_sweep_vs_step"]
        assert speedup >= args.check_min, (
            f"sweep geomean speedup {speedup:.3f}x < {args.check_min}x — "
            "the single-launch sweep regresses the per-order path")
        print(f"# sweep gate OK: {speedup:.2f}x vs per-order, "
              f"bf16 VMEM capacity {payload['vmem_bf16_capacity_ratio']:.2f}x "
              "(recorded)", flush=True)


if __name__ == "__main__":
    main()

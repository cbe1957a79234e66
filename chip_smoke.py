#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, against plain-jnp references.

    python chip_smoke.py             # one chip: three phases, below
    python chip_smoke.py --chips 4   # the 4-chip sharded path only

One chip:

1. ``sensor500`` -- the paper's configuration (``SENSOR500``: N=500,
   theta=0.074, kappa=0.075) with an SGWT bank (J=3, so eta=4) at K=20 on
   ``backend="pallas"``.  ``plan.apply`` at B=1 and B=64 runs the
   single-launch ``cheb_sweep`` kernel; ``plan.solve(method="jacobi")``
   runs ``jacobi_sweep``.
2. ``community1m`` -- ``community_graph_csr(1_000_000)`` (|E| ~ 1.01e6),
   Block-ELL (8, 8), planned ``pallas_halo`` / ``partition="general"`` on
   a 1-device mesh.  ``plan.apply`` at B=64, eta=4, K=20 is far past the
   sweep's VMEM guard, so it runs the per-order SpMV
   (``block_ell_spmv_window`` where the band's window fits VMEM, else
   ``block_ell_spmv_batched``) + ``cheb_step`` kernels.  The (64, 4, 1e6)
   f32 accumulator alone is 1 GB.
3. ``serve`` -- a ``ServeEngine`` over phase 2's plan, buckets (1, 8, 64),
   73 apply requests: every ``Response`` must be ok and match phase 2's
   reference.

``--chips 4`` plans the same community graph over a 4-device mesh (B=64),
compares it with the CSR reference on one device, and checks that
``commstats.measure`` counts exactly 2K|E| messages for the apply.

Every phase compares against a plain-jnp float32 reference written here
(dense or CSR matvec, the Chebyshev / Jacobi recurrences spelled out), and
prints which Pallas kernels the compiled program holds, the error against
its tolerance, compile seconds and peak device memory.  Nothing is timed
as a result.  The last line of stdout is one JSON object
``{"ok": true, "device": {...}}``; any failed phase exits non-zero.  The
script refuses to run anywhere but on a TPU.
"""
import argparse
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

EPS32 = float(np.finfo(np.float32).eps)
K = 20            # Chebyshev order (SENSOR500.K)
J = 3             # SGWT scales: eta = J + 1 = 4
BATCH = 64
COMMUNITY_N = 1_000_000
SERVE_COUNTS = (64, 8, 1)   # requests per flush: buckets 64, 8, 1


class PhaseFailed(RuntimeError):
    pass


def log(msg):
    print(msg, flush=True)


def tolerance(steps, row_nnz):
    """Relative tolerance for `steps` rounds of a three-term recurrence
    (or Jacobi sweep) in float32 whose matvec sums `row_nnz` products per
    row.  Kernel and reference differ only in summation order: each
    matvec's rounding is at most row_nnz ulp of its result, and the
    Chebyshev recurrence is stable with forward-error growth at most
    quadratic in the order, so steps^2 * row_nnz * eps bounds the
    difference relative to the output's scale."""
    return steps ** 2 * row_nnz * EPS32


def rel_err(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise PhaseFailed(f"shape {got.shape} != reference {want.shape}")
    if not np.all(np.isfinite(got)):
        raise PhaseFailed("non-finite values in the output")
    return float(np.abs(got - want).max() / np.abs(want).max())


def check(name, got, want, tol):
    err = rel_err(got, want)
    verdict = "ok" if err <= tol else "FAIL"
    log(f"  {name}: max rel err {err:.3e} (tol {tol:.3e}) {verdict}")
    if err > tol:
        raise PhaseFailed(f"{name}: error {err:.3e} exceeds {tol:.3e}")


def kernels_in(compiled):
    """Names of the Pallas kernels (`tpu_custom_call`s) in a compiled
    program, from its HLO text."""
    names = []
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.match(r"\s*%?([A-Za-z_]+)", line.split("=")[0])
            names.append(m.group(1) if m else "?")
    return sorted(set(names))


#: The per-order SpMV: either kernel, as `kernels.ops.spmv` picks.
SPMV = ("block_ell_spmv_window", "block_ell_spmv_batched")


def compile_path(fn, *args, expect):
    """AOT-compile fn(*args); check the program holds the `expect`ed
    kernels (a tuple: any one of them).  Returns (compiled, seconds)."""
    import jax

    t0 = time.perf_counter()
    # a plan's compiled entry lowers itself (with its structure as
    # arguments); anything else is jitted here
    lower = getattr(fn, "lower", None) or jax.jit(fn).lower
    compiled = lower(*args).compile()
    secs = time.perf_counter() - t0
    kernels = kernels_in(compiled)
    log(f"  compiled in {secs:.1f} s; tpu_custom_call kernels: {kernels}")
    missing = [e for e in expect
               if not set(e if isinstance(e, tuple) else (e,)) & set(kernels)]
    if missing:
        raise PhaseFailed(f"expected kernels {missing} not in the "
                          f"compiled program (found {kernels})")
    return compiled, secs


def peak_hbm(devices=None):
    import jax

    out = []
    for d in devices or jax.devices()[:1]:
        stats = d.memory_stats() or {}
        out.append((d.id, stats.get("peak_bytes_in_use"),
                    stats.get("bytes_limit")))
    return out


def log_hbm(devices=None):
    for dev_id, peak, limit in peak_hbm(devices):
        log(f"  device {dev_id}: peak_bytes_in_use={peak} of {limit}")


# ---------------------------------------------------------------------------
# Plain-jnp references
# ---------------------------------------------------------------------------
def cheb_reference(matvec, x, coeffs, lmax):
    """Algorithm 1 spelled out: (..., N) -> (..., eta, N)."""
    import jax.numpy as jnp

    c = jnp.asarray(coeffs, jnp.float32)
    alpha = lmax / 2.0
    t0 = x
    t1 = matvec(x) / alpha - x
    acc = (0.5 * c[:, 0, None] * t0[..., None, :]
           + c[:, 1, None] * t1[..., None, :])
    for k in range(2, c.shape[1]):
        t0, t1 = t1, (2.0 / alpha) * matvec(t1) - 2.0 * t1 - t0
        acc = acc + c[:, k, None] * t1[..., None, :]
    return acc


def dense_matvec(L):
    import jax
    import jax.numpy as jnp

    L = jnp.asarray(L, jnp.float32)
    return lambda x: jnp.matmul(x, L.T, precision=jax.lax.Precision.HIGHEST)


def csr_matvec(rows, cols, vals, n):
    """Gather/scatter CSR product along the last axis."""
    import jax.numpy as jnp

    rows, cols = jnp.asarray(rows), jnp.asarray(cols)
    vals = jnp.asarray(vals, jnp.float32)

    def mv(x):
        contrib = vals * jnp.take(x, cols, axis=-1)
        return jnp.zeros(x.shape[:-1] + (n,), x.dtype).at[..., rows].add(
            contrib)

    return mv


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
def phase_sensor500(cfg=None, batch=BATCH, n_iters=30):
    """Paper configuration on the `pallas` backend: sweep kernels."""
    import jax
    import jax.numpy as jnp

    from repro.configs import SENSOR500
    from repro.core import graph, wavelets
    from repro.dist import GraphOperator

    cfg = cfg or SENSOR500
    log(f"[sensor500] N={cfg.n_vertices} theta={cfg.theta} "
        f"kappa={cfg.kappa} K={K} eta={J + 1} backend=pallas")
    g, _ = graph.connected_sensor_graph(jax.random.PRNGKey(0),
                                        n=cfg.n_vertices, theta=cfg.theta,
                                        kappa=cfg.kappa)
    L = np.asarray(g.laplacian(), np.float32)
    lmax = float(g.lambda_max_bound())
    op = GraphOperator(P=L, multipliers=wavelets.sgwt_multipliers(lmax, J),
                       lmax=lmax, K=K)
    plan = op.plan("pallas")
    mv = dense_matvec(L)
    row_nnz = int((L != 0).sum(axis=1).max())
    key = jax.random.PRNGKey(1)
    for B in (1, batch):
        x = jax.random.normal(jax.random.fold_in(key, B), (B, L.shape[0]))
        compiled, _ = compile_path(plan.apply, x, expect=["cheb_sweep"])
        got = compiled(x)
        want = jax.jit(lambda v: cheb_reference(mv, v, op.coeffs, lmax))(x)
        check(f"apply B={B}", got, want, tolerance(K, row_nnz))

    # Jacobi (Eq. (24)) on (tau I + L) x = tau y, x0 = 0
    tau = float(cfg.tau)
    y = jax.random.normal(jax.random.fold_in(key, 7), (batch, L.shape[0]))
    compiled, _ = compile_path(
        lambda v: plan.solve(v, "jacobi", tau=tau, n_iters=n_iters).x, y,
        expect=["jacobi_sweep"])
    got = compiled(y)
    d = tau + jnp.diag(jnp.asarray(L))

    def jacobi_ref(v):
        x = jnp.zeros_like(v)
        for _ in range(n_iters):
            x = x + (tau * v - (tau * x + mv(x))) / d
        return x

    check(f"solve jacobi B={batch} n_iters={n_iters}", got,
          jax.jit(jacobi_ref)(y), tolerance(n_iters, row_nnz + 1))
    log_hbm()


def build_community(n, n_shards, mesh):
    """The CSR community graph, its operator and a pallas_halo general
    plan over `mesh`; returns (op, plan, csr, meta)."""
    from repro.core import wavelets
    from repro.dist import GraphOperator
    from repro.dist.partition import (community_graph_csr, csr_matvec_fn,
                                      partition_general)

    csr, meta = community_graph_csr(n, seed=0)
    parts = partition_general(csr, n_shards, block=(8, 8))
    op = GraphOperator(P=csr_matvec_fn(csr),
                       multipliers=wavelets.sgwt_multipliers(meta["lmax"], J),
                       lmax=meta["lmax"], K=K)
    plan = op.plan("pallas_halo", mesh=mesh, partition=parts)
    log(f"  N={n} |E|={meta['n_edges']} shards={n_shards} Block-ELL "
        f"{tuple(parts.blocks.shape[1:])} nnz_blocks={parts.nnz_blocks} "
        f"edge_cut={parts.edge_cut}")
    return op, plan, csr, meta


def community_reference(op, csr, x, device=None):
    import jax

    rows = csr.row_ids().astype(np.int32)
    cols = csr.indices.astype(np.int32)
    args = (rows, cols, csr.data.astype(np.float32), x)
    if device is not None:
        args = jax.device_put(args, device)
    r, c, v, xd = args
    mv = csr_matvec(r, c, v, csr.n)
    out = jax.jit(lambda s: cheb_reference(mv, s, op.coeffs, op.lmax))(xd)
    return np.asarray(out)


def phase_community(n=COMMUNITY_N, batch=BATCH):
    """Chip-scale general partition on one device: per-order kernels."""
    import jax

    log(f"[community1m] pallas_halo partition=general, B={batch} K={K} "
        f"eta={J + 1}")
    mesh = jax.make_mesh((1,), ("graph",))
    op, plan, csr, meta = build_community(n, 1, mesh)
    x = jax.random.normal(jax.random.PRNGKey(2), (batch, n))
    compiled, _ = compile_path(plan.compiled("apply"), x,
                               expect=[SPMV,
                                       "cheb_step"])
    got = np.asarray(compiled(x))
    want = community_reference(op, csr, x)
    row_nnz = int(np.diff(csr.indptr).max())
    tol = tolerance(K, row_nnz)
    check(f"apply B={batch}", got, want, tol)
    log_hbm()
    return plan, np.asarray(x), want, tol


def phase_serve(plan, signals, want, tol, counts=SERVE_COUNTS):
    """ServeEngine over the chip-scale plan; every response must be ok
    and match the reference rows."""
    from repro.serve import ServeEngine

    log(f"[serve] ServeEngine buckets=(1, 8, 64), "
        f"{sum(counts)} apply requests")
    engine = ServeEngine(plan, buckets=(1, 8, 64))
    futures = []
    for count in counts:
        for i in range(count):
            futures.append((i, engine.submit(signals[i])))
        engine.flush()
    buckets = set()
    worst = 0.0
    for i, fut in futures:
        resp = fut.response
        if not resp.ok:
            raise PhaseFailed(f"request {resp.id} failed: {resp.error}")
        buckets.add(resp.bucket)
        worst = max(worst, rel_err(resp.value, want[i]))
    log(f"  {len(futures)} responses ok, buckets used {sorted(buckets)}")
    if buckets != {1, 8, 64}:
        raise PhaseFailed(f"expected buckets 1, 8 and 64, got {buckets}")
    verdict = "ok" if worst <= tol else "FAIL"
    log(f"  responses: max rel err {worst:.3e} (tol {tol:.3e}) {verdict}")
    if worst > tol:
        raise PhaseFailed(f"served answers: error {worst:.3e} > {tol:.3e}")
    log_hbm()


def phase_four_chips(n=COMMUNITY_N, batch=BATCH):
    """The community graph sharded over four chips vs the one-device CSR
    reference, with the exact 2K|E| message count."""
    import jax

    from repro.dist.commstats import measure

    devices = jax.devices()
    if len(devices) != 4:
        raise PhaseFailed(f"--chips 4 needs 4 devices, found {len(devices)}")
    log(f"[community1m x4] pallas_halo partition=general over 4 chips, "
        f"B={batch} K={K} eta={J + 1}")
    mesh = jax.make_mesh((4,), ("graph",))
    op, plan, csr, meta = build_community(n, 4, mesh)
    x = jax.random.normal(jax.random.PRNGKey(2), (batch, n))
    compiled, _ = compile_path(plan.compiled("apply"), x,
                               expect=[SPMV,
                                       "cheb_step"])
    got = np.asarray(compiled(x))
    want = community_reference(op, csr, np.asarray(x), device=devices[0])
    check(f"apply B={batch} (4 chips vs 1-device CSR)", got, want,
          tolerance(K, int(np.diff(csr.indptr).max())))
    stats = measure(plan.apply, jax.ShapeDtypeStruct((batch, n), np.float32),
                    n_shards=4, batch=batch,
                    ppermutes_per_round=plan.info[
                        "exchange_collectives_per_round"])
    msgs = stats.paper_messages(meta["n_edges"])
    bound = 2 * K * meta["n_edges"]
    log(f"  messages measured {msgs}, 2K|E| = {bound}, exchange rounds "
        f"{stats.exchange_rounds}, bytes/shard {stats.bytes_per_shard}")
    if msgs != bound:
        raise PhaseFailed(f"measured {msgs} messages, expected {bound}")
    log_hbm(devices)
    idle = [d for d, peak, _ in peak_hbm(devices) if not peak]
    if idle:
        raise PhaseFailed(f"devices {idle} report no HBM in use")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the three one-chip phases; 4: only the "
                    "4-chip sharded path and its reference")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing "
              "to run anywhere else", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    log(f"jax {jax.__version__}, device_kind {dev.device_kind!r}, "
        f"{len(devices)} device(s), compile cache {enable_compile_cache()}")
    try:
        if args.chips == 4:
            phase_four_chips()
        else:
            phase_sensor500()
            plan, x, want, tol = phase_community()
            phase_serve(plan, x, want, tol)
    except PhaseFailed as exc:
        log(f"FAILED: {exc}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

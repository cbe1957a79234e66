"""Paper Figure 2 (Section V-E): Chebyshev vs Jacobi vs accelerated Jacobi
vs ARMA — error against *measured* communication budget, three (P, S)
settings:

  (a) P = L_norm, S = L_norm            (1 matvec per round for all methods)
  (b) P = L,      S = L^2               (Jacobi rounds cost 2 matvecs)
  (c) P = L_norm, S = (2I - L_norm)^-3  (Jacobi diverges; 3rd-order ARMA)

Every method runs through ``plan.solve`` on a *sharded* execution plan
(default backend: pallas_halo over forced host devices, like
bench_scaling), and the per-method communication is measured with
``repro.dist.commstats.solve_comm_stats`` — exchange rounds counted from
the compiled jaxpr, not assumed: Fig. 2(b)'s Jacobi rounds show their 2
matvecs, ARMA rounds carry length-n_poles messages.  Results land in
``BENCH_fig2.json`` (repo root by default) as an
error-vs-measured-communication-budget table.

The forward operator g_fwd = (tau + h)/tau is applied by exact *matvec*
polynomial evaluation for the polynomial kernels (a, b) — no
eigendecomposition at any size — and by the dense exact oracle only for
the rational kernel (c), guarded by ``EXACT_ORACLE_MAX_N`` (the setting is
skipped beyond it instead of silently paying O(N^3)).

    PYTHONPATH=src python -m benchmarks.bench_fig2_methods \
        [--n 500] [--budget 20] [--backend pallas_halo] [--shards 8] \
        [--json-path BENCH_fig2.json] [--check]

``--check`` gates on the paper's qualitative error ordering in setting (a)
(Chebyshev lowest at equal rounds; acceleration beats plain Jacobi) — the
CI fig2 smoke step runs it at small n.
"""
import argparse
import os
import subprocess
import sys

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT_JSON = os.path.join(REPO_ROOT, "BENCH_fig2.json")
DEFAULT_SHARDS = 8
DEFAULT_BACKEND = "pallas_halo"

#: Largest n the dense exact oracle (np.linalg.eigh) may be used for — the
#: rational setting (c) is skipped beyond this instead of paying O(N^3).
EXACT_ORACLE_MAX_N = 1500


def _forward_poly(matvec, f, h_coeffs, tau):
    """y = g_fwd(P) f for g_fwd = (tau + h)/tau with polynomial h — exact,
    deg(h) matvecs, no eigendecomposition at any size (the same Horner
    evaluation the solvers use)."""
    from repro.dist.solvers import poly_matvec

    return f + poly_matvec(matvec, h_coeffs, f) / tau


def _forward_oracle(P, g_fwd_callable, lmax, f):
    """y = g_fwd(P) f through the dense exact-apply oracle (Eq. (3));
    callers guard on EXACT_ORACLE_MAX_N."""
    from repro.core.multiplier import graph_multiplier

    op = graph_multiplier(P, g_fwd_callable, lmax, K=1)
    return op.union.exact_apply(f)[..., 0, :]


def _run_method(plan, y, f, method, E, n_iters, **kw):
    """One method through plan.solve + solve_comm_stats; returns the
    error-vs-measured-budget record (or a skip record on ValueError)."""
    import jax.numpy as jnp

    from repro.dist import solve_comm_stats

    try:
        res = plan.solve(y, method, n_iters=n_iters, **kw)
    except ValueError as e:
        return {"skipped": str(e)}
    err = float(jnp.linalg.norm(res.x - f) / jnp.linalg.norm(f))
    stats = solve_comm_stats(plan, method, n_iters=n_iters, **kw)
    msg_len = res.info.get("n_poles", 1)
    rounds = stats.exchange_rounds
    return {
        "err": err,
        "n_iters": n_iters,
        "matvecs_per_round": res.info["matvecs_per_round"],
        "predicted_rounds": res.info["exchange_rounds"],
        "measured_rounds": rounds,
        "message_len": msg_len,
        # paper-level accounting at the MEASURED round count (the repo-wide
        # CommStats.paper_messages convention: rounds x 2|E| sensor-network
        # messages; x message_len for the scalar count) — the backend-
        # independent Fig. 2 x-axis.  The *_bytes fields below are the
        # device-level traffic this backend actually shipped (boundary rows
        # under pallas_halo, whole-iterate gathers under allgather).
        "paper_messages": stats.paper_messages(E),
        "paper_scalars": stats.paper_messages(E) * msg_len,
        "measured_bytes_per_shard": stats.bytes_per_shard,
        "measured_total_bytes": stats.total_bytes,
    }


def _measure(n, budget, backend, n_shards, json_path, check):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import filters
    from repro.dist import GraphOperator

    from .common import row, seeded_sensor_graph

    tau = 0.5
    g, key = seeded_sensor_graph(n, seed=0, sort=True)
    n = g.n_vertices
    E = g.n_edges
    mesh = jax.make_mesh((n_shards,), ("graph",))
    f = jax.random.uniform(key, (n,), minval=-10.0, maxval=10.0)
    L = jnp.asarray(g.laplacian())
    Ln = jnp.asarray(g.laplacian("normalized"))
    lmaxL = g.lambda_max_bound()
    mvL = lambda x: jnp.einsum("ij,...j->...i", L, x)       # noqa: E731
    mvLn = lambda x: jnp.einsum("ij,...j->...i", Ln, x)     # noqa: E731

    def plan_for(P, lmax):
        op = GraphOperator(P=P, multipliers=[filters.identity_multiplier()],
                           lmax=lmax, K=budget)
        return op.plan(backend, mesh=mesh, allow_leak=True)

    settings = {}

    # ---------------- (a) P = L_norm, S = L_norm --------------------------
    y = _forward_poly(mvLn, f, (0.0, 1.0), tau)   # h = lambda
    plan = plan_for(Ln, 2.0)
    kw = dict(tau=tau, r=1, h_scale=1.0)
    meth = {
        "chebyshev": _run_method(plan, y, f, "chebyshev", E, budget, **kw),
        "jacobi": _run_method(plan, y, f, "jacobi", E, budget, **kw),
        "cheb_jacobi": _run_method(plan, y, f, "cheb_jacobi", E, budget,
                                   **kw),
        "arma": _run_method(plan, y, f, "arma", E, budget, **kw),
    }
    settings["a_Lnorm"] = {"P": "L_norm", "S": "L_norm", "tau": tau,
                           "methods": meth}
    row("fig2a_Lnorm", 0.0, ";".join(
        f"{m}={v.get('err', 'n/a'):.2e}" if "err" in v else f"{m}=skipped"
        for m, v in meth.items()) + f";rounds={budget}")

    # ---------------- (b) P = L, S = L^2 ----------------------------------
    y2 = _forward_poly(mvL, f, (0.0, 0.0, 1.0), tau)   # h = lambda^2
    plan2 = plan_for(L, lmaxL)
    kw2 = dict(tau=tau, r=2, h_scale=1.0)
    meth2 = {
        "chebyshev": _run_method(plan2, y2, f, "chebyshev", E, budget,
                                 **kw2),
        # one Jacobi round costs 2 matvecs -> budget/2 rounds
        "jacobi": _run_method(plan2, y2, f, "jacobi", E, budget // 2,
                              **kw2),
        "cheb_jacobi": _run_method(plan2, y2, f, "cheb_jacobi", E,
                                   budget // 2, **kw2),
        # 2 poles -> length-2 messages per round: budget/2 rounds at equal
        # scalar traffic
        "arma": _run_method(plan2, y2, f, "arma", E, budget // 2, **kw2),
    }
    settings["b_L_S2"] = {"P": "L", "S": "L^2", "tau": tau,
                          "methods": meth2}
    row("fig2b_L_S2", 0.0, ";".join(
        f"{m}={v.get('err', 'n/a'):.2e}" if "err" in v else f"{m}=skipped"
        for m, v in meth2.items()) + f";rounds={budget}")

    # ------- (c) P = L_norm, S = (2I - L_norm)^-3 (random walk) -----------
    if n <= EXACT_ORACLE_MAX_N:
        h3 = filters.random_walk_kernel(2.0, 3)
        gfwd3 = filters.fig2_target(h3, tau)
        y3 = _forward_oracle(Ln, gfwd3, 2.0, f)
        num3, den3 = filters.random_walk_rational(tau, 2.0, 3)
        plan3 = plan_for(Ln, 2.0)
        kw3 = dict(num=num3, den=den3)
        meth3 = {
            "chebyshev": _run_method(plan3, y3, f, "chebyshev", E, budget,
                                     **kw3),
            # the Jacobi split of den(P) diverges here (the paper's point);
            # cheb_jacobi raises on rho >= 1 and records the skip
            "cheb_jacobi": _run_method(plan3, y3, f, "cheb_jacobi", E,
                                       budget // 3, **kw3),
            # 3 poles -> budget/3 rounds at equal scalar traffic
            "arma": _run_method(plan3, y3, f, "arma", E, budget // 3,
                                **kw3),
        }
        settings["c_randwalk"] = {"P": "L_norm", "S": "(2I - L_norm)^-3",
                                  "tau": tau, "methods": meth3}
        row("fig2c_randwalk", 0.0, ";".join(
            f"{m}={v.get('err', 'n/a'):.2e}" if "err" in v
            else f"{m}=skipped" for m, v in meth3.items())
            + f";rounds={budget}")
    else:
        settings["c_randwalk"] = {
            "skipped": f"n={n} > EXACT_ORACLE_MAX_N={EXACT_ORACLE_MAX_N}: "
                       "the rational forward operator needs the dense "
                       "exact oracle"}
        row("fig2c_randwalk", 0.0, "skipped=exact-oracle size guard")

    payload = {
        "bench": "fig2",
        "n": int(n),
        "E": int(E),
        "budget": int(budget),
        "backend": backend,
        "n_shards": int(n_shards),
        "device_count": len(jax.devices()),
        "settings": settings,
    }
    if json_path:
        import json

        parent = os.path.dirname(os.path.abspath(json_path))
        os.makedirs(parent, exist_ok=True)
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"# wrote {json_path}", flush=True)

    if check:
        a = settings["a_Lnorm"]["methods"]
        skipped = {m: v["skipped"] for m, v in a.items() if "err" not in v}
        assert not skipped, (
            "fig2 check needs every setting-(a) method to run, but these "
            f"were skipped: {skipped}")
        assert a["chebyshev"]["err"] < a["jacobi"]["err"], (
            "Fig. 2(a) ordering violated: Chebyshev should beat Jacobi at "
            f"equal rounds ({a['chebyshev']['err']:.3e} vs "
            f"{a['jacobi']['err']:.3e})")
        assert a["chebyshev"]["err"] < a["arma"]["err"], (
            "Fig. 2(a) ordering violated: Chebyshev should beat ARMA at "
            f"equal rounds ({a['chebyshev']['err']:.3e} vs "
            f"{a['arma']['err']:.3e})")
        assert a["cheb_jacobi"]["err"] < a["jacobi"]["err"], (
            "Eq. (25) acceleration should beat plain Jacobi "
            f"({a['cheb_jacobi']['err']:.3e} vs {a['jacobi']['err']:.3e})")
        for name, rec in (("a", a), ("b", settings["b_L_S2"]["methods"])):
            for m, v in rec.items():
                if "measured_rounds" in v:
                    assert v["measured_rounds"] == v["predicted_rounds"], (
                        f"setting {name} {m}: measured rounds "
                        f"{v['measured_rounds']} != closed form "
                        f"{v['predicted_rounds']}")
        print("# fig2 check OK: method error ordering + measured rounds "
              "match closed forms", flush=True)
    return payload


def run(n: int = None, budget: int = 20, backend: str = DEFAULT_BACKEND,
        n_shards: int = DEFAULT_SHARDS, json_path: str = DEFAULT_JSON,
        check: bool = False):
    """Entry point used by `benchmarks.run`.

    Communication is *measured* (collectives vanish on 1-shard meshes), so
    when this process cannot build an `n_shards`-wide mesh it re-execs
    itself with forced host devices, like bench_scaling."""
    from repro.configs import SENSOR500

    n = n or SENSOR500.n_vertices

    import jax

    if len(jax.devices()) >= n_shards:
        return _measure(n, budget, backend, n_shards, json_path, check)

    env = dict(os.environ)
    # a CPU count run: the child must never contend for this process's chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_shards} "
        + env.get("XLA_FLAGS", ""))
    src = os.path.join(REPO_ROOT, "src")
    env["PYTHONPATH"] = (src + os.pathsep + REPO_ROOT + os.pathsep
                         + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "benchmarks.bench_fig2_methods",
           "--n", str(n), "--budget", str(budget), "--backend", backend,
           "--shards", str(n_shards), "--json-path", json_path or ""]
    if check:
        cmd.append("--check")
    proc = subprocess.run(cmd, env=env, cwd=REPO_ROOT)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_fig2 subprocess failed (rc={proc.returncode})")
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--budget", type=int, default=20)
    ap.add_argument("--backend", default=DEFAULT_BACKEND)
    ap.add_argument("--shards", type=int, default=DEFAULT_SHARDS)
    ap.add_argument("--json-path", default=DEFAULT_JSON,
                    help="output JSON; '' disables writing")
    ap.add_argument("--check", action="store_true",
                    help="fail unless the Fig. 2(a) error ordering holds "
                    "and measured rounds match the closed forms")
    args = ap.parse_args()

    import jax

    if len(jax.devices()) >= args.shards:
        from repro.configs import SENSOR500

        print("name,us_per_call,derived")
        _measure(args.n or SENSOR500.n_vertices, args.budget, args.backend,
                 args.shards, args.json_path, args.check)
    else:
        run(args.n, args.budget, args.backend, args.shards, args.json_path,
            args.check)


if __name__ == "__main__":
    main()

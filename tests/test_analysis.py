"""repro.analysis: every shipped rule flags its known-bad fixture, the
allowlist machinery audits what it silences, and the real repo comes out
clean across all five backends (1 shard in-process, 8 via subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_payload
from repro import analysis as A
from repro.dist import commstats

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
P = jax.sharding.PartitionSpec


def _shmap(inner, mesh, in_specs=None, out_specs=None):
    return jax.shard_map(inner, mesh=mesh,
                         in_specs=P("x") if in_specs is None else in_specs,
                         out_specs=P("x") if out_specs is None else out_specs,
                         check_vma=False)


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Comm-schedule safety
# ---------------------------------------------------------------------------
def test_perm_problems_unit():
    ring = [(i, (i + 1) % 8) for i in range(8)]
    assert A.perm_problems(ring, 8) == []
    assert A.perm_problems([(0, 1), (1, 0)], 2) == []
    # incomplete ring: last device dropped from the exchange
    probs = A.perm_problems(ring[:-1], 8)
    assert any("never send" in p for p in probs)
    assert any("never receive" in p for p in probs)
    # collisions and off-axis indices
    assert any("send more than once" in p
               for p in A.perm_problems([(0, 1), (0, 0)], 2))
    assert any("receive more than once" in p
               for p in A.perm_problems([(0, 1), (1, 1), (2, 0)], 3))
    assert any("outside axis" in p for p in A.perm_problems([(0, 9)], 4))


def test_incomplete_ppermute_flagged_in_trace():
    """The traced version: an empty perm on a 1-device axis is incomplete
    (device 0 neither sends nor receives)."""
    mesh = jax.make_mesh((1,), ("x",))

    def bad(v):
        return _shmap(lambda vl: jax.lax.ppermute(vl, "x", perm=[]),
                      mesh)(v)

    fs = A.check_comm_schedule(bad, jax.ShapeDtypeStruct((8,), np.float32),
                               label="fixture.bad_ring")
    assert _rules(fs) == {"JX-PPERMUTE-BIJECTION"}
    assert fs[0].symbol == "fixture.bad_ring"

    def good(v):
        return _shmap(lambda vl: jax.lax.ppermute(vl, "x", perm=[(0, 0)]),
                      mesh)(v)

    assert A.check_comm_schedule(
        good, jax.ShapeDtypeStruct((8,), np.float32)) == []


def test_collective_under_while_flagged():
    mesh = jax.make_mesh((1,), ("x",))

    def bad(v):
        def inner(vl):
            return jax.lax.while_loop(
                lambda c: jnp.sum(c) < 100.0,
                lambda c: jax.lax.ppermute(c, "x", perm=[(0, 0)]) + 1.0,
                vl)
        return _shmap(inner, mesh)(v)

    fs = A.check_comm_schedule(bad, jax.ShapeDtypeStruct((8,), np.float32))
    assert _rules(fs) == {"JX-COLLECTIVE-IN-WHILE"}

    # the commstats satellite: measure() refuses to undercount this
    with pytest.raises(commstats.UncountableCollectiveError):
        commstats.measure(bad, jax.ShapeDtypeStruct((8,), np.float32))
    with pytest.warns(UserWarning, match="lower bound"):
        st = commstats.measure(bad, jax.ShapeDtypeStruct((8,), np.float32),
                               while_loops="warn")
    assert st.n_collectives == 1
    with pytest.raises(ValueError):
        commstats.measure(bad, jax.ShapeDtypeStruct((8,), np.float32),
                          while_loops="ignore")


def test_batch_dependent_schedule_flagged():
    """A batched path that re-runs the exchange per signal (the bug the
    (..., N) contract forbids) has a B-dependent schedule."""
    mesh = jax.make_mesh((1,), ("x",))

    def mk(b):
        def fn(v):
            def inner(vl):
                for _ in range(b):  # one exchange per signal: the bug
                    vl = jax.lax.ppermute(vl, "x", perm=[(0, 0)])
                return vl
            return _shmap(inner, mesh)(v)
        return fn, (jax.ShapeDtypeStruct((8,), np.float32),)

    fs = A.check_batch_schedule(mk, batches=(1, 4), label="fixture.rerun")
    assert _rules(fs) == {"JX-BATCH-SCHEDULE"}

    def mk_good(b):
        fn, _ = mk(1)
        return fn, (jax.ShapeDtypeStruct((8,), np.float32),)

    assert A.check_batch_schedule(mk_good, batches=(1, 4)) == []


# ---------------------------------------------------------------------------
# VMEM budget
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def block_ell():
    from repro.core import graph
    g = graph.path_graph(64)
    return graph.to_block_ell(np.asarray(g.laplacian(), np.float32),
                              (8, 128)), g.lambda_max_bound()


def test_overbudget_pallas_call_flagged(block_ell):
    from repro.kernels import ops
    A_ell, lmax = block_ell
    c = np.ones((2, 6), np.float32)

    def fn(x):
        return ops.fused_cheb_sweep(A_ell, ops.pad_trailing(x, A_ell.padded_n),
                                    c, lmax, use_pallas=True)

    x = jax.ShapeDtypeStruct((64,), np.float32)
    # the real launch fits the real budget...
    assert A.check_vmem_budget(fn, x) == []
    # ...and a starved checker budget flags the same launch, proving the
    # footprint is recomputed from the traced BlockSpecs
    fs = A.check_vmem_budget(fn, x, budget=256, label="fixture.sweep")
    assert _rules(fs) == {"JX-VMEM-BUDGET"}
    assert "exceeds the sweep VMEM budget 256" in fs[0].message


def test_pallas_footprint_matches_ops_model(block_ell):
    """The jaxpr-recovered footprint agrees with the launch-side model for
    the dominant iterate terms (the model also budgets index/coeff slack,
    so launch-model >= traced is the invariant)."""
    from repro.kernels import ops
    A_ell, lmax = block_ell
    eta, K = 2, 5
    c = np.ones((eta, K + 1), np.float32)

    def fn(x):
        return ops.fused_cheb_sweep(A_ell,
                                    ops.pad_trailing(x, A_ell.padded_n),
                                    c, lmax, use_pallas=True)

    closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((64,), np.float32))
    calls = A.collect_eqns(closed, {"pallas_call"})
    assert len(calls) == 1
    traced = A.pallas_footprint(calls[0][0])["total_bytes"]
    model = ops.cheb_sweep_vmem_bytes(A_ell.blocks.shape, A_ell.padded_n,
                                      eta)
    assert 0 < traced <= model


# ---------------------------------------------------------------------------
# Dtype discipline
# ---------------------------------------------------------------------------
def test_f64_upcast_flagged():
    def bad(x):
        return jnp.sum(x.astype(jnp.float64))

    with jax.enable_x64(True):
        fs = A.check_dtype_discipline(
            bad, jax.ShapeDtypeStruct((8,), np.float32))
    assert "JX-DTYPE-F64" in _rules(fs)

    def good(x):
        return jnp.sum(x * 2.0)

    assert A.check_dtype_discipline(
        good, jax.ShapeDtypeStruct((8,), np.float32)) == []


def test_mixed_float_width_flagged():
    def bad(x):
        # f32 carry + bf16 xs into one scan: the recurrence dtype is
        # whatever promotion decides, not what the author wrote
        def body(c, w):
            return c + w.astype(jnp.float32), None
        out, _ = jax.lax.scan(body, x, jnp.zeros((3,), jnp.bfloat16))
        return out

    fs = A.check_dtype_discipline(bad, jax.ShapeDtypeStruct((8,),
                                                            np.float32))
    assert "JX-DTYPE-PROMOTION" in _rules(fs)


def test_mixed_ok_carveout_sanctions_bf16_sweep(block_ell):
    """JX-DTYPE-MIXED-OK: the bf16-scratch sweep kernel mixes widths by
    design (f32 coef table + bf16 blocks/iterates).  The raw trace flags
    it; the default carve-out — DTYPE_MIXED_OK rule metadata, NOT an
    allowlist entry — silences exactly those sanctioned-site findings."""
    from repro.kernels import ops
    from repro.kernels.cheb_sweep import cheb_sweep
    A_ell, lmax = block_ell
    c = jnp.ones((2, 6), jnp.float32)

    def fn(x):
        x2 = ops.pad_trailing(x, A_ell.padded_n)
        return cheb_sweep(A_ell.blocks, A_ell.indices, x2, c,
                          alpha=lmax / 2, interpret=True,
                          scratch_dtype="bf16")

    x = jax.ShapeDtypeStruct((64,), np.float32)
    raw = A.check_dtype_discipline(fn, x, mixed_ok=False)
    assert "JX-DTYPE-PROMOTION" in _rules(raw)
    assert all("repro/kernels/cheb_sweep.py" in f.path for f in raw)
    assert A.check_dtype_discipline(fn, x) == []
    # the carve-out is documented metadata, not a bare path list
    assert all(why for _frag, why in A.DTYPE_MIXED_OK)


def test_mixed_ok_carveout_does_not_shadow_accidents():
    """An accidental f32/bf16 mix OUTSIDE a sanctioned path still fires
    with the carve-out active (default mixed_ok=True)."""
    def bad(x):
        def body(c, w):
            return c + w.astype(jnp.float32), None
        out, _ = jax.lax.scan(body, x, jnp.zeros((3,), jnp.bfloat16))
        return out

    fs = A.check_dtype_discipline(bad, jax.ShapeDtypeStruct((8,),
                                                            np.float32))
    assert "JX-DTYPE-PROMOTION" in _rules(fs)


def test_complex_arma_solve_is_exempt():
    """ARMA mixes complex64 poles with f32 signals by design — the dtype
    rules must stay quiet on it."""
    from repro.core import graph, wavelets
    from repro.dist import GraphOperator
    g = graph.path_graph(32)
    lmax = g.lambda_max_bound()
    op = GraphOperator(P=g.laplacian(),
                       multipliers=wavelets.sgwt_multipliers(lmax, J=2),
                       lmax=lmax, K=4)
    plan = op.plan("dense")

    def fn(y):
        return plan.solve(y, "arma", tau=0.5).x

    assert A.check_dtype_discipline(
        fn, jax.ShapeDtypeStruct((32,), np.float32)) == []


# ---------------------------------------------------------------------------
# AST rules (fixture sources through lint_source)
# ---------------------------------------------------------------------------
LIB = "src/repro/somewhere.py"


def _lint(src, relpath=LIB, **kw):
    return A.lint_source(textwrap.dedent(src), relpath, **kw)


def test_ast_dense_materialization():
    src = """
    import jax.numpy as jnp

    def filt(L, f):
        w, v = jnp.linalg.eigh(L)
        return v @ (w * (v.T @ f))
    """
    fs = _lint(src)
    assert _rules(fs) == {"RP-DENSE-MAT"}
    assert fs[0].symbol == "filt"
    assert _lint(src, relpath="src/repro/kernels/ref.py") == []


def test_ast_order_loop():
    src = """
    def apply(mv, x, K):
        for k in range(K + 1):
            x = mv(x)
        return x
    """
    fs = _lint(src)
    assert _rules(fs) == {"RP-ORDER-LOOP"}
    assert _lint(src, relpath="src/repro/kernels/ref.py") == []


def test_ast_host_sync():
    fs = _lint("""
    import jax

    def pull(x):
        jax.block_until_ready(x)
        return jax.device_get(x)
    """)
    assert [f.rule for f in fs] == ["RP-HOST-SYNC", "RP-HOST-SYNC"]


def test_ast_unlogged_fallback():
    bad = """
    def dispatch(use, x):
        if not use:
            return _fallback_apply(x)
        return _fast_apply(x)
    """
    fs = _lint(bad)
    assert _rules(fs) == {"RP-FALLBACK-LOG"}
    good = """
    def dispatch(use, x):
        if not use:
            logger.info("dispatch: taking the fallback path")
            return _fallback_apply(x)
        return _fast_apply(x)
    """
    assert _lint(good) == []


def test_ast_legacy_scaffold_import(monkeypatch):
    monkeypatch.chdir(REPO)
    globs = ("src/repro/models/*", "src/repro/kernels/flash_attention.py")
    bad = "from repro.models import model\n"
    fs = _lint(bad, scaffold_globs=globs)
    assert _rules(fs) == {"RP-LEGACY-SCAFFOLD"}
    # relative form resolves too
    fs = A.lint_source("from .flash_attention import flash_attention\n",
                       "src/repro/kernels/newkernel.py",
                       scaffold_globs=globs)
    assert _rules(fs) == {"RP-LEGACY-SCAFFOLD"}
    # scaffold modules may import each other; non-scaffold imports are fine
    assert A.lint_source("from repro.models import model\n",
                         "src/repro/models/other.py",
                         scaffold_globs=globs) == []
    assert _lint("from repro.core import graph\n",
                 scaffold_globs=globs) == []


def test_ast_scaffold_files_skipped(monkeypatch):
    monkeypatch.chdir(REPO)
    src = "import jax\n\ndef f(L):\n    return jax.numpy.linalg.eigh(L)\n"
    assert A.lint_source(src, "src/repro/models/newthing.py",
                         scaffold_globs=("src/repro/models/*",)) == []


# ---------------------------------------------------------------------------
# Allowlist machinery
# ---------------------------------------------------------------------------
def test_allowlist_requires_justification(tmp_path):
    p = tmp_path / "allow.txt"
    p.write_text("[allow]\nRP-DENSE-MAT src/repro/foo.py\n")
    with pytest.raises(A.AllowlistError, match="justification"):
        A.Allowlist.load(str(p))


def test_allowlist_split_and_staleness(tmp_path):
    p = tmp_path / "allow.txt"
    p.write_text(textwrap.dedent("""
        [scaffold]
        src/repro/models/* -- dormant
        [allow]
        RP-DENSE-MAT src/repro/foo.py::g -- oracle path
        RP-ORDER-LOOP src/repro/never.py -- stale record
    """))
    al = A.Allowlist.load(str(p))
    assert al.scaffold_globs == ("src/repro/models/*",)
    hit = A.Finding(rule="RP-DENSE-MAT", path="src/repro/foo.py",
                    symbol="g", message="m")
    miss_sym = A.Finding(rule="RP-DENSE-MAT", path="src/repro/foo.py",
                         symbol="h", message="m")
    kept, suppressed = al.split([hit, miss_sym])
    assert suppressed == [hit] and kept == [miss_sym]
    stale = al.unused_entries([hit, miss_sym])
    assert [e.path_glob for e in stale] == ["src/repro/never.py"]


def test_repo_allowlist_parses_and_is_fully_exercised():
    """Every [allow] entry in the shipped allowlist must still match a
    real finding — otherwise it is a stale audit record."""
    al = A.Allowlist.load(os.path.join(REPO, "tools", "lint_allowlist.txt"))
    assert al.entries and al.scaffold
    for e in al.entries + al.scaffold:
        assert e.justification
    os.chdir(REPO)
    findings = A.lint_tree("src/repro", scaffold_globs=al.scaffold_globs)
    kept, suppressed = al.split(findings)
    assert kept == [], [str(f) for f in kept]
    assert al.unused_entries(findings) == [], "stale allowlist entries"


# ---------------------------------------------------------------------------
# Clean full-plan runs: all five backends
# ---------------------------------------------------------------------------
def _lint_op():
    from repro.core import graph, wavelets
    from repro.dist import GraphOperator
    g = graph.path_graph(64)
    lmax = g.lambda_max_bound()
    return GraphOperator(P=g.laplacian(),
                         multipliers=wavelets.sgwt_multipliers(lmax, J=2),
                         lmax=lmax, K=10)


def test_all_backends_clean_1shard():
    from repro.dist.backends import available_backends
    op = _lint_op()
    mesh = jax.make_mesh((1,), ("graph",))
    assert set(available_backends()) == {
        "dense", "pallas", "halo", "pallas_halo", "allgather"}
    for backend in available_backends():
        kwargs = {"mesh": mesh} if backend in ("halo", "pallas_halo",
                                               "allgather") else {}
        plan = op.plan(backend, **kwargs)
        fs = A.check_plan(plan, batches=(1, 8),
                          budget=plan.info.get("sweep_vmem_budget"),
                          solve_methods=("jacobi",))
        assert fs == [], (backend, [str(f) for f in fs])


PAYLOAD_8SHARD = r"""
import jax, numpy as np
from repro import analysis as A
from repro.core import graph, wavelets
from repro.dist import GraphOperator

g = graph.path_graph(64)
lmax = g.lambda_max_bound()
op = GraphOperator(P=g.laplacian(),
                   multipliers=wavelets.sgwt_multipliers(lmax, J=2),
                   lmax=lmax, K=10)
mesh = jax.make_mesh((8,), ("graph",))

# clean run: every sharded backend's real 8-shard schedule passes
for backend in ("halo", "pallas_halo", "allgather"):
    plan = op.plan(backend, mesh=mesh)
    fs = A.check_plan(plan, batches=(1, 64),
                      budget=plan.info.get("sweep_vmem_budget"),
                      solve_methods=("jacobi",))
    assert fs == [], (backend, [str(f) for f in fs])

# known-bad at real shard count: drop one link of the ring
P = jax.sharding.PartitionSpec
def bad(v):
    def inner(vl):
        perm = [(i, i + 1) for i in range(7)]   # device 7 never sends
        return jax.lax.ppermute(vl, "graph", perm=perm)
    return jax.shard_map(inner, mesh=mesh, in_specs=P("graph"),
                         out_specs=P("graph"), check_vma=False)(v)

fs = A.check_comm_schedule(bad, jax.ShapeDtypeStruct((64,), np.float32))
assert {f.rule for f in fs} == {"JX-PPERMUTE-BIJECTION"}, fs
assert "devices [7] never send" in fs[0].message, fs[0].message

# JX-FAULT-NO-EXTRA-COLLECTIVES, positive: a fully-armed fault config on
# the quantized wire traces the identical collective schedule as its
# clean twin on both sharded halo backends
fault_spec = {"drop_prob": 0.1, "stale_prob": 0.1, "noise_prob": 0.1,
              "seed": 3}
for backend in ("halo", "pallas_halo"):
    clean = op.plan(backend, mesh=mesh, exchange_dtype="int8")
    faulted = op.plan(backend, mesh=mesh, exchange_dtype="int8",
                      fault_spec=fault_spec, degradation="hold_last")
    fs = A.check_fault_schedule(clean, faulted, solve_methods=("jacobi",))
    assert fs == [], (backend, [str(f) for f in fs])

# negative: a plan whose exchange structure differs (K=12 vs K=10 — four
# extra rounds) is exactly what the rule must flag
op12 = GraphOperator(P=g.laplacian(),
                     multipliers=wavelets.sgwt_multipliers(lmax, J=2),
                     lmax=lmax, K=12)
fs = A.check_fault_schedule(op.plan("halo", mesh=mesh),
                            op12.plan("halo", mesh=mesh))
assert fs and {f.rule for f in fs} == {"JX-FAULT-NO-EXTRA-COLLECTIVES"}, fs
print("ANALYSIS 8SHARD OK")
"""


def test_all_backends_clean_8shards():
    out = run_payload(PAYLOAD_8SHARD, n_devices=8)
    assert "ANALYSIS 8SHARD OK" in out


def test_lint_cli_smoke():
    """The CLI entry point runs the ast+docs layers green on the repo."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_repro.py"),
         "--check", "--layers", "ast,docs"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout, proc.stdout + proc.stderr

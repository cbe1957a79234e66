"""The program's own tracing (`repro.obs`): every op of a compiled apply
sits in a named device phase, the scopes cost no instruction, path
changes and compiles are counted, and the Block-ELL fill is recorded at
plan build."""
import contextlib
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_payload
from repro import obs
from repro.core import graph, wavelets
from repro.dist import GraphOperator
from repro.dist.partition import partition_general

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import scopes  # noqa: E402

#: Instructions that carry the name of an argument or a value, not work
#: of a phase.
NOT_WORK = ("parameter", "constant", "tuple", "bitcast")
KIND = re.compile(r"(?:^|[\s}])([a-z][a-z0-9_-]*)\(")


@pytest.fixture(scope="module")
def op120():
    g, _ = graph.connected_sensor_graph(
        jax.random.PRNGKey(0), n=120, theta=0.2, kappa=0.25)
    lmax = g.lambda_max_bound()
    return GraphOperator(P=g.laplacian(),
                         multipliers=wavelets.sgwt_multipliers(lmax, J=2),
                         lmax=lmax, K=6)


def one_shard_mesh():
    return jax.make_mesh((1,), ("graph",))


#: The two benchmarked paths: one `cheb_sweep` launch (the `pallas`
#: backend), and the per-order SpMV + `cheb_step` loop (a one-shard
#: general `pallas_halo` plan with no VMEM for the sweep).
PATHS = {
    "sweep": (lambda op: op.plan("pallas", block=(8, 128)),
              {"repro.sweep", "repro.layout"}),
    "per_order": (lambda op: op.plan(
        "pallas_halo", mesh=one_shard_mesh(), partition="general",
        block=(8, 8), vmem_budget=0, use_pallas=True),
        {"repro.spmv", "repro.step", "repro.recurrence", "repro.layout"}),
}


def compiled_apply(plan, batch=3):
    x = jnp.ones((batch, plan.op.P.shape[0]), jnp.float32)
    return plan.compiled("apply").lower(x).compile().as_text()


def kind_of(rest):
    m = KIND.search(rest)
    return m.group(1) if m else None


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_op_of_the_apply_has_a_phase(op120, path):
    build, phases = PATHS[path]
    text = compiled_apply(build(op120))
    seen = set()
    for name, rest, op_name in scopes.instructions(text):
        if op_name is None or kind_of(rest) in NOT_WORK:
            continue
        phase = scopes.innermost(op_name)
        assert phase is not None, (name, op_name)
        seen.add(phase)
        if op_name.rsplit("/", 1)[-1] == "transpose":
            assert phase == "repro.layout", (name, op_name)
    assert phases <= seen, seen


def stripped(text):
    """The instruction lines of an HLO text, without their metadata."""
    return [re.sub(r",? metadata=\{[^}]*\}", "", rest)
            for _, rest, _ in scopes.instructions(text)]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_scopes_add_no_instruction(op120, path, monkeypatch):
    build, _ = PATHS[path]
    jax.clear_caches()
    scoped = compiled_apply(build(op120))
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    bare = compiled_apply(build(op120))
    jax.clear_caches()
    assert scopes.instruction_scopes(bare) == {}
    assert scopes.instruction_scopes(scoped)
    assert stripped(scoped) == stripped(bare)


@pytest.mark.parametrize("budget,counted", [(0, "cheb_sweep.fallback"),
                                            (None, "cheb_sweep.launch")])
def test_sweep_path_counted_once_per_trace(op120, budget, counted):
    plan = op120.plan("pallas", block=(8, 128), vmem_budget=budget)
    x = jnp.ones((2, 120), jnp.float32)
    other = ({"cheb_sweep.fallback", "cheb_sweep.launch"} - {counted}).pop()
    obs.reset()
    for traces in (1, 2):
        jax.jit(lambda v: plan.apply(v)).lower(x)  # a new function
        assert obs.snapshot().get(counted) == traces
    assert other not in obs.snapshot()


@pytest.mark.parametrize("path", sorted(PATHS))
def test_deferred_fold_counted_once_per_trace(op120, path):
    """The per-order loop counts its fold, of eta + 1 terms, once a
    trace; the single-launch sweep bypasses it."""
    plan = PATHS[path][0](op120)
    x = jnp.ones((2, 120), jnp.float32)
    eta = len(op120.multipliers)
    obs.reset()
    for traces in (1, 2):
        jax.jit(lambda v: plan.apply(v)).lower(x)  # a new function
        snap = obs.snapshot()
        if path == "per_order":
            assert snap.get("step.fold") == traces
            assert snap["step.fold_width"] == traces * (eta + 1)
            assert "cheb_sweep.launch" not in snap
        else:
            assert snap.get("cheb_sweep.launch") == traces
            assert "step.fold" not in snap


def test_compiles_counted_at_compile_time_only():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.25)
    x = jnp.arange(17.0)
    before = obs.snapshot()
    t0 = time.perf_counter()
    f(x).block_until_ready()
    first = obs.snapshot()
    assert first.get("compile.count", 0) == before.get("compile.count", 0) + 1
    assert first["compile.backend_s"] > before.get("compile.backend_s", 0.0)
    assert first["compile.trace_s"] > before.get("compile.trace_s", 0.0)
    recent = {name for end, name, _ in obs.events() if end >= t0}
    assert {"compile.trace_s", "compile.lower_s",
            "compile.backend_s"} <= recent
    f(x).block_until_ready()
    second = obs.snapshot()
    for key in ("compile.count", "compile.backend_s", "compile.trace_s"):
        assert second[key] == first[key]


def test_counters_add_and_reset():
    obs.reset()
    obs.count("a")
    obs.count("a", 2)
    obs.add("b_s", 0.25)
    obs.add("b_s", 0.5)
    assert obs.snapshot() == {"a": 3, "b_s": 0.75}
    obs.reset()
    assert obs.snapshot() == {} and obs.events() == []


PAYLOAD = r"""
import sys
sys.path.insert(0, %(root)r)
import jax, numpy as np
from bench import scopes
from repro.core import graph, wavelets
from repro.dist import GraphOperator

g, _ = graph.connected_sensor_graph(jax.random.PRNGKey(1), n=200,
                                    theta=0.12, kappa=0.15)
lmax = g.lambda_max_bound()
op = GraphOperator(P=g.laplacian(),
                   multipliers=wavelets.sgwt_multipliers(lmax, J=2),
                   lmax=lmax, K=4)
mesh = jax.make_mesh((4,), ("graph",))
gs, _ = graph.spatial_sort(g)
banded = GraphOperator(P=gs.laplacian(), multipliers=op.multipliers,
                       lmax=lmax, K=4)
x = jax.numpy.ones((2, g.n_vertices), jax.numpy.float32)
for plan in (op.plan("pallas_halo", mesh=mesh, partition="general",
                     block=(8, 8)),
             banded.plan("pallas_halo", mesh=mesh, block=(8, 8))):
    assert plan.info["exchange_collectives_per_round"] > 0
    text = plan.compiled("apply").lower(x).compile().as_text()
    perms = [(name, op_name)
             for name, rest, op_name in scopes.instructions(text)
             if " collective-permute" in rest]
    assert perms, "no collective-permute in the compiled apply"
    phases = set()
    for name, op_name in perms:
        assert op_name is not None, name
        phases.add(scopes.innermost(op_name))
    # the general plan also moves its signals between vertex and partition
    # order, shard to shard: those permutes are the reorder's, not rounds
    want = ({"repro.exchange", "repro.reorder"}
            if plan.info.get("reorder") == "sharded" else {"repro.exchange"})
    assert phases == want, (plan.info["partition"], phases)
    print("OK", plan.info["partition"], len(perms))
"""


def test_exchange_permutes_sit_in_the_exchange_phase():
    out = run_payload(PAYLOAD % {"root": ROOT}, n_devices=4)
    assert "OK general" in out and "OK banded" in out


#: The path graph 0-1-2-3-4: 5 diagonal and 8 off-diagonal entries.
PATH5 = (np.diag([1.0, 2.0, 2.0, 2.0, 1.0])
         - np.eye(5, k=1) - np.eye(5, k=-1)).astype(np.float32)


@pytest.mark.parametrize("backend,options", [
    ("pallas", {}),
    ("pallas_halo", {"partition": "banded"}),
    ("pallas_halo", {"partition": "general"}),
], ids=["pallas", "pallas_halo-banded", "pallas_halo-general"])
def test_blockell_fill_is_a_hand_count(backend, options):
    # (2, 2) blocks over 6 padded rows: 3 row blocks; rows 2-3 touch
    # column blocks 0, 1 and 2, so 3 slots: 13 of 3 * 3 * 2 * 2 = 36
    op = GraphOperator(P=PATH5, multipliers=wavelets.sgwt_multipliers(4.0,
                                                                      J=1),
                       lmax=4.0, K=3)
    if options.get("partition") == "general":
        options = {"partition": partition_general(
            PATH5, 1, order=np.arange(5), block=(2, 2))}
    if backend == "pallas_halo":
        options["mesh"] = one_shard_mesh()
    plan = op.plan(backend, block=(2, 2), **options)
    assert plan.info["blockell_fill"] == pytest.approx(13 / 36)


#: The ring 0-1-...-7-0: the edge 7-0 couples row block 0 to the last
#: column block.
RING8 = (2.0 * np.eye(8) - np.eye(8, k=1) - np.eye(8, k=-1)
         - np.eye(8, k=7) - np.eye(8, k=-7)).astype(np.float32)


@pytest.mark.parametrize("backend,options", [
    ("pallas", {}),
    ("pallas_halo", {"partition": "banded"}),
    ("pallas_halo", {"partition": "general"}),
], ids=["pallas", "pallas_halo-banded", "pallas_halo-general"])
def test_spmv_band_is_a_hand_count(backend, options):
    # (2, 2) blocks: row block 0 (rows 0-1) reaches column 7, in column
    # block 3, three blocks away; every other slot is at most one away
    op = GraphOperator(P=RING8, multipliers=wavelets.sgwt_multipliers(4.0,
                                                                      J=1),
                       lmax=4.0, K=3)
    if options.get("partition") == "general":
        options = {"partition": partition_general(
            RING8, 1, order=np.arange(8), block=(2, 2))}
    if backend == "pallas_halo":
        options["mesh"] = one_shard_mesh()
    plan = op.plan(backend, block=(2, 2), **options)
    assert plan.info["spmv_band"] == 3
    assert graph.block_ell_band(np.array([[0, 3]]), np.array([[True, False]]),
                                (2, 2)) == 0

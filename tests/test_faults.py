"""Fault injection (repro.dist.faults): spec validation, the
clean-path identity, and the 8-shard determinism / degradation gates.

The contract under test is the module's three-part promise:

* an inactive spec (None, p=0) is the *bitwise* clean path — same trace,
  same cache entries, same numbers;
* an active spec is a pure function of (seed, shard, round, link) — the
  same seed replays the identical fault trace on every backend and
  partition;
* every fault is receiver-side, after the ppermute — commstats keeps
  measuring exactly the paper's 2K|E| rounds under any injected
  configuration (the schedule half is also CI-gated by
  ``JX-FAULT-NO-EXTRA-COLLECTIVES``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_payload
from repro.dist import faults


# ---------------------------------------------------------------------------
# Spec plumbing (single device)
# ---------------------------------------------------------------------------
def test_fault_spec_validation():
    s = faults.FaultSpec(drop_prob=0.1, stale_prob=0.2, noise_prob=0.3,
                         seed=7)
    assert s.active and s.seed == 7
    assert not faults.FaultSpec().active
    for bad in ({"drop_prob": -0.1}, {"stale_prob": 1.5},
                {"noise_prob": 2.0}):
        with pytest.raises(ValueError):
            faults.FaultSpec(**bad)


def test_resolve_fault_spec_forms():
    assert faults.resolve_fault_spec(None) is None
    s = faults.FaultSpec(drop_prob=0.25)
    assert faults.resolve_fault_spec(s) is s
    assert faults.resolve_fault_spec(0.25) == s
    assert faults.resolve_fault_spec({"drop_prob": 0.25}) == s
    with pytest.raises(TypeError):
        faults.resolve_fault_spec(True)   # bool is not a probability
    with pytest.raises(TypeError):
        faults.resolve_fault_spec("0.25")


def test_fault_key_identity():
    # inactive collapses to "none": a p=0 plan may share the clean cache
    assert faults.fault_key(None) == "none"
    assert faults.fault_key(faults.FaultSpec()) == "none"
    assert faults.fault_key(0.0, "hold_last") == "none"
    k1 = faults.fault_key(0.1, "zero_fill")
    k2 = faults.fault_key(0.1, "hold_last")
    k3 = faults.fault_key({"drop_prob": 0.1, "seed": 1}, "zero_fill")
    assert len({k1, k2, k3, "none"}) == 4
    with pytest.raises(ValueError):
        faults.fault_key(0.1, "hold_first")


def test_make_injector_gating():
    # inactive spec or a non-exchanging site -> clean path (None)
    assert faults.make_injector(None, "zero_fill", "graph", True) is None
    assert faults.make_injector(0.0, "zero_fill", "graph", True) is None
    assert faults.make_injector(0.5, "zero_fill", "graph", False) is None
    inj = faults.make_injector(0.5, "hold_last", "graph", True)
    assert inj is not None and inj.degradation == "hold_last"
    # degradation typos raise even when the spec is inactive
    with pytest.raises(ValueError):
        faults.make_injector(None, "zerofill", "graph", True)


def test_spec_info_jsonable():
    import json
    assert faults.spec_info(None) is None
    d = faults.spec_info({"drop_prob": 0.1, "seed": 3})
    assert d == {"drop_prob": 0.1, "stale_prob": 0.0, "noise_prob": 0.0,
                 "seed": 3}
    json.dumps(d)


def test_plan_info_and_compat_key_carry_fault_identity():
    from repro.core import graph
    from repro.dist import GraphOperator
    from repro.serve.request import compat_key

    g = graph.path_graph(32)
    lmax = g.lambda_max_bound()
    op = GraphOperator(P=g.laplacian(),
                       multipliers=[lambda lam: jnp.exp(-lam)],
                       lmax=lmax, K=6)
    mesh = jax.make_mesh((1,), ("graph",))
    clean = op.plan("halo", mesh=mesh)
    assert clean.info["fault_key"] == "none"
    assert clean.info["fault_spec"] is None
    faulted = op.plan("halo", mesh=mesh, fault_spec=0.2,
                      degradation="hold_last")
    assert faulted.info["fault_key"] == faults.fault_key(0.2, "hold_last")
    assert faulted.info["fault_spec"]["drop_prob"] == 0.2
    kc = compat_key("default", clean, "apply", None)
    kf = compat_key("default", faulted, "apply", None)
    assert kc.faults == "none" and kf.faults == faulted.info["fault_key"]
    assert kc != kf and "faults=" in kf.label()


def test_build_rejects_bad_fault_args():
    from repro.core import graph
    from repro.dist import GraphOperator

    g = graph.path_graph(32)
    op = GraphOperator(P=g.laplacian(),
                       multipliers=[lambda lam: lam],
                       lmax=g.lambda_max_bound(), K=4)
    mesh = jax.make_mesh((1,), ("graph",))
    for backend in ("halo", "pallas_halo"):
        with pytest.raises(ValueError):
            op.plan(backend, mesh=mesh, fault_spec=0.1,
                    degradation="drop_everything")
        with pytest.raises(TypeError):
            op.plan(backend, mesh=mesh, fault_spec="lossy")


# ---------------------------------------------------------------------------
# 8-shard determinism / identity / degradation (both backends, both
# partitions, plus the gossip ring)
# ---------------------------------------------------------------------------
PAYLOAD = r"""
import functools
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.dist import FaultSpec, GraphOperator, gossip
from repro.dist.commstats import plan_comm_stats
from repro.dist.partition import community_graph_csr

rng = np.random.default_rng(0)
n, S, K, bw = 256, 8, 10, 8
B = np.zeros((n, n), dtype=np.float32)
for i in range(n):
    lo, hi = max(0, i - bw), min(n, i + bw + 1)
    B[i, lo:hi] = rng.standard_normal(hi - lo) * 0.1
B = np.abs(B + B.T) / 2
L = np.diag(B.sum(1)) - B
lmax = float(2 * B.sum(1).max())
banded_op = GraphOperator(P=jnp.asarray(L),
                          multipliers=[lambda lam: jnp.exp(-lam)],
                          lmax=lmax, K=K)

csr, meta = community_graph_csr(192, n_communities=8, seed=0)
general_op = GraphOperator(P=np.asarray(csr.to_dense()),
                           multipliers=[lambda lam: jnp.exp(-lam)],
                           lmax=meta["lmax"], K=K)

mesh = jax.make_mesh((S,), ("graph",))
spec = FaultSpec(drop_prob=0.2, stale_prob=0.1, noise_prob=0.05, seed=3)

# plans run through their jitted callables (one compile per plan; eager
# shard_map dispatch costs several times that).  pallas_halo on the
# general partition is trimmed: the injector is the
# same exchange-layer code on every backend/partition, its schedule
# equality there is lint-gated (JX-FAULT-NO-EXTRA-COLLECTIVES), and that
# combo's compile time alone pushes the payload past the CI timeout
for op, pkw, backends in ((banded_op, {}, ("halo", "pallas_halo")),
                          (general_op, {"partition": "general"},
                           ("halo",))):
    x = jnp.asarray(rng.standard_normal(
        (op.P.shape[0],)).astype(np.float32))
    for backend in backends:
        for dt in ("f32", "int8"):
            clean = op.plan(backend, mesh=mesh, exchange_dtype=dt, **pkw)
            ref = np.asarray(clean.compiled("apply")(x))
            # p=0 / None are the bitwise clean path and share its cache key
            for null_spec in (None, FaultSpec(seed=99)):
                p0 = op.plan(backend, mesh=mesh, exchange_dtype=dt,
                             fault_spec=null_spec,
                             degradation="hold_last", **pkw)
                assert p0.info["fault_key"] == "none"
                assert np.array_equal(np.asarray(p0.compiled("apply")(x)), ref), (
                    backend, dt, pkw, null_spec)
            # same seed -> bitwise-identical faulted runs (fresh plans)
            runs = [np.asarray(
                op.plan(backend, mesh=mesh, exchange_dtype=dt,
                        fault_spec=spec, degradation="zero_fill",
                        **pkw).compiled("apply")(x)) for _ in range(2)]
            assert np.array_equal(runs[0], runs[1]), (backend, dt, pkw)
            # active faults really perturb, boundedly
            err = float(np.abs(runs[0] - ref).max())
            assert err > 0 and np.isfinite(runs[0]).all(), (
                backend, dt, pkw, err)
            # a different seed replays a different trace
            other = np.asarray(
                op.plan(backend, mesh=mesh, exchange_dtype=dt,
                        fault_spec=FaultSpec(drop_prob=0.2, stale_prob=0.1,
                                             noise_prob=0.05, seed=4),
                        degradation="zero_fill", **pkw).compiled("apply")(x))
            assert not np.array_equal(other, runs[0]), (backend, dt, pkw)
            # hold_last consumes the carried tiles -> a distinct trace
            held = np.asarray(
                op.plan(backend, mesh=mesh, exchange_dtype=dt,
                        fault_spec=spec, degradation="hold_last",
                        **pkw).compiled("apply")(x))
            assert not np.array_equal(held, runs[0]), (backend, dt, pkw)
            # honest accounting: rounds identical to the clean plan
            faulted = op.plan(backend, mesh=mesh, exchange_dtype=dt,
                              fault_spec=spec, **pkw)
            stc = plan_comm_stats(clean)["apply"]
            stf = plan_comm_stats(faulted)["apply"]
            assert stf.exchange_rounds == stc.exchange_rounds == K
            assert stf.bytes_per_round == stc.bytes_per_round

# the gossip ring rides the SAME injector (link 0/1 = from-left/right)
coeffs = gossip.consensus_coeffs(S)
xg = jnp.arange(S * 4, dtype=jnp.float32).reshape(S, 4) ** 1.1
target = np.asarray(jnp.mean(xg, axis=0))

def run_gossip(fault_spec, degradation="zero_fill", quantize=False):
    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("graph"),
                       out_specs=P("graph"), check_vma=False)
    def body(xl):
        return gossip.gossip_mean(xl, "graph", coeffs, quantize=quantize,
                                  fault_spec=fault_spec,
                                  degradation=degradation)
    return np.asarray(body(xg))

g_clean = run_gossip(None)
assert np.array_equal(run_gossip(FaultSpec()), g_clean)
g_f1 = run_gossip(spec)
assert np.array_equal(g_f1, run_gossip(spec))          # deterministic
assert not np.array_equal(g_f1, g_clean)               # really faulted
assert np.isfinite(g_f1).all()
gq = run_gossip(spec, quantize=True)                   # noise on int8 wire
assert np.isfinite(gq).all() and not np.array_equal(gq, g_f1)
# bounded degradation is gated at a survivable drop rate: the consensus
# polynomial's Chebyshev weights oscillate, so at drop_prob=0.2 both
# policies overshoot the mean by >1x (the aggressive spec above is only
# for determinism/trace assertions).  Even at 5% one draw is no gate: a
# fault landing on a high-weight round overshoots on its own (single
# seeds reach ~1.5x), while most draws hit low-weight rounds — so the
# typical draw, the median over seeds, must stay under the mean's scale
rels = []
for seed in range(8):
    g_mild = run_gossip(FaultSpec(drop_prob=0.05, stale_prob=0.05,
                                  noise_prob=0.05, seed=seed))
    assert np.isfinite(g_mild).all(), seed
    rels.append(float(np.abs(g_mild - target[None]).max()
                      / np.abs(target).max()))
rel = float(np.median(rels))
assert rel < 1.0, rels                                 # degraded, bounded
print("FAULTS OK", rel)
"""


def test_faults_8shards():
    out = run_payload(PAYLOAD, n_devices=8, timeout=900)
    assert "FAULTS OK" in out

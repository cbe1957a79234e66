"""The readers the four-chip cell adds (``exchange_share``,
``reorder_share``) on hand-made events, and the cell's configuration run
on four CPU devices at a small size: sound, and not correct once the
exchange between the shards is left out."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.devtrace import Events
from bench.metrics import exchange_share, reorder_share
from bench.tests.test_readers import fake_run

ROOT = harness.ROOT
SEED = 2 ** 31 + 4242


def test_hand_made_exchange_and_reorder():
    # window [0, 10]; device 0: a permute on [1, 3] under a fusion on
    # [2, 4], so exposed on [1, 2], a reorder gather on [5, 6] and the
    # exchange's coupling scatter on [7, 8], alone but no permute;
    # device 1: a permute on [1, 2] alone, the fusion on [2, 4], the
    # reorder on [5, 5.5]
    ops = {0: [("collective-permute-start.1", 1.0, 3.0),
               ("fusion.2", 2.0, 4.0), ("gather.3", 5.0, 6.0),
               ("scatter.4", 7.0, 8.0)],
           1: [("collective-permute-start.1", 1.0, 2.0),
               ("fusion.2", 2.0, 4.0), ("gather.3", 5.0, 5.5)]}
    host = [("bench.issue", 0.0, 1.0), ("bench.wait", 1.0, 10.0)]
    phases = {"collective-permute-start.1": "repro.exchange",
              "fusion.2": "repro.spmv", "gather.3": "repro.reorder",
              "scatter.4": "repro.exchange"}
    ev = Events(ops, host)
    run = fake_run(ev, phases=phases)
    assert ev.busy_s() == pytest.approx(4.25)
    assert exchange_share.read(run) == pytest.approx(100 * 1.0 / 4.25)
    assert reorder_share.read(run) == pytest.approx(100 * 0.75 / 4.25)


def test_nothing_to_read():
    ops = {0: [("fusion.2", 2.0, 4.0)]}
    ev = Events(ops, [("bench.wait", 0.0, 10.0)])
    # a program without phases, or whose phases hold neither step
    for phases in ({}, {"fusion.2": "repro.spmv"}):
        run = fake_run(ev, phases=phases)
        assert exchange_share.read(run) is None
        assert reorder_share.read(run) is None
    # an untraced run
    run = fake_run(None, phases={"fusion.2": "repro.exchange"})
    assert exchange_share.read(run) is None
    assert reorder_share.read(run) is None


# the cell's own files, a smaller field and batch, four CPU devices
FOUR_DEVICES = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness
spec = harness.load_spec("sensor4m_x4.apply_b64")
spec.config["graph"]["n"] = 4000
spec.traffic["batch"] = 8
r = harness.run("sensor4m_x4.apply_b64", {seed}, 0.5, False,
                require_chip=False, spec=spec, plan_overrides={overrides})
print(json.dumps(r))
"""


@pytest.mark.parametrize("overrides, correct", [
    ({}, True),
    # the exchange between chips left out: every received tile dropped
    ({"fault_spec": {"drop_prob": 1.0}, "degradation": "zero_fill"}, False),
])
def test_cell_on_four_devices(overrides, correct):
    overrides = dict(overrides, use_pallas=None)
    code = FOUR_DEVICES.format(root=ROOT, src=os.path.join(ROOT, "src"),
                               seed=SEED, overrides=overrides)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [s for s in out.stdout.splitlines() if s.startswith("# messages")]
    msgs = line[0].split("# messages: ")[1].split(",")[0]
    assert msgs == line[0].split("2K|E| = ")[1].split(",")[0], line
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"] is correct, r["checks"]


PHASES = r"""
import sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness, scopes
spec = harness.load_spec("sensor4m_x4.apply_b64")
spec.config["graph"]["n"] = 4000
spec.traffic["batch"] = 8
cell = harness.build_cell(spec, harness.chips_for(4, require_chip=False),
                          {{"use_pallas": None}})
pool, entry, keep = harness.prepare(cell, {seed})
harness.program_facts(cell, entry, pool[0])
names = set(scopes.of_cell(cell).values())
assert {{"repro.exchange", "repro.reorder"}} <= names, names
print("OK", sorted(names))
"""


def test_phases_of_the_four_shard_program():
    """The traced run's readers find the exchange and the reorder in the
    program the cell times, compiled again with the structure it takes
    as arguments."""
    code = PHASES.format(root=ROOT, src=os.path.join(ROOT, "src"), seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_four_chip_work_is_the_hand_count():
    """sensor4m_x4.apply_b64's apply against PERF.md's hand count: bytes
    over four chips' HBM bandwidth bound it (2.5966 ms)."""
    from bench import work

    n, nnz = 4_000_000, 39_305_238
    assert work.apply_bytes(n, nnz, 64, eta=7) == 8_506_441_904
    assert work.apply_flops(n, nnz, 64, K=20, eta=7) == 175_885_409_280
    assert work.least_seconds(n, nnz, 64, 20, 7, 4, "TPU v5 lite") == \
        pytest.approx(8_506_441_904 / (4 * 819e9), rel=1e-12)


GATHERS = r"""
import dataclasses, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness
from bench.graphs import load
from repro.dist import partition

spec = dict(harness.load_spec("sensor4m_x4.apply_b64").config["graph"],
            n=400)
assert load(spec).n == 400
# the program as it was before it kept results sharded: every multi-shard
# plan gathers its signals whole
whole = partition.partition_general
partition.partition_general = lambda *a, **k: dataclasses.replace(
    whole(*a, **k), reorder=None)
try:
    load(spec)
except RuntimeError as e:
    assert "sharded" in str(e), e
    print("REFUSED")
"""


def test_a_program_that_gathers_is_refused_at_once():
    """The cell's graph kind refuses, before any set-up, a program whose
    multi-shard plans hand back their results whole on every device:
    such a program compiles this deployment for minutes before it can
    fail."""
    code = GATHERS.format(root=ROOT, src=os.path.join(ROOT, "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "REFUSED" in out.stdout

"""The trace reduction: busy union, idle share, op count, exposed
collective time and the breakdown, on hand-made events and on traces
recorded on the chip (``data/*.events.json.gz``), against a plain
sweep over the event boundaries."""
import glob
import os

import pytest

from bench import devtrace
from bench.devtrace import Events, is_collective_permute

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = sorted(glob.glob(os.path.join(DATA, "*.events.json.gz")))


def hand_made():
    # window [0, 10] from the host spans; device 0 busy on [1, 4] (a loop
    # enclosing two ops) and [5, 6]; device 1 on [2, 9], where the
    # collective [3, 5] overlaps the op [4.5, 5.5] for half a second
    ops = {0: [("while.1", 1.0, 4.0), ("fusion.1", 1.0, 3.0),
               ("cheb_step.2", 3.0, 4.0), ("copy", 5.0, 6.0)],
           1: [("fusion.3", 2.0, 3.0), ("collective-permute-done.1", 3.0,
                                         5.0), ("fusion.4", 4.5, 9.0)]}
    host = [("bench.pick", 0.0, 0.5), ("bench.issue", 0.5, 1.0),
            ("bench.wait", 1.0, 10.0)]
    return Events(ops, host)


def test_hand_made_arithmetic():
    ev = hand_made()
    assert ev.window() == (0.0, 10.0)
    assert [n for n, _, _ in ev.ops[0]] == ["fusion.1", "cheb_step.2",
                                            "copy"]
    assert ev.busy(0) == pytest.approx(4.0)
    assert ev.busy(1) == pytest.approx(7.0)
    assert ev.busy_s() == pytest.approx(5.5)
    assert ev.idle_share() == pytest.approx(0.45)
    assert ev.op_count() == pytest.approx(3.0)
    assert ev.exposed(1, is_collective_permute) == pytest.approx(1.5)
    assert ev.exposed(0, is_collective_permute) == 0.0
    b = ev.breakdown()
    assert b["device_ops"][0] == ["fusion", pytest.approx(3.75)]
    # device 0's gaps: [0, 1] under bench.pick/issue/wait, [4, 5] and
    # [6, 10] under bench.wait
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(4.0)]
    assert sorted(g for _, g in b["idle_gaps"]) == pytest.approx(
        [1.0, 1.0, 4.0])


def test_base_names():
    assert devtrace.base_name("fusion.12") == "fusion"
    assert devtrace.base_name("block_ell_spmv_batched.3.1") == \
        "block_ell_spmv_batched"
    assert devtrace.base_name("cheb_sweep") == "cheb_sweep"


def sweep(intervals, lo, hi, pick=lambda n: True, against=None):
    """Seconds in [lo, hi] where some picked op runs (and, given
    `against`, no op `against` picks does): a sweep over boundaries."""
    points = sorted({lo, hi} | {t for _, s, e in intervals
                                for t in (s, e) if lo < t < hi})
    total = 0.0
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        live = [n for n, s, e in intervals if s <= mid < e]
        if any(pick(n) for n in live) and not (
                against and any(against(n) for n in live)):
            total += b - a
    return total


@pytest.mark.skipif(not RECORDED, reason="no recorded trace")
@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace_against_a_sweep(path):
    ev = Events.from_json(path)
    lo, hi = ev.window()
    assert hi > lo
    for d, ops in ev.ops.items():
        assert ev.busy(d) == pytest.approx(sweep(ops, lo, hi), rel=1e-9,
                                           abs=1e-12)
        assert 0.0 < ev.busy(d) <= hi - lo
        coll = ev.exposed(d, is_collective_permute)
        assert coll == pytest.approx(
            sweep(ops, lo, hi, is_collective_permute,
                  lambda n: not is_collective_permute(n)),
            rel=1e-9, abs=1e-12)
    assert 0.0 <= ev.idle_share() < 1.0
    count = sum(1 for ops in ev.ops.values() for _, s, _ in ops
                if lo <= s < hi) / len(ev.ops)
    assert ev.op_count() == count > 0
    b = ev.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(t for _, t in b["device_ops"]) <= ev.busy_s() * (1 + 1e-9) \
        or len(b["device_ops"]) == 10

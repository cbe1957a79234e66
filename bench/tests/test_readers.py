"""The readers this benchmark adds for the program's phases and
counters, on hand-made events and on traces recorded on the chip with
the program's scopes (``data/*_scoped.events.json.gz``, written by
``bench/record_trace.py``), against a plain sweep over the event
boundaries; and every older reader pinned to what it reads on the
traces recorded before the program had scopes."""
import glob
import gzip
import json
import os
import types

import pytest

from bench import counters, scopes
from bench.devtrace import Events, is_collective_permute
from bench.metrics import (apply_roofline, blockell_fill, compile_s,
                           compiles_in_window, idle_share, launches_per_call,
                           layout_share)
from bench.tests.test_devtrace import DATA, sweep

SCOPED = sorted(glob.glob(os.path.join(DATA, "*_scoped.events.json.gz")))

#: What each reader gave on the older traces before the readers of this
#: module were added, in a cell of the given size (the run's `calls` are
#: the trace's ``bench.wait`` spans).
PINNED = {
    "per_order": {
        "graph": (1_000_000, 9_824_716), "calls": 5,
        "apply_roofline": 0.20469057756721248,
        "idle_share": 0.08154496926806587,
        "launches_per_call": 748.0,
        "busy_s": 6.342691621000009, "window_s": 6.347867988000001,
        "breakdown": {
            "device_ops": [
                ["block_ell_spmv_batched", 4.863104933000026],
                ["cheb_step", 0.7292890199999977],
                ["copy", 0.47684839100000254],
                ["pad_bitcast_fusion", 0.1562933379999949],
                ["fusion", 0.09775370399999961],
                ["multiply_add_fusion", 0.019262646000000494],
                ["pad_clamp_fusion", 9.37129999991626e-05],
                ["dynamic-slice_bitcast_fusion", 3.930199999935269e-05],
                ["copy-start", 3.98000000689791e-06],
                ["copy-done", 2.593999989919382e-06]],
            "idle_gaps": [
                ["bench.wait", 0.0011206559999998866],
                ["bench.wait", 0.0010899599999998344],
                ["bench.wait", 0.0010041469999997332],
                ["bench.wait", 0.0008844329999999623],
                ["bench.wait", 0.0006254170000001835],
                ["bench.issue", 0.0004474699999999984],
                ["bench.wait", 3.000000248221113e-09],
                ["bench.wait", 3.000000248221113e-09],
                ["bench.wait", 3.000000248221113e-09],
                ["bench.wait", 3.000000248221113e-09]]}},
    "sweep": {
        "graph": (500, 4434), "calls": 512,
        "apply_roofline": 0.10306905840500194,
        "idle_share": 35.762256766710635,
        "launches_per_call": 2.99609375,
        "busy_s": 0.6426096370000011, "window_s": 1.000361477,
        "breakdown": {
            "device_ops": [["cheb_sweep", 0.6357359170000001],
                           ["slice", 0.005851467999999915],
                           ["pad_bitcast_fusion", 0.0010222520000005009]],
            "idle_gaps": [
                ["bench.wait", 0.0016900710000000707],
                ["bench.wait", 0.0009362490000000001],
                ["bench.wait", 0.0009315510000000027],
                ["bench.wait", 0.0009103340000000126],
                ["bench.wait", 0.000906320000000016],
                ["bench.wait", 0.0008978139999999968],
                ["bench.wait", 0.0008910950000000084],
                ["bench.wait", 0.0008859079999999908],
                ["bench.wait", 0.0008842780000000161],
                ["bench.wait", 0.0008674109999999985]]}},
}


def fake_run(events, n=500, nnz=4434, calls=1, info=None, start=0.0,
             end=1.0, phases=None):
    cell = types.SimpleNamespace(
        graph=types.SimpleNamespace(n=n, nnz=nnz), batch=64,
        operator={"K": 20}, facts={"eta": 7}, devices=[0],
        plan=types.SimpleNamespace(info=info or {}), phases=phases or {})
    return types.SimpleNamespace(
        cell=cell, events=events, device_kind="TPU v5 lite",
        window=types.SimpleNamespace(calls=calls, start=start, end=end))


@pytest.mark.parametrize("name", sorted(PINNED))
def test_older_traces_read_as_before(name):
    pin = PINNED[name]
    ev = Events.from_json(os.path.join(DATA, f"{name}.events.json.gz"))
    run = fake_run(ev, *pin["graph"], calls=pin["calls"])
    assert apply_roofline.read(run) == pin["apply_roofline"]
    assert idle_share.read(run) == pin["idle_share"]
    assert launches_per_call.read(run) == pin["launches_per_call"]
    assert ev.busy_s() == pin["busy_s"]
    assert ev.window_s() == pin["window_s"]
    assert ev.exposed(0, is_collective_permute) == 0
    assert ev.breakdown() == pin["breakdown"]
    # a program without phases or counters: nothing to read
    assert layout_share.read(run) is None
    assert blockell_fill.read(run) is None


def test_hand_made_phases():
    # window [0, 10] from the host spans; device 0 busy on [1, 4] and
    # [5, 6]: a transpose, the sweep, and a copy outside every phase
    ops = {0: [("transpose.1", 1.0, 2.0), ("cheb_sweep.2", 2.0, 4.0),
               ("copy.3", 5.0, 6.0)]}
    host = [("bench.pick", 0.0, 0.3), ("bench.issue", 0.3, 1.5),
            ("bench.wait", 1.5, 10.0)]
    ev = Events(ops, host)
    phases = {"transpose.1": "repro.layout", "cheb_sweep.2": "repro.sweep"}
    assert ev.busy_s() == pytest.approx(4.0)
    assert scopes.phase_share(ev, phases, "repro.layout") == \
        pytest.approx(0.25)
    assert scopes.phase_share(ev, phases, "repro.sweep") == pytest.approx(0.5)
    assert scopes.phase_share(ev, phases, None) == pytest.approx(0.25)
    run = fake_run(ev, info={"blockell_fill": 0.0169}, phases=phases)
    assert layout_share.read(run) == pytest.approx(25.0)
    assert blockell_fill.read(run) == pytest.approx(1.69)
    assert layout_share.read(fake_run(None, phases=phases)) is None


HLO = """\
%fused_computation (param_0.5: f32[4,4]) -> f32[4,3] {
  %param_0.5 = f32[4,4]{0,1} parameter(0)
  ROOT %slice.0 = f32[4,3]{1,0} slice(%param_0.5), slice={[0:4], [0:3]}, \
metadata={op_name="jit(apply)/repro.apply/repro.layout/slice" \
stack_frame_id=5}
}
ENTRY %main (x: f32[4,4]) -> f32[4,3] {
  %x = f32[4,4]{1,0} parameter(0), metadata={op_name="x"}
  %copy.10 = f32[4,4]{0,1} copy(%x)
  %spmv.3 = f32[4,4]{1,0} custom-call(%copy.10), \
metadata={op_name="jit(apply)/repro.apply/repro.recurrence/while/body/\
closed_call/repro.spmv/jit(block_ell_spmv_batched)/pallas_call"}
  ROOT %slice_fusion = f32[4,3]{1,0} fusion(%spmv.3), kind=kLoop, \
calls=%fused_computation, \
metadata={op_name="jit(apply)/repro.apply/repro.layout/slice"}
}
"""


#: A loop as the TPU compiler leaves it: a transpose turned into a copy
#: that changes the layout, a copy of the loop carry, and the argument's
#: relayout, none with a phase of its own.
LOOP_HLO = """\
%body (arg: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %arg = (s32[], f32[4,4]{1,0}) parameter(0)
  %gte = f32[4,4]{1,0:T(8,128)} get-tuple-element(%arg), index=1
  %spmv.1 = f32[4,4]{1,0:T(8,128)} custom-call(%gte), \
metadata={op_name="jit(apply)/repro.apply/repro.recurrence/while/body/\
repro.spmv/pallas_call"}
  %copy.2 = f32[4,4]{0,1:T(8,128)} copy(%spmv.1)
  %copy.3 = f32[4,4]{1,0:T(8,128)S(1)} copy(%gte)
  %i = s32[] get-tuple-element(%arg), index=0
  ROOT %tuple.4 = (s32[], f32[4,4]{1,0}) tuple(%i, %copy.3)
}
ENTRY %main (x: f32[4,4]) -> f32[4,4] {
  %x = f32[4,4]{1,0} parameter(0), metadata={op_name="x"}
  %copy.9 = f32[4,4]{0,1} copy(%x), metadata={op_name="x"}
  %zero = s32[] constant(0)
  %t = (s32[], f32[4,4]{1,0}) tuple(%zero, %copy.9)
  %while.5 = (s32[], f32[4,4]{1,0}) while(%t), condition=%cond, \
body=%body, metadata={op_name="jit(apply)/repro.apply/repro.recurrence/while"}
  ROOT %out = f32[4,4]{1,0} get-tuple-element(%while.5), index=1
}
"""


def test_instruction_scopes_from_hlo_text():
    assert scopes.instruction_scopes(HLO) == {
        "slice.0": "repro.layout", "spmv.3": "repro.spmv",
        "slice_fusion": "repro.layout", "copy.10": "repro.layout",
        "param_0.5": "repro.layout", "x": "repro.layout"}
    got = scopes.instruction_scopes(LOOP_HLO)
    assert got["copy.2"] == "repro.layout"        # a transpose, relaid
    assert got["copy.9"] == "repro.layout"        # the argument, relaid
    assert got["copy.3"] == "repro.recurrence"    # the carry: via the loop
    assert got["while.5"] == "repro.recurrence"
    # a program without phases has none, whatever its copies
    assert scopes.instruction_scopes(LOOP_HLO.replace("repro.", "r.")) == {}
    assert scopes.innermost("jit(f)/repro.apply/while/body") == "repro.apply"
    assert scopes.innermost("jit(f)/reprox/while") is None
    assert scopes.instruction_scopes(None) == {}


def test_compile_seconds_before_counts_nested_spans_once(monkeypatch):
    from repro import obs

    events = [(2.0, "compile.trace_s", 0.5),    # inner trace [1.5, 2.0]
              (3.0, "compile.trace_s", 2.0),    # its caller [1.0, 3.0]
              (3.5, "compile.lower_s", 0.5),
              (3.5, "compile.cache_hits", 0.0),
              (5.0, "compile.backend_s", 1.0),
              (9.0, "compile.backend_s", 1.0)]  # after the window opened
    monkeypatch.setattr(obs, "events", lambda: events)
    assert counters.compile_seconds_before(6.0) == pytest.approx(3.5)
    assert counters.compiles_between(6.0, 10.0) == 1
    assert counters.compiles_between(0.0, 10.0) == 2
    run = fake_run(None, start=6.0, end=10.0)
    assert compile_s.read(run) == pytest.approx(3.5)
    assert compiles_in_window.read(run) == 1


@pytest.mark.skipif(not SCOPED, reason="no recorded trace with scopes")
@pytest.mark.parametrize("path", SCOPED, ids=os.path.basename)
def test_recorded_phases_against_a_sweep(path):
    ev = Events.from_json(path)
    with gzip.open(path, "rt") as f:
        phases = json.load(f)["scopes"]
    lo, hi = ev.window()
    assert phases
    busy = sum(sweep(ops, lo, hi) for ops in ev.ops.values()) / len(ev.ops)
    shares = {}
    for phase in set(phases.values()) | {None}:
        mine = sum(sweep(ops, lo, hi, lambda n: phases.get(n) == phase)
                   for ops in ev.ops.values()) / len(ev.ops)
        shares[phase] = scopes.phase_share(ev, phases, phase)
        assert shares[phase] == pytest.approx(mine / busy, rel=1e-9,
                                              abs=1e-12)
    # the ops of a window run one at a time: the phases part busy time
    assert sum(shares.values()) == pytest.approx(1.0, rel=1e-9)
    assert shares[None] <= 0.01
    assert shares["repro.layout"] > 0

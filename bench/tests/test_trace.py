"""The reduction from a trace to the per-layer metrics and the breakdown."""
from __future__ import annotations

import os

import pytest

from bench.trace import Trace, op_kinds, read
from bench.tests.helpers import ROOT, reader


def synthetic():
    """Two chips over a 10 s window: chip 0 busy 6 s (one overlap merged),
    chip 1 busy 4 s; the host dispatches then reads results."""
    ops = {
        "/device:TPU:0": [("fusion.1", 0.0, 2.0), ("scatter.3", 1.0, 2.0),
                          ("all-gather.2", 5.0, 1.0), ("fusion.1", 7.0, 2.0)],
        "/device:TPU:1": [("fusion.1", 0.0, 3.0), ("all-reduce.4", 6.0, 1.0)],
    }
    spans = [("dispatch", 0.0, 6.5), ("read_results", 6.5, 10.0)]
    kinds = {"fusion.1": "add", "scatter.3": "scatter",
             "all-gather.2": "all-gather", "all-reduce.4": "all-reduce"}
    return Trace(ops, spans, kinds)


def test_busy_window_and_idle_share():
    tr = synthetic()
    assert tr.window_s() == 10.0
    assert tr.device_busy_s("/device:TPU:0") == pytest.approx(6.0)
    assert tr.busy_s() == pytest.approx(5.0)
    assert reader("device.idle_share")(tr) == pytest.approx(50.0)


def test_scatter_and_collective_shares():
    tr = synthetic()
    assert reader("tick.scatter_share")(tr) == pytest.approx(100 * 2 / 11)
    # busiest chip is chip 0: 1 s of collectives in 7 s of op time
    assert reader("mesh.collective_share")(tr) == pytest.approx(100 / 7)


def test_breakdown_names_and_gaps():
    b = synthetic().breakdown()
    assert b["device_ops"][0] == ["fusion.1 [add]", 7.0]
    assert [n for n, _ in b["device_ops"]] == [
        "fusion.1 [add]", "scatter.3 [scatter]", "all-gather.2 [all-gather]",
        "all-reduce.4 [all-reduce]"]
    # chip 0 idles 3-5 (dispatch), 6-7 (0.5 dispatch, 0.5 read: dispatch
    # first), 9-10 (read_results)
    assert dict(b["idle_gaps"]) == pytest.approx({"dispatch": 3.0, "read_results": 1.0})


def test_readers_find_nothing_in_an_empty_trace():
    tr = Trace({}, [("dispatch", 0.0, 1.0)])
    for name in ("device.idle_share", "tick.scatter_share", "tick.gather_share",
                 "mesh.collective_share"):
        assert reader(name)(tr) is None
    one_chip = Trace({"/device:TPU:0": [("fusion", 0.0, 1.0)]},
                     [("dispatch", 0.0, 1.0)], {"fusion": "add"})
    assert reader("mesh.collective_share")(one_chip) is None
    # without the programs' HLO no op has a kind, and no share is read
    unknown = synthetic()
    unknown.kinds = None
    for name in ("tick.scatter_share", "tick.gather_share", "mesh.collective_share"):
        assert reader(name)(unknown) is None


# A fused computation whose root is a scatter, called by `fusion.7`; a
# fusion nested in another; a plain op; an async all-gather's two halves.
HLO = """HloModule jit_sweep, entry_computation_layout={()->f32[8]{0}}

%fused_computation.2 (param_0: f32[8], param_1: s32[4,1], param_2: f32[4]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %scatter.5 = f32[8]{0:T(1024)S(1)} scatter(f32[8]{0} %param_0, s32[4,1]{1,0} %param_1, f32[4]{0} %param_2), update_window_dims={}, to_apply=%region_0.1
}

%fused_computation.3 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %fusion.6 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused_computation.2
}

ENTRY %main.9 (a: f32[8], i: s32[4,1], u: f32[4]) -> (f32[8], f32[32]) {
  %a = f32[8]{0} parameter(0)
  %fusion.7 = f32[8]{0:T(1024)} fusion(f32[8]{0} %a, s32[4,1]{1,0} %i, f32[4]{0} %u), kind=kLoop, calls=%fused_computation.2
  %fusion.8 = f32[8]{0} fusion(f32[8]{0} %fusion.7), kind=kLoop, calls=%fused_computation.3
  %add.1 = f32[8]{0} add(f32[8]{0} %fusion.8, f32[8]{0} %a)
  %all-gather-start.1 = (f32[8]{0}, f32[32]{0}) all-gather-start(f32[8]{0} %add.1), dimensions={0}
  %all-gather-done.1 = f32[32]{0} all-gather-done((f32[8]{0}, f32[32]{0}) %all-gather-start.1)
  ROOT %tuple.2 = (f32[8]{0}, f32[32]{0}) tuple(f32[8]{0} %add.1, f32[32]{0} %all-gather-done.1)
}
"""


def test_op_kinds_see_through_fusions():
    kinds = op_kinds([HLO])
    assert kinds["fusion.7"] == "scatter"
    assert kinds["fusion.8"] == "scatter"
    assert kinds["scatter.5"] == "scatter"
    assert kinds["add.1"] == "add"
    assert kinds["all-gather-start.1"] == "all-gather-start"
    assert kinds["tuple.2"] == "tuple"


CHIP_TRACE = os.path.join(ROOT, "bench", "testdata", "ft8.permutation.trace.json.gz")


def test_recorded_chip_trace():
    """16 ms of an `ft8.permutation` window traced on a TPU v5e: about one
    and a half engine ticks, 6,000 op events, with the kinds the program's
    compiled HLO gives them.  Every scatter and gather there runs as a
    `fusion.<n>`, so a reader that matched op names would find none."""
    tr = read(CHIP_TRACE)
    assert sum(len(v) for v in tr.ops.values()) == 6000
    assert tr.window_s() == pytest.approx(0.015772402, rel=1e-6)
    scatters = [n for n in tr.op_seconds() if tr.kind(n) == "scatter"]
    assert scatters and not any("scatter" in n for n in scatters)
    assert reader("device.idle_share")(tr) == pytest.approx(0.21426032628, rel=1e-6)
    assert reader("tick.scatter_share")(tr) == pytest.approx(19.2596983956, rel=1e-6)
    assert reader("tick.gather_share")(tr) == pytest.approx(76.7351249828, rel=1e-6)
    assert reader("mesh.collective_share")(tr) is None      # one chip
    b = tr.breakdown()
    assert [n for n, _ in b["device_ops"][:4]] == [
        "fusion.430 [gather]", "fusion.433 [gather]",
        "fusion.388 [scatter]", "fusion.389 [scatter]"]
    assert [n for n, _ in b["idle_gaps"]] == ["read_results"]

"""The trace reduction on a small synthetic trace, worked by hand."""

from __future__ import annotations

import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import xplane  # noqa: E402

MS = 1_000_000   # ns


def _trace():
    # window 0..100 ms; device 0 runs ops at 10-30 (two overlapping), 40-50
    # and 90-120 (clipped to 100); device 1 runs one op 0-60.
    device = {
        "/device:TPU:0": [(10 * MS, 15 * MS, "corr"), (20 * MS, 10 * MS,
                                                        "fusion.1"),
                          (40 * MS, 10 * MS, "corr"),
                          (90 * MS, 30 * MS, "fusion.2")],
        "/device:TPU:1": [(0, 60 * MS, "fusion.1")],
    }
    host = [(0, 100 * MS, "bench.window"),
            (0, 35 * MS, "bench.select"),
            (35 * MS, 65 * MS, "bench.select"),
            (55 * MS, 30 * MS, "bench.submit"),
            (200 * MS, 5 * MS, "bench.select")]     # outside the window
    return device, host


def test_busy_idle_and_ops_by_hand():
    r = xplane.reduce(*_trace())
    assert r.window_s == pytest.approx(0.1)
    # device 0: [10, 30] + [40, 50] + [90, 100] = 40 ms; device 1: 60 ms
    assert r.busy_by_device["/device:TPU:0"] == pytest.approx(0.040)
    assert r.busy_by_device["/device:TPU:1"] == pytest.approx(0.060)
    assert r.busy_s == pytest.approx(0.050)
    assert r.op_seconds["corr"] == pytest.approx(0.025)
    assert r.op_seconds["fusion.1"] == pytest.approx(0.070)
    assert r.op_seconds["fusion.2"] == pytest.approx(0.010)
    assert r.ops_matching("corr") == pytest.approx(0.025)
    # idle gaps of device 0: 0-10 and 30-35... : [0,10] in select (10),
    # [30,40] midpoint 35 in the second select (10), [50,90] midpoint 70
    # in submit, the innermost span (40)
    assert r.idle_by_span == {"select": pytest.approx(0.020),
                              "submit": pytest.approx(0.040)}


def test_breakdown_lists_the_largest_first():
    b = xplane.reduce(*_trace()).breakdown(top=2)
    assert [n for n, _ in b["device_ops"]] == ["fusion.1", "corr"]
    assert b["idle_gaps"][0] == ["submit", pytest.approx(0.040)]
    assert len(b["device_ops"]) == 2


def test_no_window_span_takes_the_device_extent():
    device, host = _trace()
    r = xplane.reduce(device, [h for h in host if h[2] != "bench.window"])
    assert r.window_s == pytest.approx(0.120)


def test_a_trace_without_device_work_is_refused():
    with pytest.raises(ValueError):
        xplane.reduce({"/device:TPU:0": []}, [(0, MS, "bench.window")])


@pytest.mark.parametrize("name, kind", [
    ("%corr_argmax.50 = (s32[10,1,1]{2,1,0:T(1,128)S(1)}, f32[10,1,1]"
     "{2,1,0:T(1,128)}) custom-call(f32[10,50048,384]{2,1,0:T(8,128)} "
     "%pad.1), custom_call_target=\"tpu_custom_call\"", "corr_argmax"),
    ("%while.221 = (s32[]{:T(128)}, s32[10,500]{1,0:T(8,128)}) while("
     "%tuple.569), condition=%wide.region_51.88.clone", "while"),
    ("%dynamic-update-slice.94 = f32[50,25000,50]{2,1,0:T(8,128)} "
     "dynamic-update-slice(f32[50,25000,50] %a, f32[50,25000,1] %b)",
     "dynamic-update-slice"),
    ("fusion", "fusion"),
])
def test_op_kind_names_a_tpu_op_by_its_instruction(name, kind):
    assert xplane.op_kind(name) == kind

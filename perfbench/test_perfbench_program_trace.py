"""The program's view of a traced window (``program_trace.py``) on a small
synthetic trace, worked by hand."""

from __future__ import annotations

import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import harness  # noqa: E402
import program_trace  # noqa: E402
import xplane  # noqa: E402

MS = 1_000_000   # ns


def _trace():
    # window 0..100 ms, one bench.select call over all of it.  The program
    # spans: select 0-60, per_class 5-50, reweight 10-30 inside it, err
    # 50-58; markers at 0, 12, 20 and 25.  The device idles 0-30, then a
    # while loop (30-90) runs scoped leaf ops, then idles 90-100.
    device = {"/device:TPU:0": [
        (30 * MS, 60 * MS, "while", None),
        (30 * MS, 10 * MS, "corr_argmax", "omp.score"),
        (40 * MS, 10 * MS, "corr", "omp.column"),
        (50 * MS, 5 * MS, "pad", "omp.score"),
        (55 * MS, 10 * MS, "fusion", "omp.nnls"),
        (65 * MS, 5 * MS, "copy", None),
        (70 * MS, 20 * MS, "dynamic-update-slice", "omp.column"),
    ]}
    host = [(0, 100 * MS, "bench.window"),
            (0, 100 * MS, "bench.select"),
            (0, 0, "repro.count.select.calls"),
            (0, 60 * MS, "repro.select"),
            (5 * MS, 45 * MS, "repro.omp.per_class"),
            (10 * MS, 20 * MS, "repro.omp.reweight"),
            (12 * MS, 0, "repro.count.jax.traces"),
            (20 * MS, 0, "repro.count.jax.program_loads"),
            (25 * MS, 0, "repro.count.jax.traces"),
            (50 * MS, 8 * MS, "repro.gradmatch.err"),
            (150 * MS, 5 * MS, "repro.select")]         # outside the window
    return device, host


def _unprogrammed(device, host):
    """The same trace from a program without spans or scopes."""
    return ({p: [ev[:3] + (None,) for ev in evs]
             for p, evs in device.items()},
            [h for h in host if h[2].startswith("bench.")])


def test_scopes_count_leaf_ops_and_leave_the_while_out():
    pt = program_trace.reduce(*_trace())
    assert pt.scope_seconds == {"omp.score": pytest.approx(0.015),
                                "omp.column": pytest.approx(0.030),
                                "omp.nnls": pytest.approx(0.010),
                                "unscoped": pytest.approx(0.005)}
    assert pt.unscoped_share() == pytest.approx(5 / 60)
    assert pt.kinds_by_scope["omp.score"] == {
        "corr_argmax": pytest.approx(0.010), "pad": pytest.approx(0.005)}
    assert pt.kinds_by_scope["unscoped"] == {"copy": pytest.approx(0.005)}
    assert pt.base.op_seconds["while"] == pytest.approx(0.060)


def test_idle_is_split_over_the_innermost_program_spans():
    pt = program_trace.reduce(*_trace())
    # 0-5 only the select spans cover (repro.select is the shorter),
    # 5-10 per_class, 10-30 reweight; 90-100 only bench.select.
    assert pt.idle_by_innermost == {
        "repro.select": pytest.approx(0.005),
        "repro.omp.per_class": pytest.approx(0.005),
        "repro.omp.reweight": pytest.approx(0.020),
        "bench.select": pytest.approx(0.010)}
    assert pt.span_counts == {
        "repro.count.select.calls": 1, "repro.select": 1,
        "repro.omp.per_class": 1, "repro.omp.reweight": 1,
        "repro.count.jax.traces": 2, "repro.count.jax.program_loads": 1,
        "repro.gradmatch.err": 1}
    assert pt.span_seconds == {
        "repro.select": pytest.approx(0.060),
        "repro.omp.per_class": pytest.approx(0.045),
        "repro.omp.reweight": pytest.approx(0.020),
        "repro.gradmatch.err": pytest.approx(0.008)}


def test_the_base_reduction_is_the_benchmarks_own():
    device, host = _trace()
    pt = program_trace.reduce(device, host)
    plain_device, plain_host = _unprogrammed(device, host)
    base = xplane.reduce({p: [ev[:3] for ev in evs]
                          for p, evs in plain_device.items()}, plain_host)
    for field in ("window_s", "busy_s", "busy_by_device", "op_seconds",
                  "idle_by_span"):
        assert getattr(pt.base, field) == getattr(base, field), field
    assert pt.base.idle_by_span == {"select": pytest.approx(0.040)}
    assert pt.base.busy_s == pytest.approx(0.060)


def test_per_layer_reads_the_window():
    pt = program_trace.reduce(*_trace())
    got = program_trace.per_layer(pt, {"calls": 1, "rounds_run": 5})
    assert got == {"score_ms.select": pytest.approx(3.0),
                   "column_ms.select": pytest.approx(6.0),
                   "nnls_ms.select": pytest.approx(2.0),
                   "host_gap_ms.select": pytest.approx(30.0),
                   "programs_loaded.select": pytest.approx(1.0)}


@pytest.mark.parametrize("scope", ["omp.score", "omp.column", "omp.nnls"])
def test_an_empty_scope_of_a_traced_program_is_an_error(scope):
    device, host = _trace()
    device = {p: [ev for ev in evs if ev[3] != scope]
              for p, evs in device.items()}
    pt = program_trace.reduce(device, host)
    with pytest.raises(RuntimeError, match=scope.replace(".", r"\.")):
        program_trace.per_layer(pt, {"calls": 1, "rounds_run": 5})


def test_a_program_without_spans_reads_nothing():
    pt = program_trace.reduce(*_unprogrammed(*_trace()))
    assert not pt.program_traced
    assert pt.scope_s("omp.score") is None
    assert program_trace.per_layer(pt, {"calls": 1, "rounds_run": 5}) == {}
    assert pt.idle_by_innermost == {"bench.select": pytest.approx(0.040)}


def test_each_op_is_looked_up_in_the_program_it_runs_in():
    modules = [(0, 50, "jit_omp_select(15388027131515875373)"),
               (60, 10, "jit_scan(5694549794985933706)")]
    ops = [(10, 5, "%pad.33 = f32[5120,1024]{1,0:T(8,128)} pad(%copy.33)"),
           (20, 5, "%fusion.2 = f32[8]{0} fusion(%a), kind=kLoop"),
           (55, 1, "%copy.1 = f32[8]{0} copy(%p)"),
           (62, 1, "%while.1 = (s32[]) while(%tuple), condition=%c")]
    op_names = {
        ("jit_omp_select", "pad.33"):
            "jit(omp_select)/while/body/omp.score/pad",
        ("jit_omp_select", "fusion.2"): "jit(omp_select)/dot_general",
        ("jit_scan", "copy.1"): "jit(scan)/omp.nnls/copy",
        ("jit_scan", "while.1"): "jit(scan)/omp.reweight/while"}
    assert program_trace.scoped_ops(modules, ops, op_names) == [
        (10, 5, "pad", "omp.score"), (20, 5, "fusion", None),
        (55, 1, "copy", None), (62, 1, "while", "omp.reweight")]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(omp_select)/jit(main)/while/body/omp.nnls/dot_general",
     "omp.nnls"),
    ("jit(omp_select)/omp.per_class/while/body/omp.score/pad", "omp.score"),
    ("omp.column/scatter", "omp.column"),
    ("jit(f)/jit(main)/dot_general", None),
    ("args[0]", None),
])
def test_the_scope_is_the_innermost_named_component(op_name, scope):
    assert program_trace.scope_of(op_name) == scope


def test_span_names_drop_the_annotation_arguments():
    assert program_trace.span_name("repro.select#strategy=gradmatch,"
                                   "call=3#") == "repro.select"
    assert program_trace.span_name("bench.select") == "bench.select"


def test_it_refuses_to_run_without_a_tpu():
    with pytest.raises(harness.NoChip):
        program_trace.main(["--workload", "select_c100_pc", "--seed", "1",
                            "--seconds", "1"])


HLO = """HloModule jit_omp_select, is_scheduled=true

%fused_computation.8.clone (param_0.241: f32[50,25088,1], param_3.173: pred[]) -> f32[50,25000,1] {
  %param_3.173 = pred[]{:T(512)} parameter(3)
  %broadcast.458 = pred[50,25000,1]{2,1,0} broadcast(%param_3.173), dimensions={}
  %param_0.241 = f32[50,25088,1]{2,1,0} parameter(0)
  %slice.331 = f32[50,25000,1]{2,1,0} slice(%param_0.241), slice={[0:50], [0:25000], [0:1]}, metadata={op_name="jit(f)/while/body/omp.column/jit(corr)/slice" stack_frame_id=16}
  %constant.859 = s32[]{:T(128)} constant(0), metadata={op_name="jit(f)/while/body/closed_call"}
  ROOT %select.143 = f32[50,25000,1]{2,1,0} select(%broadcast.458, %slice.331, %slice.331)
}

ENTRY %main.5 (p: f32[50,25088,1]) -> f32[50,25000,1] {
  %p = f32[50,25088,1]{2,1,0} parameter(0), metadata={op_name="grads"}
  %pad.197 = f32[50,25088,1]{2,1,0} pad(%p, %c), padding=0_0x0_88x0_0, metadata={op_name="jit(f)/while/body/omp.score/jit(corr_argmax)/jit(_pad)/pad" source_file="corr.py" source_line=254}
  %copy.51 = pred[50,1]{1,0} copy(%q), backend_config={"flag_configs":[]}
  ROOT %slice_select_fusion.2 = f32[50,25000,1]{2,1,0} fusion(%pad.197, %t), kind=kLoop, calls=%fused_computation.8.clone, backend_config={"flag_configs":[]}
}
"""


def test_the_op_names_of_an_optimized_module():
    names = program_trace.module_op_names(HLO)
    assert names["pad.197"] == (
        "jit(f)/while/body/omp.score/jit(corr_argmax)/jit(_pad)/pad")
    # a fusion without metadata takes the scope its fused ops name
    assert program_trace.scope_of(names["slice_select_fusion.2"]) == \
        "omp.column"
    assert "copy.51" not in names and "select.143" not in names
    assert names["p"] == "grads"

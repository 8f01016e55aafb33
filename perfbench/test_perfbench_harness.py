"""CPU tests of the benchmark harness: files found by name, the contract
of BENCHMARK.json, the refusal to run without a TPU, the result line, the
seeded pools and the work counts."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import harness  # noqa: E402
import pools  # noqa: E402
import work  # noqa: E402
from xplane import Reduced  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = harness.load_benchmark()


def test_benchmark_json_meets_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p) and ".." not in p
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        for w in m.get("workloads", []):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        len(cells) // 2, 1)
    assert {w["config"] for w in cells.values()} == set(configs)
    pairs = {(w["config"], w["traffic"]) for w in cells.values()}
    assert len(pairs) == len(cells)
    for name, w in cells.items():
        assert NAME.match(name) and len(w["why"]) <= 200
        reported = [m for m in BENCH["end_to_end"]
                    if name in m.get("workloads", [name])]
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert any(name in m.get("workloads", [name])
                   for m in BENCH["per_layer"])
    for c in configs.values():
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank")) and NAME.match(key)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_by_name(workload):
    cell = harness.resolve_cell(BENCH, workload)
    loop = harness.loop_for(cell.traffic)
    for fn in ("setup", "window", "evidence", "check"):
        assert callable(getattr(loop, fn))
    ref = os.path.join(BENCH_DIR, cell.config["reference"])
    assert os.path.isfile(ref)
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]).read)
    assert set(cell.traffic["limits"]) and all(
        v >= 0 for v in cell.traffic["limits"].values())


def test_a_new_cell_is_found_with_no_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "perfbench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    src = root / "perfbench" / "traffic" / "select_closed_loop.json"
    mix = json.loads(src.read_text())
    mix["about"] = "a second caller's mix"
    (root / "perfbench" / "traffic" / "select_other.json").write_text(
        json.dumps(mix))
    bench["workloads"].append({"name": "select_other",
                               "config": "cifar100-resnet18-pc",
                               "traffic": "select_other", "chips": 1,
                               "why": "added by data alone"})
    cell = harness.resolve_cell(bench, "select_other", root=str(root),
                                bench_dir=str(root / "perfbench"))
    assert cell.traffic["about"] == "a second caller's mix"
    assert cell.config["name"] == "cifar100-resnet18-pc"
    # metrics without a ``workloads`` key apply to the new cell too
    assert "setup_s" in {m["name"] for m in cell.end_to_end}


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "select_c100_pc",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_machine_without_a_tpu():
    out = _run(harness.ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert "{" not in out.stdout


def test_run_refuses_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0 and "{" not in out.stdout


def test_require_chips_refuses_the_cpu():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)


@pytest.mark.parametrize("breakdown", [None, {"device_ops": [["f", 1.0]],
                                              "idle_gaps": [["none", 0.5]]}])
def test_result_line_keys(breakdown):
    checks = [harness.Check("err_gap", 1e-6, 1e-4)]
    line = harness.result_line(True, 3, 0, {"setup_s": {"value": 1.0,
                                                        "unit": "s"}},
                               {"platform": "tpu"}, checks, breakdown)
    keys = list(json.loads(line))
    want = list(harness.RESULT_KEYS) + (["breakdown"] if breakdown else [])
    assert keys == want + ["compared"]
    assert json.loads(line)["compared"] == {
        "err_gap": {"value": 1e-6, "limit": 1e-4}}


def test_check_fails_on_nan_and_over_the_limit():
    assert harness.Check("x", 0.5, 1.0).ok
    assert not harness.Check("x", 1.5, 1.0).ok
    assert not harness.Check("x", float("nan"), 1.0).ok


def test_pools_are_seeded():
    cfg = dict(harness.load_json(os.path.join(
        BENCH_DIR, "configs", "cifar100-resnet18-pc.json")),
        n=600, num_classes=6, rows_per_class=100, embed_dim=8)
    g1, y1 = pools.per_class_pool(cfg, 2 ** 33 + 3)
    g2, y2 = pools.per_class_pool(cfg, 2 ** 33 + 3)
    g3, _ = pools.per_class_pool(cfg, 2 ** 33 + 4)
    assert np.array_equal(g1, g2) and np.array_equal(y1, y2)
    assert not np.array_equal(g1, g3)
    assert np.bincount(np.asarray(y1)).tolist() == [100] * 6
    own = np.asarray(g1)[:, -1]
    assert np.all(own < 0) and np.all(own > -1)
    # at the configuration's own size the true-class probability spreads
    # over (0, 1) and does not saturate: rows are not all zero
    full, _ = pools.per_class_pool(harness.load_json(os.path.join(
        BENCH_DIR, "configs", "cifar100-resnet18-pc.json")), 1)
    p = 1.0 + np.asarray(full)[:, -1]
    assert 0.05 < np.median(p) < 0.6 and np.quantile(p, 0.95) < 0.95


def test_a_class_share_is_the_whole_pool_s_rows_of_those_classes():
    whole = dict(harness.load_json(os.path.join(
        BENCH_DIR, "configs", "cifar100-resnet18-pc.json")),
        n=1000, num_classes=10, classes_total=10, rows_per_class=100,
        embed_dim=8)
    share = dict(whole, n=400, num_classes=4)
    g, y = (np.asarray(a) for a in pools.per_class_pool(whole, 2 ** 33 + 5))
    gs, ys = (np.asarray(a) for a in pools.per_class_pool(share, 2 ** 33 + 5))
    held = y < 4
    assert np.array_equal(gs, g[held]) and np.array_equal(ys, y[held])
    assert np.bincount(ys).tolist() == [100] * 4


def test_work_counts_by_hand():
    # per-class OMP at the paper's size: 100 classes of 500 rows, d 513,
    # 50 rounds: 5.13 GB, the bytes bound
    nbytes = 100 * work.omp_scoring_bytes(50, 500, 513)
    assert nbytes == 5_130_000_000
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    secs = work.least_seconds(100 * work.omp_scoring_flops(50, 500, 513),
                              nbytes, peaks)
    assert secs == pytest.approx(5.13e9 / 819e9)


def _ctx(work_counts, reduced=None, seconds=10.0, spans=(), config=None):
    cell = harness.Cell("c", 1, config or {}, {}, [], [])
    run = harness.Run(cell, 0, seconds, True, spans=list(spans))
    win = harness.Window({}, 1, 0, seconds, work_counts)
    return {"run": run, "window": win, "trace": reduced,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_read_what_is_there_and_nothing_else():
    red = Reduced(window_s=10.0, busy_s=8.0, busy_by_device={"a": 8.0},
                  op_seconds={"_corr_argmax_kernel": 5.0, "fusion": 3.0},
                  idle_by_span={"none": 2.0})
    ctx = _ctx({"scored_rows": 1000, "d": 513, "rounds_run": 100}, red)
    assert harness.metric_reader("idle_share.select").read(ctx) == \
        pytest.approx(20.0)
    assert harness.metric_reader("select_roofline").read(ctx) == \
        pytest.approx(100 * 1000 * 513 * 4 / 819e9 / 8.0)
    assert harness.metric_reader("update_ms.select").read(ctx) == \
        pytest.approx(1000 * 3.0 / 100)
    empty = _ctx({}, None)
    for name in ("select_roofline", "update_ms.select",
                 "idle_share.select"):
        assert harness.metric_reader(name).read(empty) is None


def test_update_reader_refuses_a_trace_without_scoring_kernels():
    red = Reduced(window_s=10.0, busy_s=8.0, busy_by_device={"a": 8.0},
                  op_seconds={"fusion": 8.0}, idle_by_span={})
    ctx = _ctx({"scored_rows": 1000, "d": 513, "rounds_run": 100}, red)
    with pytest.raises(RuntimeError, match="fusion"):
        harness.metric_reader("update_ms.select").read(ctx)

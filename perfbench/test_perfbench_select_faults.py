"""The comparison that decides ``correct`` for the selection cells, on the
CPU at a small size: a sound run passes, and the control (the reference
in bfloat16 put in the program's place) and each fault planted in the
timed path make ``correct`` false."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import faults  # noqa: E402
import harness  # noqa: E402
import pools  # noqa: E402

BENCH = harness.load_benchmark()
SMALL = dict(n=3000, num_classes=6, rows_per_class=500, embed_dim=32, d=33,
             k=300)


def _run(name, seed=2 ** 33 + 11):
    cell = harness.resolve_cell(BENCH, name)
    cell.config = dict(cell.config, **SMALL)
    return harness.Run(cell, seed, 1.0, False)


CELLS = ["select_c100_pc", "select_c10_pc"]


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    run = _run(name)
    _, checks, correct = harness.drive(run,
                                       harness.loop_for(run.cell.traffic))
    assert correct, checks


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    run = _run(name)
    checks = harness.loop_for(run.cell.traffic).control(run)
    assert not all(c.ok for c in checks), checks


@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in CELLS
    for fault in faults.BY_LOOP[_run(name).cell.traffic["loop"]]])
def test_a_planted_fault_is_not_correct(name, fault):
    run = _run(name)
    with fault():
        _, checks, correct = harness.drive(
            run, harness.loop_for(run.cell.traffic))
    assert not correct, (fault.__name__, checks)


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 5])
def test_the_reference_repeats_itself_and_replays_its_own_picks(seed):
    run = _run("select_c100_pc", seed)
    cfg = run.cell.config
    ref = harness.load_module(os.path.join(BENCH_DIR, cfg["reference"]))
    pool, labels = pools.per_class_pool(cfg, seed)
    args = (pool, labels, cfg["num_classes"], cfg["k"] // cfg["num_classes"],
            cfg["lam"], cfg["eps"], cfg["nnls_iters"])
    a, b = ref.per_class_omp(*args), ref.per_class_omp(*args)
    assert np.array_equal(a["rows"], b["rows"])
    assert np.array_equal(a["err"], b["err"])
    again = ref.replay_classes(pool, labels, np.arange(cfg["num_classes"]),
                               a["rows"], a["mask"], cfg["lam"], cfg["eps"],
                               cfg["nnls_iters"])
    assert np.max(again["regret"]) == 0.0
    np.testing.assert_array_equal(again["weights"], a["weights"])

"""A library caller: a closed loop of ``core.selection.select`` calls on
one pool, each started when the last has returned.

Traffic keys: ``strategy`` (the ``select`` strategy), ``per_class``
(pass the pool's labels) and ``limits`` (the comparison's limits).
"""

from __future__ import annotations

import os
import sys
import time

import jax
import numpy as np

import harness
import pools
from compare import normalise, weight_gap


class State:
    def __init__(self, run):
        if harness.SRC not in sys.path:
            sys.path.insert(0, harness.SRC)
        from repro.core import selection as sel_lib

        cfg, tr = run.cell.config, run.cell.traffic
        self.pool, self.labels = pools.per_class_pool(cfg, run.seed)
        key = pools.seed_key(run.seed)
        kw = dict(lam=cfg["lam"], eps=cfg["eps"])
        if tr.get("per_class", True):
            kw.update(labels=self.labels, num_classes=cfg["num_classes"])

        def call():
            return sel_lib.select(tr["strategy"], key, self.pool,
                                  cfg["k"], **kw)

        self.call = call
        jax.block_until_ready(self.call())     # compiles every program
        self.results = []


def setup(run) -> State:
    return State(run)


def window(run, st: State) -> harness.Window:
    t0 = time.perf_counter()
    deadline = t0 + run.seconds
    with run.span("window"):
        while True:
            with run.span("select"):
                res = st.call()
                jax.block_until_ready(res)
            st.results.append(res)
            if time.perf_counter() >= deadline:
                break
    secs = time.perf_counter() - t0
    calls = len(st.results)
    cfg = run.cell.config
    rounds = cfg["k"] // cfg["num_classes"]          # per class problem
    return harness.Window(
        metrics={"select_ms": 1000.0 * secs / calls},
        attempted=calls, failed=0, seconds=secs,
        work={"calls": calls, "rounds_run": calls * rounds, "d": cfg["d"],
              "scored_rows": calls * rounds * cfg["n"]})


def evidence(st: State) -> list:
    """Every distinct answer of the timed calls, on the host: the picks
    in order, class by class, their weights and mask, and the objective."""
    seen, out = set(), []
    for r in st.results:
        a = {"indices": np.asarray(r.indices), "weights": np.asarray(
            r.weights), "mask": np.asarray(r.mask), "err": float(r.err)}
        key = (a["indices"].tobytes(), a["weights"].tobytes(),
               a["mask"].tobytes(), a["err"])
        if key not in seen:
            seen.add(key)
            out.append(a)
    return out


def _reference_module(run):
    return harness.load_module(os.path.join(harness.BENCH_DIR,
                                            run.cell.config["reference"]))


def check(run, answers: list) -> list:
    """Each distinct answer replayed by the reference: every pick's regret
    against the best score the reference sees at that round, the weights
    the reference solves on the same picks, and the objective."""
    cfg = run.cell.config
    ref = _reference_module(run)
    pool, labels = pools.per_class_pool(cfg, run.seed)
    g = np.asarray(pool, np.float64)
    classes = np.arange(cfg["num_classes"])
    worst = {"pick_regret": 0.0, "weight_gap": 0.0, "err_gap": 0.0}
    for a in answers:
        picks = a["indices"].reshape(cfg["num_classes"], -1)
        live = a["mask"].reshape(picks.shape)
        out = ref.replay_classes(pool, labels, classes, picks, live,
                                 cfg["lam"], cfg["eps"], cfg["nnls_iters"])
        w_ref = np.where(live, out["weights"], 0.0).ravel()
        sel = np.where(a["mask"], a["indices"], 0)
        resid = out["targets"].sum(0) - w_ref @ g[sel]
        err_ref = float(resid @ resid + cfg["lam"] * np.sum(w_ref ** 2))
        gaps = {"pick_regret": float(np.max(out["regret"])),
                "weight_gap": weight_gap(a["weights"],
                                         normalise(w_ref, a["mask"])),
                "err_gap": abs(a["err"] - err_ref) / err_ref}
        worst = {k: max(worst[k], v) for k, v in gaps.items()}
    limits = run.cell.traffic["limits"]
    return [harness.Check(k, v, limits[k]) for k, v in worst.items()]


def control(run) -> list:
    """The reference in bfloat16 in the program's place, its answer
    replayed as the program's answers are."""
    cfg = run.cell.config
    ref = _reference_module(run)
    pool, labels = pools.per_class_pool(cfg, run.seed)
    out = ref.per_class_omp(pool, labels, cfg["num_classes"],
                            cfg["k"] // cfg["num_classes"], cfg["lam"],
                            cfg["eps"], cfg["nnls_iters"], arith=ref.CONTROL)
    mask = out["mask"].ravel()
    w = out["weights"].ravel()
    g = np.asarray(pool, np.float64)[np.where(mask, out["rows"].ravel(), 0)]
    resid = out["targets"].astype(np.float64).sum(0) - np.where(
        mask, w, 0.0) @ g
    err = float(resid @ resid + cfg["lam"] * np.sum(np.where(mask, w, 0) ** 2))
    return check(run, [{"indices": out["rows"].ravel(),
                        "weights": normalise(w, mask), "mask": mask,
                        "err": err}])

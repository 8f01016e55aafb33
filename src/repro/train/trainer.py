"""Adaptive-selection trainer: the paper's Algorithm 1, end to end.

Runs any strategy from ``core.selection.STRATEGIES`` (+ their -WARM
variants) on a classification dataset with the paper's hyper-parameters
(SGD momentum 0.9, wd 5e-4, cosine annealing, R=20, lambda=0.5, kappa=1/2).

Cost accounting: wall-clock on this container measures the host CPU, not
the paper's V100, so the primary efficiency metric is **work units** — one
unit = one example forward+backward (training costs 3x a forward; selection
proxy passes cost 1x forward; OMP/greedy cost is measured in wall time and
reported separately).  Speedups reported by benchmarks are work-unit ratios
vs FULL, the quantity the paper's wall-clock ratios proxy.

Fault tolerance: ``checkpoint_dir`` makes the trainer snapshot (params,
opt state, loader state, selection state, epoch, RNG) every
``checkpoint_every`` epochs through the async CheckpointManager, and
``.run()`` resumes from the latest snapshot if one exists.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.configs.paper import ClassifierConfig, PaperHParams
from repro.core import proxies as proxy_lib
from repro.core import selection as sel_lib
from repro.core import streaming as stream_lib
from repro.core.gradmatch import SelectionResult
from repro.data.loader import ChunkedPool, SubsetLoader
from repro.data.synthetic import Dataset
from repro.optim import cosine_annealing, sgd
from repro.train import steps as steps_lib


@dataclass
class TrainerConfig:
    strategy: str = "gradmatch-pb"     # see core.selection.STRATEGIES
    budget: float = 0.1                # k / n
    epochs: int = 60
    batch_size: int = 64
    warm_start: bool = False           # -WARM variant
    early_stop_frac: Optional[float] = None  # FULL-EARLYSTOP budget match
    hp: PaperHParams = field(default_factory=PaperHParams)
    is_valid: bool = False             # match validation gradients
    per_class: bool = True
    omp_method: str = "incremental"    # OMP solver for gradmatch strategies
    chunk_size: int = 1024             # gradmatch-stream: proxy chunk rows
    stream_buffer: int = 256           # gradmatch-stream: top-M buffer slots
    # gradmatch-stream: compressed proxy-chunk cache budget (bf16 rows +
    # f32 sidecars, DESIGN.md §7) — certified buffer rounds re-verify
    # against this cache instead of re-extracting proxies per round.
    stream_cache_bytes: int = 256 << 20
    seed: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 20
    eval_every: int = 5


@dataclass
class TrainReport:
    strategy: str
    budget: float
    final_acc: float
    best_acc: float
    acc_history: list
    work_units: float            # example-equivalents of compute (see above)
    selection_seconds: float
    wall_seconds: float
    selection_rounds: int
    subset_size: int

    @property
    def energy_proxy(self) -> float:
        """J/FLOP-proportional proxy (same ratios as the paper's pyJoules)."""
        return self.work_units


class AdaptiveTrainer:
    def __init__(self, model_cfg: ClassifierConfig, tcfg: TrainerConfig,
                 train: Dataset, val: Dataset, test: Optional[Dataset] = None):
        self.mcfg = model_cfg
        self.tcfg = tcfg
        self.train_ds = train
        self.val_ds = val
        self.test_ds = test if test is not None else val

        hp = tcfg.hp
        frac = 1.0 if tcfg.strategy == "full" else tcfg.budget
        steps_per_epoch = max(
            int(train.n * frac) // tcfg.batch_size, 1)
        lr = (cosine_annealing(hp.lr, tcfg.epochs * steps_per_epoch)
              if hp.cosine_anneal else hp.lr)
        self.opt = sgd(lr, momentum=hp.momentum,
                       weight_decay=hp.weight_decay)
        self.step_fn = steps_lib.make_classifier_step(model_cfg, self.opt)
        self.eval_fn = steps_lib.make_classifier_eval(model_cfg)
        self.proxy_fn = steps_lib.make_proxy_fn(model_cfg)
        self.ckpt = (CheckpointManager(tcfg.checkpoint_dir)
                     if tcfg.checkpoint_dir else None)

    # -- selection round ------------------------------------------------------
    def _run_selection(self, params, key) -> tuple[SelectionResult, float]:
        t0 = time.perf_counter()
        tc = self.tcfg
        n = self.train_ds.n
        k = max(int(n * tc.budget), 1)
        val_target = None
        if tc.is_valid:
            _, vbias = self.proxy_fn(params, self.val_ds.x, self.val_ds.y)
            val_target = jnp.sum(vbias, axis=0)
        if tc.strategy == "gradmatch-stream":
            # Out-of-core path: proxies are extracted one chunk at a time
            # through the chunked pool — the (n, d) proxy matrix never
            # exists on host or device (core/streaming.py, DESIGN.md §7).
            # The row fetcher re-extracts individual proxy rows on demand
            # (row-wise extractors make that bit-exact), so the engine's
            # repair and cache-refill tiers work without a loader pass —
            # certified rounds never re-run the proxy forward pass.
            pool = ChunkedPool(self.train_ds.x, self.train_ds.y,
                               tc.chunk_size)
            chunks = proxy_lib.proxy_chunk_stream(pool.chunks,
                                                  self.proxy_fn, params)
            fetch = proxy_lib.proxy_row_fetch(
                self.train_ds.x, self.train_ds.y, self.proxy_fn, params)
            sel = stream_lib.gradmatch_streaming(
                chunks, k, target=val_target, lam=tc.hp.lam, eps=tc.hp.eps,
                buffer_size=tc.stream_buffer,
                cache_bytes=tc.stream_cache_bytes, row_fetch=fetch)
            jax.block_until_ready(sel.weights)
            return sel, time.perf_counter() - t0
        pcg, bias = self.proxy_fn(params, self.train_ds.x, self.train_ds.y)
        # PB variants & GLISTER use the bias-gradient proxy (comparable
        # across classes); per-class GRAD-MATCH/CRAIG use the per-gradient
        # proxy within each class (paper §4).
        per_class_ok = not tc.is_valid and tc.per_class
        proxies = pcg if (tc.strategy in ("gradmatch", "craig",
                                          "craig-lazy", "craig-stochastic")
                          and per_class_ok) else bias
        sel = sel_lib.select(
            tc.strategy, key, proxies, k,
            labels=self.train_ds.y, num_classes=self.train_ds.num_classes,
            batch_size=tc.batch_size, lam=tc.hp.lam, eps=tc.hp.eps,
            val_target=val_target,
            per_class=per_class_ok,
            omp_method=tc.omp_method,
            chunk_size=tc.chunk_size, stream_buffer=tc.stream_buffer,
        )
        sel = sel_lib.expand_if_pb(tc.strategy, sel, tc.batch_size, n)
        jax.block_until_ready(sel.weights)
        return sel, time.perf_counter() - t0

    # -- main loop --------------------------------------------------------------
    def run(self) -> TrainReport:
        tc = self.tcfg
        key = jax.random.PRNGKey(tc.seed)
        kinit, kloop = jax.random.split(key)

        from repro.models.classifier import init_classifier
        params = init_classifier(self.mcfg, kinit)
        opt_state = self.opt.init(params)

        loader = SubsetLoader(self.train_ds.x, self.train_ds.y,
                              tc.batch_size, seed=tc.seed)

        # Schedule: warm start / early stop accounting.
        n = self.train_ds.n
        epochs = tc.epochs
        warm_epochs = 0
        if tc.warm_start and tc.strategy not in ("full",):
            warm_epochs, subset_epochs = sel_lib.warm_start_epochs(
                epochs, tc.budget, tc.hp.kappa)
            epochs = warm_epochs + subset_epochs
        if tc.strategy == "full" and tc.early_stop_frac is not None:
            # FULL-EARLYSTOP: spend the same work units as a subset run.
            epochs = max(int(round(tc.epochs * tc.early_stop_frac)), 1)
        sched = sel_lib.SelectionSchedule(tc.hp.select_every, warm_epochs,
                                          total_epochs=epochs)

        start_epoch = 0
        work = 0.0
        sel_seconds = 0.0
        sel_rounds = 0
        acc_hist: list = []
        best = 0.0

        # -- resume -----------------------------------------------------------
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            snap = self.ckpt.restore()
            params = jax.tree_util.tree_map(
                jnp.asarray, snap["params"])
            opt_state = jax.tree_util.tree_map(
                jnp.asarray, snap["opt_state"])
            opt_state = type(self.opt.init(params))(
                opt_state["step"], opt_state.get("slots"))
            loader.restore_state(snap["loader"])
            start_epoch = int(snap["meta"]["epoch"])
            work = float(snap["meta"]["work"])
            sel_rounds = int(snap["meta"]["sel_rounds"])

        t_wall = time.perf_counter()
        for epoch in range(start_epoch, epochs):
            in_warm = epoch < warm_epochs
            if (tc.strategy not in ("full",) and not in_warm
                    and sched.is_selection_epoch(epoch)):
                with obs.span("train.select", epoch=epoch):
                    sel, dt = self._run_selection(
                        params, jax.random.fold_in(kloop, epoch))
                loader.set_selection(np.asarray(sel.indices),
                                     np.asarray(sel.weights),
                                     np.asarray(sel.mask))
                sel_seconds += dt
                sel_rounds += 1
                work += n  # one proxy forward over the pool
                if tc.is_valid:
                    work += self.val_ds.n
            elif in_warm or tc.strategy == "full":
                loader.set_selection(np.arange(n),
                                     np.full((n,), 1.0 / n, np.float32),
                                     np.ones((n,), bool))

            for batch in loader.epoch_batches():
                params, opt_state, _ = self.step_fn(params, opt_state, batch)
                work += 3.0 * batch["x"].shape[0]   # fwd + bwd ~ 3x fwd

            if (epoch + 1) % tc.eval_every == 0 or epoch == epochs - 1:
                m = self.eval_fn(params, {"x": self.test_ds.x,
                                          "y": self.test_ds.y})
                acc = float(m["acc"])
                acc_hist.append((epoch + 1, acc))
                best = max(best, acc)

            if (self.ckpt is not None
                    and (epoch + 1) % tc.checkpoint_every == 0):
                self.ckpt.save(epoch + 1, {
                    "params": params,
                    "opt_state": {"step": opt_state.step,
                                  "slots": opt_state.slots},
                    "loader": loader.checkpoint_state(),
                    "meta": {"epoch": epoch + 1, "work": work,
                             "sel_rounds": sel_rounds},
                })

        if self.ckpt is not None:
            self.ckpt.wait()
        jax.block_until_ready(params)
        wall = time.perf_counter() - t_wall
        final = acc_hist[-1][1] if acc_hist else 0.0
        return TrainReport(
            strategy=tc.strategy + ("-warm" if tc.warm_start else ""),
            budget=tc.budget, final_acc=final, best_acc=best,
            acc_history=acc_hist, work_units=work,
            selection_seconds=sel_seconds, wall_seconds=wall,
            selection_rounds=sel_rounds, subset_size=loader.subset_size)

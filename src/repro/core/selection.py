"""Strategy dispatch + adaptive-selection schedule (paper Algorithm 1).

``select()`` maps a strategy name to its selector over a proxy matrix —
the one place the trainer, benchmarks and examples resolve
GRAD-MATCH / CRAIG / GLISTER / RANDOM, their PB variants, and the CRAIG
greedy tiers (``craig`` = dense oracle, ``craig-lazy`` = certified lazy
greedy with identical selections, ``craig-stochastic`` = seeded
stochastic greedy — see ``core/greedy.py`` / DESIGN.md §5).

``warm_start_epochs()`` implements the paper's warm-start budget split
(§4): run ``T_f = kappa * T * (k/n)`` epochs of full-data training, then
``T_s = kappa * T`` epochs of subset training — at kappa = 1/2 the total
compute equals the non-warm schedule's (the paper's "50% warm-start / 50%
data selection").

``SelectionSchedule`` answers "is epoch t a selection epoch?" (every R
epochs, and always at the first subset epoch).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.continual import buffer as continual_lib
from repro.core import craig as craig_lib
from repro.core import glister as glister_lib
from repro.core import gradmatch as gm_lib
from repro.core import partition as part_lib
from repro.core import proxies as proxy_lib
from repro.core import random_sel
from repro.core import streaming as stream_lib
from repro.core.gradmatch import SelectionResult

STRATEGIES = ("gradmatch", "gradmatch-stream", "gradmatch-partitioned",
              "gradmatch-pb", "gradmatch-continual", "craig", "craig-lazy",
              "craig-lazy-otf", "craig-stochastic", "craig-pb", "glister",
              "random", "full")

# CRAIG tiers: the dense oracle and the fast greedy modes of the shared
# engine (core/greedy.py).  "craig-lazy" selects index-identically to
# "craig"; "craig-lazy-otf" is the same certified lazy greedy with the
# similarity tiled from the gradients on the fly — index-identical again
# (FL gains are shift-invariant in l_max) at O(1) similarity memory;
# "craig-stochastic" is the seeded approximate tier.
_CRAIG_METHODS = {"craig": "dense", "craig-lazy": "lazy",
                  "craig-lazy-otf": "lazy",
                  "craig-stochastic": "stochastic"}
_CRAIG_ON_THE_FLY = frozenset({"craig-lazy-otf"})


def select(
    strategy: str,
    key: jax.Array,
    proxies: jax.Array,            # (n, d) per-example gradient proxies
    k: int,
    labels: Optional[jax.Array] = None,
    num_classes: int = 0,
    batch_size: int = 32,
    lam: float = 0.5,
    eps: float = 1e-10,
    val_target: Optional[jax.Array] = None,   # (d,) validation-gradient sum
    per_class: bool = True,
    omp_method: str = "incremental",   # OMP solver for gradmatch strategies
    chunk_size: int = 2048,            # gradmatch-stream: pool chunk rows
    stream_buffer: int = 256,          # gradmatch-stream: top-M buffer slots
    stream_cache_bytes: int = stream_lib.DEFAULT_CACHE_BYTES,
    partitions: Optional[int] = None,  # gradmatch-partitioned: P (None = auto)
    buffer_cap: Optional[int] = None,      # gradmatch-continual: buffer rows
    continual_batch: Optional[int] = None,  # gradmatch-continual: admit size
) -> SelectionResult:
    """Resolve one selection round.  ``val_target`` switches isValid=True.

    PB variants interpret ``k`` as an example budget and convert it to
    ``k // batch_size`` mini-batches; their result indexes *batches* — use
    ``gm_lib.expand_batch_selection`` to map back to examples.

    ``omp_method`` picks the OMP solver for the gradmatch strategies:
    ``"incremental"`` (cached-correlation production path) or ``"dense"``
    (the reference re-solve-from-scratch formulation, kept for parity tests
    and benchmark baselines).

    ``"gradmatch-stream"`` runs the certified-exact streaming block-OMP
    (``core/streaming.py``, DESIGN.md §7) over the proxies chunked by
    ``chunk_size`` — the same subset as ``"gradmatch"`` with pooled
    (non-per-class) OMP, at ``O(chunk + stream_buffer·d +
    stream_cache_bytes)`` peak pool memory (the compressed chunk cache
    is what lets the engine commit many rounds per loader pass;
    ``stream_cache_bytes`` must be positive here — running cacheless is
    only available on ``streaming.omp_select_streaming`` directly).  The
    returned result
    carries the engine's ``SelectStats``.  Callers with a truly
    out-of-core pool should use ``streaming.gradmatch_streaming``
    directly with a chunk factory (the trainer does).
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; known: {STRATEGIES}")
    # Strategy-specific knobs are rejected, not silently ignored, when the
    # strategy cannot honor them — a caller passing them is expressing an
    # expectation this dispatch would otherwise quietly drop.
    if partitions is not None:
        if strategy != "gradmatch-partitioned":
            raise ValueError(
                f"partitions={partitions} only applies to "
                f"'gradmatch-partitioned', not {strategy!r} — it would be "
                "silently ignored (drop it, or switch strategy)")
        if partitions < 1:
            raise ValueError(
                f"partitions must be >= 1, got {partitions}; omit it (or "
                "pass None) for automatic partition sizing")
    for name, val in (("buffer_cap", buffer_cap),
                      ("continual_batch", continual_batch)):
        if val is None:
            continue
        if strategy != "gradmatch-continual":
            raise ValueError(
                f"{name}={val} only applies to 'gradmatch-continual', not "
                f"{strategy!r} — it would be silently ignored")
        if val < 1:
            raise ValueError(f"{name} must be >= 1, got {val}")
    # One span per call, numbered in the process; the spans of the
    # call's layers nest inside it on this thread.
    with obs.span("select", strategy=strategy,
                  call=obs.count("select.calls")):
        n = proxies.shape[0]
        if strategy == "full":
            w = jnp.full((n,), 1.0 / n, jnp.float32)
            return SelectionResult(jnp.arange(n, dtype=jnp.int32), w,
                                   jnp.ones((n,), bool), jnp.float32(0.0))
        if strategy == "random":
            return random_sel.random_select(key, n, k)
        if strategy == "gradmatch":
            if per_class and labels is not None and num_classes > 1 and (
                    val_target is None):
                return gm_lib.gradmatch_per_class(
                    proxies, labels, num_classes, k, lam=lam, eps=eps,
                    method=omp_method)
            return gm_lib.gradmatch(proxies, k, target=val_target, lam=lam,
                                    eps=eps, method=omp_method)
        if strategy == "gradmatch-stream":
            if stream_cache_bytes <= 0:
                # The engine itself accepts cache_bytes=0 (certified, but
                # every commit re-pays a loader pass); through this
                # in-memory convenience path that trade is never what the
                # caller wants — it is always a typo or a unit slip
                # (bytes, not MB/rows).
                raise ValueError(
                    f"stream_cache_bytes must be > 0, got "
                    f"{stream_cache_bytes}: the compressed chunk cache is "
                    "what lets gradmatch-stream commit rounds without "
                    "re-reading the pool.  Pass bytes (e.g. 1 << 24); to "
                    "deliberately run cacheless use "
                    "streaming.omp_select_streaming(cache_bytes=0) directly.")
            return stream_lib.gradmatch_streaming_array(
                proxies, k, target=val_target, lam=lam, eps=eps,
                chunk_size=chunk_size, buffer_size=stream_buffer,
                cache_bytes=stream_cache_bytes)
        if strategy == "gradmatch-partitioned":
            # Partition-and-merge sharded selection (core/partition.py,
            # DESIGN.md §9): per-class partitions when the per-class mode
            # applies (mirroring "gradmatch"), hashed partitions otherwise;
            # out-of-core pools go through
            # ``partition.gradmatch_partitioned_stream`` directly.
            use_labels = (per_class and labels is not None and num_classes > 1
                          and val_target is None)
            return part_lib.gradmatch_partitioned(
                proxies, k, partitions=0 if partitions is None else partitions,
                labels=labels if use_labels else None,
                num_classes=num_classes if use_labels else 0,
                target=val_target, lam=lam, eps=eps, method=omp_method)
        if strategy == "gradmatch-continual":
            # Bounded-buffer maintained selection (repro.continual, DESIGN.md
            # §11): the pool is streamed through a fixed-capacity buffer in
            # admission batches; always pooled (like gradmatch-stream).  With
            # the default buffer_cap=None the buffer covers the pool and the
            # result is the pooled gradmatch solution; a smaller cap bounds
            # memory and selects over the rows surviving eviction.
            return continual_lib.continual_select(
                proxies, k, target=val_target, capacity=buffer_cap,
                batch=continual_batch, lam=lam, eps=eps)
        if strategy == "gradmatch-pb":
            return gm_lib.gradmatch_pb(
                proxies, batch_size, max(k // batch_size, 1), lam=lam, eps=eps,
                target=val_target, method=omp_method)
        if strategy in _CRAIG_METHODS:
            return craig_lib.craig(proxies, k, method=_CRAIG_METHODS[strategy],
                                   key=key,
                                   on_the_fly=(True if strategy in
                                               _CRAIG_ON_THE_FLY else None))
        if strategy == "craig-pb":
            return craig_lib.craig_pb(proxies, batch_size,
                                      max(k // batch_size, 1))
        if strategy == "glister":
            tgt = val_target if val_target is not None else jnp.sum(proxies, 0)
            return glister_lib.glister(proxies, tgt, k)
        raise ValueError(f"unknown strategy {strategy!r}; known: {STRATEGIES}")


def expand_if_pb(strategy: str, sel: SelectionResult, batch_size: int,
                 n_examples: int) -> SelectionResult:
    if strategy.endswith("-pb"):
        return gm_lib.expand_batch_selection(sel, batch_size, n_examples)
    return sel


def warm_start_epochs(total_epochs: int, budget_frac: float,
                      kappa: float = 0.5) -> tuple[int, int]:
    """(T_f full-data epochs, T_s subset epochs) per the paper's split.

    The split only makes sense for a genuine subset run: ``budget_frac``
    is ``k/n`` and must sit in (0, 1) — at >= 1 the "warm start" would be
    longer than full training (use strategy="full" instead), and the old
    code silently produced that schedule.  ``kappa`` in (0, 1] scales the
    total compute; 0 would yield zero subset epochs.
    """
    if total_epochs <= 0:
        raise ValueError(f"total_epochs must be positive, got {total_epochs}")
    if not 0.0 < budget_frac < 1.0:
        raise ValueError(
            f"budget_frac must be in (0, 1), got {budget_frac}; a fraction "
            ">= 1 makes the warm start longer than full-data training — "
            "use strategy='full' for a full-data run")
    if not 0.0 < kappa <= 1.0:
        raise ValueError(f"kappa must be in (0, 1], got {kappa}")
    t_s = max(int(round(kappa * total_epochs)), 1)
    t_f = int(round(t_s * budget_frac))
    return t_f, t_s


@dataclass(frozen=True)
class SelectionSchedule:
    select_every: int = 20         # R
    warm_epochs: int = 0           # T_f
    # Optional: the run length this schedule is meant for.  When given,
    # a warm start covering the whole run (so *no* selection epoch ever
    # fires and the trainer silently trains full-data at subset LR) is
    # rejected here instead of surfacing as a mystery accuracy gap.
    total_epochs: Optional[int] = None

    def __post_init__(self):
        if self.select_every <= 0:
            raise ValueError(
                f"select_every (R) must be positive, got "
                f"{self.select_every}; R <= 0 never re-selects")
        if self.warm_epochs < 0:
            raise ValueError(
                f"warm_epochs must be >= 0, got {self.warm_epochs}")
        if (self.total_epochs is not None
                and self.warm_epochs >= self.total_epochs):
            raise ValueError(
                f"warm_epochs={self.warm_epochs} >= total_epochs="
                f"{self.total_epochs}: the warm start swallows the whole "
                "run and no selection epoch ever fires")

    def is_selection_epoch(self, epoch: int) -> bool:
        """Selection at the first post-warm epoch, then every R."""
        if epoch < self.warm_epochs:
            return False
        return (epoch - self.warm_epochs) % self.select_every == 0

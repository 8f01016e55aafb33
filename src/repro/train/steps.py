"""Step builders: jitted train / eval steps for classifiers and LMs.

The weighted-subset objective is a first-class input: every step takes
``batch['weights']`` (the OMP output slice, summing to 1).  LM steps support
microbatch gradient accumulation (sequential ``lax.scan`` over microbatches
— the standard memory/throughput lever) and optional EF-TopK gradient
compression before the optimizer (models the sparse all-reduce transport).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.paper import ClassifierConfig
from repro.models import classifier as clf_lib
from repro.models import lm as lm_lib
from repro.optim import Optimizer, apply_updates, global_norm
from repro.train import compression as comp_lib


# ---------------------------------------------------------------------------
# Classifier steps (paper-faithful experiments)
# ---------------------------------------------------------------------------

def make_classifier_step(cfg: ClassifierConfig, opt: Optimizer) -> Callable:
    @jax.jit
    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            clf_lib.classifier_loss, argnums=1, has_aux=True)(
                cfg, params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, metrics

    return step


def make_classifier_eval(cfg: ClassifierConfig) -> Callable:
    @jax.jit
    def evaluate(params, batch):
        logits, _ = clf_lib.apply_classifier(cfg, params, batch["x"])
        pred = jnp.argmax(logits, axis=-1)
        acc = jnp.mean((pred == batch["y"]).astype(jnp.float32))
        lg = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        own = jnp.take_along_axis(lg, batch["y"][:, None], -1)[:, 0]
        return {"acc": acc, "ce": jnp.mean(lse - own)}

    return evaluate


def make_proxy_fn(cfg: ClassifierConfig) -> Callable:
    """Per-example last-layer gradient proxies (paper §4) for a classifier.

    Returns the per-class per-gradient proxy (n, d_h + 1) and the bias-grad
    proxy (n, C); a single forward pass, no trunk backprop.
    """
    from repro.core import proxies as proxy_lib

    @jax.jit
    def proxy(params, x, y):
        logits, hidden = clf_lib.apply_classifier(cfg, params, x)
        pcg = proxy_lib.per_class_grad_proxy(hidden, logits, y)
        bias = proxy_lib.bias_grad_proxy(logits, y)
        return pcg, bias

    return proxy


# ---------------------------------------------------------------------------
# LM steps
# ---------------------------------------------------------------------------

def lm_train_step_fn(
    cfg: ModelConfig,
    opt: Optimizer,
    microbatches: int = 1,
) -> Callable:
    """Raw (un-jitted) (params, opt_state, batch) -> (params, opt_state,
    metrics) — what the dry-run lowers with explicit shardings.

    ``microbatches > 1`` splits the batch on the leading axis and
    accumulates gradients sequentially (scan) — activation memory drops by
    the same factor.  Weighted loss: microbatch weight slices are NOT
    re-normalized (they sum to 1 globally), so the accumulated gradient is
    exactly the full weighted-batch gradient.
    """

    def loss_fn(params, batch):
        return lm_lib.lm_loss(cfg, params, batch)

    def grads_of(params, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return grads, metrics

        def split(x):
            return x.reshape(microbatches, x.shape[0] // microbatches,
                             *x.shape[1:])

        mb = jax.tree_util.tree_map(split, batch)

        def body(acc, one):
            (_, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, one)
            acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), acc, g)
            return acc, metrics

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        grads, metrics = jax.lax.scan(
            body, zeros, mb,
            unroll=microbatches if cfg.unroll_scan else 1)
        metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics)
        return grads, metrics

    def step(params, opt_state, batch):
        grads, metrics = grads_of(params, batch)
        metrics = {**metrics, "grad_norm": global_norm(grads)}
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, metrics

    return step


def make_lm_train_step(
    cfg: ModelConfig,
    opt: Optimizer,
    microbatches: int = 1,
    compress_frac: Optional[float] = None,
) -> Callable:
    """Jitted LM train step; see ``lm_train_step_fn``."""
    raw = lm_train_step_fn(cfg, opt, microbatches)
    if compress_frac is None:
        return jax.jit(raw)

    def loss_fn(params, batch):
        return lm_lib.lm_loss(cfg, params, batch)

    @jax.jit
    def step_c(params, opt_state, comp_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        grads, comp_state = comp_lib.compress_with_feedback(
            grads, comp_state, compress_frac)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, comp_state, metrics

    return step_c


def make_lm_proxy_step(cfg: ModelConfig) -> Callable:
    """Per-sequence selection proxies for LM candidate pools (jit)."""

    @jax.jit
    def proxy(params, batch):
        return lm_lib.selection_proxy(cfg, params, batch)

    return proxy

"""SGD(+momentum/Nesterov/weight-decay) and AdamW, pytree-native.

Written against plain jax so the optimizer state shards with the parameters
(each state leaf inherits the parameter PartitionSpec — see
distributed/sharding.py) and checkpoints as a pytree.  Master state is f32;
updates are returned in the *parameter* dtype so bf16 training works without
caller-side casting.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Union

import jax
import jax.numpy as jnp

Schedule = Union[float, Callable[[jax.Array], jax.Array]]


class OptState(NamedTuple):
    step: jax.Array          # () int32
    slots: Any               # optimizer-specific pytree(s)


class Optimizer(NamedTuple):
    init: Callable[[Any], OptState]
    update: Callable[[Any, OptState, Any], tuple[Any, OptState]]


def _lr_at(lr: Schedule, step: jax.Array) -> jax.Array:
    if callable(lr):
        return lr(step)
    return jnp.float32(lr)


def _f32_like(params: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros_like(p, jnp.float32), params)


def global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def _clipped(grads: Any, clip_norm: float | None) -> Any:
    if clip_norm is None:
        return grads
    g = global_norm(grads)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(g, 1e-12))
    return jax.tree_util.tree_map(
        lambda x: (x.astype(jnp.float32) * scale), grads)


def sgd(lr: Schedule, momentum: float = 0.0, weight_decay: float = 0.0,
        nesterov: bool = False, clip_norm: float | None = None) -> Optimizer:
    """Paper default: momentum 0.9, weight decay 5e-4, cosine-annealed lr."""

    def init(params):
        slots = _f32_like(params) if momentum else None
        return OptState(jnp.zeros((), jnp.int32), slots)

    def update(grads, state: OptState, params):
        grads = _clipped(grads, clip_norm)
        lr_t = _lr_at(lr, state.step)

        def one(g, p, m):
            g = g.astype(jnp.float32)
            if weight_decay:
                g = g + weight_decay * p.astype(jnp.float32)
            if momentum:
                m = momentum * m + g
                d = g + momentum * m if nesterov else m
            else:
                d = g
            upd = (-lr_t * d).astype(p.dtype)
            return upd, m

        if momentum:
            pairs = jax.tree_util.tree_map(one, grads, params, state.slots)
            updates = jax.tree_util.tree_map(
                lambda pr: pr[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
            slots = jax.tree_util.tree_map(
                lambda pr: pr[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
        else:
            updates = jax.tree_util.tree_map(
                lambda g, p: one(g, p, None)[0], grads, params)
            slots = None
        return updates, OptState(state.step + 1, slots)

    return Optimizer(init, update)


def adamw(lr: Schedule, b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
          weight_decay: float = 0.1, clip_norm: float | None = 1.0
          ) -> Optimizer:
    """AdamW with f32 (m, v) master slots — the LM-pretraining default."""

    def init(params):
        return OptState(jnp.zeros((), jnp.int32),
                        {"m": _f32_like(params), "v": _f32_like(params)})

    def update(grads, state: OptState, params):
        grads = _clipped(grads, clip_norm)
        step = state.step + 1
        lr_t = _lr_at(lr, state.step)
        c1 = 1.0 - b1 ** step.astype(jnp.float32)
        c2 = 1.0 - b2 ** step.astype(jnp.float32)

        def one(g, p, m, v):
            g = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            mh = m / c1
            vh = v / c2
            d = mh / (jnp.sqrt(vh) + eps)
            if weight_decay:
                d = d + weight_decay * p.astype(jnp.float32)
            return (-lr_t * d).astype(p.dtype), m, v

        triples = jax.tree_util.tree_map(one, grads, params,
                                         state.slots["m"], state.slots["v"])
        is_t = lambda x: isinstance(x, tuple)  # noqa: E731
        updates = jax.tree_util.tree_map(lambda t: t[0], triples, is_leaf=is_t)
        m = jax.tree_util.tree_map(lambda t: t[1], triples, is_leaf=is_t)
        v = jax.tree_util.tree_map(lambda t: t[2], triples, is_leaf=is_t)
        return updates, OptState(step, {"m": m, "v": v})

    return Optimizer(init, update)


def apply_updates(params: Any, updates: Any) -> Any:
    return jax.tree_util.tree_map(lambda p, u: p + u.astype(p.dtype),
                                  params, updates)

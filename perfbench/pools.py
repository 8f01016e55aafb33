"""Candidate pools made on the device from the seed: the benchmark's own
copy of the paper's per-gradient last-layer proxy (GRAD-MATCH section 4),

    g_i = [(p_ic - 1) h_i,  p_ic - 1]      for row i of class c,

over class-clustered non-negative features ``h`` and a seeded linear
head.  Each row's features lean by a seeded share towards another class,
so the true-class probability p_ic spreads over (0, 1) as in a partly
trained network.  Every seed gives the same sizes and class counts.

A configuration that holds a share of the classes (``num_classes`` of
``classes_total``, as one chip of a deployment that splits the
per-class problems over chips) gets the rows of its classes from the
whole pool: the head and its softmax still span every class.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one beyond 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.partial(jax.jit, static_argnames=("n", "classes", "dim", "held"))
def _per_class_pool(key, n: int, classes: int, dim: int, spread, mix_low,
                    head_gain, head_noise, held: int):
    k_mu, k_h, k_w, k_perm, k_mix, k_other = jax.random.split(key, 6)
    mu = jnp.abs(jax.random.normal(k_mu, (classes, dim)))
    labels = jnp.repeat(jnp.arange(classes, dtype=jnp.int32),
                        n // classes)
    labels = jax.random.permutation(k_perm, labels)
    # each row leans towards a confusing class by a seeded share
    other = (labels + jax.random.randint(k_other, (n,), 1, classes)
             ) % classes
    a = jax.random.uniform(k_mix, (n, 1), minval=mix_low, maxval=1.0)
    centre = a * mu[labels] + (1.0 - a) * mu[other]
    h = jax.nn.relu(centre + spread * jax.random.normal(k_h, (n, dim)))
    direction = mu - jnp.mean(mu, axis=0)
    direction = direction / jnp.linalg.norm(direction, axis=1,
                                            keepdims=True)
    w = (head_gain * direction.T
         + head_noise * jax.random.normal(k_w, (dim, classes))
         / jnp.sqrt(dim))
    logits = jnp.dot(h, w, precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    own = jnp.take_along_axis(p, labels[:, None], axis=1) - 1.0
    pool = jnp.concatenate([own * h, own], axis=1)
    if held == classes:
        return pool, labels
    # the rows of classes 0..held-1, in pool order
    rows = jnp.argsort(labels >= held, stable=True)[: n // classes * held]
    return pool[rows], labels[rows]


def per_class_pool(config: dict, seed: int, index: int = 0
                   ) -> tuple[jax.Array, jax.Array]:
    """((n, embed_dim + 1) f32 pool, (n,) int32 labels); ``index`` tells
    apart the pools made from one seed."""
    if config["n"] != config["num_classes"] * config["rows_per_class"]:
        raise ValueError("n must be num_classes * rows_per_class")
    total = config.get("classes_total", config["num_classes"])
    p = config["pool"]
    return _per_class_pool(jax.random.fold_in(seed_key(seed), index),
                           total * config["rows_per_class"], total,
                           config["embed_dim"], p["spread"], p["mix_low"],
                           p["head_gain"], p["head_noise"],
                           config["num_classes"])

"""Plain reference of GRAD-MATCH's OMP (the paper's Algorithm 2) in
``jax.numpy``, independent of the program under test.

Each round scores every candidate against the residual, adds the best one
to the active set (while the objective is above ``eps``), and re-solves
the non-negative ridge problem on the active set from zero by ``iters``
steps of projected gradient with step 1 / (the largest absolute row sum of
A + lam I).  The objective is ``||target - sum w_i g_i||^2 + lam ||w||^2``.

``dtype`` and ``precision`` set the arithmetic: float32 at ``HIGHEST`` is
the reference; bfloat16 operands at ``DEFAULT`` are its control, the step
below the float32 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

REFERENCE = (jnp.float32, lax.Precision.HIGHEST)
CONTROL = (jnp.bfloat16, lax.Precision.DEFAULT)


def _dot(a, b, dtype, precision):
    return jnp.dot(a.astype(dtype), b.astype(dtype), precision=precision,
                   preferred_element_type=jnp.float32)


def _nnls(a, c, mask, lam, iters, dtype, precision):
    k = a.shape[0]
    m = mask.astype(jnp.float32)
    a = (a + lam * jnp.eye(k)) * m[:, None] * m[None, :]
    c = c * m
    step = 1.0 / jnp.maximum(jnp.max(jnp.sum(jnp.abs(a), axis=1)), 1e-6)

    def body(_, w):
        w = jnp.maximum(w - step * (_dot(a, w, dtype, precision) - c), 0.0)
        return w * m

    return lax.fori_loop(0, iters, body, jnp.zeros((k,), jnp.float32))


@functools.partial(jax.jit, static_argnames=("k", "iters", "dtype",
                                             "precision"))
def omp(rows, target, k: int, lam: float, eps: float, iters: int,
        dtype=jnp.float32, precision=lax.Precision.HIGHEST):
    """OMP over the candidate ``rows`` (m, d): (indices, weights, mask,
    err), indices into ``rows`` and -1 on slots after the eps stop."""
    m = rows.shape[0]
    mm = functools.partial(_dot, dtype=dtype, precision=precision)

    def body(t, st):
        idx, mask, w, resid, err = st
        scores = mm(rows, resid)
        taken = jnp.zeros((m,), bool).at[jnp.where(mask, idx, m)].set(
            mask, mode="drop")
        e = jnp.argmax(jnp.where(taken, -jnp.inf, scores)).astype(jnp.int32)
        grow = err > eps
        idx = idx.at[t].set(jnp.where(grow, e, -1))
        mask = mask.at[t].set(grow)
        g_s = rows[jnp.where(mask, idx, 0)] * mask[:, None]
        w = _nnls(mm(g_s, g_s.T), mm(g_s, target), mask, lam, iters,
                  dtype, precision)
        resid = target - mm(w, g_s)
        return idx, mask, w, resid, jnp.sum(resid ** 2) + lam * jnp.sum(
            w ** 2)

    init = (jnp.full((k,), -1, jnp.int32), jnp.zeros((k,), bool),
            jnp.zeros((k,), jnp.float32), target.astype(jnp.float32),
            jnp.sum(target.astype(jnp.float32) ** 2))
    idx, mask, w, _, err = lax.fori_loop(0, k, body, init)
    return idx, w, mask, err


@functools.partial(jax.jit, static_argnames=("iters", "dtype",
                                             "precision"))
def replay(rows, target, picks, live, lam: float, eps: float, iters: int,
           dtype=jnp.float32, precision=lax.Precision.HIGHEST):
    """OMP forced to take ``picks`` (positions in ``rows``, in order;
    ``live`` false where the solver stopped).  Returns the weights the
    reference solves on them, the objective, and the worst regret: at
    each round, how far the forced pick's score lies below the best
    available score, over |residual| times the largest row norm (1 for a
    pick already taken or outside ``rows``, or a stop while the
    objective is above ``eps``)."""
    m = rows.shape[0]
    k = picks.shape[0]
    mm = functools.partial(_dot, dtype=dtype, precision=precision)
    scale = jnp.max(jnp.linalg.norm(rows, axis=1))

    def body(t, st):
        idx, mask, w, resid, err, regret = st
        scores = mm(rows, resid)
        taken = jnp.zeros((m,), bool).at[jnp.where(mask, idx, m)].set(
            mask, mode="drop")
        best = jnp.max(jnp.where(taken, -jnp.inf, scores))
        e = picks[t]
        inside = (e >= 0) & (e < m)
        e_safe = jnp.clip(e, 0, m - 1)
        gap = (best - scores[e_safe]) / jnp.maximum(
            jnp.sqrt(jnp.sum(resid ** 2)) * scale, 1e-30)
        bad = ~inside | taken[e_safe]
        r = jnp.where(live[t], jnp.where(bad, 1.0, jnp.maximum(gap, 0.0)),
                      jnp.where(err > eps, 1.0, 0.0))
        idx = idx.at[t].set(jnp.where(live[t], e_safe, -1))
        mask = mask.at[t].set(live[t])
        g_s = rows[jnp.where(mask, idx, 0)] * mask[:, None]
        w = _nnls(mm(g_s, g_s.T), mm(g_s, target), mask, lam, iters,
                  dtype, precision)
        resid = target - mm(w, g_s)
        err = jnp.sum(resid ** 2) + lam * jnp.sum(w ** 2)
        return idx, mask, w, resid, err, jnp.maximum(regret, r)

    target = target.astype(jnp.float32)
    init = (jnp.full((k,), -1, jnp.int32), jnp.zeros((k,), bool),
            jnp.zeros((k,), jnp.float32), target, jnp.sum(target ** 2),
            jnp.float32(0.0))
    _, _, w, _, err, regret = lax.fori_loop(0, k, body, init)
    return w, err, regret


def replay_classes(pool, labels, classes, picks, live, lam: float,
                   eps: float, iters: int, arith=REFERENCE) -> dict:
    """``replay`` of class problems: problem q is class ``classes[q]``
    over that class's own rows against its gradient sum, forced to take
    ``picks[q]`` (pool rows in pick order, ``live[q]`` where the solver
    grew).  Host arrays: ``weights`` (Q, k) as solved on the picks,
    ``err`` (Q,), ``regret`` (Q,) and ``targets`` (Q, d)."""
    labels = np.asarray(labels)
    picks = np.asarray(picks)
    classes = np.asarray(classes)
    n = len(labels)
    num_classes = int(labels.max()) + 1
    order = np.argsort(labels, kind="stable")
    sizes = np.bincount(labels, minlength=num_classes)
    if len(set(sizes.tolist())) != 1:
        raise ValueError("the reference expects classes of equal size")
    members = order.reshape(num_classes, int(sizes[0]))
    slot = np.empty(n, np.int64)                  # pool row -> class slot
    slot[members] = np.arange(members.shape[1])[None, :]
    safe = np.clip(picks, 0, n - 1)
    pos = np.where((picks >= 0) & (picks < n)
                   & (labels[safe] == classes[:, None]), slot[safe], -1)
    class_rows = jnp.asarray(pool)[jnp.asarray(members[classes])]
    targets = jnp.sum(class_rows, axis=1)
    run = functools.partial(replay, lam=lam, eps=eps, iters=iters,
                            dtype=arith[0], precision=arith[1])
    w, err, regret = jax.vmap(run)(class_rows, targets,
                                   jnp.asarray(pos, jnp.int32),
                                   jnp.asarray(live))
    return {"weights": np.asarray(w), "err": np.asarray(err),
            "regret": np.asarray(regret), "targets": np.asarray(targets)}


@functools.partial(jax.jit, static_argnames=("k", "iters", "dtype",
                                             "precision"))
def _per_class(class_rows, k, lam, eps, iters, dtype, precision):
    targets = jnp.sum(class_rows, axis=1)
    run = functools.partial(omp, k=k, lam=lam, eps=eps, iters=iters,
                            dtype=dtype, precision=precision)
    idx, w, mask, err = jax.vmap(run)(class_rows, targets)
    return idx, w, mask, err, targets


def per_class_omp(pool, labels, num_classes: int, k: int, lam: float,
                  eps: float, iters: int, arith=REFERENCE) -> dict:
    """One OMP of ``k`` rounds per class, over that class's own rows and
    against the class's gradient sum (equal class sizes).  Host arrays:
    ``rows`` (C, k) pool rows (-1 after an eps stop), ``mask``,
    ``weights`` (C, k) as solved, ``err`` (C,) and ``targets`` (C, d)."""
    labels = np.asarray(labels)
    order = np.argsort(labels, kind="stable")
    sizes = np.bincount(labels, minlength=num_classes)
    if len(set(sizes.tolist())) != 1:
        raise ValueError("the reference expects classes of equal size")
    members = order.reshape(num_classes, int(sizes[0]))   # (C, m) rows
    class_rows = jnp.asarray(pool)[jnp.asarray(members)]
    idx, w, mask, err, targets = _per_class(class_rows, k, lam, eps, iters,
                                            *arith)
    idx, w, mask = (np.asarray(x) for x in (idx, w, mask))
    rows = np.where(mask, np.take_along_axis(members, np.maximum(idx, 0),
                                             axis=1), -1)
    return {"rows": rows, "mask": mask, "weights": w,
            "err": np.asarray(err), "targets": np.asarray(targets)}

"""Faults planted in the timed path, to show that the comparison catches
them: each is a context manager that swaps one function of the program
for a broken one and puts it back on exit.

- ``altered_answer``: a library selection has one selected row swapped
  for a row it did not select;
- ``half_pool``: a library selection sees half of the pool.
"""

from __future__ import annotations

import contextlib
import sys

import jax.numpy as jnp
import numpy as np

import harness


def _program():
    if harness.SRC not in sys.path:
        sys.path.insert(0, harness.SRC)
    from repro.core import gradmatch
    return gradmatch


@contextlib.contextmanager
def _swap(module, name, make):
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def altered_answer():
    gm = _program()

    def make(orig):
        def wrapped(*a, **kw):
            res = orig(*a, **kw)
            idx = np.asarray(res.indices)
            first = int(np.flatnonzero(np.asarray(res.mask))[0])
            unused = np.setdiff1d(np.arange(idx.max() + 2), idx)[0]
            return res._replace(indices=jnp.asarray(idx).at[first].set(
                unused))
        return wrapped
    return _swap(gm, "gradmatch_per_class", make)


def half_pool():
    gm = _program()

    def make(orig):
        def wrapped(grads, labels, *a, **kw):
            n = grads.shape[0]
            kept = jnp.where(jnp.arange(n) < n // 2, labels, -1)
            return orig(grads, kept, *a, **kw)
        return wrapped
    return _swap(gm, "gradmatch_per_class", make)


BY_LOOP = {"library": (altered_answer, half_pool)}

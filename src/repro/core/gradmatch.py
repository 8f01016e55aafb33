"""GRAD-MATCH: gradient-matching data subset selection (paper Alg. 1 + 2).

Entry points:
  - ``gradmatch``          : OMP over per-example proxies (optionally per-class)
  - ``gradmatch_pb``       : OMP over per-mini-batch proxies (the PB variant)
  - ``SelectionResult``    : padded static-shape result consumed by the trainer

The target gradient is the *sum* of candidate gradients when matching the
training loss (isValid=False) or the sum of validation-proxy gradients when
matching the validation loss (isValid=True) -- exactly eq. (2) of the paper.
Returned weights are normalized to sum to 1 (the normalization Thm 1 assumes);
the trainer multiplies back by the subset size so loss magnitudes match an
unweighted mean and the usual LR schedules transfer.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import omp as omp_lib
from repro.core import proxies as proxy_lib
from repro.kernels.ref import PRECISION


class SelectionResult(NamedTuple):
    indices: jax.Array  # (k,) int32 candidate ids, -1 on unused slots
    weights: jax.Array  # (k,) f32, >= 0, sums to 1 over valid slots
    mask: jax.Array     # (k,) bool
    err: jax.Array      # () f32  final E_lambda value (diagnostic)
    # Solver accounting (streaming entry points attach their SelectStats;
    # None elsewhere, so array-only consumers are unaffected).
    stats: Optional[Any] = None

    @property
    def size(self):
        return jnp.sum(self.mask)


def _normalize(w: jax.Array, mask: jax.Array) -> jax.Array:
    w = jnp.where(mask, w, 0.0)
    s = jnp.sum(w)
    # Degenerate all-zero solutions fall back to uniform over the mask.
    uniform = mask.astype(w.dtype) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.where(s > 1e-12, w / jnp.maximum(s, 1e-12), uniform)


def gradmatch(
    grads: jax.Array,            # (n, d) candidate gradient proxies
    k: int,
    target: jax.Array | None = None,   # (d,) defaults to sum of grads
    lam: float = 0.5,
    eps: float = 1e-10,
    valid: jax.Array | None = None,
    corr_fn=None,
    method: str = "incremental",       # OMP solver: "incremental" | "dense"
) -> SelectionResult:
    """Plain GRAD-MATCH on an explicit candidate gradient matrix."""
    if target is None:
        if valid is None:
            target = jnp.sum(grads, axis=0)
        else:
            target = jnp.sum(grads * valid[:, None].astype(grads.dtype), axis=0)
    idx, w, mask, err = omp_lib.omp_select(
        grads, target, k=k, lam=lam, eps=eps, valid=valid, corr_fn=corr_fn,
        method=method,
    )
    return SelectionResult(idx, _normalize(w, mask), mask, err)


def gradmatch_per_class(
    grads: jax.Array,       # (n, d) per-class per-gradient proxies
    labels: jax.Array,      # (n,)
    num_classes: int,
    k: int,
    lam: float = 0.5,
    eps: float = 1e-10,
    method: str = "incremental",
) -> SelectionResult:
    """Paper default: one OMP per class, budget split exactly.

    The budget split is Algorithm 1's accounting done right
    (``omp.split_budget``): the ``k % C`` remainder goes to the largest
    classes first, each quota is capped at its class size, and capped-off
    surplus is rebalanced — so the selection holds exactly ``min(k,
    n_valid)`` rows (rows whose label falls outside ``[0, num_classes)``
    are not candidates).  ``err`` is the true global objective
    ``||Σ_c g_tgt_c − Σ w·g||² + λ||w||²`` of the unnormalized per-class
    solution against the summed target — not a placeholder.
    """
    with obs.span("gradmatch.budget"):
        labels_np = np.asarray(labels)
        in_range = (labels_np >= 0) & (labels_np < num_classes)
        sizes = np.bincount(labels_np[in_range], minlength=num_classes)
        quotas = omp_lib.split_budget(k, sizes)
    with obs.span("gradmatch.targets"):
        onehot = jax.nn.one_hot(labels, num_classes,
                                dtype=grads.dtype)                   # (n, C)
        targets = jnp.dot(onehot.T, grads, precision=PRECISION)      # (C, d)
    with obs.span("omp.per_class"):
        table, valid = omp_lib._class_table(labels_np, num_classes)
        return _per_class(grads, targets, table, valid,
                          quotas.astype(np.int32), lam, eps,
                          k=int(quotas.max()), method=method)


@functools.partial(jax.jit, static_argnames=("k", "method"))
def _per_class(grads, targets, table, valid, quotas, lam, eps, k, method):
    """``gradmatch_per_class``'s one program: the per-class solve and
    reweight, the objective against the summed target and the global
    normalisation."""
    idx, w, mask = omp_lib._solve_classes(grads, targets, table, valid,
                                          quotas, lam, eps, k=k,
                                          method=method)
    with obs.scope("gradmatch.err"):
        err = omp_lib.matching_error(grads, jnp.sum(targets, axis=0), idx,
                                     w, mask, lam=lam)
    # Per-class weights each sum to ~their class share; renormalize globally.
    return SelectionResult(idx, _normalize(w, mask), mask, err)


def gradmatch_pb(
    example_proxies: jax.Array,  # (n, d)
    batch_size: int,
    k_batches: int,
    lam: float = 0.5,
    eps: float = 1e-10,
    target: jax.Array | None = None,
    corr_fn=None,
    method: str = "incremental",
) -> SelectionResult:
    """GRAD-MATCHPB: ground set = mini-batches (paper S3, 'PB' variant)."""
    pb = proxy_lib.per_batch(example_proxies, batch_size)
    if target is None:
        # Sum of *batch* gradients approximates the full gradient / B.
        target = jnp.sum(pb, axis=0)
    return gradmatch(pb, k=k_batches, target=target, lam=lam, eps=eps,
                     corr_fn=corr_fn, method=method)


def expand_batch_selection(
    sel: SelectionResult, batch_size: int, n_examples: int
) -> SelectionResult:
    """Expand a per-batch selection to per-example indices/weights.

    Batch j covers examples [j*B, (j+1)*B); each inherits w_j / B so the
    total still sums to 1.
    """
    k = sel.indices.shape[0]
    base = jnp.where(sel.mask, sel.indices, 0) * batch_size          # (k,)
    offs = jnp.arange(batch_size, dtype=jnp.int32)                   # (B,)
    ex_idx = (base[:, None] + offs[None, :]).reshape(-1)             # (k*B,)
    ex_idx = jnp.where(jnp.repeat(sel.mask, batch_size), ex_idx, -1)
    ex_idx = jnp.where(ex_idx < n_examples, ex_idx, -1)
    ex_mask = ex_idx >= 0
    ex_w = jnp.repeat(sel.weights / batch_size, batch_size)
    ex_w = jnp.where(ex_mask, ex_w, 0.0)
    s = jnp.maximum(jnp.sum(ex_w), 1e-12)
    return SelectionResult(ex_idx.astype(jnp.int32), ex_w / s, ex_mask,
                           sel.err, sel.stats)

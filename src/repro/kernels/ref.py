"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (tests sweep
shapes/dtypes with ``interpret=True`` and assert_allclose against these), and
they are also the dispatch fallback on backends without Pallas support
(see ops.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Selection contractions are f32-exact on every backend: left to its
# default, XLA on a TPU rounds f32 matmul operands to bf16 (DESIGN §1).
PRECISION = jax.lax.Precision.HIGHEST


def corr_ref(grads: jax.Array, residual: jax.Array) -> jax.Array:
    """OMP residual-correlation scores:  (n, d) @ (d,) -> (n,) in f32."""
    return jnp.dot(grads.astype(jnp.float32), residual.astype(jnp.float32),
                   precision=PRECISION)


def corr_argmax_ref(colcache: jax.Array, w: jax.Array, base: jax.Array,
                    mask: jax.Array, absolute: bool = False
                    ) -> tuple[jax.Array, jax.Array]:
    """Masked argmax of  scores = base - colcache @ w  (incremental OMP).

    colcache (n, k), w (k,), base (n,), mask (n,) bool ->
    (argmax index i32 (), max score f32 ()).  Ties resolve to the lowest
    index (jnp.argmax semantics); an all-False mask yields (0, -inf).
    """
    scores = base.astype(jnp.float32) - jnp.dot(
        colcache.astype(jnp.float32), w.astype(jnp.float32),
        precision=PRECISION)
    if absolute:
        scores = jnp.abs(scores)
    scores = jnp.where(mask, scores, -jnp.inf)
    idx = jnp.argmax(scores).astype(jnp.int32)
    return idx, scores[idx]


def corr_batched_ref(grads: jax.Array, vecs: jax.Array) -> jax.Array:
    """Batched OMP scores:  (n, d) @ (B, d)^T -> **(n, B)** in f32.

    One shared-operand matmul instead of B matvecs — the batched serving
    path's scoring step (column b is ``corr_ref(grads, vecs[b])``).  The
    transposed orientation is deliberate: contracting along the pool's
    contiguous rows (``g @ v^T``) runs ~2x faster on XLA:CPU than
    ``v @ g^T`` and feeds an axis-0 argmax with no output transpose.
    """
    return jnp.dot(grads.astype(jnp.float32), vecs.astype(jnp.float32).T,
                   precision=PRECISION)


def corr_argmax_batched_ref(mat: jax.Array, w: jax.Array, base_t: jax.Array,
                            mask_t: jax.Array, absolute: bool = False
                            ) -> tuple[jax.Array, jax.Array]:
    """Batched twin of ``corr_argmax_ref``:  B fused score-and-argmax.

    ``mat`` is either a per-problem column cache ``(B, n, p)`` or a shared
    pool matrix ``(n, p)`` (the narrow-regime call, where every problem
    scores the same pool against its own residual ``w``).  w (B, p);
    ``base_t``/``mask_t`` are **pool-major** ``(n, B)`` (same orientation
    as ``corr_batched_ref`` output — the hot matmul then never transposes)
    -> (indices (B,) i32, values (B,) f32).  Per-problem semantics match
    the single-problem reference: lowest-index tie-break (axis-0 argmax),
    all-masked column yields (0, -inf).
    """
    w = w.astype(jnp.float32)
    base_t = base_t.astype(jnp.float32)
    if mat.ndim == 2:
        scores = base_t - jnp.dot(mat.astype(jnp.float32), w.T,
                                  precision=PRECISION)          # (n, B)
    else:
        scores = base_t - jnp.einsum("bnp,bp->nb",
                                     mat.astype(jnp.float32), w,
                                     precision=PRECISION)
    if absolute:
        scores = jnp.abs(scores)
    scores = jnp.where(mask_t, scores, -jnp.inf)
    idx = jnp.argmax(scores, axis=0).astype(jnp.int32)
    vals = scores[idx, jnp.arange(scores.shape[1])]
    return idx, vals


def bound_max_ref(rows: jax.Array, norms: jax.Array, errn: jax.Array,
                  residual: jax.Array, acc: jax.Array, thresh: jax.Array,
                  mask: jax.Array, absolute: bool = False
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused interval-bound scan over a compressed row cache (streaming
    OMP certification rung 2, DESIGN.md §7).

    rows (n, d) bf16 (or f32), norms/errn (n,) f32 sidecars (exact row
    norm, ``‖g − bf16(g)‖``), residual (d,), acc () accumulation-margin
    scalar, thresh () comparison threshold (the buffer max), mask (n,)
    bool -> (max upper bound f32 (), its argmax index i32 (), count of
    masked rows with ``u >= thresh`` i32 ()).

    ``u_i = s̃_i + (e_i + acc·‖g_i‖)·‖r‖`` upper-bounds the exact f32
    score of the uncompressed row; the count is the certification
    offender count.  Ties resolve to the lowest index; an all-False mask
    yields (-inf, 0, 0).
    """
    r = residual.astype(jnp.float32)
    s = jnp.dot(rows.astype(jnp.float32), r, precision=PRECISION)
    if absolute:
        s = jnp.abs(s)
    rnorm = jnp.sqrt(jnp.sum(r * r))
    u = s + (errn + acc * norms) * rnorm
    u_m = jnp.where(mask, u, -jnp.inf)
    idx = jnp.argmax(u_m).astype(jnp.int32)
    return (u_m[idx], idx,
            jnp.sum(mask & (u_m >= thresh)).astype(jnp.int32))


def fl_gain_argmax_ref(sim: jax.Array, cover: jax.Array, mask: jax.Array
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Facility-location gain scan (CRAIG greedy, resident similarity).

    sim (n, n), cover (n,), mask (n,) bool ->
    (gains (n,) f32 with gain_j = sum_i relu(s_ij - cover_i), masked argmax
    index i32 (), max gain f32 ()).  Gains are raw (unmasked); ties resolve
    to the lowest index (jnp.argmax semantics) and an all-False mask yields
    (0, -inf).  XLA fuses the relu into the column reduction, so no
    (n, n) temporary materializes on the reference path either.
    """
    gains = jnp.sum(
        jnp.maximum(sim.astype(jnp.float32)
                    - cover.astype(jnp.float32)[:, None], 0.0),
        axis=0,
    )
    masked = jnp.where(mask, gains, -jnp.inf)
    idx = jnp.argmax(masked).astype(jnp.int32)
    return gains, idx, masked[idx]


def fl_gains_cols_ref(cand: jax.Array, cand_sqn: jax.Array,
                      grads: jax.Array, sqnorms: jax.Array,
                      cover: jax.Array, row_ok: jax.Array,
                      l_max: jax.Array, block: int = 256) -> jax.Array:
    """FL gains for an explicit candidate slice, blocked over coverage
    rows: cand (m, d) against the pool grads (n, d) -> (m,) gains with
    ``gain_j = Σ_i relu((l_max - ||g_i - c_j||)·row_ok_i − cover_i)``,
    peak memory O(block·m).  The single copy of the strip computation:
    the full scan below runs it with cand = grads, the lazy engine's
    block refresh and the pmap-sharded scan run it on slices — keeping
    every on-the-fly gain bit-for-bit reduction-order-identical, which
    the lazy certification margin assumes.
    """
    n, d = grads.shape
    g = grads.astype(jnp.float32)
    lm = jnp.asarray(l_max, jnp.float32)
    nb = -(-n // block)
    pad = nb * block - n
    gp = jnp.pad(g, ((0, pad), (0, 0)))
    sqnp = jnp.pad(sqnorms, (0, pad))
    cp = jnp.pad(cover.astype(jnp.float32), (0, pad))
    okp = jnp.pad(row_ok.astype(jnp.float32), (0, pad))
    cand = cand.astype(jnp.float32)

    def body(b, gains):
        lo = b * block
        rows = jax.lax.dynamic_slice(gp, (lo, 0), (block, d))
        rn = jax.lax.dynamic_slice(sqnp, (lo,), (block,))
        cv = jax.lax.dynamic_slice(cp, (lo,), (block,))
        ok = jax.lax.dynamic_slice(okp, (lo,), (block,))
        d2 = rn[:, None] + cand_sqn[None, :] - 2.0 * jnp.dot(
            rows, cand.T, precision=PRECISION)
        s = (lm - jnp.sqrt(jnp.maximum(d2, 0.0))) * ok[:, None]
        return gains + jnp.sum(jnp.maximum(s - cv[:, None], 0.0), axis=0)

    return jax.lax.fori_loop(0, nb, body,
                             jnp.zeros((cand.shape[0],), jnp.float32))


def fl_gain_argmax_otf_ref(grads: jax.Array, cover: jax.Array,
                           row_ok: jax.Array, mask: jax.Array,
                           l_max: jax.Array, block: int = 1024,
                           sqnorms: jax.Array | None = None
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """On-the-fly twin of ``fl_gain_argmax_ref``: same outputs, but the
    similarity ``s_ij = (l_max - ||g_i - g_j||) * row_ok_i`` is produced in
    (block, n) row strips from grads (n, d) — the (n, n) matrix never
    materializes, which is the whole point of this code path (it doubles
    as the off-TPU dispatch target at pool sizes where a resident
    similarity would be GBs).  ``sqnorms`` (the squared row norms) lets
    callers that already hold them (the lazy engine hoists them once per
    selection) skip the per-call recomputation.  The 1024-row strip
    default is the measured CPU sweet spot for the full scan (~1.9x over
    256-row strips at pool 32768 — fewer passes over the candidate
    operand); the strip size only changes reduction order, which the
    lazy certification margin absorbs.
    """
    g = grads.astype(jnp.float32)
    sqn = (jnp.sum(g * g, axis=1) if sqnorms is None
           else jnp.asarray(sqnorms, jnp.float32))
    gains = fl_gains_cols_ref(g, sqn, g, sqn, cover, row_ok, l_max,
                              block=block)
    masked = jnp.where(mask, gains, -jnp.inf)
    idx = jnp.argmax(masked).astype(jnp.int32)
    return gains, idx, masked[idx]


def sqdist_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """Pairwise squared euclidean distances  (n, d), (m, d) -> (n, m), f32.

    Computed the numerically-stable expanded way (same contraction order the
    kernel uses) so the oracle and the kernel agree to float tolerance.
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    an = jnp.sum(a * a, axis=-1)
    bn = jnp.sum(b * b, axis=-1)
    d2 = an[:, None] + bn[None, :] - 2.0 * jnp.dot(a, b.T,
                                                 precision=PRECISION)
    return jnp.maximum(d2, 0.0)


def lastlayer_grad_ref(
    hidden: jax.Array,   # (n, d_h)
    logits: jax.Array,   # (n, v)
    labels: jax.Array,   # (n,) int32
) -> tuple[jax.Array, jax.Array]:
    """Fused last-layer CE gradient pieces.

    Returns
      resid : (n, v)  = softmax(logits) - onehot(labels)   (dL/db per sample)
      hgrad : (n, d_h) = resid @ nothing -- the *hidden-side* reduction the
              per-batch proxy needs is resid^T @ hidden aggregated per batch;
              here we return the per-sample row-scaled hidden
              own_resid * hidden (the paper's per-gradient approximation),
              own_resid = resid[i, labels[i]].
    """
    z = logits.astype(jnp.float32)
    z = z - jax.lax.stop_gradient(jnp.max(z, axis=-1, keepdims=True))
    p = jnp.exp(z) / jnp.sum(jnp.exp(z), axis=-1, keepdims=True)
    y = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    resid = p - y
    own = jnp.take_along_axis(resid, labels[:, None].astype(jnp.int32), axis=-1)
    hgrad = own * hidden.astype(jnp.float32)
    return resid, hgrad

"""Orthogonal Matching Pursuit (Algorithm 2 of the paper), TPU-native.

The paper minimizes, over subsets ``X`` (|X| <= k) and non-negative weights
``w``::

    Err_lambda(w, X) = || sum_{i in X} w_i g_i  -  g_tgt ||^2 + lambda ||w||^2

where ``g_i`` are candidate gradients (rows of ``G``, shape (n, d)) and
``g_tgt`` is the full training-set or validation-set gradient.  OMP greedily
adds the candidate with the largest |residual correlation| and re-solves the
(regularized, non-negative) least squares on the active set.

Two solvers live here (see DESIGN.md §2):

``omp_select`` (default ``method="incremental"``)
    The production path.  Cross-round state is cached so nothing is ever
    recomputed from scratch:

    * ``c0 = G @ g_tgt`` is computed once; a column cache ``C[:, t] = G @
      g_{e_t}`` is extended by one column per round, so the per-round scores
      are ``c0 - C @ w`` — the candidate matrix is touched once per round
      for the single new column instead of a full residual matvec plus a
      ``(k, d)`` active-set gather.
    * the active-set Gram ``A = G_S G_S^T`` and target correlation ``c_S =
      G_S g_tgt`` grow by one row/col per round (the new Gram row is a free
      read out of the column cache: ``A[t, j] = C[e_t, j]``), and the NNLS
      consumes these cached buffers — the ``(k, d)`` active matrix is never
      re-materialized.
    * the residual norm is tracked through the identity ``||r||^2 =
      ||g_tgt||^2 - 2 w^T c_S + w^T A w``; it is evaluated in the factored
      form ``||g_tgt - w^T R||^2`` over the cached active rows ``R`` (the
      same value, but immune to the f32 cancellation that the expanded form
      suffers when the residual is ~eps, which would defeat the early stop).
    * rounds are processed in blocks with statically-growing prefix buffers,
      so round ``t`` pays O(t)-sized matvecs rather than O(k)-sized ones.

    Per-round cost: O(n·t) scores + O(n·d) new column + O(t·min(t, d)) per
    NNLS iteration, versus the dense solver's O(t^2·d) Gram rebuild.

``omp_select_dense`` (= ``method="dense"``)
    The straightforward re-solve-from-scratch formulation (what CORDS does
    with dynamic Python lists + scipy NNLS on CPU, here as a fixed-iteration
    ``lax.fori_loop`` over a *padded* active set).  Kept as the reference
    implementation: parity tests assert the incremental path reproduces its
    selections to f32 tolerance, and benchmarks report the speedup.

Both jit, vmap (per-class decomposition = leading batch axis) and run
sharded on a pod without host round-trips.  Weights are solved by
projected-gradient non-negative ridge regression on the active set — a
small problem solved in VMEM-resident registers.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.kernels import ops
from repro.kernels.ref import PRECISION


class OMPState(NamedTuple):
    """Carry for the dense OMP loop (all static shapes)."""

    indices: jax.Array   # (k,) int32, selected candidate ids, -1 = unused slot
    mask: jax.Array      # (k,) bool, slot valid
    weights: jax.Array   # (k,) f32, non-negative weights for active slots
    residual: jax.Array  # (d,) f32, g_tgt - G_S^T w
    err: jax.Array       # () f32, current ||residual||^2 + lam*||w||^2


class OMPIncState(NamedTuple):
    """Carry for the incremental OMP loop.

    ``indices``/``mask`` are full ``(k,)``; everything else is a prefix
    buffer of the current block width P (grown between blocks, see
    ``omp_select``), so early rounds pay O(t)-sized work.
    """

    indices: jax.Array   # (k,) int32
    mask: jax.Array      # (k,) bool
    weights: jax.Array   # (P,) f32
    colcache: jax.Array  # (n, P) f32, C[:, t] = G @ g_{e_t} (wide regime)
    gram: jax.Array      # (P, P) f32, active-set Gram (inactive rows/cols 0)
    gram_absrow: jax.Array  # (P,) f32, sum_j |A_ij| over active j (cached
                            # Gershgorin row sums for the NNLS step size)
    tcorr: jax.Array     # (P,) f32, c_S[t] = g_{e_t} . g_tgt
    rows: jax.Array      # (P, d) f32, cached active rows (zero when unused)
    residual: jax.Array  # (d,) f32, g_tgt - w^T rows
    err: jax.Array       # () f32


def _nnls_active(
    gram: jax.Array,      # (k, k) = G_S G_S^T  (masked rows/cols zeroed)
    corr: jax.Array,      # (k,)   = G_S g_tgt
    mask: jax.Array,      # (k,) bool
    lam: float,
    n_iters: int,
) -> jax.Array:
    """Non-negative ridge LS on the (masked) active set via projected gradient.

    Solves  min_{w>=0} 0.5 w^T (A + lam I) w - c^T w  restricted to mask.
    Lipschitz step 1/L with L = trace upper bound; fixed iterations keep the
    whole thing jittable.  k is small (<= few hundred) so this is negligible
    next to the correlation scan over n candidates.
    """
    k = gram.shape[0]
    a = gram + lam * jnp.eye(k, dtype=gram.dtype)
    # Zero out inactive rows/cols so they stay at w=0.
    m = mask.astype(gram.dtype)
    a = a * m[:, None] * m[None, :]
    c = corr * m
    # Lipschitz bound: row-sum (Gershgorin) of |A|, floored for stability.
    lip = jnp.maximum(jnp.max(jnp.sum(jnp.abs(a), axis=1)), 1e-6)
    step = 1.0 / lip

    def body(_, w):
        grad = jnp.dot(a, w, precision=PRECISION) - c
        w = jnp.maximum(w - step * grad, 0.0)
        return w * m

    w0 = jnp.zeros((k,), dtype=gram.dtype)
    return lax.fori_loop(0, n_iters, body, w0)


def _nnls_active_cached(
    gram: jax.Array,         # (k, k) cached Gram, inactive rows/cols zero
    gram_absrow: jax.Array,  # (k,) cached sum_j |A_ij| over active j
    rows: jax.Array,         # (k, d) cached active rows, inactive rows zero
    corr: jax.Array,         # (k,) cached c_S, inactive entries zero
    mask: jax.Array,         # (k,) bool
    lam: float,
    n_iters: int,
) -> jax.Array:
    """Same math as ``_nnls_active``, consuming the incremental caches.

    The masked system matrix is never materialized: the step size comes from
    the cached Gershgorin row sums (O(1) per round instead of O(k^2) per
    call), and the matvec ``A @ w`` uses whichever factor is cheaper —
    ``R (R^T w)`` at O(k·d) when d < k, or the cached ``(k, k)`` Gram at
    O(k^2) when the proxy dimension dominates.
    """
    m = mask.astype(rows.dtype)
    c = corr * m
    lip = jnp.maximum(jnp.max(m * (gram_absrow + lam)), 1e-6)
    step = 1.0 / lip
    k, d = rows.shape
    use_factor = d < k  # static shapes -> trace-time choice

    def body(_, w):
        if use_factor:
            aw = jnp.dot(rows, jnp.dot(w, rows, precision=PRECISION),
                         precision=PRECISION) + lam * w
        else:
            aw = jnp.dot(gram, w, precision=PRECISION) + lam * w
        w = jnp.maximum(w - step * (aw - c), 0.0)
        return w * m

    w0 = jnp.zeros((k,), dtype=rows.dtype)
    return lax.fori_loop(0, n_iters, body, w0)


def _omp_select_dense(grads, target, k, lam, eps, nnls_iters, positive,
                      valid, corr_fn):
    """Reference solver: re-gather + re-solve the active set every round."""
    n, d = grads.shape
    neg_inf = jnp.float32(-jnp.inf)

    def correlate(residual):
        if corr_fn is not None:
            return corr_fn(grads, residual)
        return jnp.dot(grads, residual, precision=PRECISION)

    def body(t, state: OMPState):
        # 1) residual correlations;  already-selected / invalid candidates out.
        with obs.scope("omp.score"):
            scores = correlate(state.residual)
            if positive:
                scores_sel = scores          # match direction of the target
            else:
                scores_sel = jnp.abs(scores)
            # Unused slots point at the out-of-bounds sentinel n so
            # mode="drop" discards them (an in-bounds sentinel would race
            # duplicate writes).
            taken = jnp.zeros((n,), dtype=bool).at[
                jnp.where(state.mask, state.indices, n)
            ].set(state.mask, mode="drop")
            scores_sel = jnp.where(valid & ~taken, scores_sel, neg_inf)
            e = jnp.argmax(scores_sel).astype(jnp.int32)

        # stop criterion E_lambda <= eps  -> do not grow the active set.
        with obs.scope("omp.column"):
            grow = state.err > eps
            new_indices = state.indices.at[t].set(jnp.where(grow, e, -1))
            new_mask = state.mask.at[t].set(grow)
            # 2) gather the active set and rebuild its Gram.
            sel = jnp.where(new_mask, new_indices, 0)
            g_s = grads[sel] * new_mask[:, None].astype(grads.dtype)  # (k, d)
            gram = jnp.dot(g_s, g_s.T, precision=PRECISION)
            corr = jnp.dot(g_s, target, precision=PRECISION)

        # 3) re-solve non-negative ridge LS; residual + error refresh.
        with obs.scope("omp.nnls"):
            w = _nnls_active(gram, corr, new_mask, lam, nnls_iters)
            approx = jnp.dot(w, g_s, precision=PRECISION)
            residual = target - approx
            err = jnp.sum(residual**2) + lam * jnp.sum(w**2)
        return OMPState(new_indices, new_mask, w, residual, err)

    init = OMPState(
        indices=jnp.full((k,), -1, dtype=jnp.int32),
        mask=jnp.zeros((k,), dtype=bool),
        weights=jnp.zeros((k,), dtype=jnp.float32),
        residual=target,
        err=jnp.sum(target**2) + jnp.float32(0.0),
    )
    out = lax.fori_loop(0, k, body, init)
    return out.indices, out.weights, out.mask, out.err


def _grow_prefix(st: OMPIncState, width: int, keep_cols: bool) -> OMPIncState:
    """Zero-pad the prefix buffers out to ``width`` slots (static).

    ``keep_cols=False`` (narrow-proxy regime, see below) stops growing the
    column cache — it is dead state from that block on.
    """
    pad = width - st.weights.shape[0]
    return OMPIncState(
        indices=st.indices,
        mask=st.mask,
        weights=jnp.pad(st.weights, (0, pad)),
        colcache=(jnp.pad(st.colcache, ((0, 0), (0, pad))) if keep_cols
                  else st.colcache),
        gram=jnp.pad(st.gram, ((0, pad), (0, pad))),
        gram_absrow=jnp.pad(st.gram_absrow, (0, pad)),
        tcorr=jnp.pad(st.tcorr, (0, pad)),
        rows=jnp.pad(st.rows, ((0, pad), (0, 0))),
        residual=st.residual,
        err=st.err,
    )


def _inc_body_factory(grads, target, c0, valid, lam, eps, nnls_iters,
                      absolute):
    """Round-body factory shared by every incremental-Gram consumer.

    ``_omp_select_incremental`` (one-shot), the anytime session engine
    (``omp_session_start`` / ``omp_session_extend``) and their tests all
    run the body this returns — one copy of the cached-correlation round
    update, so a session resume is bit-identical to the one-shot rounds it
    skips.
    """
    n = grads.shape[0]
    zeros_n = jnp.zeros((n,), dtype=jnp.float32)

    def make_body(use_cols: bool):
        def body(t, st: OMPIncState):
            p = st.weights.shape[0]     # static prefix width, t < p <= k
            # 1) fused scores-and-argmax (one streaming pass, no (n,)
            #    score vector materialized on TPU).
            with obs.scope("omp.score"):
                # Out-of-bounds sentinel for unused slots, dropped by the
                # scatter — see the dense body for why n-1 would be wrong.
                taken = jnp.zeros((n,), dtype=bool).at[
                    jnp.where(st.mask, st.indices, n)
                ].set(st.mask, mode="drop")
                avail = valid & ~taken
                if use_cols:
                    e, _ = ops.corr_argmax(st.colcache, st.weights, c0,
                                           avail, absolute=absolute)
                else:
                    e, _ = ops.corr_argmax(grads, -st.residual, zeros_n,
                                           avail, absolute=absolute)

            # 2) extend the caches by one slot (updates are gated on `grow`
            #    so a stopped solver leaves every buffer unchanged).
            with obs.scope("omp.column"):
                # stop criterion E_lambda <= eps -> do not grow the set.
                grow = st.err > eps
                growf = grow.astype(jnp.float32)
                indices = st.indices.at[t].set(jnp.where(grow, e, -1))
                mask = st.mask.at[t].set(grow)
                mask_p = mask[:p]
                g_e = grads[e] * growf
                rows = st.rows.at[t].set(g_e)
                if use_cols:
                    # Single touch of G this round; the new Gram row is a
                    # free read out of the cache:
                    # A[t, j] = g_{e_t}.g_{e_j} = C[e, j].
                    colcache = st.colcache.at[:, t].set(ops.corr(grads, g_e))
                    row_vals = jnp.where(mask_p, colcache[e], 0.0) * growf
                else:
                    colcache = st.colcache
                    row_vals = jnp.where(
                        mask_p, jnp.dot(rows, g_e, precision=PRECISION), 0.0)
                gram = st.gram.at[t, :].set(row_vals).at[:, t].set(row_vals)
                # Gershgorin row sums pick up the new row/col in O(p).
                absrow = jnp.where(mask_p,
                                   st.gram_absrow + jnp.abs(row_vals), 0.0)
                absrow = absrow.at[t].set(jnp.sum(jnp.abs(row_vals)))
                tcorr = st.tcorr.at[t].set(c0[e] * growf)

            # 3) NNLS on the cached active-set buffers.
            with obs.scope("omp.nnls"):
                w = _nnls_active_cached(gram, absrow, rows, tcorr, mask_p,
                                        lam, nnls_iters)
                # ||r||^2 = ||g_tgt||^2 - 2 w^T c_S + w^T A w, evaluated in
                # the factored form over cached rows (immune to the
                # cancellation the expanded form suffers near the eps-stop).
                resid = target - jnp.dot(w, rows, precision=PRECISION)
                err = jnp.sum(resid**2) + lam * jnp.sum(w**2)
            return OMPIncState(indices, mask, w, colcache, gram, absrow,
                               tcorr, rows, resid, err)
        return body

    return make_body


def _empty_inc_state(k: int, n: int, d: int,
                     target: jax.Array) -> OMPIncState:
    return OMPIncState(
        indices=jnp.full((k,), -1, dtype=jnp.int32),
        mask=jnp.zeros((k,), dtype=bool),
        weights=jnp.zeros((0,), dtype=jnp.float32),
        colcache=jnp.zeros((n, 0), dtype=jnp.float32),
        gram=jnp.zeros((0, 0), dtype=jnp.float32),
        gram_absrow=jnp.zeros((0,), dtype=jnp.float32),
        tcorr=jnp.zeros((0,), dtype=jnp.float32),
        rows=jnp.zeros((0, d), dtype=jnp.float32),
        residual=target,
        err=jnp.sum(target**2) + jnp.float32(0.0),
    )


def _omp_select_incremental(grads, target, k, lam, eps, nnls_iters, positive,
                            valid, block):
    """Incremental-Gram OMP: cached correlations, no per-round rebuilds.

    Two statically-chosen regimes per block of rounds, both O(t)-incremental
    (the ``(k, d)`` active matrix is never re-gathered and the Gram never
    rebuilt), differing only in which cached factor scores candidates:

    * wide-proxy (P <= d): scores = c0 - C @ w over the ``(n, P)`` column
      cache; the new Gram row is a free read ``C[e, :]``.  O(n·P) < O(n·d)
      per round.
    * narrow-proxy (d < P): scores = G @ r with the residual maintained
      from the cached active rows (r = g_tgt - w^T R, O(P·d)); the new
      Gram row is ``R @ g_e``.  O(n·d) < O(n·P) per round.

    Both feed the same fused ``corr_argmax`` kernel (scores never hit HBM
    on TPU): the wide call is (C, w, c0), the narrow call is (G, -r, 0).
    """
    n, d = grads.shape
    with obs.scope("omp.init"):
        c0 = ops.corr(grads, target)        # (n,), computed exactly once
        st = _empty_inc_state(k, n, d, target)
    make_body = _inc_body_factory(grads, target, c0, valid, lam, eps,
                                  nnls_iters, absolute=not positive)
    for lo in range(0, k, block):
        hi = min(lo + block, k)
        use_cols = hi <= d
        with obs.scope("omp.prefix"):
            st = _grow_prefix(st, hi, keep_cols=use_cols)
        st = lax.fori_loop(lo, hi, make_body(use_cols), st)
    return st.indices, st.weights, st.mask, st.err


@functools.partial(
    jax.jit,
    static_argnames=("k", "nnls_iters", "positive", "corr_fn", "method",
                     "block"),
)
def omp_select(
    grads: jax.Array,          # (n, d) candidate gradients (rows)
    target: jax.Array,         # (d,)   target gradient (full train or val)
    k: int,
    lam: float = 0.5,
    eps: float = 1e-10,
    nnls_iters: int = 50,
    positive: bool = True,
    valid: jax.Array | None = None,   # (n,) bool — candidate availability
    corr_fn=None,              # optional kernel: (G, r) -> (n,) scores
    method: str = "incremental",      # "incremental" | "dense"
    block: int = 128,          # rounds per statically-sized prefix block
):
    """Run OMP for exactly ``k`` rounds (slots beyond the eps-stop get masked).

    Returns (indices (k,), weights (k,), mask (k,), err ()).  Indices of
    unused slots are -1 and their weights 0, so downstream consumers can use
    the padded arrays directly (static shapes for jit).

    ``method="incremental"`` (default) runs the cached-correlation solver;
    ``method="dense"`` runs the reference re-solve-from-scratch formulation.
    A custom ``corr_fn`` scores against an explicit residual vector, which
    only the dense formulation materializes, so it implies ``method="dense"``.
    """
    if method not in ("incremental", "dense"):
        raise ValueError(f"unknown OMP method {method!r}")
    n, d = grads.shape
    grads = grads.astype(jnp.float32)
    target = target.astype(jnp.float32)
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    if method == "dense" or corr_fn is not None:
        return _omp_select_dense(grads, target, k, lam, eps, nnls_iters,
                                 positive, valid, corr_fn)
    return _omp_select_incremental(grads, target, k, lam, eps, nnls_iters,
                                   positive, valid, block)


def omp_select_dense(grads, target, k, lam=0.5, eps=1e-10, nnls_iters=50,
                     positive=True, valid=None, corr_fn=None):
    """Reference dense solver — parity oracle for ``omp_select``."""
    return omp_select(grads, target, k, lam=lam, eps=eps,
                      nnls_iters=nnls_iters, positive=positive, valid=valid,
                      corr_fn=corr_fn, method="dense")


# ---------------------------------------------------------------------------
# anytime sessions: checkpointed solves with budget extension k -> k'
# ---------------------------------------------------------------------------

class OMPAnytimeState(NamedTuple):
    """Host-side checkpoint of an in-flight incremental OMP solve.

    The serve layer (``repro.serve``) stores one of these per client
    session so a budget extension ``k -> k'`` is a *resume*: the cached
    prefix buffers pick up at round ``k`` and only the new rounds run.

    Unlike ``omp_select`` — whose prefix widths depend on the final ``k``
    through ``hi = min(lo + block, k)`` — the session engine always grows
    prefixes to **full block multiples**, so the width schedule (and the
    wide/narrow regime choice) at every round is independent of the budget
    the caller happened to ask for first.  That makes the resumed rounds
    bit-identical to the rounds a single ``extend`` straight to ``k'``
    would run: ``extend(k) ; extend(k')`` and ``extend(k')`` produce the
    same arrays, and both match a one-shot ``omp_select(k')`` selection
    index-exactly away from the f32 noise floor (weights to tolerance —
    the NNLS sees block-padded buffers whose extra rows are exact zeros).

    ``k`` is the rounds solved so far; ``st`` carries the (k,)-capacity
    index/mask buffers (capacity = ``k`` rounded up to ``block``) plus the
    prefix-grown caches; ``c0``/``target``/``valid`` are per-session
    constants so an extension never rescans the pool for them.
    """

    k: int               # rounds solved so far (static)
    block: int           # prefix growth quantum (static)
    st: OMPIncState      # buffers at block-multiple capacity
    c0: jax.Array        # (n,) G @ g_tgt, computed once at session start
    target: jax.Array    # (d,)
    valid: jax.Array     # (n,) bool
    lam: float
    eps: float
    nnls_iters: int
    positive: bool

    @property
    def indices(self) -> jax.Array:
        return self.st.indices[: self.k]

    @property
    def weights(self) -> jax.Array:
        return self.st.weights[: self.k]

    @property
    def mask(self) -> jax.Array:
        return self.st.mask[: self.k]

    @property
    def err(self) -> jax.Array:
        return self.st.err


def _block_cap(k: int, block: int) -> int:
    return max(block * (-(-k // block)), block)


@functools.partial(
    jax.jit,
    static_argnames=("use_cols", "lam", "eps", "nnls_iters", "absolute"),
)
def _run_session_block(grads, target, c0, valid, st: OMPIncState, t0, t1,
                       use_cols: bool, lam: float, eps: float,
                       nnls_iters: int, absolute: bool) -> OMPIncState:
    # t0/t1 are dynamic so arbitrary k -> k' extensions inside one block
    # width reuse a single compiled program (one per prefix width).
    body = _inc_body_factory(grads, target, c0, valid, lam, eps, nnls_iters,
                             absolute)(use_cols)
    return lax.fori_loop(t0, t1, body, st)


def _pad_slots(st: OMPIncState, cap: int) -> OMPIncState:
    """Grow the full-(k,) index/mask buffers to ``cap`` slots."""
    pad = cap - st.indices.shape[0]
    if pad <= 0:
        return st
    return st._replace(
        indices=jnp.pad(st.indices, (0, pad), constant_values=-1),
        mask=jnp.pad(st.mask, (0, pad)),
    )


def omp_session_start(
    grads: jax.Array,          # (n, d) candidate pool (shared, not stored)
    target: jax.Array,         # (d,)
    k: int,
    lam: float = 0.5,
    eps: float = 1e-10,
    nnls_iters: int = 50,
    positive: bool = True,
    valid: jax.Array | None = None,
    block: int = 128,
) -> OMPAnytimeState:
    """Open an anytime OMP session and solve the first ``k`` rounds.

    The pool itself is not captured in the state — callers (the serve
    registry) own it and pass the *same* array back to
    ``omp_session_extend``; the session holds everything derived from it.
    """
    n, d = grads.shape
    grads = grads.astype(jnp.float32)
    target = target.astype(jnp.float32)
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    c0 = ops.corr(grads, target)
    st = _empty_inc_state(_block_cap(k, block), n, d, target)
    sess = OMPAnytimeState(k=0, block=int(block), st=st, c0=c0,
                           target=target, valid=valid, lam=float(lam),
                           eps=float(eps), nnls_iters=int(nnls_iters),
                           positive=bool(positive))
    return omp_session_extend(grads, sess, k)


def omp_session_extend(grads: jax.Array, sess: OMPAnytimeState,
                       k_new: int) -> OMPAnytimeState:
    """Extend a session's budget to ``k_new`` rounds (a resume, not a
    recompute: only rounds ``[sess.k, k_new)`` execute).

    ``grads`` must be the pool the session was started on.  ``k_new`` may
    not shrink the budget — the prefix property means a client wanting
    fewer rounds already has them (``sess.indices[:k_small]`` *is* the
    ``k_small`` solution), so a smaller ask is a caller bug worth raising.
    """
    if k_new < sess.k:
        raise ValueError(
            f"cannot shrink an anytime session: have k={sess.k}, asked "
            f"k'={k_new} (slice indices[:k'] instead — prefix property)")
    if k_new == sess.k:
        return sess
    grads = grads.astype(jnp.float32)
    d = grads.shape[1]
    block = sess.block
    st = _pad_slots(sess.st, _block_cap(k_new, block))
    for lo in range((sess.k // block) * block, k_new, block):
        width = lo + block           # full-block width: independent of k
        use_cols = width <= d
        if st.weights.shape[0] < width:
            st = _grow_prefix(st, width, keep_cols=use_cols)
        t0, t1 = max(lo, sess.k), min(lo + block, k_new)
        st = _run_session_block(
            grads, sess.target, sess.c0, sess.valid, st, t0, t1, use_cols,
            sess.lam, sess.eps, sess.nnls_iters, absolute=not sess.positive)
    return sess._replace(k=int(k_new), st=st)


def session_result(sess: OMPAnytimeState
                   ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(indices (k,), weights (k,), mask (k,), err ()) — the same contract
    as ``omp_select`` at the session's current budget."""
    return sess.indices, sess.weights, sess.mask, sess.err


def session_prefix_result(sess: OMPAnytimeState, k: int
                          ) -> tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
    """First-``k`` slice of a session: the serve tier's degraded answer.

    The *indices and mask* are certified — the anytime prefix property
    means ``sess.indices[:k]`` is exactly what a one-shot ``k`` solve
    picks.  The *weights* are not: the NNLS weights at budget ``sess.k``
    restricted to the prefix differ from a fresh ``k``-round solve's, so
    they are returned as-is (the caller renormalizes) and the answer must
    be labelled degraded (``anytime-prefix``), never passed off as a full
    solve.  ``k`` may not exceed the session's solved budget.
    """
    k = int(k)
    if k > sess.k:
        raise ValueError(
            f"session has only {sess.k} solved rounds, asked prefix {k} "
            "(extend the session instead)")
    return (sess.indices[:k], sess.weights[:k], sess.mask[:k], sess.err)


class OMPTrajectory(NamedTuple):
    """Host-side record of a full anytime solve to ``k_max`` — the payload
    the artifact store persists (``repro.artifacts``, DESIGN.md §12).

    ``weights_traj`` is lower-triangular: row ``t-1`` holds the NNLS
    weights *after round t* (entries ``>= t`` are zero), so slicing
    ``(indices[:k], weights_traj[k-1, :k], mask[:k], err_trace[k-1])``
    reproduces the session engine's answer at budget ``k`` bit-exactly.
    """

    indices: np.ndarray       # (k_max,) int32
    mask: np.ndarray          # (k_max,) bool
    weights_traj: np.ndarray  # (k_max, k_max) f32, row t-1 = after round t
    err_trace: np.ndarray     # (k_max,) f32, Err_lambda after round t


def omp_session_trajectory(
    grads: jax.Array,
    target: jax.Array,
    k_max: int,
    lam: float = 0.5,
    eps: float = 1e-10,
    nnls_iters: int = 50,
    positive: bool = True,
    valid: jax.Array | None = None,
    block: int = 128,
) -> tuple[OMPAnytimeState, OMPTrajectory]:
    """Solve to ``k_max`` one round at a time, recording every prefix.

    Because the session engine's prefix-width schedule is independent of
    the budget asked for (full block multiples — see ``OMPAnytimeState``),
    extending round-by-round is bit-identical to extending straight to
    ``k_max``: row ``t-1`` of the trajectory equals what a fresh
    ``omp_session_start(grads, target, t)`` reports, and the recorded
    indices/mask match a one-shot ``omp_select(t)`` prefix exactly.  This
    is the offline builder's path (one solve, every budget served), not a
    hot path — the per-round host round-trip is the cost of recording.

    Inputs are handed to the session engine *unconverted*: bit-exactness
    between the recorded trajectory and a later live solve holds when
    the live call sees the same arrays (host/device placement included)
    — the differential gate and the serve fast path both arrange that.
    """
    k_max = int(k_max)
    if k_max < 1:
        raise ValueError(f"k_max must be >= 1, got {k_max}")
    sess = omp_session_start(grads, target, 0, lam=lam, eps=eps,
                             nnls_iters=nnls_iters, positive=positive,
                             valid=valid, block=block)
    weights_traj = np.zeros((k_max, k_max), np.float32)
    err_trace = np.zeros((k_max,), np.float32)
    for t in range(1, k_max + 1):
        sess = omp_session_extend(grads, sess, t)
        weights_traj[t - 1, :t] = np.asarray(sess.weights, np.float32)
        err_trace[t - 1] = np.float32(sess.err)
    traj = OMPTrajectory(
        indices=np.asarray(sess.indices, np.int32),
        mask=np.asarray(sess.mask, bool),
        weights_traj=weights_traj,
        err_trace=err_trace,
    )
    return sess, traj


# ---------------------------------------------------------------------------
# batched multi-target OMP: one pool scan serves B concurrent targets
# ---------------------------------------------------------------------------

class OMPBatchState(NamedTuple):
    """Leading-batch-axis twin of ``OMPIncState`` (see that docstring)."""

    indices: jax.Array   # (B, k) int32
    mask: jax.Array      # (B, k) bool
    weights: jax.Array   # (B, P) f32
    colcache: jax.Array  # (B, n, P) f32
    gram: jax.Array      # (B, P, P) f32
    gram_absrow: jax.Array  # (B, P) f32
    tcorr: jax.Array     # (B, P) f32
    rows: jax.Array      # (B, P, d) f32
    residual: jax.Array  # (B, d) f32
    err: jax.Array       # (B,) f32


def _grow_prefix_batched(st: OMPBatchState, width: int,
                         keep_cols: bool) -> OMPBatchState:
    pad = width - st.weights.shape[1]
    z2 = ((0, 0), (0, pad))
    return OMPBatchState(
        indices=st.indices,
        mask=st.mask,
        weights=jnp.pad(st.weights, z2),
        colcache=(jnp.pad(st.colcache, ((0, 0), (0, 0), (0, pad)))
                  if keep_cols else st.colcache),
        gram=jnp.pad(st.gram, ((0, 0), (0, pad), (0, pad))),
        gram_absrow=jnp.pad(st.gram_absrow, z2),
        tcorr=jnp.pad(st.tcorr, z2),
        rows=jnp.pad(st.rows, ((0, 0), (0, pad), (0, 0))),
        residual=st.residual,
        err=st.err,
    )


def _omp_select_batched_incremental(grads, targets, k, lam, eps, nnls_iters,
                                    positive, valids, block):
    """Incremental-Gram OMP over ``B`` targets sharing one pool.

    The per-round structure is identical to ``_omp_select_incremental``
    (same block-quantized prefix widths, same wide/narrow regime choice,
    same NNLS on cached buffers), but every pool-touching step is batched:
    the narrow-regime scoring is one ``(n, d) @ (d, B)`` matmul instead of
    ``B`` matvecs and the new column build is one ``(n, d) @ (d, B)``
    matmul — the candidate matrix is read once per round *for the whole
    batch*, which is where the serve scheduler's throughput comes from.
    Selections match per-target ``omp_select`` index-exactly away from the
    f32 noise floor (the math is identical; only reduction shapes differ).
    """
    n, d = grads.shape
    bsz = targets.shape[0]
    # Pool-sized arrays live pool-major (n, B) — the orientation the
    # shared-operand scan matmul produces natively (see kernels/ref.py).
    with obs.scope("omp.init"):
        c0_t = ops.corr_batched(grads, targets)    # (n, B), exactly once
        zeros_nb = jnp.zeros((n, bsz), dtype=jnp.float32)
        valids_t = valids.T                        # (n, B), hoisted
    bcol = jnp.arange(bsz, dtype=jnp.int32)
    bcols_k = jnp.broadcast_to(bcol[:, None], (bsz, k))
    absolute = not positive
    take_b = jax.vmap(lambda mat, i: mat[i])       # (B, n, p)[b, e_b]
    nnls_b = jax.vmap(_nnls_active_cached,
                      in_axes=(0, 0, 0, 0, 0, None, None))

    def scatter_taken_t(mask, indices):
        # One 2-D scatter into the (n, B) taken mask; out-of-bounds row
        # sentinel n drops unused slots (same trick as the single solver).
        return jnp.zeros((n, bsz), dtype=bool).at[
            jnp.where(mask, indices, n), bcols_k].set(mask, mode="drop")

    def make_body(use_cols: bool):
        def body(t, st: OMPBatchState):
            p = st.weights.shape[1]
            with obs.scope("omp.score"):
                avail_t = valids_t & ~scatter_taken_t(st.mask, st.indices)
                if use_cols:
                    e, _ = ops.corr_argmax_batched(st.colcache, st.weights,
                                                   c0_t, avail_t,
                                                   absolute=absolute)
                else:
                    e, _ = ops.corr_argmax_batched(grads, -st.residual,
                                                   zeros_nb, avail_t,
                                                   absolute=absolute)

            with obs.scope("omp.column"):
                grow = st.err > eps                            # (B,)
                growf = grow.astype(jnp.float32)
                indices = st.indices.at[:, t].set(jnp.where(grow, e, -1))
                mask = st.mask.at[:, t].set(grow)
                mask_p = mask[:, :p]

                g_e = grads[e] * growf[:, None]                # (B, d)
                rows = st.rows.at[:, t].set(g_e)
                if use_cols:
                    newcol = ops.corr_batched(grads, g_e)      # (n, B)
                    colcache = st.colcache.at[:, :, t].set(newcol.T)
                    row_vals = jnp.where(mask_p, take_b(colcache, e),
                                         0.0) * growf[:, None]
                else:
                    colcache = st.colcache
                    row_vals = jnp.where(
                        mask_p, jnp.einsum("bpd,bd->bp", rows, g_e,
                                           precision=PRECISION), 0.0)
                gram = st.gram.at[:, t, :].set(row_vals).at[:, :, t].set(
                    row_vals)
                absrow = jnp.where(mask_p,
                                   st.gram_absrow + jnp.abs(row_vals), 0.0)
                absrow = absrow.at[:, t].set(jnp.sum(jnp.abs(row_vals),
                                                     axis=1))
                tcorr = st.tcorr.at[:, t].set(c0_t[e, bcol] * growf)

            with obs.scope("omp.nnls"):
                w = nnls_b(gram, absrow, rows, tcorr, mask_p, lam,
                           nnls_iters)
                resid = targets - jnp.einsum("bp,bpd->bd", w, rows,
                                             precision=PRECISION)
                err = jnp.sum(resid**2, axis=1) + lam * jnp.sum(w**2, axis=1)
            return OMPBatchState(indices, mask, w, colcache, gram, absrow,
                                 tcorr, rows, resid, err)
        return body

    st = OMPBatchState(
        indices=jnp.full((bsz, k), -1, dtype=jnp.int32),
        mask=jnp.zeros((bsz, k), dtype=bool),
        weights=jnp.zeros((bsz, 0), dtype=jnp.float32),
        colcache=jnp.zeros((bsz, n, 0), dtype=jnp.float32),
        gram=jnp.zeros((bsz, 0, 0), dtype=jnp.float32),
        gram_absrow=jnp.zeros((bsz, 0), dtype=jnp.float32),
        tcorr=jnp.zeros((bsz, 0), dtype=jnp.float32),
        rows=jnp.zeros((bsz, 0, d), dtype=jnp.float32),
        residual=targets,
        err=jnp.sum(targets**2, axis=1),
    )
    for lo in range(0, k, block):
        hi = min(lo + block, k)      # same prefix schedule as omp_select
        # Regime choice re-derived for the batch: the column cache is
        # *per-target* (``B·n·P`` touched per wide round) while the
        # narrow-regime pool scan is *shared* (``n·d`` once for the whole
        # batch) — so wide only pays off when ``B·P <= d``, not ``P <= d``.
        # Same math either way (scores are c0 - C@w == G@r); only the
        # reduction shapes differ, below the index-parity noise floor.
        use_cols = hi * bsz <= d
        with obs.scope("omp.prefix"):
            st = _grow_prefix_batched(st, hi, keep_cols=use_cols)
        st = lax.fori_loop(lo, hi, make_body(use_cols), st)
    return st.indices, st.weights, st.mask, st.err


@functools.partial(
    jax.jit,
    static_argnames=("k", "nnls_iters", "positive", "method", "block"),
)
def omp_select_batched(
    grads: jax.Array,          # (n, d) shared candidate pool
    targets: jax.Array,        # (B, d) one target per concurrent request
    k: int,
    lam: float = 0.5,
    eps: float = 1e-10,
    nnls_iters: int = 50,
    positive: bool = True,
    valid: jax.Array | None = None,   # (B, n) or (n,) availability
    method: str = "incremental",
    block: int = 128,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Solve ``B`` OMP problems over one shared pool in a single program.

    Returns ``(indices (B, k), weights (B, k), mask (B, k), err (B,))`` —
    row ``b`` is what ``omp_select(grads, targets[b], ...)`` returns.  The
    serve scheduler micro-batches same-pool ``SelectRequest``s through
    this: B sequential solves become one batched solve whose pool-touching
    matvecs are shared-operand matmuls (see DESIGN.md §6).
    """
    if method not in ("incremental", "dense"):
        raise ValueError(f"unknown OMP method {method!r}")
    n, d = grads.shape
    grads = grads.astype(jnp.float32)
    targets = targets.astype(jnp.float32)
    bsz = targets.shape[0]
    if valid is None:
        valid = jnp.ones((bsz, n), dtype=bool)
    elif valid.ndim == 1:
        valid = jnp.broadcast_to(valid, (bsz, n))
    if method == "dense":
        return jax.vmap(
            lambda t, v: _omp_select_dense(grads, t, k, lam, eps,
                                           nnls_iters, positive, v, None)
        )(targets, valid)
    return _omp_select_batched_incremental(grads, targets, k, lam, eps,
                                           nnls_iters, positive, valid,
                                           block)


def split_budget(k: int, sizes: Sequence[int]) -> np.ndarray:
    """Split a global budget ``k`` across partitions of the given sizes.

    Paper Algorithm 1's per-class accounting, done exactly: an even split
    with the ``k % P`` remainder going to the largest partitions first,
    every quota capped at its partition size, and capped-off surplus
    rebalanced over the partitions that still have capacity — iterated
    until the budget is placed.  Guarantees ``sum(quota) == min(k,
    sum(sizes))`` and ``quota[p] <= sizes[p]`` for every partition.

    Host-side (numpy) on purpose: quotas are static solver shapes.
    """
    sizes = np.asarray(sizes, np.int64)
    if sizes.ndim != 1 or sizes.shape[0] == 0:
        raise ValueError(f"sizes must be a non-empty 1-D sequence, got "
                         f"shape {sizes.shape}")
    if (sizes < 0).any():
        raise ValueError(f"negative partition size in {sizes}")
    quota = np.zeros(sizes.shape[0], np.int64)
    remaining = min(int(k), int(sizes.sum()))
    # Largest-first order, ties broken by partition id for determinism.
    order = np.argsort(-sizes, kind="stable")
    while remaining > 0:
        cap = sizes - quota
        act = order[cap[order] > 0]
        base, rem = divmod(remaining, len(act))
        add = np.full(len(act), base, np.int64)
        add[:rem] += 1                      # remainder to largest first
        add = np.minimum(add, cap[act])
        quota[act] += add
        remaining -= int(add.sum())
    return quota


def _class_table(labels, num_classes: int):
    """Host-side class table: ``(table (C, m) int32, valid (C, m) bool)``.
    Row ``c`` lists class ``c``'s global row ids in increasing order
    (``m`` = largest class size, at least 1); slots past the class's size
    are padding, invalid and pointing at row 0.  Labels outside ``[0, C)``
    are in no class.  Counts the slab's rows and padding."""
    lab = np.asarray(labels)
    rows = np.flatnonzero((lab >= 0) & (lab < num_classes))
    sizes = np.bincount(lab[rows], minlength=num_classes)
    m = max(int(sizes.max()), 1)
    valid = np.arange(m) < sizes[:, None]
    table = np.zeros((num_classes, m), np.int32)
    # Row-major boolean assignment fills class 0's slots first, so the
    # class-stable order lands each class's rows in its own row.
    table[valid] = rows[np.argsort(lab[rows], kind="stable")]
    obs.count("omp.class_rows", table.size)
    obs.count("omp.class_pad_rows", table.size - rows.size)
    return table, valid


def _reweight(grads, target, idx, mask, quota, lam, nnls_iters):
    """Exact weights of a class's first ``quota`` picks: one NNLS over the
    truncated active set against the class target."""
    mask = mask & (jnp.arange(mask.shape[0]) < quota)
    sel = jnp.where(mask, idx, 0)
    g_s = grads[sel] * mask[:, None].astype(grads.dtype)
    gram = jnp.dot(g_s, g_s.T, precision=PRECISION)
    corr = jnp.dot(g_s, target.astype(grads.dtype), precision=PRECISION)
    w = _nnls_active(gram, corr, mask, lam, nnls_iters)
    return jnp.where(mask, idx, -1), jnp.where(mask, w, 0.0), mask


@functools.partial(jax.jit, static_argnames=("k", "method", "nnls_iters"))
def _solve_classes(grads, targets, table, valid, quotas, lam, eps, k, method,
                   nnls_iters=50):
    """The per-class program: ``omp_select`` vmapped over the ``(C, m, d)``
    class slab ``grads[table]`` for ``k`` rounds, then, with ``quotas``
    (``(C,)`` int32, traced), each class's truncation to its quota and
    reweight.  Returns flat (global row ids, -1 on unused slots, weights,
    mask).  Round ``t`` of a class with ``t`` or fewer rows finds none
    left and picks local row 0 again: such picks are dropped."""
    if k == 0:                          # empty budget: all-off result
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32),
                jnp.zeros((0,), bool))

    def solve(g, target, valid_c):
        idx, w, mask, _ = omp_select(g, target, k=k, lam=lam, eps=eps,
                                     valid=valid_c, method=method)
        mask = mask & (jnp.arange(k) < jnp.sum(valid_c))
        return idx, jnp.where(mask, w, 0.0), mask

    idx, w, mask = jax.vmap(solve)(grads[table], targets, valid)
    idx = jnp.take_along_axis(table, jnp.where(mask, idx, 0), axis=1)
    idx = jnp.where(mask, idx, -1)
    if quotas is not None:
        with obs.scope("omp.reweight"):
            idx, w, mask = jax.vmap(
                functools.partial(_reweight, grads, lam=lam,
                                  nnls_iters=nnls_iters))(
                targets, idx, mask, quotas)
    return idx.reshape(-1), w.reshape(-1), mask.reshape(-1)


def omp_select_per_class(
    grads: jax.Array,        # (n, d)
    labels: jax.Array,       # (n,) int class ids
    targets: jax.Array,      # (num_classes, d) per-class target gradients
    num_classes: int,
    k_per_class: int,
    lam: float = 0.5,
    eps: float = 1e-10,
    method: str = "incremental",
    quotas: Optional[Sequence[int]] = None,   # (C,) per-class budgets
    nnls_iters: int = 50,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Paper's per-class decomposition, batched over classes with vmap.

    Each class-c problem only sees candidates with label c.  Returns
    flattened (num_classes*k, ...) padded arrays of global row ids, -1 on
    unused slots.

    The class problems run on the class's own rows: a host-side table
    (``_class_table``) gathers a ``(C, m, d)`` slab, ``m`` the largest
    class size, and the solver is vmapped over it, so every round scores
    ``m`` rows, not ``n``, and each column cache is ``(m, P)``.  A row's
    score depends only on that row, and each class keeps its rows in
    increasing global order, so the picks (lowest-index tie-break
    included), and the weights of the quota reweight, are those of
    masking the whole pool per class.  The slab is one copy of the pool
    padded to ``C*m`` rows: on a pool where one class holds almost every
    row it approaches ``C*n*d``.  Rounds past a class's last row pick
    nothing; without ``quotas`` the weights of such a class still come
    from the solve that ran those rounds, so pass ``quotas`` capped at
    the class sizes (``split_budget``) for exact weights.

    ``quotas`` gives each class its own round budget (``split_budget``'s
    output; ``k_per_class`` is ignored then, the vmap runs ``max(quotas)``
    rounds for every class so shapes stay static).  Class ``c`` keeps the
    first ``quotas[c]`` rounds — index-exact by the greedy prefix
    property: round ``t`` of OMP only depends on rounds ``< t``, so the
    truncated prefix equals a fresh ``quotas[c]``-round solve — and its
    weights are re-solved by one NNLS on the truncated active set (the
    full-budget weights are *not* the prefix weights).

    Solve, truncation and reweight are one compiled program
    (``_solve_classes``), keyed on ``n``, ``d``, ``C``, ``m`` and the
    round budget; the quotas are a traced argument, so other class sizes
    at the same shapes run the same program.
    """
    if quotas is not None:
        quotas = np.asarray(quotas, np.int64)
        if quotas.shape != (num_classes,):
            raise ValueError(
                f"quotas must be ({num_classes},), got {quotas.shape}")
        k_per_class = int(quotas.max()) if quotas.size else 0
        quotas = quotas.astype(np.int32)
    table, valid = _class_table(labels, num_classes)
    return _solve_classes(grads, targets, table, valid, quotas, lam, eps,
                          k=k_per_class, method=method,
                          nnls_iters=nnls_iters)


def matching_error(
    grads: jax.Array, target: jax.Array, indices: jax.Array,
    weights: jax.Array, mask: jax.Array, lam: float = 0.0,
) -> jax.Array:
    """Err_lambda for a given (X, w) — used by tests & benchmarks.

    Returns the paper's squared objective  ||G_S^T w - g_tgt||^2 +
    lam ||w||^2, matching the ``err`` tracked inside ``omp_select``.
    """
    sel = jnp.where(mask, indices, 0)
    g_s = grads[sel] * mask[:, None].astype(grads.dtype)
    resid = target - jnp.dot(weights, g_s, precision=PRECISION)
    return jnp.sum(resid**2) + lam * jnp.sum(weights**2)

"""Decremental OMP: remove committed rows from an anytime solution.

Every engine so far only *grows* the active set.  The continual buffer
(``repro.continual``) also needs to shrink it — evicting a committed row
when the buffer is full — without paying a from-scratch re-solve.  The
math rests on the **greedy prefix property**: round ``t`` of the
incremental solver is a pure function of the pool, the target, and the
state left by rounds ``< t``.  A candidate that never won an argmax never
influenced any round, so

* removing a *non-committed* candidate from the pool changes nothing;
* removing the pick of round ``i`` leaves rounds ``< i`` bit-identical —
  the tail ``[i, k)`` is the only part that must be recomputed.

``omp_downdate`` therefore truncates the session's prefix buffers at the
removed pick's round (deleting its Gram row/column, cached row and target
correlation), re-runs the factor-form NNLS on the surviving active set,
recomputes the residual, and replays the tail with real argmaxes.  When
the removed pick is the *last* round — the common case for the continual
buffer, whose eviction policy targets the lowest-gain (latest-ladder)
picks — there is no tail and the whole removal is one truncation +
NNLS + residual refresh: O(k·d + k²), versus O(k·n·d) for a re-solve.

``session_extend_traced`` is the replay engine the buffer maintainer
uses: identical state transitions to ``omp_session_extend`` (it steps the
same compiled ``_run_session_block`` program one round at a time), while
recording the residual trajectory and each round's winning gain — the
**admission certificate** ``certify_admission`` checks newcomers against.
A newcomer whose correlation with some round's entering residual is not
clearly below that round's recorded winning gain *might* have won it;
fail-closed, the maintainer replays from the earliest such round (and a
violation at round 0 is exactly a full re-solve on the buffer).

Exactness bar (same as the anytime sessions, DESIGN.md §6): indices are
exact away from the f32 noise floor, weights to tolerance.  The one
deliberate deviation from bit-replay is ``gram_absrow``: truncation
recomputes the Gershgorin row sums from the surviving Gram instead of
replaying their incremental accumulation, which can move the NNLS step
size by an ulp.  Ties (duplicate rows) still resolve identically —
identical rows produce identical scores and ``corr_argmax`` breaks ties
by slot order.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.omp import (OMPAnytimeState, OMPIncState, _block_cap,
                            _empty_inc_state, _grow_prefix, _nnls_active_cached,
                            _pad_slots, _run_session_block, omp_session_extend)
from repro.kernels.ref import PRECISION

__all__ = [
    "DowndateInfo",
    "ReplayTrace",
    "certify_admission",
    "omp_downdate",
    "session_extend_traced",
    "session_truncate",
]


@functools.partial(jax.jit, static_argnames=("lam", "nnls_iters"))
def _truncate_buffers(st: OMPIncState, target, t, lam: float,
                      nnls_iters: int) -> OMPIncState:
    """Slice the prefix buffers to the first ``t`` rounds and re-tighten.

    The sliced width is the *fresh* session's block-quantized width after
    ``t`` rounds (the caller guarantees the slices land on it), stale
    slots are zeroed — they were written by the discarded rounds — and
    weights / residual / err are re-derived by the same factor-form NNLS
    call round ``t - 1`` made over the same buffers (w0 = 0, fixed
    iterations: a deterministic function of the caches).
    """
    wt = st.weights.shape[0]            # == block * ceil(t / block)
    keep = jnp.arange(wt) < t
    indices = jnp.where(keep, st.indices[:wt], -1).astype(jnp.int32)
    mask = st.mask[:wt] & keep
    rows = jnp.where(keep[:, None], st.rows, 0.0)
    tcorr = jnp.where(keep, st.tcorr, 0.0)
    gram = jnp.where(keep[:, None] & keep[None, :], st.gram, 0.0)
    wc = st.colcache.shape[1]
    colcache = jnp.where(jnp.arange(wc)[None, :] < t, st.colcache, 0.0)
    absrow = jnp.where(keep, jnp.sum(jnp.abs(gram), axis=1), 0.0)
    w = _nnls_active_cached(gram, absrow, rows, tcorr, mask, lam, nnls_iters)
    resid = target - jnp.dot(w, rows, precision=PRECISION)
    err = jnp.sum(resid**2) + lam * jnp.sum(w**2)
    return OMPIncState(indices, mask, w, colcache, gram, absrow, tcorr,
                       rows, resid, err)


def session_truncate(sess: OMPAnytimeState, t: int,
                     valid: Optional[jax.Array] = None) -> OMPAnytimeState:
    """Truncate an anytime session to its first ``t`` rounds — exactly.

    By the greedy prefix property the result is the state a fresh
    ``t``-round session over the same pool holds (weights at the noise
    floor — see the module docstring on ``gram_absrow``), so a subsequent
    ``omp_session_extend`` continues as if rounds ``>= t`` never ran.

    ``valid`` optionally replaces the candidate mask the replayed rounds
    will see (the downdate path clears the removed candidate's slot).
    """
    t = int(t)
    if not 0 <= t <= sess.k:
        raise ValueError(
            f"cannot truncate to t={t}: session holds k={sess.k} rounds")
    v = sess.valid if valid is None else jnp.asarray(valid, bool)
    if t == sess.k and valid is None:
        return sess
    block = sess.block
    d = sess.st.rows.shape[1]
    n = v.shape[0]
    if t == 0:
        st0 = _empty_inc_state(_block_cap(1, block), n, d, sess.target)
        return sess._replace(k=0, st=st0, valid=v)
    cap_t = _block_cap(t, block)        # == fresh width after t rounds
    st = sess.st._replace(
        indices=sess.st.indices[:cap_t],
        mask=sess.st.mask[:cap_t],
        weights=sess.st.weights[:cap_t],
        colcache=sess.st.colcache[:, :min(cap_t, sess.st.colcache.shape[1])],
        gram=sess.st.gram[:cap_t, :cap_t],
        gram_absrow=sess.st.gram_absrow[:cap_t],
        tcorr=sess.st.tcorr[:cap_t],
        rows=sess.st.rows[:cap_t],
    )
    st = _truncate_buffers(st, sess.target, t, sess.lam, sess.nnls_iters)
    return sess._replace(k=t, st=st, valid=v)


class DowndateInfo(NamedTuple):
    """Accounting for one ``omp_downdate`` call."""

    round: int      # earliest round the removed candidate was committed at
    replayed: int   # tail rounds re-run with real argmaxes
    resolved: bool  # True when the removal degenerated to a full re-solve


def omp_downdate(grads: jax.Array, sess: OMPAnytimeState, idx: int,
                 k_new: Optional[int] = None):
    """Remove committed candidate ``idx`` from an anytime OMP solution.

    Deletes the candidate's Gram row/column, cached row and target
    correlation by truncating the prefix buffers at its round ``i``,
    re-runs the factor-form NNLS on the surviving active set, recomputes
    the residual, and replays rounds ``[i, k_new)`` with real argmaxes
    over the surviving pool (``valid[idx]`` is cleared: the row leaves
    both the solution and the candidate set).  ``k_new`` defaults to
    ``sess.k - 1`` — the budget shrinks with the removal.

    Differential guarantee: ``omp_downdate`` (optionally followed by
    ``omp_session_extend``) matches a from-scratch ``omp_select`` /
    ``omp_session_start`` on the surviving rows at the session engine's
    usual parity — indices exact away from the f32 noise floor, weights
    to tolerance.  Cost: O(k·d + k²) when the removed pick is the last
    round (truncate + one NNLS + one residual, zero replay); an earlier
    pick replays its ``k_new - i`` tail rounds; ``i == 0`` degenerates to
    a full re-solve (``resolved=True`` — the fail-closed floor).

    Returns ``(new_session, DowndateInfo)``.
    """
    idx = int(idx)
    ind = np.asarray(sess.indices)
    msk = np.asarray(sess.mask)
    hits = np.nonzero((ind == idx) & msk)[0]
    if hits.size == 0:
        committed = np.unique(ind[msk])
        raise ValueError(
            f"candidate {idx} is not committed in this session "
            f"(committed: {committed[:16].tolist()}"
            f"{'...' if committed.size > 16 else ''})")
    i = int(hits[0])
    if k_new is None:
        k_new = sess.k - 1
    if k_new < i:
        raise ValueError(
            f"k_new={k_new} would truncate below the removed round {i}")
    new_valid = sess.valid.at[idx].set(False)
    out = session_truncate(sess, i, valid=new_valid)
    if k_new > i:
        out = omp_session_extend(grads, out, k_new)
    return out, DowndateInfo(round=i, replayed=int(k_new) - i,
                             resolved=(i == 0))


class ReplayTrace(NamedTuple):
    """Per-round certificate data for the continual buffer maintainer.

    ``resid[t]`` is the residual *entering* round ``t``; ``win[t]`` is the
    winner's residual-correlation gain at that round — the quantity the
    engine's argmax maximized, so it is exactly what a newcomer must beat
    to change the round.  Sentinels: ``+inf`` for eps-stopped rounds (no
    newcomer can un-stop the criterion), ``-inf`` for degenerate rounds
    (pool exhausted: the engine re-commits an already-taken slot; any
    newcomer wins such a round and must force a replay).
    """

    resid: np.ndarray   # (k, d) f32
    win: np.ndarray     # (k,) f32, +/-inf sentinels as above


def _empty_trace(d: int) -> ReplayTrace:
    return ReplayTrace(resid=np.zeros((0, d), np.float32),
                       win=np.zeros((0,), np.float32))


def session_extend_traced(grads: jax.Array, sess: OMPAnytimeState,
                          k_new: int, trace: Optional[ReplayTrace] = None):
    """``omp_session_extend`` that also records a ``ReplayTrace``.

    Steps the same compiled ``_run_session_block`` program one round at a
    time (the fori_loop body composes, so the resulting state is
    bit-identical to the block extension), capturing each round's entering
    residual; winning gains are batch-computed afterwards in the same
    arithmetic ``certify_admission`` uses.  ``trace`` must cover the
    ``sess.k`` rounds already solved (pass ``None`` only for a fresh
    session); the returned trace covers ``[0, k_new)``.

    Returns ``(new_session, new_trace)``.
    """
    d = grads.shape[1]
    if trace is None:
        if sess.k != 0:
            raise ValueError(
                f"session holds {sess.k} rounds but no trace was given")
        trace = _empty_trace(d)
    if trace.win.shape[0] != sess.k:
        raise ValueError(
            f"trace covers {trace.win.shape[0]} rounds, session holds "
            f"{sess.k}")
    if k_new < sess.k:
        raise ValueError(
            f"cannot shrink an anytime session: have k={sess.k}, asked "
            f"k'={k_new} (use session_truncate)")
    if k_new == sess.k:
        return sess, trace
    grads = grads.astype(jnp.float32)
    block = sess.block
    absolute = not sess.positive
    st = _pad_slots(sess.st, _block_cap(k_new, block))
    resids = []
    for t in range(sess.k, k_new):
        width = block * (t // block + 1)     # full-block session schedule
        use_cols = width <= d
        if st.weights.shape[0] < width:
            st = _grow_prefix(st, width, keep_cols=use_cols)
        resids.append(st.residual)
        st = _run_session_block(
            grads, sess.target, sess.c0, sess.valid, st, t, t + 1, use_cols,
            sess.lam, sess.eps, sess.nnls_iters, absolute=absolute)
    new_sess = sess._replace(k=int(k_new), st=st)

    ind = np.asarray(st.indices[:k_new])
    msk = np.asarray(st.mask[:k_new])
    valid_np = np.asarray(sess.valid)
    r_new = np.asarray(jnp.stack(resids), np.float32)        # (T, d)
    picks = ind[sess.k:k_new]
    rows_t = np.asarray(grads[jnp.asarray(np.where(picks >= 0, picks, 0))],
                        np.float32)
    gains = np.einsum("td,td->t", rows_t, r_new)
    if absolute:
        gains = np.abs(gains)
    win_new = np.empty((k_new - sess.k,), np.float32)
    seen = set(ind[:sess.k][msk[:sess.k]].tolist())
    for j, t in enumerate(range(sess.k, k_new)):
        if not msk[t]:
            win_new[j] = np.inf          # eps-stopped: unbeatable
        elif int(picks[j]) in seen or not valid_np[picks[j]]:
            win_new[j] = -np.inf         # degenerate re-pick: always replay
        else:
            win_new[j] = gains[j]
            seen.add(int(picks[j]))
    return new_sess, ReplayTrace(
        resid=np.concatenate([trace.resid, r_new], axis=0),
        win=np.concatenate([trace.win, win_new]))


def certify_admission(new_rows: np.ndarray, trace: ReplayTrace, k: int,
                      positive: bool = True, band_rel: float = 1e-4,
                      band_abs: float = 1e-6) -> int:
    """Earliest committed round a newcomer could win — fail-closed.

    Scores every newcomer row against the recorded residual trajectory; a
    round whose winning gain does not clear the best newcomer score by
    the f32 tolerance band cannot be certified to survive the admission
    and must be replayed.  Returns ``k`` when every round is certified
    (the committed solution is already the from-scratch solution over the
    new pool); ``0`` means nothing is certain — a full re-solve.
    """
    if k == 0:
        return 0
    if new_rows.shape[0] == 0:
        return k
    s = np.asarray(new_rows, np.float32) @ trace.resid[:k].T     # (B, k)
    if not positive:
        s = np.abs(s)
    best = s.max(axis=0)
    win = trace.win[:k]
    band = band_rel * np.abs(win) + band_abs
    with np.errstate(invalid="ignore"):
        ok = np.where(np.isposinf(win), True,
                      np.where(np.isneginf(win), False, best < win - band))
    bad = ~ok.astype(bool)
    return int(np.argmax(bad)) if bad.any() else k

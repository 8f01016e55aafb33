"""Shared fixed-shape greedy-maximization engine (DESIGN.md §5).

The submodular baselines (CRAIG's facility location, GLISTER's Taylor
greedy) all reduce to "argmax a per-candidate score k times under a taken
mask".  The seed implementations paid ``O(n²)`` per round for CRAIG — every
round recomputed all n marginal gains over a resident ``(n, n)``
similarity.  This module provides the engine they are refactored onto:

- **Certified lazy greedy** (``method="lazy"``): cached stale gains are
  upper bounds by submodularity (coverage only grows, so marginal gains
  only shrink).  Each round re-evaluates a fixed-size top-``B`` block of
  candidates ordered by stale bound and accepts the block argmax whenever
  its exact gain strictly beats the best stale bound outside the block —
  the same certify-or-rescan structure as the streaming OMP buffer
  (DESIGN.md §4), so selections stay **index-identical** to the naive
  greedy (ties re-broken to the lowest global id, matching
  ``jnp.argmax``).  When certification fails after ``max_tries`` block
  refreshes, one full gain scan (the fused ``ops.fl_gain_argmax`` kernel)
  restores exactness and refreshes every bound.
- **Stochastic greedy** (``method="stochastic"``): the approximate tier —
  per round a seeded uniform sample of the available candidates is scored
  exactly and its argmax accepted (Mirzasoleiman et al.'s stochastic
  greedy; (1 − 1/e − ε) in expectation at sample ≈ (n/k)·ln(1/ε)).
- **Dense greedy** (``method="dense"``): the naive full-rescan
  formulation, kept as the parity oracle for the differential tests.
- **Tile-on-the-fly similarity** (``on_the_fly=True``, auto beyond
  ``_OTF_AUTO_BYTES``): every similarity access is reconstructed from the
  ``(n, d)`` gradients (``s_ij = L_max − ‖g_i − g_j‖``), so the ``(n, n)``
  matrix never materializes and CRAIG runs at pool sizes where it alone
  would be 4–16 GB.

The whole solver is one jitted program per (shape, method): a
``fori_loop`` over rounds with a bounded ``while_loop`` of block refreshes
and a ``lax.cond`` rescan fallback inside — no host round-trips.

``modular_greedy`` is the non-submodular sibling: a fixed-k greedy over a
score vector ``grads @ v`` with a caller-supplied state-advance hook,
argmax'd by the fused ``ops.corr_argmax`` kernel (GLISTER's loop).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops, ref
from repro.kernels.ref import PRECISION

_NEG_INF = jnp.float32(-jnp.inf)

# Materialize the similarity below this footprint; stream it from grads
# above (n = 11585 at f32 — the 8192 bench pool stays resident, 32768+ is
# tiled on the fly).
_OTF_AUTO_BYTES = 512 << 20


def pairwise_sim(grads: jax.Array, dist_fn=None,
                 l_max: jax.Array | float | None = None) -> jax.Array:
    """Similarity  s_ij = L_max - ||g_i - g_j||  (n, n).

    ``l_max`` defaults to the max observed distance (the seed behavior);
    pass it explicitly when mixing resident and tiled/on-the-fly scans so
    both use a consistent offset (any upper bound on the pairwise
    distances is valid — ``default_l_max`` gives the cheap O(n·d) one).
    """
    if dist_fn is not None:
        d2 = dist_fn(grads, grads)
    else:
        sq = jnp.sum(grads**2, axis=-1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * jnp.dot(
            grads, grads.T, precision=PRECISION)
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    lm = jnp.max(dist) if l_max is None else jnp.asarray(l_max, jnp.float32)
    return lm - dist


def default_l_max(grads: jax.Array) -> jax.Array:
    """O(n·d) distance upper bound: the diameter bound 2·max‖g‖."""
    g = grads.astype(jnp.float32)
    return 2.0 * jnp.sqrt(jnp.max(jnp.sum(g * g, axis=1)))


def auto_on_the_fly(n: int) -> bool:
    """The engine's resident-vs-tiled default: tile the similarity on the
    fly once the (n, n) f32 matrix would exceed ``_OTF_AUTO_BYTES``.  The
    single source of truth — benchmarks read it too."""
    return n * n * 4 > _OTF_AUTO_BYTES


@functools.lru_cache(maxsize=None)
def _sim_builder(dist_fn, with_lmax: bool):
    if with_lmax:
        return jax.jit(lambda g, lm: pairwise_sim(g, dist_fn=dist_fn,
                                                  l_max=lm))
    return jax.jit(lambda g: pairwise_sim(g, dist_fn=dist_fn))


def build_sim(grads: jax.Array,
              l_max: jax.Array | float | None = None,
              dist_fn=None) -> jax.Array:
    """Jit-compiled ``pairwise_sim`` — the eager build dispatches several
    (n, n) intermediates one op at a time, which at pool 8192 costs more
    than the entire lazy greedy.  ``dist_fn`` must be a stable (module-
    level) callable: the jitted builder is cached per function."""
    g = grads.astype(jnp.float32)
    if l_max is None:
        return _sim_builder(dist_fn, False)(g)
    return _sim_builder(dist_fn, True)(g, jnp.asarray(l_max, jnp.float32))


@dataclass(frozen=True)
class GreedyStats:
    """Accounting for benchmarks and the certification tests."""
    rounds: int = 0             # accepted selections
    certified_rounds: int = 0   # rounds resolved inside the top-B block
    rescans: int = 0            # full gain scans (incl. the round-0 init)
    block_evals: int = 0        # top-B refresh iterations


class GreedyResult(NamedTuple):
    indices: jax.Array   # (k,) int32 candidate ids, -1 on unused slots
    mask: jax.Array      # (k,) bool
    gains: jax.Array     # (k,) f32 accepted marginal gain per round
    cover: jax.Array     # (n,) f32 final coverage  max_{j in S} s_ij
    stats: Optional[GreedyStats]


# ---------------------------------------------------------------------------
# shared fixed-shape pieces
# ---------------------------------------------------------------------------

def taken_mask(indices: jax.Array, mask: jax.Array, n: int) -> jax.Array:
    """(n,) bool of already-selected candidates.  Unused slots point at the
    out-of-bounds sentinel n so mode="drop" discards them (an in-bounds
    sentinel races duplicate writes when candidate n-1 is genuinely
    selected — see omp.py)."""
    return jnp.zeros((n,), bool).at[
        jnp.where(mask, indices, n)].set(mask, mode="drop")


def _lowest_id_argmax(vals: jax.Array, ids: jax.Array, sentinel: int
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(max value, winning global id, local position), ties -> lowest id.

    The candidate vector is ordered by stale bound (not by id), so the
    plain positional argmax would not reproduce ``jnp.argmax``'s global
    lowest-index tie-breaking; re-break by id explicitly.
    """
    m = jnp.max(vals)
    pos = jnp.argmin(jnp.where(vals == m, ids, jnp.int32(sentinel)))
    return m, ids[pos], pos


def fl_rows(grads: jax.Array, sqnorms: jax.Array, row_okf: jax.Array,
            l_max: jax.Array, ids: jax.Array) -> jax.Array:
    """Similarity columns for candidate ``ids``, transposed to (B, n) —
    ``(l_max - ||g_i - g_j||) * row_ok_i`` laid out row-contiguous (the
    distance is symmetric, so candidate j's column is its row; the
    coverage-row validity lands on the fast axis).  Row B of the result is
    exactly the ``cover`` update vector for candidate ids[B]."""
    cand = grads[ids]                                      # (B, d)
    d2 = (sqnorms[ids][:, None] + sqnorms[None, :]
          - 2.0 * jnp.dot(cand, grads.T, precision=PRECISION))
    return (l_max - jnp.sqrt(jnp.maximum(d2, 0.0))) * row_okf[None, :]


def fl_gains_cols(cand: jax.Array, cand_sqn: jax.Array, grads: jax.Array,
                  sqnorms: jax.Array, cover: jax.Array, row_okf: jax.Array,
                  l_max: jax.Array, block: int = 256) -> jax.Array:
    """FL gains for an explicit candidate slice, blocked over coverage
    rows — the building block the pmap-sharded scan maps over column
    shards (core/distributed.py).  One shared implementation with the
    full-scan oracle (``ref.fl_gains_cols_ref``) so block-path and
    scan-path gains stay reduction-order-identical.
    """
    return ref.fl_gains_cols_ref(cand, cand_sqn, grads, sqnorms, cover,
                                 row_okf, l_max, block=block)


# ---------------------------------------------------------------------------
# facility-location greedy solvers (one jitted program each)
# ---------------------------------------------------------------------------

def _fl_gains_ids(sim, grads, sqnorms, row_okf, l_max, cover, ids,
                  otf: bool):
    """Exact gains + similarity rows (B, n) for a candidate block.

    The resident path gathers *rows* of the (symmetric, doubly-masked)
    similarity — contiguous reads, where a column gather would stride the
    whole matrix — and reduces along the fast axis; row ``b`` doubles as
    the cover-update vector for ``ids[b]``.
    """
    if otf:
        rows = fl_rows(grads, sqnorms, row_okf, l_max, ids)
    else:
        rows = sim[ids]
    return jnp.sum(jnp.maximum(rows - cover[None, :], 0.0), axis=1), rows


def _fl_gains_all(sim, grads, row_okf, l_max, cover, avail, otf: bool,
                  sqnorms=None):
    """Full exact gain scan via the fused kernel dispatch.  ``sqnorms``
    hands the engine's hoisted row norms down to the on-the-fly scan so
    the dispatch does not recompute them per rescan."""
    if otf:
        return ops.fl_gain_argmax_otf(grads, cover, row_okf > 0, avail,
                                      l_max, sqnorms=sqnorms)
    return ops.fl_gain_argmax(sim, cover, avail)


def _fl_col_of(sim, grads, sqnorms, row_okf, l_max, e, otf: bool):
    """Cover-update vector of candidate ``e`` (its similarity column ==
    its row under the symmetric doubly-masked layout)."""
    if otf:
        return fl_rows(grads, sqnorms, row_okf, l_max, e[None])[0]
    return sim[e]


@functools.partial(jax.jit, static_argnames=("k", "block", "max_tries",
                                             "otf"))
def _fl_lazy(sim, grads, valid, l_max, *, k: int, block: int,
             max_tries: int, otf: bool):
    # Escalation tier: when the top-B block cannot certify, one refresh
    # of a much wider stale-bound block usually can — at O(wide·n)
    # versus the O(n²) full rescan it replaces, which dominates
    # on-the-fly runs (pool 32768: a rescan reconstructs the whole
    # similarity from grads).  Only the truly adversarial rounds (ties,
    # mass bound decay) still pay the rescan.  The otf escalation runs
    # through the *blocked* column scan (peak O(row_block·wide), and
    # reduction-order-identical to the full rescan's gains, which share
    # the implementation); the resident escalation gathers similarity
    # rows and is kept narrower so the (wide, n) gather stays small.
    wide = min((64 if otf else 8) * block, valid.shape[0])
    n = valid.shape[0]
    row_okf = valid.astype(jnp.float32)
    if otf:
        grads = grads.astype(jnp.float32)
        sqnorms = jnp.sum(grads * grads, axis=1)
    else:
        # Invalid rows can neither be selected nor demand coverage; zero
        # both their rows AND columns so the matrix stays symmetric (the
        # block refresh gathers rows where the scan reduces columns —
        # gains of valid candidates are identical either way, and invalid
        # columns are masked out of every argmax).
        sim = (sim.astype(jnp.float32) * row_okf[:, None]
               * row_okf[None, :])
        sqnorms = None
    # Certification margin: with a resident similarity the block and scan
    # formulas reduce identically; the on-the-fly paths accumulate in a
    # different order, so inflate the outside bound past f32 reassociation
    # noise (failing closed into a rescan is exact, certifying on noise is
    # not).
    rel = jnp.float32(1e-5 if otf else 1e-6)

    def gains_ids(cover, ids):
        return _fl_gains_ids(sim, grads, sqnorms, row_okf, l_max, cover,
                             ids, otf)

    def col_of(e):
        return _fl_col_of(sim, grads, sqnorms, row_okf, l_max, e, otf)

    def body(t, carry):
        (indices, mask, cover, bounds, picked, evals, rescans,
         certified) = carry
        avail = valid & ~taken_mask(indices, mask, n)

        def round_fn(carry):
            (indices, mask, cover, bounds, picked, evals, rescans,
             certified) = carry

            def try_cond(st):
                _, tries, cert, _, _, _ = st
                return (~cert) & (tries < max_tries)

            def try_body(st):
                bounds, tries, _, _, _, _ = st
                _, bids = lax.top_k(jnp.where(avail, bounds, _NEG_INF),
                                    block)
                exact, rows = gains_ids(cover, bids)
                # Exact gains are valid bounds for *any* candidate (taken
                # ones drop to ~0, but they are masked off anyway).
                bounds = bounds.at[bids].set(exact)
                ex_m = jnp.where(avail[bids], exact, _NEG_INF)
                bmax, e, pos = _lowest_id_argmax(ex_m, bids, n)
                outside = jnp.max(jnp.where(avail, bounds,
                                            _NEG_INF).at[bids].set(
                                                _NEG_INF))
                thresh = jnp.where(jnp.isfinite(outside),
                                   outside + rel * jnp.abs(outside),
                                   outside)
                return (bounds, tries + 1, bmax > thresh, e, ex_m[pos],
                        rows[pos])

            st0 = (bounds, jnp.int32(0), jnp.bool_(False), jnp.int32(0),
                   _NEG_INF, jnp.zeros((n,), jnp.float32))
            bounds, tries, cert, e_b, g_b, col_b = lax.while_loop(
                try_cond, try_body, st0)

            def keep(_):
                return bounds, e_b, g_b, col_b, jnp.int32(0)

            def rescan_from(bounds):
                gains, idx, val = _fl_gains_all(sim, grads, row_okf,
                                                l_max, cover, avail, otf,
                                                sqnorms=sqnorms)
                return gains, idx, val, col_of(idx), jnp.int32(1)

            if wide > block:
                def fallback(_):
                    _, wids = lax.top_k(jnp.where(avail, bounds,
                                                  _NEG_INF), wide)
                    if otf:
                        exact = fl_gains_cols(
                            grads[wids], sqnorms[wids], grads, sqnorms,
                            cover, row_okf, l_max, block=1024)
                    else:
                        exact, rows_w = gains_ids(cover, wids)
                    b2 = bounds.at[wids].set(exact)
                    ex_m = jnp.where(avail[wids], exact, _NEG_INF)
                    bmax, e2, pos2 = _lowest_id_argmax(ex_m, wids, n)
                    outside = jnp.max(jnp.where(avail, b2,
                                                _NEG_INF).at[wids].set(
                                                    _NEG_INF))
                    thresh = jnp.where(jnp.isfinite(outside),
                                       outside + rel * jnp.abs(outside),
                                       outside)

                    def keep2(_):
                        col = (col_of(e2) if otf else rows_w[pos2])
                        return b2, e2, ex_m[pos2], col, jnp.int32(0)

                    return lax.cond(bmax > thresh, keep2,
                                    lambda _: rescan_from(b2),
                                    operand=None)
            else:
                def fallback(_):
                    return rescan_from(bounds)

            bounds, e, gain, col, scanned = lax.cond(cert, keep, fallback,
                                                     operand=None)
            indices = indices.at[t].set(e)
            mask = mask.at[t].set(True)
            cover = jnp.maximum(cover, col)
            picked = picked.at[t].set(gain)
            return (indices, mask, cover, bounds, picked, evals + tries,
                    rescans + scanned,
                    certified + jnp.int32(scanned == 0))

        # Exhausted pool (k > #valid): skip the whole round — no block
        # refreshes, no rescan, stats untouched (they are the published
        # certification accounting).
        return lax.cond(jnp.any(avail), round_fn, lambda c: c, carry)

    # Round 0 is a full scan by construction: it initializes every bound
    # exactly (stale +inf bounds would force max_tries wasted refreshes).
    cover0 = jnp.zeros((n,), jnp.float32)
    gains0, e0, val0 = _fl_gains_all(sim, grads, row_okf, l_max, cover0,
                                     valid, otf, sqnorms=sqnorms)
    grow0 = jnp.any(valid)
    indices = jnp.full((k,), -1, jnp.int32).at[0].set(
        jnp.where(grow0, e0, -1))
    mask = jnp.zeros((k,), bool).at[0].set(grow0)
    cover = jnp.where(grow0, jnp.maximum(cover0, col_of(e0)), cover0)
    picked = jnp.zeros((k,), jnp.float32).at[0].set(
        jnp.where(grow0, val0, 0.0))
    carry = (indices, mask, cover, gains0, picked, jnp.int32(0),
             jnp.int32(1), jnp.int32(0))
    (indices, mask, cover, _, picked, evals, rescans,
     certified) = lax.fori_loop(1, k, body, carry)
    return indices, mask, picked, cover, evals, rescans, certified


@functools.partial(jax.jit, static_argnames=("k", "sample", "otf"))
def _fl_stochastic(sim, grads, valid, l_max, key, *, k: int, sample: int,
                   otf: bool):
    n = valid.shape[0]
    row_okf = valid.astype(jnp.float32)
    if otf:
        grads = grads.astype(jnp.float32)
        sqnorms = jnp.sum(grads * grads, axis=1)
    else:
        sim = (sim.astype(jnp.float32) * row_okf[:, None]
               * row_okf[None, :])
        sqnorms = None

    def body(t, carry):
        indices, mask, cover, picked = carry
        avail = valid & ~taken_mask(indices, mask, n)

        def round_fn(carry):
            indices, mask, cover, picked = carry
            # Uniform sample without replacement over the available pool:
            # the top-s of i.i.d. uniforms masked to avail (fixed shape,
            # seeded).
            u = jax.random.uniform(jax.random.fold_in(key, t), (n,))
            _, sids = lax.top_k(jnp.where(avail, u, _NEG_INF), sample)
            exact, rows = _fl_gains_ids(sim, grads, sqnorms, row_okf,
                                        l_max, cover, sids, otf)
            ex_m = jnp.where(avail[sids], exact, _NEG_INF)
            _, e, pos = _lowest_id_argmax(ex_m, sids, n)
            indices = indices.at[t].set(e)
            mask = mask.at[t].set(True)
            cover = jnp.maximum(cover, rows[pos])
            picked = picked.at[t].set(ex_m[pos])
            return indices, mask, cover, picked

        # Exhausted pool: skip the sample eval entirely.
        return lax.cond(jnp.any(avail), round_fn, lambda c: c, carry)

    carry = (jnp.full((k,), -1, jnp.int32), jnp.zeros((k,), bool),
             jnp.zeros((n,), jnp.float32), jnp.zeros((k,), jnp.float32))
    indices, mask, cover, picked = lax.fori_loop(0, k, body, carry)
    return indices, mask, picked, cover


@functools.partial(jax.jit, static_argnames=("k",))
def _fl_dense(sim, valid, *, k: int):
    """Naive full-rescan greedy — the parity oracle (every round scores
    all n candidates exactly; nothing cached, nothing certified)."""
    n = valid.shape[0]
    sim = sim.astype(jnp.float32) * valid[:, None].astype(jnp.float32)

    def body(t, carry):
        indices, mask, cover, picked = carry
        avail = valid & ~taken_mask(indices, mask, n)
        gains = jnp.sum(jnp.maximum(sim - cover[:, None], 0.0), axis=0)
        gains = jnp.where(avail, gains, _NEG_INF)
        e = jnp.argmax(gains).astype(jnp.int32)
        grow = jnp.any(avail)
        indices = indices.at[t].set(jnp.where(grow, e, -1))
        mask = mask.at[t].set(grow)
        cover = jnp.where(grow, jnp.maximum(cover, sim[:, e]), cover)
        picked = picked.at[t].set(jnp.where(grow, gains[e], 0.0))
        return indices, mask, cover, picked

    carry = (jnp.full((k,), -1, jnp.int32), jnp.zeros((k,), bool),
             jnp.zeros((n,), jnp.float32), jnp.zeros((k,), jnp.float32))
    indices, mask, cover, picked = lax.fori_loop(0, k, body, carry)
    return indices, mask, picked, cover


def resolve_fl_scan(grads, sim, method: str,
                    l_max=None, on_the_fly: bool | None = None):
    """One place that decides how the similarity is scanned: returns
    ``(sim, l_max, on_the_fly)`` with the resident matrix built (jitted)
    when needed and the offset defaulted consistently.  ``fl_greedy`` and
    ``craig`` both consume this, so the post-selection weights/objective
    can never use a different offset than the selection did."""
    if grads is None and sim is None:
        raise ValueError("need grads or a resident sim")
    n = (sim if grads is None else grads).shape[0]
    if sim is not None or method == "dense":
        if on_the_fly:
            raise ValueError(
                "on_the_fly=True contradicts a resident similarity: the "
                "dense oracle scans a materialized sim, and a passed-in "
                "sim is already materialized — drop one or the other")
        on_the_fly = False            # the oracle scores a resident sim
    elif on_the_fly is None:
        on_the_fly = auto_on_the_fly(n)
    if on_the_fly:
        if grads is None:
            raise ValueError("on-the-fly similarity needs grads")
        lm = default_l_max(grads) if l_max is None else l_max
        sim = None
    else:
        if sim is None:
            sim = build_sim(grads, l_max=l_max)
        lm = jnp.max(sim) if l_max is None else l_max
    return sim, jnp.asarray(lm, jnp.float32), on_the_fly


def fl_greedy(
    grads: jax.Array | None = None,   # (n, d) — required when on_the_fly
    k: int = 1,
    *,
    sim: jax.Array | None = None,     # (n, n) resident similarity
    valid: jax.Array | None = None,
    l_max: jax.Array | float | None = None,
    method: str = "lazy",             # "lazy" | "stochastic" | "dense"
    block: int = 64,                  # B — lazy top-B refresh width
    max_tries: int = 6,               # block refreshes before a rescan
    sample: int = 64,                 # s — stochastic per-round sample
    key: jax.Array | None = None,     # stochastic sampling seed
    on_the_fly: bool | None = None,   # None: auto by similarity footprint
) -> GreedyResult:
    """Facility-location maximization over ``grads`` (or a resident
    ``sim``).  ``method="lazy"`` is index-identical to ``"dense"``;
    ``"stochastic"`` is the seeded approximate tier.

    A resident ``sim`` must be **symmetric** (any metric similarity is):
    the lazy/stochastic block refresh reads candidate *rows* where the
    full scan reduces columns — contiguous gathers instead of striding
    the whole matrix.

    ``l_max`` is the similarity offset; it defaults to the observed max
    distance (resident) or the ``default_l_max`` diameter bound
    (on-the-fly).  Pass it explicitly when comparing the two scans.
    """
    if grads is None and sim is None:
        raise ValueError("need grads or a resident sim")
    n = (sim if grads is None else grads).shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    sim, lm, on_the_fly = resolve_fl_scan(grads, sim, method, l_max=l_max,
                                          on_the_fly=on_the_fly)
    k = int(k)

    if method == "dense":
        indices, mask, picked, cover = _fl_dense(sim, valid, k=k)
        stats = GreedyStats(rounds=int(jnp.sum(mask)),
                            rescans=int(jnp.sum(mask)))
    elif method == "stochastic":
        if key is None:
            key = jax.random.PRNGKey(0)
        indices, mask, picked, cover = _fl_stochastic(
            sim, grads, valid, lm, key, k=k, sample=min(int(sample), n),
            otf=on_the_fly)
        stats = GreedyStats(rounds=int(jnp.sum(mask)))
    elif method == "lazy":
        indices, mask, picked, cover, evals, rescans, certified = _fl_lazy(
            sim, grads, valid, lm, k=k, block=min(int(block), n),
            max_tries=int(max_tries), otf=on_the_fly)
        stats = GreedyStats(rounds=int(jnp.sum(mask)),
                            certified_rounds=int(certified),
                            rescans=int(rescans), block_evals=int(evals))
    else:
        raise ValueError(f"unknown greedy method {method!r}")
    return GreedyResult(indices, mask, picked, cover, stats)


# ---------------------------------------------------------------------------
# modular greedy (GLISTER): argmax of grads @ v with a state-advance hook
# ---------------------------------------------------------------------------

def modular_greedy(
    grads: jax.Array,                 # (n, d)
    k: int,
    advance: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
    v0: jax.Array,                    # (d,) initial score state
    valid: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fixed-k greedy over the score hook ``scores_t = grads @ v_t``.

    ``advance(v, e, t)`` produces the next score state after accepting
    candidate ``e`` in round ``t``.  The per-round masked argmax runs
    through the fused ``ops.corr_argmax`` kernel (scores never hit HBM on
    TPU); rows exhaust gracefully when k >= #valid (mask False, index -1).
    Returns (indices (k,), mask (k,), picked scores (k,)).
    """
    n = grads.shape[0]
    g = grads.astype(jnp.float32)
    if valid is None:
        valid = jnp.ones((n,), bool)
    zeros = jnp.zeros((n,), jnp.float32)

    def body(t, carry):
        indices, mask, v, picked = carry
        avail = valid & ~taken_mask(indices, mask, n)
        # scores = g @ v  ==  zeros - g @ (-v): the corr_argmax contract.
        e, val = ops.corr_argmax(g, -v, zeros, avail)
        grow = jnp.any(avail)
        indices = indices.at[t].set(jnp.where(grow, e, -1))
        mask = mask.at[t].set(grow)
        v = jnp.where(grow, advance(v, e, t), v)
        picked = picked.at[t].set(jnp.where(grow, val, 0.0))
        return indices, mask, v, picked

    carry = (jnp.full((k,), -1, jnp.int32), jnp.zeros((k,), bool),
             v0.astype(jnp.float32), jnp.zeros((k,), jnp.float32))
    indices, mask, _, picked = lax.fori_loop(0, int(k), body, carry)
    return indices, mask, picked

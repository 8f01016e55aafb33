"""Pod-scale GRAD-MATCH: sharded proxies + cross-host OMP (DESIGN.md §3).

At selection time the candidate proxy matrix ``G`` is ``(n, d)`` with rows
sharded over the data-parallel axis (each worker scored its own candidate
micro-batches — no gathering of ``G``).  OMP needs, per round:

  1. ``scores = G @ r``            — embarrassingly row-parallel (local)
  2. the global argmax             — one f32 ``pmax`` + index ``pmin``
  3. the winning row ``g_e``       — one masked ``psum`` of a (d,) vector

so per-round communication is ``O(d)`` (two scalars + one proxy vector),
``O(k * d)`` per selection round overall — negligible against a single
training step, which is the paper's requirement that selection cost stays
invisible at scale.  The small ``(k, k)`` NNLS is computed redundantly on
every shard (replicated), avoiding another collective; its Gram and
target-correlation buffers grow one row/col per round from the cached
active rows (same incremental scheme as ``omp.omp_select``) instead of
being rebuilt at ``O(k^2 d)`` each round.

The whole solver is ONE ``shard_map`` with a ``fori_loop`` inside: no host
round-trips, no per-round dispatch, works identically on the 512-way
dry-run mesh and the single-CPU test mesh.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.gradmatch import SelectionResult, _normalize
from repro.core.omp import _nnls_active_cached
from repro.kernels.ref import PRECISION


def sharded_omp_select(
    mesh: Mesh,
    grads: jax.Array,            # (n, d) — will be row-sharded over `axis`
    target: jax.Array,           # (d,)   — replicated
    k: int,
    axis: str = "data",
    lam: float = 0.5,
    eps: float = 1e-10,
    nnls_iters: int = 50,
) -> SelectionResult:
    """Distributed OMP: same math as ``omp.omp_select``, sharded over rows.

    ``n`` must be divisible by the axis size (the caller pads the candidate
    pool; padded rows are zero so they can never win the argmax against the
    eps-stop).  Returns replicated (indices, weights, mask, err) with
    *global* candidate indices.
    """
    n, d = grads.shape
    n_shards = mesh.shape[axis]
    assert n % n_shards == 0, (n, n_shards)
    n_local = n // n_shards

    def solver(g_local: jax.Array, tgt: jax.Array):
        g_local = g_local.astype(jnp.float32)
        tgt = tgt.astype(jnp.float32)
        shard_id = lax.axis_index(axis)
        base = shard_id * n_local
        neg_inf = jnp.float32(-jnp.inf)

        def body(t, carry):
            (indices, mask, weights, rows, gram, absrow, tcorr, residual,
             err) = carry
            # 1) local scores against the shared residual.
            scores = jnp.dot(g_local, residual,
                             precision=PRECISION)             # (n_local,)
            # Slots owned by other shards (or unused) point at the
            # out-of-bounds sentinel n_local, dropped by the scatter —
            # an in-bounds sentinel would spuriously mark local candidate
            # 0 taken on multi-shard meshes.
            own = (indices >= base) & (indices < base + n_local) & mask
            local_slots = jnp.where(own, indices - base, n_local)
            taken = jnp.zeros((n_local,), bool).at[local_slots].set(
                own, mode="drop")
            scores = jnp.where(taken, neg_inf, scores)
            # 2) global argmax: pmax on value, pmin on index at max ties.
            best_local = jnp.argmax(scores).astype(jnp.int32)
            best_val = scores[best_local]
            gmax = lax.pmax(best_val, axis)
            cand = jnp.where(best_val == gmax, base + best_local,
                             jnp.int32(n))
            e = lax.pmin(cand, axis)                          # global id
            # 3) fetch the winning row with one masked psum.
            mine = (e >= base) & (e < base + n_local)
            row_local = g_local[jnp.where(mine, e - base, 0)]
            g_e = lax.psum(
                jnp.where(mine, row_local, jnp.zeros_like(row_local)), axis)

            grow = err > eps
            growf = grow.astype(jnp.float32)
            indices = indices.at[t].set(jnp.where(grow, e, -1))
            mask = mask.at[t].set(grow)
            g_e = g_e * growf
            rows = rows.at[t].set(g_e)
            # 4) grow the replicated Gram/target-correlation caches by one
            #    row/col (O(k d), vs the O(k^2 d) rebuild they replace) and
            #    re-solve the small NNLS on the cached buffers.
            row_vals = jnp.where(
                mask, jnp.dot(rows, g_e, precision=PRECISION), 0.0)
            gram = gram.at[t, :].set(row_vals).at[:, t].set(row_vals)
            absrow = jnp.where(mask, absrow + jnp.abs(row_vals), 0.0)
            absrow = absrow.at[t].set(jnp.sum(jnp.abs(row_vals)))
            tcorr = tcorr.at[t].set(jnp.dot(g_e, tgt, precision=PRECISION))
            weights = _nnls_active_cached(gram, absrow, rows, tcorr, mask,
                                          lam, nnls_iters)
            approx = jnp.dot(weights, rows, precision=PRECISION)
            residual = tgt - approx
            err = jnp.sum(residual ** 2) + lam * jnp.sum(weights ** 2)
            return (indices, mask, weights, rows, gram, absrow, tcorr,
                    residual, err)

        init = (
            jnp.full((k,), -1, jnp.int32),
            jnp.zeros((k,), bool),
            jnp.zeros((k,), jnp.float32),
            jnp.zeros((k, d), jnp.float32),
            jnp.zeros((k, k), jnp.float32),
            jnp.zeros((k,), jnp.float32),
            jnp.zeros((k,), jnp.float32),
            tgt,
            jnp.sum(tgt ** 2),
        )
        out = lax.fori_loop(0, k, body, init)
        indices, mask, weights, err = out[0], out[1], out[2], out[8]
        return indices, mask, weights, err

    mapped = jax.shard_map(
        solver, mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=(P(), P(), P(), P()),
    )
    indices, mask, weights, err = jax.jit(mapped)(grads, target)
    return SelectionResult(indices, _normalize(weights, mask), mask, err)


def sharded_gradmatch_pb(
    mesh: Mesh,
    example_proxies: jax.Array,   # (n, d) row-sharded candidate proxies
    batch_size: int,
    k_batches: int,
    axis: str = "data",
    lam: float = 0.5,
    eps: float = 1e-10,
    target: Optional[jax.Array] = None,
) -> SelectionResult:
    """GRAD-MATCHPB at pod scale.

    Per-batch mean proxies are computed shard-locally (each shard owns
    whole micro-batches); the full-pool target gradient is one ``psum``.
    """
    n, d = example_proxies.shape
    n_shards = mesh.shape[axis]
    assert n % (n_shards * batch_size) == 0, (n, n_shards, batch_size)

    def to_batches(g_local):
        nb = g_local.shape[0] // batch_size
        pb = g_local.reshape(nb, batch_size, -1).mean(axis=1)
        tgt = lax.psum(jnp.sum(pb, axis=0), axis)
        return pb, tgt

    pb, tgt = jax.jit(jax.shard_map(
        to_batches, mesh=mesh,
        in_specs=(P(axis, None),),
        out_specs=(P(axis, None), P()),
    ))(example_proxies.astype(jnp.float32))
    if target is not None:
        tgt = target
    return sharded_omp_select(mesh, pb, tgt, k_batches, axis=axis, lam=lam,
                              eps=eps)


# ---------------------------------------------------------------------------
# shard-parallel chunk scoring for streaming selection (core/streaming.py)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _pmap_scorer(m_loc: int, absolute: bool, need_norms: bool):
    """pmap'd per-device top-m chunk scorer."""
    from repro.core.streaming import _score_chunk_impl

    def local(chunk, ok, gids, offset, residual, sel_idx, sel_mask):
        return _score_chunk_impl(chunk, ok, gids, offset, residual,
                                 sel_idx, sel_mask, m_loc, absolute,
                                 need_norms)

    return jax.pmap(local, in_axes=(0, 0, 0, 0, None, None, None))


def pmap_chunk_topm(chunk, pool_ok, gids, offset, residual, sel_idx,
                    sel_mask, *, m: int, absolute: bool,
                    need_norms: bool = True):
    """Shard-parallel drop-in for ``streaming._score_chunk``.

    Rows of the chunk are split across local devices; each computes its
    local top-m, the host merges to the global chunk top-m.  Thresholds
    are combined conservatively (max of local thresholds and the merged
    boundary), so the certification bound stays safe.
    """
    from repro.core import streaming as stream_lib

    ndev = jax.local_device_count()
    c, d = chunk.shape
    per = -(-c // ndev)
    pad = per * ndev - c
    if pad:
        chunk = jnp.pad(jnp.asarray(chunk, jnp.float32), ((0, pad), (0, 0)))
        pool_ok = jnp.pad(pool_ok, (0, pad))
        gids = jnp.pad(gids, (0, pad), constant_values=-1)
    m_loc = min(m, per)
    # Shard s owns the contiguous id range [offset + s*per, offset+(s+1)*per)
    offsets = offset + jnp.arange(ndev, dtype=jnp.int32) * per
    vals, ids, rows, ok, cmax, cthresh = _pmap_scorer(
        m_loc, absolute, need_norms)(
        chunk.reshape(ndev, per, d), pool_ok.reshape(ndev, per),
        gids.reshape(ndev, per), offsets, residual, sel_idx, sel_mask)
    # host-side merge of the ndev local buffers down to the chunk top-m
    mv = jnp.full((m,), -jnp.inf, jnp.float32)
    mi = jnp.full((m,), -1, jnp.int32)
    mr = jnp.zeros((m, d), jnp.float32)
    mok = jnp.zeros((m,), bool)
    for s in range(ndev):
        mv, mi, mr, mok = stream_lib._merge_topm(
            mv, mi, mr, mok, vals[s], ids[s], rows[s], ok[s], size=m)
    thresh = jnp.max(cthresh)
    if ndev * m_loc > m:           # merge itself dropped candidates
        thresh = jnp.maximum(thresh, mv[m - 1])
    return mv, mi, mr, mok, jnp.max(cmax), thresh


# ---------------------------------------------------------------------------
# shard-parallel facility-location gain scan (core/greedy.py, DESIGN.md §5)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _pmap_fl_scorer(per: int, row_block: int):
    """pmap'd per-device FL gain scorer over a candidate-column shard
    (same pattern as ``_pmap_scorer`` above)."""
    from repro.core import greedy as greedy_lib

    def local(cand, cand_sqn, avail_l, offset, grads, sqnorms, cover,
              row_okf, l_max):
        gains = greedy_lib.fl_gains_cols(cand, cand_sqn, grads, sqnorms,
                                         cover, row_okf, l_max,
                                         block=row_block)
        gm = jnp.where(avail_l, gains, -jnp.inf)
        v = jnp.max(gm)
        # Lowest local position attaining the max (ties -> lowest global
        # id, since each shard owns a contiguous id range).
        pos = jnp.argmin(jnp.where(gm == v, jnp.arange(per), per))
        return v, offset + pos.astype(jnp.int32)

    return jax.pmap(local, in_axes=(0, 0, 0, 0, None, None, None, None,
                                    None))


class FLPoolShards(NamedTuple):
    """Round-invariant operands of the sharded gain scan, prepared once:
    the candidate shards, their norms, the replicated pool and the shard
    id offsets.  Only (cover, avail) change between greedy rounds, so
    only they are re-fed per round."""
    cand: jax.Array       # (ndev, per, d) candidate column shards
    cand_sqn: jax.Array   # (ndev, per)
    offsets: jax.Array    # (ndev,) global id base per shard
    grads: jax.Array      # (n, d) replicated coverage-row pool, f32
    sqnorms: jax.Array    # (n,)
    per: int
    n: int


def shard_fl_pool(grads) -> FLPoolShards:
    ndev = jax.local_device_count()
    n, d = grads.shape
    g = jnp.asarray(grads, jnp.float32)
    sqnorms = jnp.sum(g * g, axis=1)
    per = -(-n // ndev)
    pad = per * ndev - n
    cand = jnp.pad(g, ((0, pad), (0, 0))).reshape(ndev, per, d)
    cand_sqn = jnp.pad(sqnorms, (0, pad)).reshape(ndev, per)
    offsets = jnp.arange(ndev, dtype=jnp.int32) * per
    return FLPoolShards(cand, cand_sqn, offsets, g, sqnorms, per, n)


def pmap_fl_gains(shards: FLPoolShards, cover, avail, row_okf, l_max, *,
                  row_block: int = 256):
    """One facility-location gain scan, candidate columns sharded across
    local devices.  Returns the replicated (argmax id, max gain) with
    global lowest-id tie-breaking — the per-round collective of the
    sharded CRAIG greedy.  The similarity is reconstructed from the pool
    in (row_block, per-shard) strips, so no device ever holds an (n, n)
    block."""
    ndev = shards.cand.shape[0]
    avail_p = jnp.pad(avail, (0, ndev * shards.per - shards.n))
    vals, ids = _pmap_fl_scorer(shards.per, row_block)(
        shards.cand, shards.cand_sqn, avail_p.reshape(ndev, shards.per),
        shards.offsets, shards.grads, shards.sqnorms, cover, row_okf,
        jnp.asarray(l_max, jnp.float32))
    gmax = jnp.max(vals)
    e = jnp.min(jnp.where(vals == gmax, ids, jnp.int32(shards.n)))
    return e, gmax


def fl_greedy_pmap(grads, k: int, valid=None, l_max=None,
                   row_block: int = 256):
    """CRAIG's greedy with every per-round gain scan pmap-sharded over
    local devices (each shard scores its candidate columns, the host
    merges one (value, id) pair per device — O(devices) per-round
    traffic, mirroring ``sharded_omp_select``'s pmax/pmin election).

    Scan semantics match the dense oracle (every round is a full exact
    scan), so selections are index-identical to ``greedy.fl_greedy
    (method="dense")`` up to similarity-reconstruction rounding; the
    similarity itself is tiled on the fly, never materialized.
    """
    from repro.core import greedy as greedy_lib
    from repro.core.greedy import GreedyResult, GreedyStats

    n = grads.shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    row_okf = valid.astype(jnp.float32)
    lm = greedy_lib.default_l_max(grads) if l_max is None else l_max
    lm = jnp.asarray(lm, jnp.float32)
    shards = shard_fl_pool(grads)     # round-invariant: shipped once

    indices = jnp.full((k,), -1, jnp.int32)
    mask = jnp.zeros((k,), bool)
    picked = jnp.zeros((k,), jnp.float32)
    cover = jnp.zeros((n,), jnp.float32)
    avail = valid
    for t in range(int(k)):
        if not bool(jnp.any(avail)):
            break
        e, gain = pmap_fl_gains(shards, cover, avail, row_okf, lm,
                                row_block=row_block)
        indices = indices.at[t].set(e)
        mask = mask.at[t].set(True)
        picked = picked.at[t].set(gain)
        col = greedy_lib.fl_rows(shards.grads, shards.sqnorms, row_okf,
                                 lm, e[None])[0]
        cover = jnp.maximum(cover, col)
        avail = avail & ~(jnp.arange(n) == e)
    stats = GreedyStats(rounds=int(jnp.sum(mask)),
                        rescans=int(jnp.sum(mask)))
    return GreedyResult(indices, mask, picked, cover, stats)


# ---------------------------------------------------------------------------
# device-parallel partition solves (core/partition.py, DESIGN.md §9)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _pmap_partition_solver(k: int, lam: float, eps: float, nnls_iters: int,
                           method: str, block: int):
    """pmap'd per-device partition OMP (same pattern as ``_pmap_scorer``
    above).  One device solves one whole partition; partitions are
    independent problems, so no collective is ever needed."""
    from repro.core.omp import omp_select

    def local(grads, target, valid):
        return omp_select(grads, target, k=k, lam=lam, eps=eps,
                          nnls_iters=nnls_iters, valid=valid,
                          method=method, block=block)

    return jax.pmap(local, in_axes=(0, 0, 0))


def pmap_partition_omp(parts, targets, valids, k: int, lam: float = 0.5,
                       eps: float = 1e-10, nnls_iters: int = 50,
                       method: str = "incremental", block: int = 128):
    """Solve ``P`` independent partition OMPs device-parallel.

    ``parts`` is ``(P, n_max, d)`` padded partition pools, ``targets``
    ``(P, d)``, ``valids`` ``(P, n_max)`` (padding rows False).  Partitions
    are dispatched in groups of ``local_device_count``; a ragged tail
    group is padded by repeating its first partition and the extra solves
    dropped.  Returns ``(idx, w, mask, err)`` stacked over partitions with
    *partition-local* row indices — the caller owns the local→global map.
    """
    parts = jnp.asarray(parts, jnp.float32)
    targets = jnp.asarray(targets, jnp.float32)
    valids = jnp.asarray(valids, bool)
    ndev = jax.local_device_count()
    p_total = parts.shape[0]
    fn = _pmap_partition_solver(int(k), float(lam), float(eps),
                                int(nnls_iters), str(method), int(block))
    outs = []
    for s in range(0, p_total, ndev):
        g = parts[s:s + ndev]
        t = targets[s:s + ndev]
        v = valids[s:s + ndev]
        got = g.shape[0]
        if got < ndev:
            reps = ndev - got
            g = jnp.concatenate([g, jnp.repeat(g[:1], reps, axis=0)])
            t = jnp.concatenate([t, jnp.repeat(t[:1], reps, axis=0)])
            v = jnp.concatenate([v, jnp.repeat(v[:1], reps, axis=0)])
        idx, w, mask, err = fn(g, t, v)
        outs.append((idx[:got], w[:got], mask[:got], err[:got]))
    return tuple(jnp.concatenate([o[i] for o in outs], axis=0)
                 for i in range(4))


def replicate(mesh: Mesh, x: jax.Array) -> jax.Array:
    return jax.device_put(x, NamedSharding(mesh, P()))


def shard_rows(mesh: Mesh, x: jax.Array, axis: str = "data") -> jax.Array:
    return jax.device_put(x, NamedSharding(mesh, P(axis)))

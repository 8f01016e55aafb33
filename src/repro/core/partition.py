"""Partition-and-merge sharded selection (DESIGN.md §9).

Million-row pools make a single global OMP the bottleneck twice over: the
per-round argmax scans all ``n`` rows, and the streaming engine's
certification traffic grows with the pool (the overhead ratio climbed
3.75x @ 8k → 8.59x @ 65k in ``BENCH_selection.json``).  CRAIG's
decomposition argument (arXiv:1906.01827) — and the paper's own per-class
mode — justify the classic fix: split the pool into ``P`` partitions,
solve each small problem with the existing certified engines, then run a
**certified merge round** over the union of partition picks.

The three layers here:

* ``make_plan`` / ``split_budget`` — partition the pool (per-class when
  labels exist, hashed otherwise; contiguous ranges for out-of-core
  streams) and split the budget exactly (remainder to the largest
  partitions, quotas capped at partition size, surplus rebalanced).
* per-partition solves — device-parallel via plain ``pmap``
  (``distributed.pmap_partition_omp``, the ``_pmap_scorer`` pattern) for
  resident pools, or chunk-wise via the PR-5/6
  streaming engine (``subrange_chunks`` views of one shared loader) for
  out-of-core partitions.  Each partition matches its own gradient-sum
  target; the targets sum to the global eq.-2 target, so the union of
  picks covers it.
* the **certified merge** — one incremental-Gram OMP re-solve
  (``omp_select``, index-exact vs the dense oracle) over the union of
  partition picks against the *global* target.  The merge reweights every
  pick globally, drops redundant cross-partition picks, and its ``err``
  is the true global objective of the returned solution.

Per-partition weights never survive to the result — only indices do —
which is what makes quota truncation exact: OMP round ``t`` depends only
on rounds ``< t`` (the greedy prefix property), so the first ``quota_p``
picks of a ``k_cap``-round solve equal a fresh ``quota_p``-round solve's.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import omp as omp_lib
from repro.core import streaming as stream_lib
from repro.core.gradmatch import SelectionResult, _normalize
from repro.core.omp import split_budget
from repro.kernels.ref import PRECISION

__all__ = [
    "PartitionPlan", "PartitionStats", "make_plan", "split_budget",
    "gradmatch_partitioned", "gradmatch_partitioned_stream",
]

# Knuth's multiplicative hash over the row id: deterministic, stateless,
# spreads contiguous id ranges uniformly over partitions.
_HASH_MULT = np.uint64(2654435761)
_HASH_MOD = np.uint64(1 << 32)


class PartitionPlan(NamedTuple):
    """How the pool splits: ``kind`` in {"class", "hash", "contiguous"}.

    ``assign`` maps each row to its partition (class/hash kinds);
    ``bounds`` is the ``(P+1,)`` row-offset fence (contiguous kind, the
    streaming path — no (n,) array needs materializing there).  ``sizes``
    counts *candidate* rows per partition (invalid rows excluded).
    """
    kind: str
    num_parts: int
    sizes: np.ndarray                       # (P,) candidate rows per part
    assign: Optional[np.ndarray] = None     # (n,) partition id per row
    bounds: Optional[np.ndarray] = None     # (P+1,) contiguous offsets


@dataclasses.dataclass
class PartitionStats:
    """Partition/merge accounting attached to ``SelectionResult.stats``."""
    num_parts: int
    kind: str
    quotas: tuple
    union_size: int          # partition picks entering the merge
    merged: int              # picks surviving the merge re-solve
    stream: Optional[stream_lib.SelectStats] = None  # out-of-core solves


def make_plan(n: int, partitions: int = 0, labels=None, num_classes: int = 0,
              kind: str = "auto", valid=None) -> PartitionPlan:
    """Build a partition plan over ``n`` rows.

    ``kind="auto"`` picks per-class when labels exist (the paper's
    decomposition — partition targets are then exactly the per-class
    targets), hashed otherwise.  ``partitions`` only applies to the
    non-class kinds (class partitioning is one partition per class);
    ``0`` means auto: ``max(local_device_count, 2)`` so the pmap path has
    work per device even on small hosts.
    """
    n = int(n)
    if kind == "auto":
        kind = "class" if (labels is not None and num_classes > 1) else "hash"
    valid_np = (np.ones(n, bool) if valid is None
                else np.asarray(valid, bool))
    if kind == "class":
        if labels is None or num_classes <= 0:
            raise ValueError("kind='class' needs labels and num_classes")
        assign = np.asarray(labels, np.int64)
        p = int(num_classes)
        ok = valid_np & (assign >= 0) & (assign < p)
        sizes = np.bincount(assign[ok], minlength=p)
        return PartitionPlan("class", p, sizes, assign=assign)
    p = int(partitions) if partitions > 0 else max(
        jax.local_device_count(), 2)
    p = max(1, min(p, n)) if n else 1
    if kind == "hash":
        ids = np.arange(n, dtype=np.uint64)
        assign = (((ids * _HASH_MULT) % _HASH_MOD) % np.uint64(p)).astype(
            np.int64)
        sizes = np.bincount(assign[valid_np], minlength=p)
        return PartitionPlan("hash", p, sizes, assign=assign)
    if kind == "contiguous":
        bounds = (np.arange(p + 1, dtype=np.int64) * n) // p
        sizes = np.array([int(valid_np[bounds[i]:bounds[i + 1]].sum())
                          for i in range(p)], np.int64)
        return PartitionPlan("contiguous", p, sizes, bounds=bounds)
    raise ValueError(f"unknown partition kind {kind!r}; "
                     "known: class, hash, contiguous, auto")


def _empty_result(k: int, err) -> SelectionResult:
    z = jnp.zeros((k,))
    return SelectionResult(jnp.full((k,), -1, jnp.int32),
                           z.astype(jnp.float32), z.astype(bool),
                           jnp.float32(err))


def _certified_merge(union_rows, union_gids, target, k: int, lam: float,
                     eps: float, nnls_iters: int):
    """The merge round: incremental-Gram OMP over the union of partition
    picks against the global target.  Returns padded ``(k,)`` arrays with
    *global* ids plus the true global ``err`` of the merged solution.

    The merge budget is ``min(k, |union|)`` — never more rounds than
    candidates, so every committed slot is a distinct union row (beyond
    exhaustion the solver would duplicate its argmax-of-nothing pick).
    """
    u = int(union_rows.shape[0])
    k_merge = min(int(k), u)
    m_idx, m_w, m_mask, m_err = omp_lib.omp_select(
        jnp.asarray(union_rows, jnp.float32),
        jnp.asarray(target, jnp.float32), k=k_merge, lam=lam, eps=eps,
        nnls_iters=nnls_iters, method="incremental")
    m_idx = np.asarray(m_idx)
    m_mask_np = np.asarray(m_mask)
    out_idx = np.full((k,), -1, np.int32)
    out_w = np.zeros((k,), np.float32)
    out_mask = np.zeros((k,), bool)
    out_idx[:k_merge][m_mask_np] = union_gids[m_idx[m_mask_np]]
    out_w[:k_merge] = np.where(m_mask_np, np.asarray(m_w), 0.0)
    out_mask[:k_merge] = m_mask_np
    return (jnp.asarray(out_idx), jnp.asarray(out_w), jnp.asarray(out_mask),
            m_err, int(m_mask_np.sum()))


def gradmatch_partitioned(
    proxies,                     # (n, d) candidate gradient proxies
    k: int,
    partitions: int = 0,
    labels=None,
    num_classes: int = 0,
    target=None,                 # (d,) global target; None = eq.-2 sum
    lam: float = 0.5,
    eps: float = 1e-10,
    valid=None,
    kind: str = "auto",
    method: str = "incremental",
    use_pmap: Optional[bool] = None,   # None = auto (>1 local device)
    nnls_iters: int = 50,
) -> SelectionResult:
    """Partition-and-merge GRAD-MATCH over a resident pool.

    Splits per ``make_plan``, solves every partition against its own
    target (per-class sums for the class kind — bit-identical to
    ``gradmatch_per_class``'s targets — else the partition's row sum, or
    a size-proportional slice of an explicit ``target``), truncates each
    partition to its exact ``split_budget`` quota, and re-solves the
    union in one certified merge round.  Device-parallel across
    partitions via ``distributed.pmap_partition_omp`` when more than one
    local device is present (``use_pmap=True`` forces the pmap path even
    on one device — same groups, sequential dispatch).
    """
    pool_np = np.asarray(proxies, np.float32)
    n, d = pool_np.shape
    valid_np = (np.ones(n, bool) if valid is None
                else np.asarray(valid, bool))
    plan = make_plan(n, partitions, labels=labels, num_classes=num_classes,
                     kind=kind, valid=valid_np)
    quotas = split_budget(k, plan.sizes)
    k_cap = int(quotas.max()) if quotas.size else 0
    stats = PartitionStats(plan.num_parts, plan.kind, tuple(quotas.tolist()),
                           0, 0)
    if k_cap == 0:
        err = float(np.sum(np.square(
            np.zeros(d) if target is None else np.asarray(target))))
        return SelectionResult(*_empty_result(k, err)[:4], stats)

    # Gather rows per partition, padded to the widest partition.
    p_count = plan.num_parts
    if plan.assign is not None:
        gid_lists = [np.flatnonzero(valid_np & (plan.assign == p))
                     for p in range(p_count)]
    else:
        gid_lists = [
            plan.bounds[p] + np.flatnonzero(
                valid_np[plan.bounds[p]:plan.bounds[p + 1]])
            for p in range(p_count)]
    n_max = max(1, max(len(g) for g in gid_lists))
    parts = np.zeros((p_count, n_max, d), np.float32)
    pvalid = np.zeros((p_count, n_max), bool)
    pgids = np.full((p_count, n_max), -1, np.int64)
    for p, gi in enumerate(gid_lists):
        parts[p, :len(gi)] = pool_np[gi]
        pvalid[p, :len(gi)] = True
        pgids[p, :len(gi)] = gi

    n_valid = int(valid_np.sum())
    if target is not None:
        g_target = jnp.asarray(target, jnp.float32)
        fracs = plan.sizes / max(n_valid, 1)
        targets_p = jnp.asarray(fracs, jnp.float32)[:, None] * g_target
    elif plan.kind == "class":
        # The exact per-class targets gradmatch_per_class matches against
        # (same one-hot contraction, so the class path is index-exact
        # against it — summing gathered rows instead would drift an ulp).
        g_j = jnp.asarray(pool_np * valid_np[:, None])
        onehot = jax.nn.one_hot(jnp.asarray(plan.assign), p_count,
                                dtype=g_j.dtype)
        targets_p = jnp.dot(onehot.T, g_j, precision=PRECISION)
        g_target = jnp.sum(targets_p, axis=0)
    else:
        targets_p = jnp.sum(jnp.asarray(parts)
                            * jnp.asarray(pvalid)[:, :, None], axis=1)
        g_target = jnp.sum(targets_p, axis=0)

    if use_pmap is None:
        use_pmap = jax.local_device_count() > 1
    if use_pmap:
        from repro.core import distributed as dist_lib
        idx, _, mask, _ = dist_lib.pmap_partition_omp(
            parts, targets_p, pvalid, k_cap, lam=lam, eps=eps,
            nnls_iters=nnls_iters, method=method)
    else:
        def one_part(g, t, v):
            p_idx, _, p_mask, _ = omp_lib.omp_select(
                g, t, k=k_cap, lam=lam, eps=eps, nnls_iters=nnls_iters,
                valid=v, method=method)
            return p_idx, p_mask

        idx, mask = jax.vmap(one_part)(jnp.asarray(parts), targets_p,
                                       jnp.asarray(pvalid))

    # Quota truncation (index-exact, see module docstring) + global ids.
    idx_np = np.asarray(idx)
    mask_np = np.asarray(mask) & (np.arange(k_cap)[None, :]
                                  < quotas[:, None])
    union_gids = np.concatenate(
        [pgids[p][idx_np[p][mask_np[p]]] for p in range(p_count)]
        or [np.zeros((0,), np.int64)])
    stats.union_size = int(union_gids.shape[0])
    if stats.union_size == 0:
        err = float(jnp.sum(g_target ** 2))
        return SelectionResult(*_empty_result(k, err)[:4], stats)

    out_idx, out_w, out_mask, err, merged = _certified_merge(
        pool_np[union_gids], union_gids, g_target, k, lam, eps, nnls_iters)
    stats.merged = merged
    return SelectionResult(out_idx, _normalize(out_w, out_mask), out_mask,
                           err, stats)


def _accumulate_stats(agg: stream_lib.SelectStats,
                      s: stream_lib.SelectStats) -> None:
    for f in dataclasses.fields(stream_lib.SelectStats):
        if f.name == "pool_size":
            continue
        setattr(agg, f.name, getattr(agg, f.name) + getattr(s, f.name))


def _gather_rows_by_scan(pool_iter: Callable, gids: np.ndarray,
                         d: int) -> np.ndarray:
    """One loader pass gathering exact rows by global id (factory-only
    pools without a ``row_fetch`` capability)."""
    rows = np.zeros((len(gids), d), np.float32)
    slot = {int(g): i for i, g in enumerate(gids)}
    order = np.sort(np.asarray(gids, np.int64))
    j, off = 0, 0
    for chunk, _ in pool_iter():
        c = chunk.shape[0]
        while j < len(order) and order[j] < off + c:
            g = int(order[j])
            rows[slot[g]] = np.asarray(chunk[g - off], np.float32)
            j += 1
        off += c
        if j >= len(order):
            break
    return rows


def gradmatch_partitioned_stream(
    pool=None,                   # (n, d) array/memmap; or None + pool_iter
    k: int = 0,
    partitions: int = 0,
    pool_iter: Optional[Callable] = None,  # (chunk, valid) factory
    n: Optional[int] = None,     # pool rows (counted in one pass if None)
    row_fetch: Optional[Callable] = None,
    target=None,
    lam: float = 0.5,
    eps: float = 1e-10,
    chunk_size: int = 4096,
    buffer_size: int = 256,
    cache_bytes: int = stream_lib.DEFAULT_CACHE_BYTES,  # per partition
    retry=None,
    nnls_iters: int = 50,
) -> SelectionResult:
    """Out-of-core partition-and-merge: contiguous row ranges, each solved
    by the PR-5/6 certified streaming engine over a ``subrange_chunks``
    view of one shared loader, then the certified merge.

    Why the overhead ratio goes *flat* in pool size: every certification/
    buffer cost the streaming engine pays scales with its pool — here
    each engine sees ``n/P`` rows and solves ``~k/P`` rounds, so growing
    ``n`` at fixed ``n/P`` keeps per-partition work at the small-pool
    regime where streaming is cheap.  ``cache_bytes`` is a *per-partition*
    budget; partitions run sequentially on one host, so peak cache
    residency is one partition's (each cache is dropped before the next
    partition solves).

    ``partitions=0`` sizes partitions to ~128k rows (capped at 16).  The
    per-partition quota assumes valid-dense pools (quotas come from raw
    range sizes; the engine still never *selects* an invalid row).
    """
    if pool is not None:
        n, d = int(pool.shape[0]), int(pool.shape[1])
        pool_iter = stream_lib.array_chunks(pool, chunk_size)
        if row_fetch is None:
            row_fetch = stream_lib.array_row_fetch(pool)
    else:
        if pool_iter is None:
            raise ValueError("need pool= or pool_iter=")
        first = next(iter(pool_iter()), None)
        if first is None:
            raise ValueError("empty pool iterator")
        d = int(first[0].shape[1])
        if n is None:
            n = sum(int(c.shape[0]) for c, _ in pool_iter())
    p_count = int(partitions) if partitions > 0 else min(
        16, max(2, -(-n // 131072)))
    p_count = max(1, min(p_count, n))
    bounds = (np.arange(p_count + 1, dtype=np.int64) * n) // p_count
    sizes = np.diff(bounds)
    quotas = split_budget(k, sizes)
    agg = stream_lib.SelectStats(pool_size=n)
    picks = []
    part_targets = []
    g_target = (None if target is None
                else jnp.asarray(target, jnp.float32))
    for p in range(p_count):
        if quotas[p] == 0:
            continue
        lo, hi = int(bounds[p]), int(bounds[p + 1])
        sub = stream_lib.subrange_chunks(pool_iter, lo, hi)
        cache = stream_lib.ChunkCache(int(cache_bytes), d)
        sub_fetch = (None if row_fetch is None
                     else stream_lib.offset_row_fetch(row_fetch, lo))
        # One summing pass per partition: the partition target *and* the
        # cache warm-up (so the solve's certified rounds hit memory).
        t_p, _ = stream_lib.streaming_target(sub, cache=cache, retry=retry)
        if g_target is not None:
            t_p = g_target * ((hi - lo) / n)
        part_targets.append(t_p)
        out = stream_lib.omp_select_streaming(
            sub, t_p, int(quotas[p]), lam=lam, eps=eps,
            nnls_iters=nnls_iters, buffer_size=buffer_size, cache=cache,
            row_fetch=sub_fetch, retry=retry)
        _accumulate_stats(agg, out.stats)
        local = np.asarray(out.indices)[np.asarray(out.mask)]
        picks.append(lo + local.astype(np.int64))
    stats = PartitionStats(p_count, "contiguous", tuple(quotas.tolist()),
                           0, 0, stream=agg)
    if g_target is None:
        g_target = jnp.sum(jnp.stack(part_targets), axis=0) \
            if part_targets else jnp.zeros((d,), jnp.float32)
    union_gids = np.concatenate(picks or [np.zeros((0,), np.int64)])
    stats.union_size = int(union_gids.shape[0])
    if stats.union_size == 0:
        err = float(jnp.sum(g_target ** 2))
        return SelectionResult(*_empty_result(k, err)[:4], stats)
    if row_fetch is not None:
        union_rows = np.asarray(row_fetch(union_gids), np.float32)
    else:
        union_rows = _gather_rows_by_scan(pool_iter, union_gids, d)
        agg.passes += 1
    out_idx, out_w, out_mask, err, merged = _certified_merge(
        union_rows, union_gids, g_target, k, lam, eps, nnls_iters)
    stats.merged = merged
    return SelectionResult(out_idx, _normalize(out_w, out_mask), out_mask,
                           err, stats)

"""Streaming block-OMP selection over out-of-core candidate pools.

``omp_select`` (core/omp.py) holds the whole ``(n, d)`` proxy pool in
memory and touches it every round.  This module selects from pools that do
NOT fit: the pool is consumed through a re-iterable *chunk factory* (a
callable returning a fresh iterator of ``(chunk, valid)`` pairs in a fixed
order — e.g. ``array_chunks`` over an ``np.memmap``, or a per-chunk proxy
extractor, see ``data/loader.ChunkedPool`` + ``core/proxies``), so peak
pool-dependent memory is ``O(chunk + M·d + cache_bytes)`` for a top-``M``
candidate buffer plus a *compressed chunk cache* — independent of the pool
size ``n``.  (The active-set state is ``O(k·d + k²)``, exactly as
in-memory OMP.)

The solver is *certified-exact*: it selects the identical subset the
in-memory incremental solver would (the differential tests in
``tests/test_omp_parity.py`` assert index-exact parity, with the dense
solver as the common oracle).  The engine is **multi-round-per-pass**
(DESIGN.md §7): each loader pass refreshes a top-``M`` exact-row buffer
*and* the compressed cache, then commits ``B >= 1`` certified OMP rounds
against the buffer before touching the loader again.  A round is
certified when the buffer's best in-buffer score provably beats every
out-of-buffer candidate, established by a ladder of bounds (cheapest
first, each fail-closed into the next):

  1. **Residual-projection sketch** (per chunk, O(C)): every out-of-
     buffer row of chunk ``c`` had pass-score ``g·r0 <= T_c`` (the
     chunk/merge admission threshold).  Decomposing the drifted residual
     ``r = α·r0 + r_perp`` gives ``g·r <= α·T_c + ‖g‖·‖r_perp‖`` (α >= 0
     case), bounded per chunk by its max valid row norm — strictly
     tighter than the plain Cauchy–Schwarz ``T + gmax·‖r − r0‖`` bound
     because only the *orthogonal* drift pays the norm product.
  2. **Compressed-cache interval bound** (per row, O(n·d) in-memory
     bf16): cached chunks are re-scored from their bf16 rows in f32
     accumulation; ``u_i = s̃_i + (e_i + acc·‖g_i‖)·‖r‖`` upper-bounds
     the exact f32 score, where ``e_i = ‖g_i − bf16(g_i)‖`` is the
     *measured* compression error stored in the f32 sidecar (typically
     ~2^-9.5·‖g_i‖, versus the worst-case 2^-8 bound — which is what
     keeps the interval tight enough to fire).  If no available
     out-of-buffer row's ``u_i`` reaches the buffer max, the round is
     certified.  Ties fail closed, exactly like the lazy greedy tier
     (DESIGN.md §5).
  3. **Exact-row repair** (optional, needs ``row_fetch``): when only a
     few cached rows' intervals overlap the buffer max, their *exact*
     f32 rows are fetched by id and admitted into a bounded repair annex
     of the buffer; the re-run argmax is then exact by construction.
  4. **Rescan**: otherwise the buffer is refreshed — from the cache when
     it covers the whole pool and ``row_fetch`` exists (an interval scan
     picks every possible top-``M`` member, their exact rows are
     fetched: no loader traffic), else by a full loader pass.

Worst case (no cache, adversarial residual drift) is one selection per
pass — ``O(n·d)`` scoring flops per round, the same as the in-memory
solver's narrow regime, paid through chunked streaming reads.  With the
cache resident the loader is touched ~once: rescans hit memory instead
of the loader, which is what makes the streaming tier's overhead vs the
in-memory solver a small constant (the parity gate enforces <= 5x at
pool 8192 with ``passes <= k/8 + 2``).

The NNLS re-solve consumes the same cached Gram / Gershgorin / target-
correlation buffers as ``omp.OMPIncState``, sliced to the identical
``block``-quantized prefix widths, so weights match the in-memory solver
to f32 tolerance.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.checkpoint.solver_state import (load_solver_state,
                                           save_solver_state)
from repro.core.gradmatch import SelectionResult, _normalize
from repro.core.omp import _nnls_active_cached
from repro.kernels import ops
from repro.resilience.faults import CorruptChunkError
from repro.resilience.recovery import RetryPolicy, with_retries
from repro.kernels.ref import PRECISION

_NEG_INF = jnp.float32(-jnp.inf)
_BIG_ID = jnp.int32(2**31 - 1)

# Soundness margin for scoring a bf16-compressed row in f32 accumulation
# against the exact f32 row.  The compression error is *measured*, not
# bounded: the cache stores ‖g − bf16(g)‖ per row (f32 sidecar), so by
# Cauchy–Schwarz |s̃ − s| <= e_i·‖r‖ plus the accumulation-order term —
# two different f32 summation orders differ by <= d·2^-23 relative to
# ‖g‖·‖r‖; the 1.25 factor absorbs second-order terms.  The measured
# e_i is typically ~2^-9.5·‖g‖ (RMS of half-ulp rounding) versus the
# worst-case 2^-8·‖g‖ a bound-only margin would have to assume, which is
# what keeps false interval overlaps — and therefore repair fetches —
# rare.  See DESIGN.md §7 for the derivation and when the bf16 cache is
# bit-safe outright.
DEFAULT_CACHE_BYTES = 256 << 20


def _acc_margin(d: int) -> float:
    return float(d * 2.0 ** -23 * 1.25)


# ---------------------------------------------------------------------------
# chunk protocol
# ---------------------------------------------------------------------------

def array_chunks(pool, chunk_size: int, valid=None) -> Callable[[], Iterator]:
    """Chunk factory over an ``(n, d)`` array (in-memory or ``np.memmap``).

    Each call returns a fresh iterator of ``(chunk, valid_chunk)`` in the
    same deterministic order — streaming selection makes several passes.
    Rows are only touched one chunk at a time, so a memory-mapped pool is
    never materialized.
    """
    n = pool.shape[0]
    cs = int(chunk_size)

    def chunks():
        for lo in range(0, n, cs):
            hi = min(lo + cs, n)
            yield pool[lo:hi], (None if valid is None else valid[lo:hi])

    return chunks


def array_row_fetch(pool) -> Callable:
    """Exact-row fetch capability for an array-backed pool: the repair
    and cache-refill tiers gather a handful of rows by global id instead
    of paying a loader pass.  Must return the same f32 rows the chunk
    factory yields (here: a plain gather)."""

    def fetch(ids):
        return np.asarray(pool[np.asarray(ids)], np.float32)

    return fetch


def chunked_pool_iter(pool, valid=None) -> Callable[[], Iterator]:
    """Adapt a ``data.loader.ChunkedPool`` to the ``(chunk, valid)``
    protocol ``omp_select_streaming`` consumes.

    ``pool.chunks()`` yields ``(x, y, offset)``; the labels are dropped
    (proxy pools registered with the serve layer are already gradient
    proxies — raw-data pools go through ``proxies.proxy_chunk_stream``
    instead).  ``valid`` is an optional full-length (n,) mask sliced per
    chunk by the offsets the pool reports.
    """

    def chunks():
        for x, _, lo in pool.chunks():
            c = x.shape[0]
            yield x, (None if valid is None else valid[lo:lo + c])

    return chunks


def subrange_chunks(pool_iter: Callable[[], Iterator], lo: int,
                    hi: int) -> Callable[[], Iterator]:
    """Clip a chunk factory to the global row range ``[lo, hi)``.

    The partition solver's per-partition view of a shared loader: chunk
    boundaries need not align with the range — straddling chunks are
    sliced — and a fresh iterator walks the same sub-chunks in the same
    order on every call (the streaming engine's determinism contract),
    because the parent factory's order is deterministic and the clipping
    is pure arithmetic on its offsets.  Row ids inside the view are
    partition-local; add ``lo`` to map a pick back to a global id.
    """
    lo, hi = int(lo), int(hi)

    def chunks():
        off = 0
        for chunk, v in pool_iter():
            c = chunk.shape[0]
            if off + c > lo:
                s = max(lo - off, 0)
                e = min(hi - off, c)
                if s < e:
                    yield chunk[s:e], (None if v is None else v[s:e])
            off += c
            if off >= hi:
                break

    return chunks


def offset_row_fetch(row_fetch: Callable, lo: int) -> Callable:
    """Shift an exact-row fetcher into a ``subrange_chunks`` view: local
    id ``i`` fetches global row ``lo + i``."""
    lo = int(lo)

    def fetch(ids):
        return row_fetch(np.asarray(ids, np.int64) + lo)

    return fetch


def streaming_target(pool_iter: Callable[[], Iterator],
                     cache: "ChunkCache | None" = None,
                     retry: "RetryPolicy | None" = None):
    """One pass: ``(sum of valid rows, total row count)`` — eq. (2) target.

    When a ``cache`` is given the same pass also warms the compressed
    chunk cache (the serve registry's admission pass doubles as the cache
    fill, so the first request's rescans already hit memory).  With a
    ``retry`` policy, transient iterator faults restart the pass (the
    summing accumulators are pass-local and ``cache.offer`` is idempotent
    for resident chunks, so a restart is exact).
    """

    def scan():
        total = None
        n = 0
        idx = 0
        for chunk, v in pool_iter():
            c = jnp.asarray(chunk, jnp.float32)
            if v is not None:
                c = c * jnp.asarray(v)[:, None].astype(jnp.float32)
            s = jnp.sum(c, axis=0)
            total = s if total is None else total + s
            offer_chunk(cache, idx, n, chunk, v)
            n += chunk.shape[0]
            idx += 1
        return total, n, idx

    if retry is None:
        total, n, idx = scan()
    else:
        total, n, idx = with_retries(scan, retry)
    if total is None:
        raise ValueError("empty pool iterator")
    if cache is not None and cache.covers(idx):
        cache.complete = idx
    return total, n


def _bucket(c: int) -> int:
    """Pad chunk length to the next power of two (bounds jit variants)."""
    p = 8
    while p < c:
        p *= 2
    return p


def offer_chunk(cache: "ChunkCache | None", idx: int, offset: int,
                chunk, v) -> None:
    """Offer one ``(chunk, valid)`` pair to the compressed cache: pad the
    chunk to its power-of-two bucket, build the ok-mask and global row
    ids for rows ``[offset, offset + len(chunk))``, and hand it to
    ``cache.offer``.  The warming-pass body, shared by the one-shot
    ``streaming_target`` scan and the registry's incremental
    (deferred-warm) admission so the two can never drift."""
    if cache is None:
        return
    c = chunk.shape[0]
    cpad = _bucket(c)
    ch = jnp.asarray(chunk, jnp.float32)
    if cpad != c:
        ch = jnp.pad(ch, ((0, cpad - c), (0, 0)))
    ok = jnp.arange(cpad) < c
    if v is not None:
        ok = ok & jnp.pad(jnp.asarray(v, bool), (0, cpad - c))
    gids = jnp.where(jnp.arange(cpad) < c,
                     offset + jnp.arange(cpad, dtype=jnp.int32), -1)
    cache.offer(idx, offset, c, ch, ok, gids)


# ---------------------------------------------------------------------------
# compressed chunk cache (bf16 rows + f32 row-norm sidecar, LRU-bounded)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=())
def _compress_chunk(ch, ok):
    """bf16 rows + f32 sidecars: the exact row norm and the *measured*
    compression-error norm ‖g − bf16(g)‖ (both computed against the
    pre-rounding rows — they are what make the interval bound sound AND
    tight; a worst-case 2^-8 relative margin would be ~3-4x looser)."""
    norms = jnp.sqrt(jnp.sum(ch * ch, axis=1))
    rows_bf = ch.astype(jnp.bfloat16)
    diff = ch - rows_bf.astype(jnp.float32)
    errn = jnp.sqrt(jnp.sum(diff * diff, axis=1))
    return rows_bf, jnp.where(ok, norms, 0.0), jnp.where(ok, errn, 0.0)


@jax.jit
def _arena_write(rows_a, norms_a, errn_a, gids_a, ok_a, rows_c, norms_c,
                 errn_c, gids_c, ok_c, lo):
    rows_a = lax.dynamic_update_slice(rows_a, rows_c, (lo, 0))
    norms_a = lax.dynamic_update_slice(norms_a, norms_c, (lo,))
    errn_a = lax.dynamic_update_slice(errn_a, errn_c, (lo,))
    gids_a = lax.dynamic_update_slice(gids_a, gids_c, (lo,))
    ok_a = lax.dynamic_update_slice(ok_a, ok_c, (lo,))
    return rows_a, norms_a, errn_a, gids_a, ok_a


class ChunkCache:
    """Compressed chunk cache: one flat bf16 row arena with f32 norm /
    global-id / validity sidecars, slotted per chunk, LRU-evicted to stay
    under ``cache_bytes``.

    The cache is keyed by chunk position in the (stable) iteration order
    and is safe to share across solves over the same pool (the serve
    registry admits it once and every request reuses it) — per-solve
    state (taken / in-buffer masks) lives in the solver, not here.
    """

    def __init__(self, cache_bytes: int, d: int):
        self.cache_bytes = int(cache_bytes)
        self.d = int(d)
        # bf16 row + f32 norm + f32 error norm + i32 gid + bool ok (+
        # the solver's two per-solve masks, counted so the budget is
        # honest).
        self.bytes_per_row = 2 * d + 4 + 4 + 4 + 3
        self.cap_rows_budget = max(self.cache_bytes // self.bytes_per_row, 0)
        self.slot_rows = 0            # fixed once the first chunk arrives
        self.cap_slots = 0
        self.rows = None              # (cap_rows, d) bf16
        self.norms = None             # (cap_rows,) f32 exact row norms
        self.errn = None              # (cap_rows,) f32 ‖g − bf16(g)‖
        self.gids = None              # (cap_rows,) i32
        self.ok = None                # (cap_rows,) bool
        # chunk_idx -> (slot, offset, length); insertion-recency ordered.
        self.entries: dict[int, tuple[int, int, int]] = {}
        self._lru: list[int] = []
        self.insertions = 0
        self.evictions = 0
        # Set by a full warming pass (streaming_target): the pool's total
        # chunk count.  A solver handed a cache that still covers all
        # `complete` chunks can bootstrap straight from it — zero loader
        # passes (the serve registry's admission pass is the only scan
        # the pool ever sees).
        self.complete = 0

    @property
    def cap_rows(self) -> int:
        return 0 if self.rows is None else self.rows.shape[0]

    def slot_of(self, chunk_idx: int) -> int | None:
        e = self.entries.get(chunk_idx)
        return None if e is None else e[0]

    def _touch(self, chunk_idx: int) -> None:
        self._lru.remove(chunk_idx)
        self._lru.append(chunk_idx)

    def _grow_to(self, slots: int) -> None:
        rows_new = slots * self.slot_rows
        pad = rows_new - self.cap_rows
        if pad <= 0:
            return
        if self.rows is None:
            self.rows = jnp.zeros((rows_new, self.d), jnp.bfloat16)
            self.norms = jnp.zeros((rows_new,), jnp.float32)
            self.errn = jnp.zeros((rows_new,), jnp.float32)
            self.gids = jnp.full((rows_new,), -1, jnp.int32)
            self.ok = jnp.zeros((rows_new,), bool)
        else:
            self.rows = jnp.pad(self.rows, ((0, pad), (0, 0)))
            self.norms = jnp.pad(self.norms, (0, pad))
            self.errn = jnp.pad(self.errn, (0, pad))
            self.gids = jnp.pad(self.gids, (0, pad), constant_values=-1)
            self.ok = jnp.pad(self.ok, (0, pad))

    def offer(self, chunk_idx: int, offset: int, length: int, ch, ok,
              gids) -> bool:
        """Present one (padded f32) chunk; returns True when its rows are
        resident after the call.  A resident chunk is only LRU-touched
        (its content is static across passes); a new chunk is compressed
        and written, evicting least-recently-offered chunks if needed.
        """
        ent = self.entries.get(chunk_idx)
        if ent is not None:
            if ent[1] != offset or ent[2] != length:
                raise RuntimeError(
                    "pool iterator unstable: chunk %d moved from offset %d"
                    " (len %d) to offset %d (len %d)"
                    % (chunk_idx, ent[1], ent[2], offset, length))
            self._touch(chunk_idx)
            return True
        cpad = ch.shape[0]
        if self.slot_rows == 0:
            self.slot_rows = cpad
            self.cap_slots = self.cap_rows_budget // max(self.slot_rows, 1)
        if cpad > self.slot_rows or self.cap_slots == 0:
            return False              # uncacheable under this budget
        if len(self.entries) < self.cap_slots:
            slot = len(self.entries)
            want = min(self.cap_slots,
                       max(2 * max(len(self.entries), 1), slot + 1))
            self._grow_to(want)
        else:
            victim = self._lru.pop(0)
            slot, _, _ = self.entries.pop(victim)
            self.evictions += 1
        if cpad < self.slot_rows:
            ch = jnp.pad(ch, ((0, self.slot_rows - cpad), (0, 0)))
            ok = jnp.pad(ok, (0, self.slot_rows - cpad))
            gids = jnp.pad(gids, (0, self.slot_rows - cpad),
                           constant_values=-1)
        rows_c, norms_c, errn_c = _compress_chunk(ch, ok)
        lo = jnp.int32(slot * self.slot_rows)
        self.rows, self.norms, self.errn, self.gids, self.ok = _arena_write(
            self.rows, self.norms, self.errn, self.gids, self.ok, rows_c,
            norms_c, errn_c, gids, ok, lo)
        self.entries[chunk_idx] = (slot, offset, length)
        self._lru.append(chunk_idx)
        self.insertions += 1
        return True

    def covers(self, num_chunks: int) -> bool:
        return len(self.entries) == num_chunks and num_chunks > 0

    def quarantine(self, pos) -> None:
        """Mask arena rows out of every certification scan (the engine's
        fail-closed corruption response — see DESIGN.md §8).  Positions at
        or past ``cap_rows`` scatter-drop.  The mask persists for the
        cache's lifetime: a shared serve cache keeps refusing rows whose
        backing data went bad, across requests."""
        if self.ok is None:
            return
        p = jnp.asarray(np.asarray(pos, np.int64), jnp.int32)
        self.ok = self.ok.at[p].set(False, mode="drop")

    def state_dict(self) -> dict:
        """Checkpointable snapshot (streaming checkpoint/resume).  The
        entry table is stored in LRU order so a restore reproduces the
        eviction behavior — and therefore the solve — exactly."""
        st = {"cache_bytes": np.int64(self.cache_bytes),
              "d": np.int64(self.d),
              "slot_rows": np.int64(self.slot_rows),
              "cap_slots": np.int64(self.cap_slots),
              "complete": np.int64(self.complete),
              "insertions": np.int64(self.insertions),
              "evictions": np.int64(self.evictions),
              "ent_cidx": np.asarray(self._lru, np.int64),
              "ent_slot": np.asarray(
                  [self.entries[c][0] for c in self._lru], np.int64),
              "ent_off": np.asarray(
                  [self.entries[c][1] for c in self._lru], np.int64),
              "ent_len": np.asarray(
                  [self.entries[c][2] for c in self._lru], np.int64)}
        if self.rows is not None:
            st.update(rows=self.rows, norms=self.norms, errn=self.errn,
                      gids=self.gids, ok=self.ok)
        return st

    def load_state(self, st: dict) -> None:
        if int(st["d"]) != self.d:
            raise ValueError(
                f"cache checkpoint is for d={int(st['d'])}, "
                f"this cache has d={self.d}")
        self.cache_bytes = int(st["cache_bytes"])
        self.cap_rows_budget = max(self.cache_bytes // self.bytes_per_row,
                                   0)
        self.slot_rows = int(st["slot_rows"])
        self.cap_slots = int(st["cap_slots"])
        self.complete = int(st["complete"])
        self.insertions = int(st["insertions"])
        self.evictions = int(st["evictions"])
        self.entries = {}
        self._lru = []
        for c, s, o, ln in zip(np.asarray(st["ent_cidx"]).tolist(),
                               np.asarray(st["ent_slot"]).tolist(),
                               np.asarray(st["ent_off"]).tolist(),
                               np.asarray(st["ent_len"]).tolist()):
            self.entries[int(c)] = (int(s), int(o), int(ln))
            self._lru.append(int(c))
        if "rows" in st:
            self.rows = jnp.asarray(st["rows"])
            self.norms = jnp.asarray(st["norms"])
            self.errn = jnp.asarray(st["errn"])
            self.gids = jnp.asarray(st["gids"])
            self.ok = jnp.asarray(st["ok"])
        else:
            self.rows = self.norms = self.errn = None
            self.gids = self.ok = None

    def stats(self) -> dict:
        return {"resident_chunks": len(self.entries),
                "cap_slots": self.cap_slots,
                "slot_rows": self.slot_rows,
                "insertions": self.insertions,
                "evictions": self.evictions,
                "resident_bytes": self.cap_rows * self.bytes_per_row}


# ---------------------------------------------------------------------------
# jitted pieces (module-level so the jit cache persists across calls)
# ---------------------------------------------------------------------------

def _score_chunk_impl(chunk, pool_ok, gids, offset, residual, sel_idx,
                      sel_mask, m: int, absolute: bool,
                      need_norms: bool = True):
    """Top-``m`` of one chunk against the carried residual.

    Returns (vals (m,), ids (m,), rows (m, d), ok (m,), cmax (), cthresh ())
    where ``cthresh`` upper-bounds the pass-score of every row this chunk
    *dropped* (−inf when nothing real could have been dropped) and ``cmax``
    is the max valid row norm — both feed the certification sketch.  Norms
    are only reduced on a chunk's first pass (``need_norms=False`` returns
    0 — the pool is static across passes, so the per-chunk norm bound is
    frozen then).
    """
    c = chunk.shape[0]
    scores = ops.corr(chunk, residual)                       # (c,)
    s = jnp.abs(scores) if absolute else scores
    # Chunk rows cover the contiguous id range [offset, offset+c), so the
    # taken mask is an O(k) scatter, not an O(c*k) compare.  Slots owned by
    # other chunks (or unused) point at the out-of-bounds sentinel c and
    # are dropped — an in-bounds sentinel would race duplicate writes.
    local = sel_idx - offset
    inb = sel_mask & (local >= 0) & (local < c)
    taken = jnp.zeros((c,), bool).at[
        jnp.where(inb, local, c)].set(inb, mode="drop")
    avail = pool_ok & ~taken
    s_sel = jnp.where(avail, s, _NEG_INF)
    vals, pos = lax.top_k(s_sel, m)                          # ties: low pos
    if need_norms:
        norms = jnp.sqrt(jnp.sum(chunk * chunk, axis=1))
        cmax = jnp.max(jnp.where(pool_ok, norms, 0.0))
    else:
        cmax = jnp.float32(0.0)
    cthresh = vals[m - 1] if chunk.shape[0] > m else _NEG_INF
    return vals, gids[pos], chunk[pos], pool_ok[pos], cmax, cthresh


_score_chunk = functools.partial(
    jax.jit, static_argnames=("m", "absolute", "need_norms"))(
        _score_chunk_impl)


@functools.partial(jax.jit, static_argnames=("size",))
def _merge_topm(bv, bi, br, bok, cv, ci, cr, cok, size: int):
    """Merge two candidate buffers, keep top-``size`` by (score desc, id asc).

    The explicit lexicographic order (padding ids last) is what makes the
    buffer argmax reproduce ``jnp.argmax`` lowest-index tie-breaking
    globally.
    """
    vals = jnp.concatenate([bv, cv])
    ids = jnp.concatenate([bi, ci])
    rows = jnp.concatenate([br, cr])
    ok = jnp.concatenate([bok, cok])
    id_order = jnp.where(ids >= 0, ids, _BIG_ID)
    order = jnp.lexsort((id_order, -vals))[:size]
    return vals[order], ids[order], rows[order], ok[order]


def _buffer_scores_argmax(buf_rows, buf_ids, buf_dead, residual,
                          absolute: bool):
    """Score-and-argmax over the buffer (current residual), one matvec.

    ``buf_dead`` marks slots that can never win — invalid rows, pads and
    already-picked rows (the commit loop folds each pick in directly, so
    no per-round (slots, k) selection compare is paid).  The buffer is
    ordered by *pass-scan* score, so a positional argmax tie-break is
    not lowest-global-id under a drifted residual; ties are broken by id
    explicitly to match ``jnp.argmax`` over the full pool (the
    all-masked degenerate resolves to the lowest id too, mirroring the
    in-memory argmax-of-all--inf picking index 0).  Per-row scores are
    the same f32 dot the in-memory solver's ``ops.corr`` computes, so
    the value parity the certification compares against is exact.
    """
    s = ops.corr(buf_rows, residual)
    s = jnp.abs(s) if absolute else s
    s_m = jnp.where(buf_dead, _NEG_INF, s)
    maxv = jnp.max(s_m)
    cand = jnp.where(s_m == maxv,
                     jnp.where(buf_ids >= 0, buf_ids, _BIG_ID), _BIG_ID)
    pos = jnp.argmin(cand)
    return pos, buf_ids[pos], maxv


def _sketch_bound(residual, r0, chunk_thresh, chunk_norm, chunk_cached,
                  absolute: bool):
    """Max possible drifted-residual score of any out-of-buffer row of an
    *uncached* chunk: the residual-projection bound of the module
    docstring, NaN-safe at T_c = -inf (empty tail) and inflated past f32
    reassociation noise (certifying on noise would break parity; failing
    closed into the next rung is exact)."""
    r0n2 = jnp.sum(r0 * r0)
    r0n = jnp.sqrt(r0n2)
    alpha = (jnp.dot(residual, r0, precision=PRECISION)
             / jnp.maximum(r0n2, 1e-30))
    rperp = residual - alpha * r0
    rpn = jnp.sqrt(jnp.sum(rperp * rperp))
    fin = jnp.isfinite(chunk_thresh)
    t_safe = jnp.where(fin, chunk_thresh, 0.0)
    if absolute:
        proj = jnp.abs(alpha) * t_safe
    else:
        proj = jnp.where(alpha >= 0, alpha * t_safe,
                         -alpha * chunk_norm * r0n)
    bound = jnp.where(fin, proj + chunk_norm * rpn, _NEG_INF)
    # f32-noise inflation (fail closed); -inf stays -inf, not NaN.
    bound = jnp.where(fin, bound + 1e-6 * jnp.abs(bound) + 1e-30, bound)
    return jnp.max(jnp.where(chunk_cached, _NEG_INF, bound))


@functools.partial(jax.jit, static_argnames=("fmax",))
def _admit_fetched(buf_rows, buf_ids, buf_dead, new_rows, new_ids,
                   new_ok, cursor, ar_inbuf, new_pos, *, fmax: int):
    """Write up to ``fmax`` fetched exact rows into the repair annex at
    ``cursor`` and mark their arena slots in-buffer.  Slot positions past
    the annex (or dead entries, id -1) scatter-drop."""
    live = new_ids >= 0
    slots = jnp.where(live, cursor + jnp.cumsum(live) - 1,
                      buf_ids.shape[0])
    buf_rows = buf_rows.at[slots].set(new_rows, mode="drop")
    buf_ids = buf_ids.at[slots].set(new_ids, mode="drop")
    buf_dead = buf_dead.at[slots].set(~new_ok, mode="drop")
    ar_inbuf = ar_inbuf.at[new_pos].set(live, mode="drop")
    return buf_rows, buf_ids, buf_dead, ar_inbuf


@functools.partial(jax.jit, static_argnames=("absolute", "cand_cap", "m"))
def _arena_refresh_scan(ar_rows, ar_norms, ar_errn, ar_gids, ar_ok,
                        ar_taken, ar_inbuf, buf_rows, buf_ids, buf_dead,
                        residual, acc, *,
                        absolute: bool, cand_cap: int, m: int):
    """Cache-served refill, phase 1: interval-scan the arena and return
    every *new* row that could belong to the exact top-``m`` of the pool
    under the current residual.

    ``cutoff`` is the ``m``-th largest *lower* bound over (out-of-buffer
    arena rows, exact current-buffer scores); any out-of-buffer row
    whose *upper* bound clears it is a candidate.  Rows already in the
    buffer/annex are excluded — their exact rows are on hand and merge
    back via their exact scores, so only the genuine newcomers (usually
    a few dozen) pay a fetch.  Rows below the cutoff provably score
    below all ``m`` eventual buffer members, so the merged result
    reproduces the loader pass's top-``m`` bit-exactly.
    """
    rnorm = jnp.sqrt(jnp.sum(residual * residual))
    s = ops.corr(ar_rows.astype(jnp.float32), residual)
    s = jnp.abs(s) if absolute else s
    pad = (ar_errn + acc * ar_norms) * rnorm
    u = s + pad
    l = s - pad
    avail = ar_ok & ~ar_taken & ~ar_inbuf
    sb = ops.corr(buf_rows, residual)
    sb = jnp.abs(sb) if absolute else sb
    avail_b = ~buf_dead & (buf_ids >= 0)
    l_all = jnp.concatenate([jnp.where(avail, l, _NEG_INF),
                             jnp.where(avail_b, sb, _NEG_INF)])
    cutoff = lax.top_k(l_all, m)[0][m - 1]
    cand = avail & (u >= cutoff)
    vals, pos = lax.top_k(jnp.where(cand, u, _NEG_INF), cand_cap)
    pos = pos.astype(jnp.int32)
    live = vals > _NEG_INF
    return (jnp.where(live, ar_gids[pos], -1),
            jnp.where(live, pos, ar_rows.shape[0]),
            jnp.sum(cand), jnp.sum(avail) + jnp.sum(avail_b))


@functools.partial(jax.jit, static_argnames=("absolute", "m"))
def _refresh_merge(f_rows, f_ids, f_ok, buf_rows, buf_ids, buf_dead,
                   residual, ar_inbuf, chunk_off,
                   slot_lo, *, absolute: bool, m: int):
    """Cache-served refill, phase 2: exact-score the fetched candidates
    plus the surviving buffer rows and keep the top-``m`` by (score desc,
    id asc) — the identical ordering a loader pass's merge produces.
    Also rebuilds the arena in-buffer mask from the merged ids via the
    device-side chunk map (no host round-trip per refill)."""
    sf = ops.corr(f_rows, residual)
    sf = jnp.abs(sf) if absolute else sf
    vf = jnp.where(f_ok & (f_ids >= 0), sf, _NEG_INF)
    sb = ops.corr(buf_rows, residual)
    sb = jnp.abs(sb) if absolute else sb
    avail_b = ~buf_dead & (buf_ids >= 0)
    vb = jnp.where(avail_b, sb, _NEG_INF)
    mv, mi, mr, mok = _merge_topm(vb, buf_ids, buf_rows, avail_b, vf,
                                  f_ids, f_rows, f_ok, size=m)
    nc = chunk_off.shape[0]
    cap = ar_inbuf.shape[0]
    j = jnp.clip(jnp.searchsorted(chunk_off, mi, side="right") - 1, 0,
                 nc - 1)
    pos = slot_lo[j] + mi - chunk_off[j]
    pos = jnp.where((mi >= 0) & (slot_lo[j] >= 0), pos, jnp.int32(cap))
    inbuf = jnp.zeros_like(ar_inbuf).at[pos].set(True, mode="drop")
    return mv, mi, mr, mv == _NEG_INF, inbuf


@jax.jit
def _scatter_mask(mask, pos):
    return mask.at[pos].set(True, mode="drop")


@jax.jit
def _verify_norms(ch, ok, ref):
    """Per-row corruption check of a re-read chunk against the cache's
    f32 exact-norm sidecar (recorded at first contact).  The tolerance
    covers f32 reassociation between the two norm computations; real
    corruption (a flipped exponent/sign-magnitude bit, truncation) moves
    the norm orders of magnitude past it.  A norm-preserving corruption
    (pure sign flips) is not detectable this way — DESIGN.md §8 scopes
    the fault model."""
    nn = jnp.where(ok, jnp.sqrt(jnp.sum(ch * ch, axis=1)), 0.0)
    return ok & (jnp.abs(nn - ref) > 1e-4 * (ref + 1e-6))


@functools.partial(
    jax.jit, static_argnames=("p", "nnls_iters", "absolute", "has_arena",
                              "fmax"))
def _commit_rounds(buf_rows, buf_ids, buf_dead, indices, mask, weights,
                   rows, gram, absrow, tcorr, target, residual, err,
                   lam, r0, chunk_thresh, chunk_norm, chunk_cached,
                   ar_rows, ar_norms, ar_errn, ar_gids, ar_ok, ar_inbuf,
                   ar_taken, chunk_off, slot_lo, t0, t_hi, t_first, eps,
                   acc, *, p: int, nnls_iters: int, absolute: bool,
                   has_arena: bool, fmax: int):
    """Commit as many certified OMP rounds against the buffer as the
    bounds allow, entirely on device — the lookahead core of the
    multi-round-per-pass engine.  No per-round host dispatch: the
    incremental-Gram update runs in-place inside the while_loop (same
    flops as the in-memory solver's round body), the sketch rung is
    O(C), and the cache-arena interval rung is an in-memory matvec whose
    bf16->f32 operand conversion is loop-invariant (XLA hoists it, so
    each round pays an f32-speed scan).

    Round ``t_first`` (the one right after a buffer refresh, -1 for
    none) is exact by construction and bypasses certification.  The loop
    stops at ``t_hi`` (the next prefix-block boundary), at the eps-stop,
    or at the first round the bounds cannot certify; the failing round's
    (maxv, sketch, u_max, #offenders) plus the top-``fmax`` offender
    (gid, arena row) pairs land in the result so the host can run the
    repair tier without re-scanning.
    """
    use_ref = ops.active_mode() == "ref"
    if has_arena:
        # Ref/CPU path: hoist the bf16->f32 conversion out of the loop
        # (one resident f32 copy, f32-speed scans every round).  On the
        # fused-kernel path the copy would defeat the kernel's whole
        # point (u and the converted rows never touching HBM), so no
        # persistent conversion is made there.
        arf = ar_rows.astype(jnp.float32) if use_ref else None
        cap = ar_rows.shape[0]
        nc = chunk_off.shape[0]

    def pick_pos(e):
        """Arena slot of global id ``e`` (device-side chunk map);
        sentinel ``cap`` (dropped by the scatter) when uncached."""
        j = jnp.clip(jnp.searchsorted(chunk_off, e, side="right") - 1,
                     0, nc - 1)
        pos = slot_lo[j] + e - chunk_off[j]
        return jnp.where((e >= 0) & (slot_lo[j] >= 0), pos,
                         jnp.int32(cap))

    def cond(c):
        t, go = c[0], c[1]
        return go & (t < t_hi) & (c[10] > eps)

    def body(c):
        (t, go, indices, mask, weights, rows, gram, absrow, tcorr,
         residual, err, ar_taken, bdead, diag, off) = c
        pos, e, maxv = _buffer_scores_argmax(buf_rows, buf_ids, bdead,
                                             residual, absolute)
        sk = _sketch_bound(residual, r0, chunk_thresh, chunk_norm,
                           chunk_cached, absolute)
        sketch_ok = maxv > sk
        if has_arena:
            avail_a = ar_ok & ~ar_taken & ~ar_inbuf
            # The interval scan is only consulted when the sketch rung
            # passed — on fully-cached pools the sketch is -inf and the
            # scan runs every round; on structured pools the sketch
            # often settles it alone.  On TPU the fused ``bound_max``
            # kernel consumes the cache directly (one streaming pass, u
            # never hits HBM); the ref path passes arf pre-converted so
            # the bf16->f32 cast stays loop-invariant.
            def scan(_):
                if not use_ref:
                    u_max, _, n_off = ops.bound_max(
                        ar_rows, ar_norms, ar_errn, residual, acc,
                        maxv, avail_a, absolute=absolute)
                    return u_max, n_off
                rnorm = jnp.sqrt(jnp.sum(residual * residual))
                s = jnp.dot(arf, residual, precision=PRECISION)
                s = jnp.abs(s) if absolute else s
                u = s + (ar_errn + acc * ar_norms) * rnorm
                u_m = jnp.where(avail_a, u, _NEG_INF)
                return jnp.max(u_m), jnp.sum(avail_a & (u_m >= maxv))

            u_max, n_off = lax.cond(
                sketch_ok, scan,
                lambda _: (_NEG_INF, jnp.int32(0)), operand=None)
        else:
            u_max, n_off = _NEG_INF, jnp.int32(0)
        cert = (sketch_ok & (maxv > u_max) & jnp.isfinite(maxv)
                ) | (t == t_first)
        diag = (maxv, sk, u_max, n_off)

        def commit(_):
            g_e = buf_rows[pos]
            ind = indices.at[t].set(e)
            msk = mask.at[t].set(True)
            rws = rows.at[t].set(g_e)
            mask_p = msk[:p]
            row_vals = jnp.where(
                mask_p, jnp.dot(rws[:p], g_e, precision=PRECISION), 0.0)
            grm = gram.at[t, :p].set(row_vals).at[:p, t].set(row_vals)
            ar = jnp.where(mask_p, absrow[:p] + jnp.abs(row_vals), 0.0)
            ar = ar.at[t].set(jnp.sum(jnp.abs(row_vals)))
            arow = absrow.at[:p].set(ar)
            tc = tcorr.at[t].set(jnp.dot(g_e, target, precision=PRECISION))
            w_p = _nnls_active_cached(grm[:p, :p], arow[:p], rws[:p],
                                      tc[:p], mask_p, lam, nnls_iters)
            w = jnp.zeros_like(weights).at[:p].set(w_p)
            resid = target - jnp.dot(w_p, rws[:p], precision=PRECISION)
            er = jnp.sum(resid**2) + lam * jnp.sum(w_p**2)
            tk = (ar_taken.at[pick_pos(e)].set(True, mode="drop")
                  if has_arena else ar_taken)
            bd = bdead.at[pos].set(True)
            return (t + 1, jnp.bool_(True), ind, msk, w, rws, grm, arow,
                    tc, resid, er, tk, bd, diag, off)

        def stop(_):
            # Runs once, at the exit round: hand the host the repair
            # tier's worklist (the offending rows' ids/slots by upper
            # bound) so it never re-scans the arena.
            if has_arena and fmax > 0:
                rnorm = jnp.sqrt(jnp.sum(residual * residual))
                # Runs once per loop exit: a transient conversion here is
                # fine on the fused-kernel path (no persistent f32 copy).
                rows_f = arf if use_ref else ar_rows.astype(jnp.float32)
                s = jnp.dot(rows_f, residual, precision=PRECISION)
                s = jnp.abs(s) if absolute else s
                u = s + (ar_errn + acc * ar_norms) * rnorm
                u_m = jnp.where(avail_a, u, _NEG_INF)
                vals, opos = lax.top_k(u_m, fmax)
                opos = opos.astype(jnp.int32)
                live = vals > _NEG_INF
                off_out = (jnp.where(live, ar_gids[opos], -1),
                           jnp.where(live, opos,
                                     jnp.int32(ar_rows.shape[0])))
            else:
                off_out = off
            return (t, jnp.bool_(False), indices, mask, weights, rows,
                    gram, absrow, tcorr, residual, err, ar_taken, bdead,
                    diag, off_out)

        return lax.cond(cert, commit, stop, operand=None)

    diag0 = (_NEG_INF, _NEG_INF, _NEG_INF, jnp.int32(0))
    off0 = (jnp.full((max(fmax, 1),), -1, jnp.int32),
            jnp.full((max(fmax, 1),), ar_rows.shape[0], jnp.int32))
    init = (t0, jnp.bool_(True), indices, mask, weights, rows, gram,
            absrow, tcorr, residual, err, ar_taken, buf_dead, diag0,
            off0)
    return lax.while_loop(cond, body, init)


# ---------------------------------------------------------------------------
# the streaming solver
# ---------------------------------------------------------------------------

@dataclass
class SelectStats:
    """Pass/round/cache accounting for benchmarks, the harness tests and
    the ``max_passes`` diagnostics."""
    passes: int = 0             # full loader scans
    rounds: int = 0
    certified_rounds: int = 0   # rounds committed without loader traffic
    chunks: int = 0
    pool_size: int = 0
    refills: int = 0            # buffer refreshes served from the cache
    repairs: int = 0            # bounded exact-row repair events
    fetched_rows: int = 0       # exact rows fetched by id (repair+refill)
    cache_hits: int = 0         # certification chunk lookups in the arena
    cache_misses: int = 0       # ... that had to use the sketch bound
    retries: int = 0            # transient faults retried (chunks + rows)
    quarantined: int = 0        # rows masked out after persistent
                                # corruption (never silently selected)
    checkpoints: int = 0        # mid-solve snapshots written
    resumes: int = 0            # solves resumed from a checkpoint
    admits: int = 0             # continual: rows admitted to the buffer
    evicts: int = 0             # continual: buffer rows evicted (any tier)
    downdates: int = 0          # continual: committed rows removed via the
                                # decremental downdate path
    resolves: int = 0           # continual: fail-closed full re-solves

    @property
    def cache_hit_rate(self) -> float:
        tot = self.cache_hits + self.cache_misses
        return self.cache_hits / tot if tot else 0.0

    def summary(self) -> str:
        s = (f"passes={self.passes} rounds={self.rounds} "
             f"certified_rounds={self.certified_rounds} "
             f"refills={self.refills} repairs={self.repairs} "
             f"fetched_rows={self.fetched_rows} "
             f"cache_hit_rate={self.cache_hit_rate:.2f}")
        if self.retries or self.quarantined:
            s += (f" retries={self.retries} "
                  f"quarantined={self.quarantined}")
        if self.resumes:
            s += f" resumes={self.resumes}"
        if self.admits or self.evicts or self.downdates or self.resolves:
            s += (f" admits={self.admits} evicts={self.evicts} "
                  f"downdates={self.downdates} resolves={self.resolves}")
        return s


# Backwards-compatible alias (PR 2 name).
StreamStats = SelectStats


class StreamingPassBudgetError(RuntimeError):
    """Raised when streaming OMP exceeds its ``max_passes`` budget.

    Carries the accumulated ``SelectStats`` so the failure is diagnosable
    without re-running (is the iterator unstable?  did certification
    never fire?  was the cache thrashing?)."""

    def __init__(self, cap: int, stats: SelectStats):
        self.cap = cap
        self.stats = stats
        super().__init__(
            f"streaming OMP exceeded its pass budget (cap={cap}). "
            f"Solver state at failure: {stats.summary()}. "
            "Is the pool iterator stable across passes?  An adversarial "
            "pool that never certifies needs max_passes >= k + 2.")


class StreamingOMPResult(NamedTuple):
    indices: jax.Array   # (k,) int32, -1 on unused slots
    weights: jax.Array   # (k,) f32
    mask: jax.Array      # (k,) bool
    err: jax.Array       # () f32
    stats: SelectStats


def omp_select_streaming(
    pool_iter: Callable[[], Iterator],   # factory of (chunk, valid) iters
    target,                              # (d,) target gradient
    k: int,
    lam: float = 0.5,
    eps: float = 1e-10,
    nnls_iters: int = 50,
    positive: bool = True,
    buffer_size: int = 256,              # M — carried top-M candidate buffer
    chunk_topm: Optional[int] = None,    # m per chunk (default: M)
    block: int = 128,                    # NNLS prefix growth (parity w/ omp)
    max_passes: Optional[int] = None,
    score_chunk_fn=None,                 # hook: distributed.pmap_chunk_topm
    cache: Optional[ChunkCache] = None,  # shared compressed cache (serve)
    cache_bytes: int = DEFAULT_CACHE_BYTES,  # budget when cache is None
    row_fetch: Optional[Callable] = None,    # ids -> exact f32 rows
    repair_slots: int = 512,             # annex width for exact-row repairs
    retry: Optional[RetryPolicy] = None,     # transient-fault recovery
    checkpoint_dir: Optional[str] = None,    # mid-solve snapshots
    checkpoint_every: int = 8,           # committed rounds between saves
    resume: bool = True,                 # pick up a prior checkpoint
) -> StreamingOMPResult:
    """OMP over a chunked pool; exact parity with ``omp_select``.

    ``pool_iter()`` must yield the same chunks in the same order on every
    call (the solver rescans when certification fails).  ``score_chunk_fn``
    overrides the local chunk scorer with the same signature/returns as
    ``_score_chunk`` — ``core.distributed.pmap_chunk_topm`` scores chunks
    shard-parallel across local devices.

    ``cache``/``cache_bytes`` control the compressed chunk cache (pass
    ``cache_bytes=0`` to disable).  ``row_fetch(ids)`` is the optional
    exact-row gather capability (``array_row_fetch`` for array pools);
    without it the repair and cache-refill tiers are skipped and every
    certification failure costs a loader pass, which is still exact.

    Recovery (DESIGN.md §8): transient loader/fetch faults
    (``resilience.TransientFault``) are retried per ``retry`` (default
    ``RetryPolicy()``) at whole-pass / fetch granularity — a restarted
    pass rebuilds its accumulators from scratch, so recovery never
    changes the selection.  Re-read chunks and re-fetched rows are
    verified against the cache's f32 exact-norm sidecars; content that
    still disagrees after the retry budget is *quarantined* — masked out
    of the certificate ladder and never silently selected.  With
    ``checkpoint_dir``, the commit-loop state is snapshotted every
    ``checkpoint_every`` committed rounds via ``repro.checkpoint`` and a
    later call with the same arguments resumes bit-exactly
    (``resume=False`` ignores an existing checkpoint).
    """
    target = jnp.asarray(target, jnp.float32)
    d = target.shape[0]
    k = int(k)
    m_cfg = int(chunk_topm) if chunk_topm is not None else int(buffer_size)
    big_m = int(buffer_size)
    annex = int(repair_slots) if row_fetch is not None else 0
    fmax = min(128, annex) if annex else 0
    absolute = not positive
    scorer = score_chunk_fn if score_chunk_fn is not None else _score_chunk
    if cache is None:
        cache = ChunkCache(int(cache_bytes), d)
    if retry is None:
        retry = RetryPolicy()
    acc = jnp.float32(_acc_margin(d))

    indices = jnp.full((k,), -1, jnp.int32)
    mask = jnp.zeros((k,), bool)
    weights = jnp.zeros((k,), jnp.float32)
    rows = jnp.zeros((k, d), jnp.float32)
    gram = jnp.zeros((k, k), jnp.float32)
    absrow = jnp.zeros((k,), jnp.float32)
    tcorr = jnp.zeros((k,), jnp.float32)
    residual = target
    err = float(jnp.sum(target**2))
    lam_f = jnp.float32(lam)

    stats = SelectStats()
    cap = int(max_passes) if max_passes is not None else k + 2
    t = 0

    # Buffer (M exact rows + annex repair slots), sketch state, per-solve
    # arena masks.  All built by the first loader pass.
    bi = br = bdead = None
    annex_cursor = big_m
    r0 = None
    chunk_thresh = chunk_norm = chunk_cached = None
    chunk_norm_host: list[float] = []
    chunk_meta: list[tuple[int, int]] = []   # (offset, length) per chunk
    ar_taken = ar_inbuf = None
    num_chunks = 0
    quarantined: set[int] = set()   # global ids failed-closed (corruption)
    corrupt_seen: dict[int, int] = {}   # chunk idx -> mismatched reads
    last_ckpt = 0

    def _note_retry(attempt, exc) -> None:
        stats.retries += 1

    def arena_ready() -> bool:
        return cache.cap_rows > 0 and len(cache.entries) > 0

    def _quarantine(gids_np) -> None:
        """Fail-closed response to persistent corruption: drop the rows
        from every candidate source (arena validity, buffer liveness, and
        — via the ``quarantined`` set — future loader passes).  Rows
        already committed to the selection were read clean when picked
        and stay; quarantine governs candidacy, not history."""
        nonlocal bdead
        fresh = [int(g) for g in np.atleast_1d(np.asarray(gids_np))
                 if g >= 0 and int(g) not in quarantined]
        if not fresh:
            return
        quarantined.update(fresh)
        stats.quarantined = len(quarantined)
        if arena_ready() and chunk_meta:
            cache.quarantine(gids_to_pos(np.asarray(fresh, np.int64)))
        if bi is not None:
            hit = jnp.zeros_like(bdead)
            for g in fresh:
                hit = hit | (bi == g)
            bdead = bdead | hit

    def sync_arena_masks() -> None:
        """(Re)size the per-solve arena masks to the arena capacity."""
        nonlocal ar_taken, ar_inbuf
        cap_r = cache.cap_rows
        if ar_taken is None or ar_taken.shape[0] != cap_r:
            old_t, old_i = ar_taken, ar_inbuf
            ar_taken = jnp.zeros((cap_r,), bool)
            ar_inbuf = jnp.zeros((cap_r,), bool)
            if old_t is not None and old_t.shape[0] <= cap_r:
                pad = cap_r - old_t.shape[0]
                ar_taken = jnp.pad(old_t, (0, pad))
                ar_inbuf = jnp.pad(old_i, (0, pad))

    def rebuild_inbuf(ids) -> None:
        """Mark the (host-synced) buffer ids' arena slots in-buffer.
        Positions are sentinel-padded to a fixed width so the scatter jit
        compiles once per buffer size."""
        nonlocal ar_inbuf
        if ar_inbuf is None:
            return
        pos = gids_to_pos(np.asarray(ids, np.int64))
        ar_inbuf = _scatter_mask(jnp.zeros_like(ar_inbuf),
                                 jnp.asarray(pos))

    def loader_pass() -> bool:
        """Full loader scan: refresh buffer + cache + sketch state.
        Returns False on an empty pool.  Transient iterator faults
        restart the whole scan under the retry policy — the merge
        accumulators below are scan-local, ``chunk_meta`` appends are
        guarded, ``chunk_norm_host`` only extends after a completed scan
        and ``cache.offer`` is idempotent for resident chunks, so a
        restart recomputes the identical refresh (``stats.chunks`` may
        over-count across aborted scans; passes count completed scans)."""
        if stats.passes >= cap:
            raise StreamingPassBudgetError(cap, stats)
        return with_retries(_scan_pass, retry, on_retry=_note_retry)

    def _scan_pass() -> bool:
        nonlocal bi, br, bdead, annex_cursor, r0, chunk_thresh
        nonlocal chunk_norm, chunk_cached, num_chunks
        mv = jnp.full((big_m,), -jnp.inf, jnp.float32)
        mi = jnp.full((big_m,), -1, jnp.int32)
        mr = jnp.zeros((big_m, d), jnp.float32)
        mok = jnp.zeros((big_m,), bool)
        # Device-scalar accumulators: no host sync inside the chunk loop.
        threshs = []
        norms_new = []
        offset = 0
        cidx = 0
        first_visit = len(chunk_norm_host) == 0
        for chunk, cvalid in pool_iter():
            c = int(chunk.shape[0])
            cpad = _bucket(c)
            ch = jnp.asarray(chunk, jnp.float32)
            pos_in = jnp.arange(cpad, dtype=jnp.int32)
            if cpad != c:
                ch = jnp.pad(ch, ((0, cpad - c), (0, 0)))
            ok = pos_in < c
            if cvalid is not None:
                ok = ok & jnp.pad(jnp.asarray(cvalid, bool),
                                  (0, cpad - c))
            if quarantined:
                ql = [g - offset for g in quarantined
                      if offset <= g < offset + c]
                if ql:
                    ok = ok & ~jnp.zeros((cpad,), bool).at[
                        jnp.asarray(ql, jnp.int32)].set(True)
            gids = jnp.where(pos_in < c, offset + pos_in, -1)
            if cidx >= len(chunk_meta):
                chunk_meta.append((offset, c))
            slot = cache.slot_of(cidx)
            if slot is not None:
                # Re-read of a resident chunk: verify the content against
                # the exact-norm sidecar recorded at first contact.  A
                # mismatch is first treated as a transient misread (the
                # scan restarts); a chunk that keeps disagreeing past the
                # retry budget has its mismatching rows quarantined and
                # the scan proceeds without them.
                lo = slot * cache.slot_rows
                bad = np.asarray(_verify_norms(
                    ch, ok, cache.norms[lo:lo + cpad]))
                if bad.any():
                    seen = corrupt_seen.get(cidx, 0) + 1
                    corrupt_seen[cidx] = seen
                    if seen <= retry.max_retries:
                        raise CorruptChunkError(
                            f"chunk {cidx} disagrees with its exact-norm "
                            f"sidecar on {int(bad.sum())} row(s) "
                            f"(mismatched read {seen})")
                    _quarantine(offset + np.flatnonzero(bad))
                    ok = ok & jnp.asarray(~bad)
            m_eff = min(m_cfg, cpad, big_m)
            need_n = cidx >= len(chunk_norm_host)
            vals, ids, rws, rok, cmax, cthresh = scorer(
                ch, ok, gids, jnp.int32(offset), residual, indices, mask,
                m=m_eff, absolute=absolute, need_norms=need_n)
            mv, mi, mr, mok = _merge_topm(mv, mi, mr, mok, vals, ids, rws,
                                          rok, size=big_m)
            if need_n:
                norms_new.append(cmax)
            cache.offer(cidx, offset, c, ch, ok, gids)
            threshs.append(cthresh)
            offset += c
            cidx += 1
            stats.chunks += 1
        if offset == 0:
            return False
        stats.pool_size = offset
        if first_visit:
            num_chunks = cidx
        chunk_norm_host.extend(float(x) for x in norms_new)
        # A chunk inserted this pass may have evicted an earlier one —
        # the resident set is only final once the pass completes.
        cached_flags = [cache.slot_of(i) is not None for i in range(cidx)]
        # Rows dropped at the merge are bounded by the buffer's min value
        # (−inf while the buffer is not full, i.e. nothing real dropped).
        merge_min = mv[big_m - 1]
        chunk_thresh = jnp.maximum(jnp.stack(threshs), merge_min)
        chunk_norm = jnp.asarray(chunk_norm_host, jnp.float32)
        chunk_cached = jnp.asarray(cached_flags)
        r0 = residual
        bi = jnp.concatenate([mi, jnp.full((annex,), -1, jnp.int32)])
        br = jnp.concatenate([mr, jnp.zeros((annex, d), jnp.float32)])
        # Slots that can never win the argmax: taken/invalid rows were
        # scored -inf by the chunk scorer, pads carry -inf too; annex
        # slots start dead until a repair admits into them.
        bdead = jnp.concatenate([mv == _NEG_INF,
                                 jnp.ones((annex,), bool)])
        annex_cursor = big_m
        sync_arena_masks()
        rebuild_inbuf(mi)
        stats.passes += 1
        return True

    def cache_refill() -> bool:
        """Refresh the buffer from the arena (no loader traffic).  Only
        sound when the cache covers every chunk; returns False when the
        candidate set is empty/oversized and a loader pass is needed."""
        nonlocal bi, br, bdead, annex_cursor, r0, ar_inbuf
        if not (row_fetch is not None and cache.covers(num_chunks)
                and arena_ready()):
            return False
        # Merge deeper than M: pushing the buffer boundary well below the
        # decaying in-buffer max keeps the endgame rounds (where score
        # spacing shrinks under the interval width) free of offender
        # churn, while two repair batches' worth of annex stays free.
        deep = big_m + max(annex - 2 * fmax, 0)
        cand_cap = min(_bucket(min(4 * big_m, cache.cap_rows)),
                       cache.cap_rows)
        gids, pos, n_cand, n_avail = _arena_refresh_scan(
            cache.rows, cache.norms, cache.errn, cache.gids, cache.ok,
            ar_taken, ar_inbuf, br, bi, bdead, residual,
            acc, absolute=absolute, cand_cap=cand_cap, m=deep)
        n_cand = int(n_cand)
        if n_cand == 0 or n_cand > cand_cap or int(n_avail) == 0:
            return False
        # fb >= n_cand always (n_cand <= cand_cap), but the bucket can
        # round past gids' length when cap_rows is not a power of two.
        fb = min(_bucket(max(n_cand, 1)), cand_cap)
        ids_np = np.asarray(gids[:fb])
        fetched, live = checked_fetch(ids_np, np.asarray(pos[:fb]))
        f_ids = jnp.asarray(np.where(live, ids_np, -1))
        mv, mi, mr, mdead, inbuf_new = _refresh_merge(
            jnp.asarray(fetched), f_ids, f_ids >= 0, br, bi, bdead,
            residual, ar_inbuf, chunk_off_d, slot_lo_d,
            absolute=absolute, m=deep)
        # Outside rows now provably score below the new buffer minimum
        # (they sat under the refill cutoff); the sketch rung is moot
        # while coverage is complete, so only r0 needs refreshing.
        r0 = residual
        pad = big_m + annex - deep
        bi = jnp.concatenate([mi, jnp.full((pad,), -1, jnp.int32)])
        br = jnp.concatenate([mr, jnp.zeros((pad, d), jnp.float32)])
        bdead = jnp.concatenate([mdead, jnp.ones((pad,), bool)])
        annex_cursor = deep
        ar_inbuf = inbuf_new
        stats.refills += 1
        stats.fetched_rows += int(live.sum())
        return True

    chunk_off_d = slot_lo_d = None    # device-side chunk map (pick_pos)

    def checked_fetch(ids_np, pos_np):
        """Exact-row fetch with transient retry + corruption detection.

        Fetched rows whose arena position holds an f32 exact-norm sidecar
        must reproduce it (the sidecar was computed from the row at first
        contact; the fetch contract is byte-identical f32 rows).  Rows
        that disagree are re-fetched under the retry budget; persistent
        disagreement quarantines them — returned ``live`` drops them, so
        a corrupted row is never admitted to the buffer.  Entries with
        id -1 are dead padding and fetch nothing.
        """
        ids_np = np.asarray(ids_np, np.int64)
        pos_np = np.asarray(pos_np, np.int64)
        live = ids_np >= 0
        out = np.zeros((len(ids_np), d), np.float32)
        if not live.any():
            return out, live
        todo = live.copy()
        misreads = 0
        while True:
            sel = np.flatnonzero(todo)
            rows_f = with_retries(
                lambda: np.asarray(row_fetch(ids_np[sel]), np.float32),
                retry, on_retry=_note_retry)
            out[sel] = rows_f
            if not arena_ready():
                break
            have = pos_np[sel] < cache.cap_rows
            if not have.any():
                break
            ref = np.asarray(cache.norms[jnp.asarray(
                np.clip(pos_np[sel], 0, cache.cap_rows - 1), jnp.int32)])
            r64 = rows_f.astype(np.float64)
            nf = np.sqrt(np.einsum("ij,ij->i", r64, r64))
            bad = have & (np.abs(nf - ref) > 1e-4 * (ref + 1e-6))
            if not bad.any():
                break
            misreads += 1
            if misreads > retry.max_retries:
                _quarantine(ids_np[sel[bad]])
                live[sel[bad]] = False
                out[sel[bad]] = 0.0
                break
            _note_retry(misreads, None)
            retry.sleep(retry.delay(misreads - 1))
            todo = np.zeros_like(todo)
            todo[sel[bad]] = True
        return out, live

    def gids_to_pos(ids_np: np.ndarray) -> np.ndarray:
        """Vectorized host map: global ids -> arena rows (sentinel
        ``cap_rows`` for dead ids / uncached chunks)."""
        offs = np.asarray([m[0] for m in chunk_meta], np.int64)
        slo = np.full((len(chunk_meta),), -1, np.int64)
        for cidx, (slot, _, _) in cache.entries.items():
            if cidx < len(slo):
                slo[cidx] = slot * cache.slot_rows
        j = np.clip(np.searchsorted(offs, ids_np, side="right") - 1, 0,
                    len(offs) - 1)
        pos = slo[j] + ids_np - offs[j]
        return np.where((ids_np >= 0) & (slo[j] >= 0), pos,
                        cache.cap_rows).astype(np.int32)

    def rebuild_taken() -> None:
        """Rebuild the arena taken-mask from the committed selection —
        one sentinel-padded scatter.  Needed after loader passes (slot
        assignments may change); between them the device commit loop
        maintains the mask itself."""
        nonlocal ar_taken
        sync_arena_masks()
        sel_np = np.asarray(indices)
        msk_np = np.asarray(mask)
        pos = np.where(msk_np, gids_to_pos(sel_np), cache.cap_rows)
        ar_taken = _scatter_mask(jnp.zeros_like(ar_taken),
                                 jnp.asarray(pos.astype(np.int32)))

    def rebuild_chunk_map() -> None:
        """Device copy of the chunk->arena-slot map the commit loop uses
        to fold its own picks into the taken mask."""
        nonlocal chunk_off_d, slot_lo_d
        off = np.asarray([m[0] for m in chunk_meta] or [0], np.int32)
        slo = np.full((max(num_chunks, 1),), -1, np.int32)
        for cidx, (slot, _, _) in cache.entries.items():
            if cidx < len(slo):
                slo[cidx] = slot * cache.slot_rows
        chunk_off_d = jnp.asarray(off)
        slot_lo_d = jnp.asarray(slo)

    def _capture_tree() -> dict:
        """Snapshot everything the commit loop needs to resume bit-exactly:
        solver prefix state (Gram/NNLS buffers, residual), the candidate
        buffer + annex, sketch state, the compressed-cache manifest and
        arena, per-solve arena masks, host bookkeeping and stats."""
        tree = {
            "cfg": {"k": np.int64(k), "d": np.int64(d),
                    "big_m": np.int64(big_m), "annex": np.int64(annex),
                    "block": np.int64(block),
                    "absolute": np.int64(absolute),
                    "nnls_iters": np.int64(nnls_iters),
                    "lam": np.float64(lam), "eps": np.float64(eps)},
            "solver": {"t": np.int64(t), "err": np.float64(err),
                       "t_first": np.int64(t_first),
                       "need_refresh": np.int64(need_refresh),
                       "annex_cursor": np.int64(annex_cursor),
                       "num_chunks": np.int64(num_chunks),
                       "indices": indices, "mask": mask,
                       "weights": weights, "rows": rows, "gram": gram,
                       "absrow": absrow, "tcorr": tcorr,
                       "residual": residual, "r0": r0,
                       "bi": bi, "br": br, "bdead": bdead,
                       "chunk_thresh": chunk_thresh,
                       "chunk_norm": chunk_norm,
                       "chunk_cached": chunk_cached},
            "host": {"chunk_off": np.asarray(
                         [mm[0] for mm in chunk_meta], np.int64),
                     "chunk_len": np.asarray(
                         [mm[1] for mm in chunk_meta], np.int64),
                     "chunk_norm_host": np.asarray(chunk_norm_host,
                                                   np.float64),
                     "quarantined": np.asarray(sorted(quarantined),
                                               np.int64)},
            "stats": {kk: np.int64(vv) for kk, vv in vars(stats).items()},
            "arena": cache.state_dict(),
        }
        if ar_taken is not None:
            tree["masks"] = {"ar_taken": ar_taken, "ar_inbuf": ar_inbuf}
        return tree

    need_refresh = True
    t_first = -1
    resumed = False
    if checkpoint_dir is not None and resume:
        _tree = load_solver_state(checkpoint_dir)
        if _tree is not None:
            cfg = _tree["cfg"]
            want = {"k": k, "d": d, "big_m": big_m, "annex": annex,
                    "block": int(block), "absolute": int(absolute),
                    "nnls_iters": int(nnls_iters)}
            got = {kk: int(cfg[kk]) for kk in want}
            if (got != want or float(cfg["lam"]) != float(lam)
                    or float(cfg["eps"]) != float(eps)):
                raise ValueError(
                    f"checkpoint under {checkpoint_dir!r} was written by "
                    f"an incompatible solve (saved {got}, this solve "
                    f"{want}) — pass resume=False or a fresh "
                    "checkpoint_dir")
            sol = _tree["solver"]
            t = int(sol["t"])
            err = float(sol["err"])
            t_first = int(sol["t_first"])
            need_refresh = bool(int(sol["need_refresh"]))
            annex_cursor = int(sol["annex_cursor"])
            num_chunks = int(sol["num_chunks"])
            indices = jnp.asarray(sol["indices"])
            mask = jnp.asarray(sol["mask"])
            weights = jnp.asarray(sol["weights"])
            rows = jnp.asarray(sol["rows"])
            gram = jnp.asarray(sol["gram"])
            absrow = jnp.asarray(sol["absrow"])
            tcorr = jnp.asarray(sol["tcorr"])
            residual = jnp.asarray(sol["residual"])
            r0 = jnp.asarray(sol["r0"])
            bi = jnp.asarray(sol["bi"])
            br = jnp.asarray(sol["br"])
            bdead = jnp.asarray(sol["bdead"])
            chunk_thresh = jnp.asarray(sol["chunk_thresh"])
            chunk_norm = jnp.asarray(sol["chunk_norm"])
            chunk_cached = jnp.asarray(sol["chunk_cached"])
            host = _tree["host"]
            chunk_meta.extend(
                zip(np.asarray(host["chunk_off"]).tolist(),
                    np.asarray(host["chunk_len"]).tolist()))
            chunk_norm_host.extend(
                float(x) for x in np.asarray(host["chunk_norm_host"]))
            quarantined.update(
                int(x) for x in np.asarray(host["quarantined"]))
            for kk, vv in _tree["stats"].items():
                setattr(stats, kk, int(vv))
            cache.load_state(_tree["arena"])
            masks_t = _tree.get("masks")
            if masks_t is not None:
                ar_taken = jnp.asarray(masks_t["ar_taken"])
                ar_inbuf = jnp.asarray(masks_t["ar_inbuf"])
            rebuild_chunk_map()
            stats.resumes += 1
            last_ckpt = t
            resumed = True

    if (not resumed and cache.complete > 0 and cache.covers(cache.complete)
            and row_fetch is not None):
        # Bootstrap from a pre-warmed cache (serve admission already paid
        # the summing pass and filled it): the first buffer refresh is a
        # cache refill, so this solve touches the loader zero times.
        num_chunks = cache.complete
        metas = sorted((cidx, off, ln) for cidx, (slot, off, ln)
                       in cache.entries.items())
        chunk_meta.extend((off, ln) for _, off, ln in metas)
        stats.pool_size = sum(ln for _, _, ln in metas)
        chunk_thresh = jnp.zeros((num_chunks,), jnp.float32)  # all cached:
        chunk_norm = jnp.zeros((num_chunks,), jnp.float32)    # sketch moot
        chunk_cached = jnp.ones((num_chunks,), bool)
        r0 = target
        bi = jnp.full((big_m + annex,), -1, jnp.int32)
        br = jnp.zeros((big_m + annex, d), jnp.float32)
        bdead = jnp.ones((big_m + annex,), bool)
        annex_cursor = big_m + annex
        sync_arena_masks()
        rebuild_chunk_map()

    while t < k and err > eps:
        if need_refresh:
            if not cache_refill():
                if not loader_pass():
                    break
                rebuild_taken()
                rebuild_chunk_map()
            need_refresh = False
            t_first = t
        p = min(k, block * (t // block + 1))
        has_arena = arena_ready()
        fm = min(fmax, cache.cap_rows) if has_arena else 0
        dummy = jnp.zeros((1,), jnp.int32)
        (t_new, go, indices, mask, weights, rows, gram, absrow, tcorr,
         residual, err_d, ar_taken_new, bdead, diag,
         offs) = _commit_rounds(
            br, bi, bdead, indices, mask, weights, rows, gram, absrow,
            tcorr, target, residual, jnp.float32(err), lam_f, r0,
            chunk_thresh, chunk_norm, chunk_cached,
            cache.rows if has_arena else jnp.zeros((1, d), jnp.bfloat16),
            cache.norms if has_arena else jnp.zeros((1,)),
            cache.errn if has_arena else jnp.zeros((1,)),
            cache.gids if has_arena else dummy,
            cache.ok if has_arena else jnp.zeros((1,), bool),
            ar_inbuf if has_arena else jnp.zeros((1,), bool),
            ar_taken if has_arena else jnp.zeros((1,), bool),
            chunk_off_d if has_arena else dummy,
            slot_lo_d if has_arena else dummy,
            jnp.int32(t), jnp.int32(p), jnp.int32(t_first), eps, acc,
            p=p, nnls_iters=nnls_iters, absolute=absolute,
            has_arena=has_arena, fmax=fm)
        if has_arena:
            ar_taken = ar_taken_new
        # One host transfer for every per-entry scalar.
        t_new, go, err, d_maxv, d_sk, d_umax, d_noff = [
            x.item() for x in jax.device_get(
                (t_new, go, err_d, *diag))]
        committed = t_new - t
        stats.rounds += committed
        certified = committed - (1 if t_first == t and committed > 0
                                 else 0)
        stats.certified_rounds += certified
        stats.cache_hits += certified * len(cache.entries)
        stats.cache_misses += certified * (num_chunks
                                           - len(cache.entries))
        t = t_new
        t_first = -1
        if (checkpoint_dir is not None and bi is not None and t > last_ckpt
                and t - last_ckpt >= checkpoint_every):
            save_solver_state(checkpoint_dir, t, _capture_tree())
            last_ckpt = t
            stats.checkpoints += 1
        if t >= k or err <= eps:
            break
        if go:
            continue          # block boundary: re-enter at the next p
        # Certification failed at round t; the loop's own scan already
        # localized the blockers.  Repair the few offending cached rows
        # when possible, else refresh the buffer.
        maxv, sk_now, n_off = d_maxv, d_sk, int(d_noff)
        free = big_m + annex - annex_cursor
        if (has_arena and row_fetch is not None
                and 0 < n_off <= min(fm, free)
                and sk_now < maxv and np.isfinite(maxv)):
            gids, a_pos = offs     # extracted by the loop's stop branch
            ids_np = np.asarray(gids).copy()
            pos_np = np.asarray(a_pos).copy()
            # The worklist is the top-fm rows by upper bound: the true
            # offenders (u >= maxv, first by construction — they have
            # the highest bounds) plus a prefetch band that amortizes
            # future boundary crossings.  Clamp it to the free annex
            # room: admitting past it would scatter-drop the buffer
            # writes while still marking the rows in-buffer arena-side —
            # invisible to both scans, a silent exactness hole.  The
            # guard above (n_off <= free) keeps every true offender
            # inside the clamp.
            ids_np[free:] = -1
            pos_np[free:] = cache.cap_rows
            fetched, live = checked_fetch(ids_np, pos_np)
            br, bi, bdead, ar_inbuf = _admit_fetched(
                br, bi, bdead, jnp.asarray(fetched),
                jnp.asarray(np.where(live, ids_np, -1)),
                jnp.asarray(live), jnp.int32(annex_cursor),
                ar_inbuf, jnp.asarray(pos_np), fmax=fm)
            annex_cursor += int(live.sum())
            stats.fetched_rows += int(live.sum())
            stats.repairs += 1
            continue
        need_refresh = True

    return StreamingOMPResult(indices, weights, mask, jnp.float32(err),
                              stats)


# ---------------------------------------------------------------------------
# GRAD-MATCH wrappers
# ---------------------------------------------------------------------------

def gradmatch_streaming(
    pool_iter: Callable[[], Iterator],
    k: int,
    target=None,
    lam: float = 0.5,
    eps: float = 1e-10,
    buffer_size: int = 256,
    chunk_topm: Optional[int] = None,
    score_chunk_fn=None,
    cache: Optional[ChunkCache] = None,
    cache_bytes: int = DEFAULT_CACHE_BYTES,
    row_fetch: Optional[Callable] = None,
    retry: Optional["RetryPolicy"] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 8,
    resume: bool = True,
) -> SelectionResult:
    """GRAD-MATCH over a chunked pool; target defaults to one summing pass
    (which also warms the compressed cache).  The returned
    ``SelectionResult`` carries the solver's ``SelectStats``."""
    if target is None:
        if cache is None:
            first = next(iter(pool_iter()), None)
            if first is None:
                raise ValueError("empty pool iterator")
            cache = ChunkCache(cache_bytes, int(first[0].shape[1]))
        target, _ = streaming_target(pool_iter, cache=cache, retry=retry)
    out = omp_select_streaming(
        pool_iter, target, k, lam=lam, eps=eps, buffer_size=buffer_size,
        chunk_topm=chunk_topm, score_chunk_fn=score_chunk_fn, cache=cache,
        cache_bytes=cache_bytes, row_fetch=row_fetch, retry=retry,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        resume=resume)
    return SelectionResult(out.indices, _normalize(out.weights, out.mask),
                           out.mask, out.err, out.stats)


def gradmatch_streaming_array(
    proxies,                 # (n, d) array (in-memory or memmap)
    k: int,
    target=None,
    valid=None,
    lam: float = 0.5,
    eps: float = 1e-10,
    chunk_size: int = 2048,
    buffer_size: int = 256,
    score_chunk_fn=None,
    cache_bytes: int = DEFAULT_CACHE_BYTES,
) -> SelectionResult:
    """Streaming GRAD-MATCH over an explicit array, chunked on the fly.

    The target matches ``gradmatch``'s (full-matrix sum) so the two paths
    agree bit-for-bit on the pools the in-memory solver can hold; the
    array doubles as the exact-row fetch capability for the repair and
    cache-refill tiers.
    """
    if target is None:
        g = jnp.asarray(proxies, jnp.float32)
        if valid is None:
            target = jnp.sum(g, axis=0)
        else:
            target = jnp.sum(g * jnp.asarray(valid)[:, None].astype(g.dtype),
                             axis=0)
    out = omp_select_streaming(
        array_chunks(proxies, chunk_size, valid=valid), target, k, lam=lam,
        eps=eps, buffer_size=buffer_size, score_chunk_fn=score_chunk_fn,
        cache_bytes=cache_bytes, row_fetch=array_row_fetch(proxies))
    return SelectionResult(out.indices, _normalize(out.weights, out.mask),
                           out.mask, out.err, out.stats)

"""Pallas TPU kernels for the OMP scoring step.

``corr``: residual correlation  scores = G @ r — the inner loop of OMP
(Algorithm 2): every selection round scores all ``n`` candidates against the
current residual.  ``G`` is ``(n, d)`` gradient proxies (n up to ~1e5
candidate micro-batches, d = proxy dim ≲ 8192), ``r`` is ``(d,)``.

``corr_argmax``: the incremental solver's fused scores-and-argmax.  Scores
are ``c0 - C @ w`` over the cached correlation columns ``C`` (DESIGN.md §2);
the kernel streams row tiles of ``C``, applies the availability mask, and
carries a running (max, argmin-index) pair across the grid — the ``(n,)``
score vector is never materialized in HBM and the candidate pool is read
exactly once per round.

TPU tiling: rows are processed in MXU-aligned tiles of 128 and the
contraction dimension in VMEM-sized chunks of 512; each grid step multiplies
a ``(128, 512)`` tile against the matching slice of the vector operand and
accumulates into a per-row register tile, so the working set stays well
inside VMEM (128*512*4B = 256 KiB per tile) regardless of n and d.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import PRECISION

TILE_N = 128   # rows per grid step (MXU sublane-aligned)
TILE_D = 512   # proxy-dim chunk per grid step (lane-aligned, 128 | TILE_D)


def _corr_kernel(g_ref, r_ref, out_ref):
    j = pl.program_id(1)
    g = g_ref[...].astype(jnp.float32)          # (TILE_N, TILE_D)
    r = r_ref[...].astype(jnp.float32)          # (TILE_D, 1)
    partial = jnp.dot(g, r, precision=PRECISION)  # (TILE_N, 1) -- MXU

    @pl.when(j == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(j > 0)
    def _acc():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("interpret",))
def corr(grads: jax.Array, residual: jax.Array, *, interpret: bool = False
         ) -> jax.Array:
    """scores = grads @ residual, f32.  grads (n, d), residual (d,) -> (n,).

    Pads n up to TILE_N and d up to TILE_D (zero padding is exact for a dot
    product) and strips the padding afterwards.
    """
    n, d = grads.shape
    n_pad = (-n) % TILE_N
    d_pad = (-d) % TILE_D
    g = jnp.pad(grads, ((0, n_pad), (0, d_pad)))
    r = jnp.pad(residual, (0, d_pad)).reshape(-1, 1)
    np_, dp = g.shape

    out = pl.pallas_call(
        _corr_kernel,
        grid=(np_ // TILE_N, dp // TILE_D),
        in_specs=[
            pl.BlockSpec((TILE_N, TILE_D), lambda i, j: (i, j)),
            pl.BlockSpec((TILE_D, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_N, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
    )(g, r)
    return out[:n, 0]


def _bound_max_kernel(g_ref, nrm_ref, err_ref, r_ref, sc_ref, mask_ref,
                      val_ref, idx_ref, cnt_ref, acc_ref, *,
                      absolute: bool, n_valid: int):
    """Fused interval-bound scan (streaming OMP certification, §7).

    Row tiles of the bf16 cache are matvec'd against the residual across
    d chunks; at the last chunk the per-row upper bound ``u = s̃ +
    (e + acc·‖g‖)·‖r‖`` is formed from the f32 sidecars and folded into
    running (max, lowest-index, offender-count) SMEM scalars — ``u``
    never hits HBM.  ``sc_ref`` is (1, 3) SMEM: [‖r‖, acc, thresh].
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    last_j = pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)          # (TILE_N, TILE_D)
    r = r_ref[...].astype(jnp.float32)          # (TILE_D, 1)
    acc_ref[...] += jnp.dot(g, r, precision=PRECISION)

    @pl.when(j == last_j)
    def _reduce():
        neg_inf = jnp.float32(-jnp.inf)
        rnorm = sc_ref[0, 0]
        acc = sc_ref[0, 1]
        thresh = sc_ref[0, 2]
        s = acc_ref[...]                        # (TILE_N, 1)
        if absolute:
            s = jnp.abs(s)
        u = s + (err_ref[...] + acc * nrm_ref[...]) * rnorm
        u = jnp.where(mask_ref[...] > 0, u, neg_inf)
        tile_max = jnp.max(u)
        row_ids = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
        tile_idx = jnp.min(
            jnp.where(u == tile_max, row_ids, jnp.int32(n_valid))
        ) + i * TILE_N
        tile_cnt = jnp.sum(((mask_ref[...] > 0)
                            & (u >= thresh)).astype(jnp.int32))

        @pl.when(i == 0)
        def _first():
            val_ref[0, 0] = tile_max
            idx_ref[0, 0] = tile_idx
            cnt_ref[0, 0] = tile_cnt

        @pl.when(i > 0)
        def _fold():
            cnt_ref[0, 0] += tile_cnt

            @pl.when(tile_max > val_ref[0, 0])
            def _better():
                val_ref[0, 0] = tile_max
                idx_ref[0, 0] = tile_idx


@functools.partial(jax.jit, static_argnames=("absolute", "interpret"))
def bound_max(rows: jax.Array, norms: jax.Array, errn: jax.Array,
              residual: jax.Array, acc: jax.Array, thresh: jax.Array,
              mask: jax.Array, *, absolute: bool = False,
              interpret: bool = False
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused compressed-cache bound scan: see ``ref.bound_max_ref`` for
    the contract.  Pads n to TILE_N (padded rows masked out, zero
    sidecars) and d to TILE_D (zero padding is exact for the dot)."""
    n, d = rows.shape
    n_pad = (-n) % TILE_N
    d_pad = (-d) % TILE_D
    g = jnp.pad(rows, ((0, n_pad), (0, d_pad)))
    r = jnp.pad(residual.astype(jnp.float32), (0, d_pad)).reshape(-1, 1)
    nrm = jnp.pad(norms.astype(jnp.float32), (0, n_pad)).reshape(-1, 1)
    err = jnp.pad(errn.astype(jnp.float32), (0, n_pad)).reshape(-1, 1)
    m = jnp.pad(mask.astype(jnp.float32), (0, n_pad)).reshape(-1, 1)
    rnorm = jnp.sqrt(jnp.sum(r * r))
    sc = jnp.stack([rnorm, jnp.asarray(acc, jnp.float32),
                    jnp.asarray(thresh, jnp.float32)]).reshape(1, 3)
    np_, dp = g.shape

    kernel = functools.partial(_bound_max_kernel, absolute=absolute,
                               n_valid=np_)
    val, idx, cnt = pl.pallas_call(
        kernel,
        grid=(np_ // TILE_N, dp // TILE_D),
        in_specs=[
            pl.BlockSpec((TILE_N, TILE_D), lambda i, j: (i, j)),
            pl.BlockSpec((TILE_N, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((TILE_N, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((TILE_D, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 3), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((TILE_N, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((TILE_N, 1), jnp.float32)],
        interpret=interpret,
    )(g, nrm, err, r, sc, m)
    return val[0, 0], idx[0, 0], cnt[0, 0]


def _corr_argmax_kernel(c_ref, w_ref, base_ref, mask_ref, idx_ref, val_ref,
                        acc_ref, *, absolute: bool, n_valid: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    last_j = pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[...].astype(jnp.float32)          # (TILE_N, TILE_D)
    w = w_ref[...].astype(jnp.float32)          # (TILE_D, 1)
    acc_ref[...] += jnp.dot(c, w, precision=PRECISION)  # (TILE_N, 1)

    @pl.when(j == last_j)
    def _reduce():
        neg_inf = jnp.float32(-jnp.inf)
        s = base_ref[...] - acc_ref[...]        # (TILE_N, 1) scores
        if absolute:
            s = jnp.abs(s)
        s = jnp.where(mask_ref[...] > 0, s, neg_inf)
        tile_max = jnp.max(s)
        # Lowest row index attaining the tile max (first-occurrence tie
        # break, matching jnp.argmax); -inf == -inf keeps the all-masked
        # tile well-defined at local index 0.
        row_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        tile_idx = jnp.min(
            jnp.where(s == tile_max, row_ids, jnp.int32(n_valid))
        ) + i * TILE_N

        @pl.when(i == 0)
        def _first():
            val_ref[0, 0] = tile_max
            idx_ref[0, 0] = tile_idx

        @pl.when((i > 0) & (tile_max > val_ref[0, 0]))
        def _better():
            val_ref[0, 0] = tile_max
            idx_ref[0, 0] = tile_idx


@functools.partial(jax.jit, static_argnames=("absolute", "interpret"))
def corr_argmax(colcache: jax.Array, w: jax.Array, base: jax.Array,
                mask: jax.Array, *, absolute: bool = False,
                interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Fused masked argmax of  scores = base - colcache @ w.

    colcache (n, k), w (k,), base (n,), mask (n,) bool ->
    (argmax index i32 (), max score f32 ()).

    One streaming pass: row tiles accumulate the matvec across k chunks,
    then fold their masked tile-max into a running (value, index) carried in
    SMEM across the sequential TPU grid.  Ties resolve to the lowest index
    and an all-False mask yields (0, -inf), both matching the jnp reference.
    Pads n up to TILE_N (padded rows are masked out) and k up to TILE_D
    (zero padding is exact for the dot product).
    """
    n, k = colcache.shape
    n_pad = (-n) % TILE_N
    k_pad = (-k) % TILE_D
    c = jnp.pad(colcache, ((0, n_pad), (0, k_pad)))
    wv = jnp.pad(w, (0, k_pad)).astype(jnp.float32).reshape(-1, 1)
    b = jnp.pad(base, (0, n_pad)).astype(jnp.float32).reshape(-1, 1)
    m = jnp.pad(mask.astype(jnp.float32), (0, n_pad)).reshape(-1, 1)
    np_, kp = c.shape

    kernel = functools.partial(_corr_argmax_kernel, absolute=absolute,
                               n_valid=np_)
    idx, val = pl.pallas_call(
        kernel,
        grid=(np_ // TILE_N, kp // TILE_D),
        in_specs=[
            pl.BlockSpec((TILE_N, TILE_D), lambda i, j: (i, j)),
            pl.BlockSpec((TILE_D, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((TILE_N, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((TILE_N, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((TILE_N, 1), jnp.float32)],
        interpret=interpret,
    )(c, wv, b, m)
    return idx[0, 0], val[0, 0]

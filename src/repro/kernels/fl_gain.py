"""Pallas TPU kernels for the facility-location greedy (CRAIG, DESIGN.md §5).

Every greedy round scores all ``n`` candidates by their marginal coverage
gain  ``gain_j = Σ_i relu(s_ij − cover_i)``  and takes the argmax.  The seed
formulation materialized the ``(n, n)`` ``maximum(cover, sim)`` temporary per
round; these kernels stream column tiles instead and carry a running
(max, index) pair across the sequential grid, so the full gain scan reads
the similarity exactly once and the only outputs are the ``(n,)`` gain
vector (consumed by the lazy engine's bound refresh) plus two scalars.

``fl_gain_argmax``      — resident ``(n, n)`` similarity, tiled reduction.
``fl_gain_argmax_otf``  — tile-on-the-fly similarity: ``s_ij`` blocks are
computed from the ``(n, d)`` gradient matrix inside the kernel loop
(``s_ij = L_max − ‖g_i − g_j‖``, the sqdist expansion), so CRAIG runs at
pool sizes where the dense similarity alone is 4–16 GB and the ``(n, n)``
matrix never exists in any memory space.

TPU tiling: ``(128, 128)`` similarity tiles, contraction chunked 512-wide
(matching ``sqdist``); per-column partial gains accumulate in a
``(1, TILE_J)`` VMEM scratch across row tiles, and the masked argmax folds
into SMEM scalars at each column tile's last row step (ties → lowest
index, matching ``jnp.argmax``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import PRECISION

TILE_I = 128   # coverage-row tile (sublane-aligned)
TILE_J = 128   # candidate-column tile (lane-aligned)
TILE_D = 512   # proxy-dim chunk for the on-the-fly inner product


def _fold_argmax(gains, mask, j, idx_ref, val_ref, *, n_sentinel):
    """Fold one column tile's masked (max, lowest-index) into the running
    SMEM pair.  gains/mask are (1, TILE_J); ties resolve to the lowest
    global column index; an all-masked tile is well-defined at -inf."""
    neg_inf = jnp.float32(-jnp.inf)
    gm = jnp.where(mask > 0, gains, neg_inf)
    tile_max = jnp.max(gm)
    col_ids = jax.lax.broadcasted_iota(jnp.int32, gm.shape, 1)
    tile_idx = jnp.min(
        jnp.where(gm == tile_max, col_ids, jnp.int32(n_sentinel))
    ) + j * TILE_J

    @pl.when(j == 0)
    def _first():
        val_ref[0, 0] = tile_max
        idx_ref[0, 0] = tile_idx

    @pl.when((j > 0) & (tile_max > val_ref[0, 0]))
    def _better():
        val_ref[0, 0] = tile_max
        idx_ref[0, 0] = tile_idx


def _fl_gain_kernel(s_ref, cover_ref, mask_ref, gains_ref, idx_ref, val_ref,
                    acc_ref, *, n_sentinel):
    j = pl.program_id(0)
    i = pl.program_id(1)
    last_i = pl.num_programs(1) - 1

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = s_ref[...].astype(jnp.float32)            # (TILE_I, TILE_J)
    c = cover_ref[...].astype(jnp.float32)        # (TILE_I, 1)
    acc_ref[...] += jnp.sum(jnp.maximum(s - c, 0.0), axis=0, keepdims=True)

    @pl.when(i == last_i)
    def _reduce():
        g = acc_ref[...]                          # (1, TILE_J)
        gains_ref[...] = g
        _fold_argmax(g, mask_ref[...], j, idx_ref, val_ref,
                     n_sentinel=n_sentinel)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fl_gain_argmax(sim: jax.Array, cover: jax.Array, mask: jax.Array, *,
                   interpret: bool = False
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Facility-location gain scan over a resident similarity.

    sim (n, n), cover (n,), mask (n,) bool ->
    (gains (n,) f32, argmax index i32 (), max gain f32 ()).

    Gains are raw (unmasked); the argmax honors ``mask`` with lowest-index
    tie-breaking and an all-False mask yields (0, -inf), matching the jnp
    reference.  Zero row/column padding is exact: padded rows contribute
    ``relu(0 − 0) = 0`` and padded columns are masked out.
    """
    n = sim.shape[0]
    i_pad = (-n) % TILE_I
    j_pad = (-n) % TILE_J
    s = jnp.pad(sim, ((0, i_pad), (0, j_pad)))
    c = jnp.pad(cover, (0, i_pad)).astype(jnp.float32).reshape(-1, 1)
    m = jnp.pad(mask.astype(jnp.float32), (0, j_pad)).reshape(1, -1)
    ni, nj = s.shape

    kernel = functools.partial(_fl_gain_kernel, n_sentinel=nj)
    gains, idx, val = pl.pallas_call(
        kernel,
        grid=(nj // TILE_J, ni // TILE_I),
        in_specs=[
            pl.BlockSpec((TILE_I, TILE_J), lambda j, i: (i, j)),
            pl.BlockSpec((TILE_I, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((1, TILE_J), lambda j, i: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, TILE_J), lambda j, i: (0, j)),
            pl.BlockSpec((1, 1), lambda j, i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda j, i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, nj), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, TILE_J), jnp.float32)],
        interpret=interpret,
    )(s, c, m)
    return gains[0, :n], idx[0, 0], val[0, 0]


def _fl_gain_otf_kernel(gr_ref, gc_ref, rn_ref, cn_ref, cover_ref, rok_ref,
                        mask_ref, lmax_ref, gains_ref, idx_ref, val_ref,
                        dot_ref, acc_ref, *, n_sentinel):
    j = pl.program_id(0)
    i = pl.program_id(1)
    kd = pl.program_id(2)
    last_i = pl.num_programs(1) - 1
    last_kd = pl.num_programs(2) - 1

    @pl.when(kd == 0)
    def _init_dot():
        dot_ref[...] = jnp.zeros_like(dot_ref)

    @pl.when((i == 0) & (kd == 0))
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = gr_ref[...].astype(jnp.float32)           # (TILE_I, TILE_D) rows
    b = gc_ref[...].astype(jnp.float32)           # (TILE_J, TILE_D) cands
    dot_ref[...] += jnp.dot(a, b.T, precision=PRECISION)  # (TILE_I, TILE_J)

    @pl.when(kd == last_kd)
    def _accumulate():
        rn = rn_ref[...].astype(jnp.float32)      # (TILE_I, 1) |g_i|^2
        cn = cn_ref[...].astype(jnp.float32)      # (1, TILE_J) |g_j|^2
        d2 = rn + cn - 2.0 * dot_ref[...]
        dist = jnp.sqrt(jnp.maximum(d2, 0.0))
        s = (lmax_ref[0, 0] - dist) * rok_ref[...]   # invalid/pad rows -> 0
        c = cover_ref[...].astype(jnp.float32)
        acc_ref[...] += jnp.sum(jnp.maximum(s - c, 0.0), axis=0,
                                keepdims=True)

        @pl.when(i == last_i)
        def _reduce():
            g = acc_ref[...]
            gains_ref[...] = g
            _fold_argmax(g, mask_ref[...], j, idx_ref, val_ref,
                         n_sentinel=n_sentinel)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fl_gain_argmax_otf(grads: jax.Array, cover: jax.Array,
                       row_ok: jax.Array, mask: jax.Array,
                       l_max: jax.Array,
                       sqnorms: jax.Array | None = None, *,
                       interpret: bool = False
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Gain scan with the similarity computed tile-by-tile from ``grads``.

    grads (n, d), cover (n,), row_ok (n,) bool (rows allowed to demand
    coverage — invalid rows contribute 0, exactly like the zeroed rows of
    the resident path), mask (n,) bool (candidate columns), l_max () f32
    (the similarity offset; must upper-bound all pairwise distances) ->
    (gains (n,) f32, argmax index i32 (), max gain f32 ()).

    The (n, n) similarity never exists: each (TILE_I, TILE_J) block is
    reconstructed from two gradient tiles and folded into the per-column
    gain accumulator immediately.  ``sqnorms`` (squared row norms of the
    unpadded grads) skips the per-call norm reduction when the caller
    already holds them; zero-padded rows have zero norm either way.
    """
    n, d = grads.shape
    n_pad = (-n) % TILE_I          # TILE_I == TILE_J: one row/col pad
    d_pad = (-d) % TILE_D
    g = jnp.pad(grads.astype(jnp.float32), ((0, n_pad), (0, d_pad)))
    sqn = (jnp.sum(g * g, axis=1) if sqnorms is None
           else jnp.pad(jnp.asarray(sqnorms, jnp.float32), (0, n_pad)))
    rn = sqn.reshape(-1, 1)
    cn = sqn.reshape(1, -1)
    c = jnp.pad(cover, (0, n_pad)).astype(jnp.float32).reshape(-1, 1)
    rok = jnp.pad(row_ok.astype(jnp.float32), (0, n_pad)).reshape(-1, 1)
    m = jnp.pad(mask.astype(jnp.float32), (0, n_pad)).reshape(1, -1)
    lm = jnp.asarray(l_max, jnp.float32).reshape(1, 1)
    np_, dp = g.shape

    kernel = functools.partial(_fl_gain_otf_kernel, n_sentinel=np_)
    gains, idx, val = pl.pallas_call(
        kernel,
        grid=(np_ // TILE_J, np_ // TILE_I, dp // TILE_D),
        in_specs=[
            pl.BlockSpec((TILE_I, TILE_D), lambda j, i, kd: (i, kd)),
            pl.BlockSpec((TILE_J, TILE_D), lambda j, i, kd: (j, kd)),
            pl.BlockSpec((TILE_I, 1), lambda j, i, kd: (i, 0)),
            pl.BlockSpec((1, TILE_J), lambda j, i, kd: (0, j)),
            pl.BlockSpec((TILE_I, 1), lambda j, i, kd: (i, 0)),
            pl.BlockSpec((TILE_I, 1), lambda j, i, kd: (i, 0)),
            pl.BlockSpec((1, TILE_J), lambda j, i, kd: (0, j)),
            pl.BlockSpec((1, 1), lambda j, i, kd: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, TILE_J), lambda j, i, kd: (0, j)),
            pl.BlockSpec((1, 1), lambda j, i, kd: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda j, i, kd: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((TILE_I, TILE_J), jnp.float32),
            pltpu.VMEM((1, TILE_J), jnp.float32),
        ],
        interpret=interpret,
    )(g, g, rn, cn, c, rok, m, lm)
    return gains[0, :n], idx[0, 0], val[0, 0]

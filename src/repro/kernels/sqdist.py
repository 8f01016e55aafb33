"""Pallas TPU kernel: tiled pairwise squared distances for CRAIG.

CRAIG's facility-location greedy needs the full pairwise similarity
``s_ij = L_max - ||g_i - g_j||`` over the candidate ground set.  Materializing
the ``(n, n)`` matrix from an ``(n, d)`` gradient matrix is the memory hot
spot (the reason CRAIG "could not run on ImageNet" in the paper).

This kernel emits ``(128, 128)`` output tiles and accumulates the inner
product over d in 512-wide chunks, so HBM traffic is one pass over G per
output block-row and VMEM holds only three small tiles at a time.  The squared
norms enter on the *last* d-chunk so the accumulator is a single f32 tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import PRECISION

TILE_M = 128
TILE_N = 128
TILE_D = 512


def _sqdist_kernel(a_ref, b_ref, an_ref, bn_ref, out_ref, *, n_dchunks):
    k = pl.program_id(2)
    a = a_ref[...].astype(jnp.float32)           # (TILE_M, TILE_D)
    b = b_ref[...].astype(jnp.float32)           # (TILE_N, TILE_D)
    partial = jnp.dot(a, b.T, precision=PRECISION)  # (TILE_M, TILE_N)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(k > 0)
    def _acc():
        out_ref[...] += partial

    # Final chunk: fold in the norms, flip sign:  d2 = an + bn - 2 ab.
    @pl.when(k == n_dchunks - 1)
    def _finish():
        an = an_ref[...].astype(jnp.float32)     # (TILE_M, 1)
        bn = bn_ref[...].astype(jnp.float32)     # (TILE_N, 1)
        d2 = an + bn.T - 2.0 * out_ref[...]
        out_ref[...] = jnp.maximum(d2, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sqdist(a: jax.Array, b: jax.Array, *, interpret: bool = False
           ) -> jax.Array:
    """Pairwise squared euclidean distance (n, d) x (m, d) -> (n, m) f32."""
    n, d = a.shape
    m, _ = b.shape
    n_pad = (-n) % TILE_M
    m_pad = (-m) % TILE_N
    d_pad = (-d) % TILE_D
    ap = jnp.pad(a, ((0, n_pad), (0, d_pad)))
    bp = jnp.pad(b, ((0, m_pad), (0, d_pad)))
    an = jnp.sum(ap.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    bn = jnp.sum(bp.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    np_, dp = ap.shape
    mp = bp.shape[0]
    n_dchunks = dp // TILE_D

    out = pl.pallas_call(
        functools.partial(_sqdist_kernel, n_dchunks=n_dchunks),
        grid=(np_ // TILE_M, mp // TILE_N, n_dchunks),
        in_specs=[
            pl.BlockSpec((TILE_M, TILE_D), lambda i, j, k: (i, k)),
            pl.BlockSpec((TILE_N, TILE_D), lambda i, j, k: (j, k)),
            pl.BlockSpec((TILE_M, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((TILE_N, 1), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_M, TILE_N), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, mp), jnp.float32),
        interpret=interpret,
    )(ap, bp, an, bn)
    return out[:n, :m]

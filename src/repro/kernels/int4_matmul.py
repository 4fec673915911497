"""Pallas TPU kernel: matmul against int4-packed weights, dequant in VMEM.

TPU mapping of the paper's "0.1 MB model stays on-chip": 4-bit weights cut
HBM->VMEM weight traffic 4-8x vs bf16/fp32, and the dequant (unpack nibbles,
scale) happens in VMEM right before the MXU — weights never exist in HBM at
full precision. Per-output-channel scales match
repro.core.compression.quantization.

Blocking: grid (M/bM, N/bN, K/bK) with a VMEM fp32 accumulator; K-blocks
stream through VMEM so arbitrarily large K fits. All block dims are
128-aligned for the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack_block(packed):
    """(bK//2, bN) int8 -> (bK, bN) f32 in [-8, 7] (low nibble = even row).

    The byte widens to int32 before the shift: Mosaic cannot shift an
    int8 vector."""
    p = packed.astype(jnp.int32)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo).astype(jnp.float32)
    hi = jnp.where(hi >= 8, hi - 16, hi).astype(jnp.float32)
    k2, bn = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(k2 * 2, bn)


def _int4_matmul_kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *, k_tiles):
    kt = pl.program_id(2)

    @pl.when(kt == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_block(w_ref[...])
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(kt == k_tiles - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * scale_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                              "interpret"))
def int4_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array, *,
                block_m: int = 128, block_n: int = 128, block_k: int = 512,
                interpret: bool = False) -> jax.Array:
    """x: (M, K) float; packed: (K//2, N) int8 nibble pairs; scale: (N,).
    Returns (M, N) float32."""
    m, k = x.shape
    k2, n = packed.shape
    assert k == 2 * k2, (k, k2)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    k_tiles = k // bk
    grid = (m // bm, n // bn, k_tiles)
    return pl.pallas_call(
        functools.partial(_int4_matmul_kernel, k_tiles=k_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kt: (i, kt)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kt: (kt, j)),
            pl.BlockSpec((1, bn), lambda i, j, kt: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kt: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, packed, scale.reshape(1, n))

"""Pallas TPU kernel: fused zero-skip FC over the group-packed N:M layout.

Consumes ``core.layouts.nm.NMGroupPacked`` directly — the regular-sparsity
deployment layout of N:M-pruned weights (fixed ``n`` survivors per ``m``
input rows, value nibble + in-group offset nibble in one byte, no index
padding).  Compared to ``kernels/sparse_fc.py`` (padded CSC), the weight
tile carries *half* the HBM->VMEM traffic at equal nnz — one int8 byte per
entry instead of an int32 index plus a float32 value — and the global row
ids are reconstructed in VMEM from the entry position (``e // n``) and the
stored offset, the software analogue of the accelerator's implicit-index
regular-sparsity fetch.  As in ``sparse_fc``, the tile expands to its dense
integer codes in VMEM and feeds one MXU dot.

Merged-spike input path (paper §II-D2): the kernel accepts the raw
``(TS, B, H)`` spike trains and sums them over TS in VMEM first — one
pass serves every time step.  The integer accumulate is exact, so the
same mask packed as CSC or N:M-group executes bit-identically
(tests/test_nm_fc.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sparse_fc import _fit_block


def _expand_nm(p_ref, rows: int, nm_n: int, nm_m: int) -> jax.Array:
    """Group-packed N:M operand (E, N) int8 -> dense (rows, N) int4 codes.

    Entry ``e`` belongs to row group ``e // n``; its global row is
    ``group * m + offset`` (the high nibble), its value the low nibble;
    one entry per (static) iteration.  Tail pad slots carry value 0, so
    every sum here is exact.
    """
    # widen before the shifts (Mosaic cannot shift an int8 vector); the
    # loop is static because Mosaic loads int8 rows only 8-aligned
    p = p_ref[...].astype(jnp.int32)
    val = p & 0xF
    val = jnp.where(val >= 8, val - 16, val).astype(jnp.float32)
    row = (p >> 4) & 0xF
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows, p.shape[1]), 0)
    w = jnp.zeros((rows, p.shape[1]), jnp.float32)
    for e in range(p.shape[0]):
        hit = iota == (e // nm_n) * nm_m + row[e:e + 1]
        w = w + jnp.where(hit, val[e:e + 1], 0.0)
    return w


def _nm_fc_kernel(s_ref, p_ref, scale_ref, o_ref, *, n, m):
    # merge time steps in VMEM: one pass for all TS
    x = s_ref[...].astype(jnp.float32).sum(axis=0)  # (bB, H)
    w = _expand_nm(p_ref, x.shape[1], n, m)  # (H, bN) int4 codes
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)
    o_ref[...] = (acc * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "m", "block_b", "block_n",
                                             "interpret"))
def nm_fc(spikes_ts: jax.Array, packed: jax.Array, scale: jax.Array, *,
          n: int, m: int, block_b: int = 128, block_n: int = 512,
          interpret: bool = False) -> jax.Array:
    """Zero-skip FC: merged spikes @ N:M-group-packed int4 -> (B, N) f32.

    spikes_ts: (TS, B, H) binary spike trains (a pre-merged (B, H) input is
    also accepted); packed: (groups * n, N) int8 from
    ``core.layouts.nm.NMGroupPacked``; scale: (N,) or (1, N) per-channel.
    The integer accumulate is exact, then scaled — the same two steps as
    ``layouts.nm.nm_matmul`` — so results agree bitwise with it and with
    the padded-CSC path for the same mask.
    """
    if spikes_ts.ndim == 2:
        spikes_ts = spikes_ts[None]
    ts, b, h = spikes_ts.shape
    e, nn = packed.shape
    bb, bn = _fit_block(b, block_b, 8), _fit_block(nn, block_n, 128)
    grid = (b // bb, nn // bn)
    return pl.pallas_call(
        functools.partial(_nm_fc_kernel, n=n, m=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ts, bb, h), lambda i, j: (0, i, 0)),
            pl.BlockSpec((e, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, nn), jnp.float32),
        interpret=interpret,
    )(spikes_ts, packed, scale.reshape(1, nn))

"""Pallas TPU kernel: fused zero-skip sparse FC over padded-CSC columns.

Consumes ``core.sparse.SparseColumns`` directly — the deployment layout of
the paper's 40%-unstructured-pruned FC.  The jnp reference
(``core.sparse.sparse_matmul``) gathers ``x[:, indices]`` which XLA
materializes as a ``(B, nnz_max, N)`` HBM intermediate; here only the
compressed ``(nnz_max, bN)`` index/value tiles cross into VMEM, where they
expand into the dense ``(H, bN)`` integer-code tile (``_expand_csc``) next
to the batch tile of the merged spike vector, and one MXU dot plus the
per-channel scale produce the ``(bB, bN)`` result.  The MXU has no
per-column gather cheaper than that dot; weight traffic still scales with
the CSC payload.  Spike counts times int4 codes sum exactly, so the
integer accumulate does not depend on the summation order.

Merged-spike input path (paper §II-D2): the kernel accepts the raw
``(TS, B, H)`` spike trains and sums them over TS in VMEM before the
dot — one CSC pass serves every time step, the same trick
``kernels/merged_spike_fc.py`` plays for the dense int4 FC.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fit_block(dim: int, block: int, align: int) -> int:
    """Largest tile <= ``block`` that divides ``dim`` and is a multiple of
    ``align`` — the TPU tiles a block's last two dims by (8, 128) — or the
    whole dim when there is none (a full-extent block is always legal).
    The paper's fc_dim=1920 is 15 x 128, so the FC tiles are 384 wide."""
    for tile in range(min(block, dim) // align * align, 0, -align):
        if dim % tile == 0:
            return tile
    return dim


def _expand_csc(idx_ref, val_ref, rows: int) -> jax.Array:
    """Padded-CSC operands (nnz_max, N) -> dense (rows, N) int4 codes.

    One stored entry per iteration: row ``idx[e, n]`` of column ``n``
    receives ``val[e, n]``.  A column stores each row at most once and pad
    slots carry value 0, so every sum here is exact.
    """
    nnz, n = idx_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0)

    def body(e, w):
        hit = row == idx_ref[pl.ds(e, 1), :]
        return w + jnp.where(hit, val_ref[pl.ds(e, 1), :], 0.0)

    return jax.lax.fori_loop(0, nnz, body, jnp.zeros((rows, n), jnp.float32))


def _sparse_fc_kernel(s_ref, idx_ref, val_ref, scale_ref, o_ref):
    # merge time steps in VMEM: one CSC pass for all TS
    x = s_ref[...].astype(jnp.float32).sum(axis=0)  # (bB, H)
    w = _expand_csc(idx_ref, val_ref, x.shape[1])  # (H, bN) int4 codes
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)
    o_ref[...] = (acc * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "block_n", "interpret"))
def sparse_fc(spikes_ts: jax.Array, indices: jax.Array, values: jax.Array,
              scale: jax.Array, *, block_b: int = 128, block_n: int = 512,
              interpret: bool = False) -> jax.Array:
    """Zero-skip FC: merged spikes @ padded-CSC int4 weights -> (B, N) f32.

    spikes_ts: (TS, B, H) binary spike trains (a pre-merged (B, H) input is
    also accepted); indices/values: (nnz_max, N) from
    ``core.sparse.SparseColumns``; scale: (N,) or (1, N) per-channel.
    The integer accumulate is exact, then scaled — the same two steps as
    ``core.sparse.sparse_matmul``, so results agree bitwise.
    """
    if spikes_ts.ndim == 2:
        spikes_ts = spikes_ts[None]
    ts, b, h = spikes_ts.shape
    nnz, n = indices.shape
    bb, bn = _fit_block(b, block_b, 8), _fit_block(n, block_n, 128)
    grid = (b // bb, n // bn)
    return pl.pallas_call(
        _sparse_fc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ts, bb, h), lambda i, j: (0, i, 0)),
            pl.BlockSpec((nnz, bn), lambda i, j: (0, j)),
            pl.BlockSpec((nnz, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
    )(spikes_ts, indices, values, scale.reshape(1, n))

"""Pallas TPU kernel: event-driven spike-broadcast matmul (input zero-skip).

The paper's input-broadcasting scheme "eliminates zero computations" on the
*activation* side: each binary spike vector is scanned by a priority
encoder, and only the surviving spike indices broadcast their weight rows
into the accumulators — a column of W is fetched/accumulated per *event*,
not per neuron.  This module is that scheme mapped onto the TPU, the
activation-side twin of the weight-side zero-skip layouts
(``kernels/sparse_fc`` / ``kernels/nm_fc``):

  * ``event_rank`` — the running population count of each row (the
    priority encoder's event number per position), computed as one dot
    with an upper-triangular ones matrix.  Its operands are 0/1 and its
    sums are integers <= K, so it is exact at any MXU precision (Mosaic
    has no cumsum).
  * ``compact_spikes`` — the priority encoder itself: each row's nonzero
    entries compact into a fixed-``capacity`` ascending-index event list
    (index + value), zero-padded past the row's population count.
  * ``gather_matmul`` / ``spike_broadcast`` — the matmul over each row's
    event list.  A finite ``capacity`` models the hardware event queue:
    rows with more events TRUNCATE their highest-index events (the oracle
    ``ref.spike_broadcast_ref`` defines the same tail-drop semantics).  On
    the MXU the accumulate over the kept events is one dense dot over the
    row with the dropped events zeroed: an explicit per-event row gather
    would cost ``capacity`` times the dense MACs, and zero activations add
    exact zeros.  So at lossless capacity it runs the dense ``x @ W``
    itself, the same dot the dense kernels run; the zero-skip saving
    of the ASIC is accounted analytically
    (``core.complexity.spike_broadcast_report``), not in TPU time.  A 3-D
    ``(TS, B, H)`` input takes the merged-spike-union path (paper §II-D2):
    TS trains sum in VMEM and one pass serves every time step.
  * ``spike_cell`` — the fused recurrent-spiking-layer step of
    ``kernels/rsnn_cell`` with the recurrent matmul run over the event
    lists: one W fetch per batch tile, TS folded into the row axis, LIF
    chain fused in the epilogue.

Capacity contract: ``capacity=None`` sizes the event list to the full
contraction dim (lossless — every active row fits).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sparse_fc import _fit_block


def event_rank(x: jax.Array) -> jax.Array:
    """(R, K) -> (R, K) f32 running count of nonzeros along each row
    (inclusive): the event number of every active position."""
    k = x.shape[1]
    nz = (x != 0).astype(jnp.float32)
    upper = (jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (k, k), 1))
    return jnp.dot(nz, upper.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def compact_spikes(x: jax.Array, capacity: int
                   ) -> tuple[jax.Array, jax.Array]:
    """Priority-encode each row of ``x (R, K)`` into an ascending-index
    event list.

    Returns ``(idx, vals)``, each ``(R, capacity)``: ``idx[r, j]`` is the
    column of row ``r``'s ``(j+1)``-th nonzero (clamped to ``K-1`` past the
    end) and ``vals[r, j]`` that entry's value, ``0.0`` on padding.  Rows
    with more than ``capacity`` active entries truncate their highest
    indices.  Pure jnp over 2-D values (a static loop over the K columns;
    no cumsum, sort, scatter or gather), so it runs inside Pallas kernels
    on the TPU as well as in the interpreter.
    """
    r, k = x.shape
    rank = event_rank(x)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, capacity), 1) \
        .astype(jnp.float32)
    idx = jnp.zeros((r, capacity), jnp.float32)
    vals = jnp.zeros((r, capacity), x.dtype)
    for c in range(k):
        rc, xc = rank[:, c:c + 1], x[:, c:c + 1]
        # slot j holds the (j+1)-th event: its column is the number of
        # columns whose running count is still <= j
        idx = idx + (rc <= slot).astype(jnp.float32)
        vals = jnp.where((xc != 0) & (rc == slot + 1), xc, vals)
    idx = jnp.minimum(idx, k - 1).astype(jnp.int32)  # clamp padding slots
    return idx, vals


def gather_matmul(x: jax.Array, w: jax.Array, capacity: int) -> jax.Array:
    """Event-list matmul: ``x (R, K) @ w (K, N)`` over each row's first
    ``capacity`` events.  At lossless capacity (``capacity >= K``) every
    event is kept and this is the dense dot; otherwise the events past the
    queue's capacity are zeroed first (``ref.spike_broadcast_ref``'s
    truncation, bit for bit).  Pure jnp: the kernel bodies and the
    mega-step's spike mode both call this."""
    if capacity < x.shape[1]:
        x = jnp.where(event_rank(x) <= capacity, x, jnp.zeros((), x.dtype))
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _spike_broadcast_kernel(x_ref, w_ref, o_ref, *, capacity: int):
    x = x_ref[...].astype(jnp.float32)
    if x.ndim == 3:
        # merged-spike union path (paper §II-D2): one event-list pass
        # serves every time step, values land in {0..TS}
        x = x.sum(axis=0)
    o_ref[...] = gather_matmul(
        x, w_ref[...].astype(jnp.float32), capacity).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("capacity", "block_r",
                                             "block_n", "interpret"))
def spike_broadcast(x: jax.Array, w: jax.Array, *, capacity: int | None = None,
                    block_r: int = 128, block_n: int = 512,
                    interpret: bool = False) -> jax.Array:
    """Event-driven matmul ``x @ w`` over each row's event list.

    ``x``: ``(R, K)`` rows (binary spikes, merged counts, or any input),
    or ``(TS, B, K)`` spike trains which merge over TS in VMEM first (the
    FC readout's union variant).  ``w``: ``(K, N)`` dense float weights.
    Returns ``(R|B, N)`` float32, bit-identical to the dense matmul when
    ``capacity`` is lossless (see module docstring for the truncation
    contract otherwise).
    """
    if x.ndim == 3:
        ts, rows, k = x.shape
    else:
        rows, k = x.shape
    n = w.shape[1]
    cap = k if capacity is None else min(capacity, k)
    br, bn = _fit_block(rows, block_r, 8), _fit_block(n, block_n, 128)
    grid = (rows // br, n // bn)
    if x.ndim == 3:
        x_spec = pl.BlockSpec((ts, br, k), lambda i, j: (0, i, 0))
    else:
        x_spec = pl.BlockSpec((br, k), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_spike_broadcast_kernel, capacity=cap),
        grid=grid,
        in_specs=[x_spec, pl.BlockSpec((k, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((br, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        interpret=interpret,
    )(x, w)


def _spike_cell_kernel(stim_ref, s_ref, w_ref, u0_ref, h0_ref, beta_ref,
                       vth_ref, spikes_ref, u_out_ref, *, num_ts: int,
                       capacity: int):
    ts, bb, h_in = s_ref.shape
    # --- recurrent stimulus: TS folds into the event-list row axis, so one
    # W fetch serves every time step --------------------------------------
    s2 = s_ref[...].astype(jnp.float32).reshape(ts * bb, h_in)
    rec = gather_matmul(s2, w_ref[...].astype(jnp.float32), capacity)
    stim = stim_ref[...].astype(jnp.float32) + rec.reshape(ts, bb, -1)
    # --- fused LIF chain: identical to kernels/rsnn_cell ------------------
    beta = beta_ref[...].astype(jnp.float32)
    vth = vth_ref[...].astype(jnp.float32)
    u = u0_ref[...].astype(jnp.float32)
    h = h0_ref[...].astype(jnp.float32)
    for t in range(num_ts):
        u = stim[t] + beta * u * (1.0 - h)
        h = (u >= vth).astype(jnp.float32)
        spikes_ref[t, :, :] = h.astype(spikes_ref.dtype)
    u_out_ref[...] = u.astype(u_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("capacity", "block_b",
                                             "interpret"))
def spike_cell(stim_base: jax.Array, s_prev: jax.Array, w: jax.Array,
               u0: jax.Array, h0: jax.Array, beta: jax.Array,
               vth: jax.Array, *, capacity: int | None = None,
               block_b: int = 128, interpret: bool = False):
    """Fused spiking-layer step with the event-list recurrent matmul.

    Drop-in for ``kernels/rsnn_cell.rsnn_cell`` / ``ref.rsnn_cell_ref``
    (same shapes and LIF chain) but the ``s_prev @ W`` runs over each
    row's event list — bit-identical to the dense cell at lossless
    ``capacity``.  Batch tiles via ``_fit_block``.
    """
    ts, b, h = s_prev.shape
    bb = _fit_block(b, block_b, 8)
    cap = h if capacity is None else min(capacity, h)
    beta2 = beta.reshape(1, h)
    vth2 = vth.reshape(1, h)
    grid = (b // bb,)
    return pl.pallas_call(
        functools.partial(_spike_cell_kernel, num_ts=ts, capacity=cap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ts, bb, h), lambda i: (0, i, 0)),  # stim_base
            pl.BlockSpec((ts, bb, h), lambda i: (0, i, 0)),  # s_prev
            pl.BlockSpec((h, h), lambda i: (0, 0)),  # W: one fetch / tile
            pl.BlockSpec((bb, h), lambda i: (i, 0)),  # u0
            pl.BlockSpec((bb, h), lambda i: (i, 0)),  # h0
            pl.BlockSpec((1, h), lambda i: (0, 0)),  # beta
            pl.BlockSpec((1, h), lambda i: (0, 0)),  # vth
        ],
        out_specs=[
            pl.BlockSpec((ts, bb, h), lambda i: (0, i, 0)),
            pl.BlockSpec((bb, h), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ts, b, h), stim_base.dtype),
            jax.ShapeDtypeStruct((b, h), jnp.float32),
        ],
        interpret=interpret,
    )(stim_base, s_prev, w, u0, h0, beta2, vth2)

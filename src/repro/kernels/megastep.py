"""Pallas TPU kernel: the whole RSNN frame step in ONE dispatch.

``kernels/rsnn_cell.py`` fuses one recurrent layer; the engine still
crossed layer boundaries through HBM — each frame was one jitted step but
internally three op-table calls (l0 cell -> l1 cell -> layout-resolved
FC), each a separate kernel dispatch re-fetching weights and state.  The
packed model is 0.1 MB and the slot batch's recurrent state a few KB, so
*everything* fits in VMEM at once.  This kernel is the paper's
whole-network-per-frame pass as one ``pallas_call``:

  * l0 recurrent-spiking cell across all ``num_ts`` time steps (TS folded
    into the matmul M dim — one recurrent-weight fetch serves every time
    step, the paper's parallel-time-step trick);
  * l1 cell, consuming l0's spikes straight from registers/VMEM;
  * the layout-resolved FC readout — dense int4, padded CSC, or
    group-packed N:M, selected by the static ``fc_mode`` that the packed
    FC tensor's ``WeightLayout.megastep_fc`` binding resolved;
  * the per-slot sparsity counters (L0/L1 spike counts, merged-spike
    union, input one-bits) as aux outputs of the same dispatch.

Weights ride into VMEM in their *packed* form (int4 nibbles for the layer
matrices, the layout tensor for the FC) and expand next to the MACs;
membrane/spike state stays resident across the whole step and — via the
static ``frames`` axis — across an F-frame chunk (one weight fetch serves
F frames x TS time steps; the software echo of EdgeDRNN keeping RNN state
next to the datapath).  The sparse FC layouts expand to their dense
integer-code matrix in VMEM (one stored entry per loop iteration) and
feed one MXU dot: the MXU has no cheaper form of a per-column gather, and
small integer codes times spike counts sum exactly, so the readout equals
the layout's gather oracle bit for bit in interpret mode (within one f32
rounding on the chip, ``chip_smoke.py``).

Bit-identity contract: every float op matches the ``jnp`` backend's
composition (same dots, same LIF order, same scale order per layout), so
under the same dot precision the ``fused`` backend is bit-identical to
``jnp`` at every loop contract — proven by ``tests/test_megastep.py``
against ``kernels/ref.megastep_ref``.  On the TPU the MXU accumulates in
another order than the CPU, so there the contract is a tolerance
(``chip_smoke.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.int4_matmul import _unpack_block
from repro.kernels.nm_fc import _expand_nm
from repro.kernels.sparse_fc import _expand_csc
from repro.kernels.spike_broadcast import gather_matmul

# operand count per FC mode (after the 11 common + weight refs)
_FC_OPERANDS = {"dense_float": 1, "dense_int4": 2, "csc": 3, "nm": 2}


def _dequant(q_ref, scale_ref) -> jax.Array:
    """In-kernel int4 nibble dequant: (K//2, N) int8 pairs -> (K, N) f32.

    Bit-exact with ``compression.quantization.unpack_int4`` followed by the
    per-channel scale (``layouts.dense.dequantize``) — the weights stay
    4-bit in VMEM and widen next to the MACs.
    """
    return _unpack_block(q_ref[...]) * scale_ref[...]


def _lif_chain(stim, u, h, beta, vth, num_ts: int):
    """The sequential LIF membrane chain (paper Eq. 2-3), exactly
    ``ref.rsnn_cell_ref``'s epilogue.  Returns the per-time-step spikes as
    a list (the last one is the next frame's chain carry, taken without
    indexing a stacked array) and the final membrane."""
    spikes = []
    for t in range(num_ts):
        u = stim[t] + beta * u * (1.0 - h)
        h = (u >= vth).astype(jnp.float32)
        spikes.append(h)
    return spikes, u


def _fc_codes(fc_refs, rows: int, *, fc_mode: str, nm_n: int, nm_m: int):
    """The FC operands as (weight (rows, FC) f32, post-dot scale or None)."""
    if fc_mode == "dense_float":
        return fc_refs[0][...], None
    if fc_mode == "dense_int4":
        return _dequant(fc_refs[0], fc_refs[1]), None
    if fc_mode == "csc":
        return _expand_csc(fc_refs[0], fc_refs[1], rows), fc_refs[2][...]
    if fc_mode == "nm":
        return _expand_nm(fc_refs[0], rows, nm_n, nm_m), fc_refs[1][...]
    raise ValueError(f"unknown fc_mode {fc_mode!r}")


def _megastep_kernel(*refs, num_ts: int, frames: int, precision: str,
                     fc_mode: str, nm_n: int, nm_m: int, input_bits: int,
                     spike: bool):
    def _spikes_dot(s2, w):
        # spike-consuming matmul: dense MXU dot, or — in spike mode — the
        # event-list matmul at lossless capacity (the same dot)
        if spike:
            return gather_matmul(s2, w, s2.shape[1])
        return jnp.dot(s2, w, preferred_element_type=jnp.float32)

    (x_ref, s0_ref, u0_ref, h0_ref, s1_ref, u1_ref, h1_ref,
     beta0_ref, vth0_ref, beta1_ref, vth1_ref) = refs[:11]
    nw = 8 if precision == "int4" else 4
    w_refs = refs[11:11 + nw]
    fc_refs = refs[11 + nw:11 + nw + _FC_OPERANDS[fc_mode]]
    s0_out, u0_out, s1_out, u1_out, logits_out, counts_out = \
        refs[11 + nw + _FC_OPERANDS[fc_mode]:]

    # --- weights: fetched/expanded ONCE for the whole F-frame chunk -------
    if precision == "int4":
        w0x = _dequant(w_refs[0], w_refs[1])
        w0h = _dequant(w_refs[2], w_refs[3])
        w1x = _dequant(w_refs[4], w_refs[5])
        w1h = _dequant(w_refs[6], w_refs[7])
    else:
        w0x, w0h, w1x, w1h = (r[...] for r in w_refs)
    h = u0_ref.shape[1]
    w_fc, fc_scale = _fc_codes(fc_refs, h, fc_mode=fc_mode, nm_n=nm_n,
                               nm_m=nm_m)
    beta0 = beta0_ref[...].astype(jnp.float32)
    vth0 = vth0_ref[...].astype(jnp.float32)
    beta1 = beta1_ref[...].astype(jnp.float32)
    vth1 = vth1_ref[...].astype(jnp.float32)

    # --- recurrent state: VMEM-resident across the whole chunk ------------
    s0 = [s0_ref[t].astype(jnp.float32) for t in range(num_ts)]
    s1 = [s1_ref[t].astype(jnp.float32) for t in range(num_ts)]
    u0 = u0_ref[...].astype(jnp.float32)
    h0 = h0_ref[...].astype(jnp.float32)
    u1 = u1_ref[...].astype(jnp.float32)
    h1 = h1_ref[...].astype(jnp.float32)
    b = u0.shape[0]

    def ts_rows(spikes):  # TS folded into the matmul M dim
        return jnp.concatenate(spikes, axis=0)  # (TS * B, H)

    def per_ts(y):
        return [y[t * b:(t + 1) * b] for t in range(num_ts)]

    for f in range(frames):
        x = x_ref[f].astype(jnp.float32)  # (B, input_dim)
        # L0: feedforward stimulus once per frame, shared across time
        # steps; recurrent matmul with TS folded into M (one W fetch)
        ff0 = jnp.dot(x, w0x, preferred_element_type=jnp.float32)
        rec0 = per_ts(_spikes_dot(ts_rows(s0), w0h))
        s0, u0 = _lif_chain([ff0 + r for r in rec0], u0, h0, beta0, vth0,
                            num_ts)
        h0 = s0[-1]

        # L1: per-ts feedforward from L0 spikes (straight from VMEM)
        ff1 = per_ts(_spikes_dot(ts_rows(s0), w1x))
        rec1 = per_ts(_spikes_dot(ts_rows(s1), w1h))
        s1, u1 = _lif_chain([a + r for a, r in zip(ff1, rec1)], u1, h1,
                            beta1, vth1, num_ts)
        h1 = s1[-1]

        # merged-spike readout (paper §II-D2)
        merged = functools.reduce(jnp.add, s1)  # (B, H) in {0..TS}
        logits = _spikes_dot(merged, w_fc)
        if fc_scale is not None:
            logits = logits * fc_scale
        logits_out[f] = logits

        # per-slot sparsity counters: aux outputs of the same dispatch
        # (bit-exact with serving.stream._frame_counters), one column each
        cols = [s.sum(axis=1, keepdims=True) for s in s0 + s1]
        cols.append(functools.reduce(jnp.maximum, s1)
                    .sum(axis=1, keepdims=True))
        mag = jnp.abs(x).astype(jnp.int32)
        bits = functools.reduce(
            jnp.add, [(mag >> i) & 1 for i in range(input_bits)])
        cols.append(bits.astype(jnp.float32).sum(axis=1, keepdims=True))
        for j, col in enumerate(cols):
            counts_out[f, :, j:j + 1] = col

    for t in range(num_ts):
        s0_out[t] = s0[t]
        s1_out[t] = s1[t]
    u0_out[...] = u0
    u1_out[...] = u1


@functools.partial(jax.jit, static_argnames=("precision", "fc_mode",
                                             "input_bits", "nm_n", "nm_m",
                                             "spike", "interpret"))
def megastep(x, s0, u0, h0, s1, u1, h1, beta0, vth0, beta1, vth1,
             wargs: tuple, fcargs: tuple, *, precision: str, fc_mode: str,
             input_bits: int, nm_n: int = 0, nm_m: int = 0,
             spike: bool = False, interpret: bool = False):
    """Single-dispatch mega-step over an F-frame chunk.

    Shapes: ``x`` (F, B, input_dim) quantized frames; ``s0``/``s1``
    (TS, B, H) previous-frame spikes; ``u0``/``h0``/``u1``/``h1`` (B, H)
    membrane chain carries; ``beta*/vth*`` (H,) LIF constants.

    ``wargs`` holds the layer weights: dense ``(w0x, w0h, w1x, w1h)`` at
    float precision, packed ``(q, scale)`` pairs per weight at int4.
    ``fcargs`` holds the FC operands that the packed tensor's layout
    binding (``WeightLayout.megastep_fc``) resolved for ``fc_mode``.
    ``spike=True`` — the ``fused_spike`` backend's binding — runs every
    spike-consuming matmul (L0-recurrent, L1-feedforward, L1-recurrent,
    and the dense FC modes) through ``spike_broadcast.gather_matmul`` at
    lossless capacity, bit-identical to the dense dots.

    The kernel writes its counters as one (F, B, 2*TS + 2) block, a
    column per counter (Mosaic stores a (B, 1) column; it cannot store
    a rank-reduced (B,) row); this wrapper slices them back out.

    Returns ``(s0, u0, s1, u1, logits (F, B, fc_dim), spikes_l0 (F, TS, B),
    spikes_l1 (F, TS, B), union_l1 (F, B), input_one_bits (F, B))``.
    """
    frames, b, _ = x.shape
    ts, _, h = s0.shape
    fc_dim = fcargs[0].shape[1]  # every mode's first operand is (*, fc_dim)
    lif2 = [a.reshape(1, h) for a in (beta0, vth0, beta1, vth1)]
    out_shape = [
        jax.ShapeDtypeStruct((ts, b, h), jnp.float32),  # s0
        jax.ShapeDtypeStruct((b, h), jnp.float32),  # u0
        jax.ShapeDtypeStruct((ts, b, h), jnp.float32),  # s1
        jax.ShapeDtypeStruct((b, h), jnp.float32),  # u1
        jax.ShapeDtypeStruct((frames, b, fc_dim), jnp.float32),  # logits
        jax.ShapeDtypeStruct((frames, b, 2 * ts + 2), jnp.float32),  # counts
    ]
    kernel = functools.partial(
        _megastep_kernel, num_ts=ts, frames=frames, precision=precision,
        fc_mode=fc_mode, nm_n=nm_n, nm_m=nm_m, input_bits=input_bits,
        spike=spike)
    s0, u0, s1, u1, logits, counts = pl.pallas_call(
        kernel, out_shape=out_shape, interpret=interpret)(
        x, s0, u0, h0, s1, u1, h1, *lif2, *wargs, *fcargs)
    counts = jnp.swapaxes(counts, 1, 2)  # (F, counter, B)
    return (s0, u0, s1, u1, logits, counts[:, :ts], counts[:, ts:2 * ts],
            counts[:, 2 * ts], counts[:, 2 * ts + 1])

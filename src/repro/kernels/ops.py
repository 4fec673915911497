"""Public jit'd wrappers for the Pallas kernels.

On a TPU the kernels compile to Mosaic; on any other backend (the CPU test
suite) they execute in interpret mode, which checks their semantics but
not what the chip's compiler accepts (``tests/test_tpu_compile.py`` does).
"""

from __future__ import annotations

import jax

from repro.kernels import delta_step as _delta
from repro.kernels import int4_matmul as _i4
from repro.kernels import megastep as _mega
from repro.kernels import merged_spike_fc as _mfc
from repro.kernels import nm_fc as _nfc
from repro.kernels import rsnn_cell as _cell
from repro.kernels import sparse_fc as _sfc
from repro.kernels import spike_broadcast as _sb


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def rsnn_cell(stim_base, s_prev, w, u0, h0, beta, vth, *, block_b: int = 128):
    return _cell.rsnn_cell(stim_base, s_prev, w, u0, h0, beta, vth,
                           block_b=block_b, interpret=_interpret())


def delta_step(x, x_prev, pre_prev, w, threshold, *, block_b: int = 128):
    """Delta-temporal input gating (``kernels/delta_step.py``): returns
    (x_hat, pre, mask) with skipped elements held at their last-propagated
    value and unchanged slots reusing the cached pre-activation row."""
    return _delta.delta_step(x, x_prev, pre_prev, w, threshold,
                             block_b=block_b, interpret=_interpret())


def spike_broadcast(x, w, *, capacity=None, block_r=128, block_n=512):
    """Event-driven matmul skipping zero activations
    (``kernels/spike_broadcast.py``): bit-identical to ``x @ w`` at
    lossless capacity; a 3-D input takes the merged-spike-union path."""
    return _sb.spike_broadcast(x, w, capacity=capacity, block_r=block_r,
                               block_n=block_n, interpret=_interpret())


def spike_cell(stim_base, s_prev, w, u0, h0, beta, vth, *, capacity=None,
               block_b: int = 128):
    """Fused spiking-layer step with the event-gather recurrent matmul
    (``kernels/spike_broadcast.spike_cell``) — drop-in for ``rsnn_cell``."""
    return _sb.spike_cell(stim_base, s_prev, w, u0, h0, beta, vth,
                          capacity=capacity, block_b=block_b,
                          interpret=_interpret())


def int4_matmul(x, packed, scale, *, block_m=128, block_n=128, block_k=512):
    return _i4.int4_matmul(x, packed, scale, block_m=block_m, block_n=block_n,
                           block_k=block_k, interpret=_interpret())


def merged_spike_fc(spikes_ts, packed, scale, *, block_b=128, block_n=128):
    return _mfc.merged_spike_fc(spikes_ts, packed, scale, block_b=block_b,
                                block_n=block_n, interpret=_interpret())


def sparse_fc(spikes_ts, indices, values, scale, *, block_b=128, block_n=512):
    return _sfc.sparse_fc(spikes_ts, indices, values, scale, block_b=block_b,
                          block_n=block_n, interpret=_interpret())


def nm_fc(spikes_ts, packed, scale, *, n, m, block_b=128, block_n=512):
    return _nfc.nm_fc(spikes_ts, packed, scale, n=n, m=m, block_b=block_b,
                      block_n=block_n, interpret=_interpret())


def megastep(x, s0, u0, h0, s1, u1, h1, beta0, vth0, beta1, vth1,
             wargs, fcargs, *, precision, fc_mode, input_bits,
             nm_n=0, nm_m=0, spike=False):
    """Whole frame step (both cells + layout FC + counters) in one dispatch
    over an F-frame chunk — see ``kernels/megastep.py``.  ``spike=True``
    runs the spike-consuming matmuls over compacted event lists."""
    return _mega.megastep(x, s0, u0, h0, s1, u1, h1, beta0, vth0, beta1,
                          vth1, tuple(wargs), tuple(fcargs),
                          precision=precision, fc_mode=fc_mode,
                          input_bits=input_bits, nm_n=nm_n, nm_m=nm_m,
                          spike=spike, interpret=_interpret())

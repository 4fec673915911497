"""The paper's recurrent spiking neural network (Fig. 1, Eq. 1-3).

Two recurrent spiking layers + one FC readout, SNN time steps TS in {1, 2}.

Dependency structure (paper Fig. 3) — this is what enables the accelerator's
*parallel time steps*:

  * the recurrent input of frame t at time step ts is the spike output of
    frame t-1 at the SAME ts  ->  the TS stimulus matmuls of one frame are
    independent and share weights (computed here as one stacked matmul, the
    TPU analogue of fetching the weight once for both PE sets);
  * the membrane potential chains ts -> ts+1 *within* a frame (Eq. 2), and
    carries from the last ts of frame t-1 into ts=0 of frame t; this chain
    is cheap (elementwise) and stays sequential;
  * the L0 feedforward stimulus x[t] @ Wx does not depend on ts and is
    computed once and reused for all time steps (paper §III-D1 step 5);
  * the FC readout sums spikes over ts before the matmul (*merged spike*).

Everything is a pure function over an explicit parameter pytree; no
framework dependencies.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import lif as lif_lib
from repro.core import spike_ops
from repro.core.lif import LIFParams, LIFState


@dataclasses.dataclass(frozen=True)
class RSNNConfig:
    """Paper model hyper-parameters (Table I)."""

    input_dim: int = 40
    hidden_dim: int = 256  # 256 baseline, 128 after structured pruning
    fc_dim: int = 1920
    num_ts: int = 2  # SNN time steps (1 or 2; training may start higher)
    beta_init: float = 0.9
    vth_init: float = 1.0
    surrogate_slope: float = 25.0
    merged_spike: bool = True
    input_bits: int = 8  # 8-bit fixed-point input features
    hw_rounded_lif: bool = False  # power-of-2 beta/vth (inference hardware)
    dtype: Any = jnp.float32

    @property
    def layer_shapes(self) -> dict[str, tuple[int, int]]:
        h = self.hidden_dim
        return {
            "l0_wx": (self.input_dim, h),
            "l0_wh": (h, h),
            "l1_wx": (h, h),
            "l1_wh": (h, h),
            "fc_w": (h, self.fc_dim),
        }

    @property
    def num_params(self) -> int:
        return sum(a * b for a, b in self.layer_shapes.values())


class RSNNState(NamedTuple):
    """Carried across frames: per-ts recurrent spikes + LIF membrane chain."""

    h0: jax.Array  # (TS, B, H)  L0 spike outputs of the previous frame
    h1: jax.Array  # (TS, B, H)  L1 spike outputs of the previous frame
    lif0: LIFState  # membrane chain of L0 (last ts of the previous frame)
    lif1: LIFState


def init_params(key: jax.Array, cfg: RSNNConfig) -> dict:
    """Uniform(-1/sqrt(fan_in)) init, PyTorch-RNN style (paper trains in PyTorch)."""
    keys = jax.random.split(key, len(cfg.layer_shapes))
    params: dict[str, Any] = {}
    for k, (name, shape) in zip(keys, cfg.layer_shapes.items()):
        bound = 1.0 / jnp.sqrt(shape[0])
        params[name] = jax.random.uniform(k, shape, cfg.dtype, -bound, bound)
    params["lif0"] = lif_lib.init_lif(cfg.hidden_dim, cfg.beta_init, cfg.vth_init, cfg.dtype)
    params["lif1"] = lif_lib.init_lif(cfg.hidden_dim, cfg.beta_init, cfg.vth_init, cfg.dtype)
    return params


def init_state(cfg: RSNNConfig, batch: int, num_ts: int | None = None) -> RSNNState:
    ts = num_ts or cfg.num_ts
    h = cfg.hidden_dim
    # one buffer per leaf: the slot loops donate every leaf of the state
    return RSNNState(
        h0=jnp.zeros((ts, batch, h), cfg.dtype),
        h1=jnp.zeros((ts, batch, h), cfg.dtype),
        lif0=lif_lib.init_lif_state(batch, h, cfg.dtype),
        lif1=lif_lib.init_lif_state(batch, h, cfg.dtype),
    )


def _lif_chain(lif_params: LIFParams, state: LIFState, stim_ts: jax.Array,
               cfg: RSNNConfig) -> tuple[LIFState, jax.Array]:
    """Sequential membrane chain over the (small) TS axis. stim_ts: (TS,B,H)."""
    spikes = []
    for ts in range(stim_ts.shape[0]):
        state, h = lif_lib.lif_step(lif_params, state, stim_ts[ts],
                                    cfg.surrogate_slope, cfg.hw_rounded_lif)
        spikes.append(h)
    return state, jnp.stack(spikes)


def frame_step(params: dict, state: RSNNState, x_t: jax.Array, cfg: RSNNConfig,
               ) -> tuple[RSNNState, tuple[jax.Array, dict]]:
    """Process one 10-ms frame through the RSNN. x_t: (B, input_dim) (already
    8-bit-quantized integer-valued features). Returns (state, (logits, aux))."""
    num_ts = state.h0.shape[0]

    # ---- L0: feedforward stimulus shared across ts; recurrent per ts -----
    ff0 = x_t @ params["l0_wx"]  # (B,H), computed once, reused for all ts
    rec0 = state.h0 @ params["l0_wh"]  # (TS,B,H): stacked-ts matmul, W read once
    lif0, s0 = _lif_chain(params["lif0"], state.lif0, ff0[None] + rec0, cfg)

    # ---- L1: feedforward depends on per-ts spikes --------------------------
    stim1 = s0 @ params["l1_wx"] + state.h1 @ params["l1_wh"]
    lif1, s1 = _lif_chain(params["lif1"], state.lif1, stim1, cfg)

    # ---- FC readout: merged spike (one matmul for all ts) ------------------
    if cfg.merged_spike:
        logits = spike_ops.merged_spike_fc(s1, params["fc_w"])
    else:
        logits = (s1 @ params["fc_w"]).sum(axis=0)

    aux = {
        "spike_rate_l0": s0.mean(axis=(1, 2)),  # per-ts firing rate
        "spike_rate_l1": s1.mean(axis=(1, 2)),
        # OR over time steps: merged-spike effective density (cycle model)
        "union_rate_l1": s1.max(axis=0).mean(),
    }
    new_state = RSNNState(h0=s0, h1=s1, lif0=lif0, lif1=lif1)
    return new_state, (logits, aux)


def forward(params: dict, x: jax.Array, cfg: RSNNConfig,
            state: RSNNState | None = None, num_ts: int | None = None,
            ) -> tuple[jax.Array, RSNNState, dict]:
    """Run the RSNN over a frame sequence.

    x: (B, T, input_dim) raw features. Returns (logits (B,T,fc_dim), state, aux).
    """
    b = x.shape[0]
    ts = num_ts or cfg.num_ts
    if state is None:
        state = init_state(cfg, b, ts)
    xq, _ = spike_ops.quantize_input(x, cfg.input_bits)

    def body(st, x_t):
        st, (logits, aux) = frame_step(params, st, x_t, cfg)
        return st, (logits, aux)

    state, (logits, aux) = jax.lax.scan(body, state, jnp.swapaxes(xq, 0, 1))
    logits = jnp.swapaxes(logits, 0, 1)  # (B,T,fc_dim)
    aux = {k: v.mean(axis=0) for k, v in aux.items()}  # avg over frames -> (TS,)
    aux["input_bit_sparsity"] = spike_ops.input_bit_sparsity(xq, cfg.input_bits)
    return logits, state, aux


def loss_fn(params: dict, batch: dict, cfg: RSNNConfig,
            materialize: Callable[[dict], dict] | None = None,
            num_ts: int | None = None) -> tuple[jax.Array, dict]:
    """Frame-level cross entropy (paper §IV-A). batch: {features, labels}.

    ``materialize`` lets the compression pipeline rewrite weights
    (pruning masks, fake-quant) before the forward pass.
    """
    p = materialize(params) if materialize is not None else params
    logits, _, aux = forward(p, batch["features"], cfg, num_ts=num_ts)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1).squeeze(-1)
    mask = batch.get("mask", jnp.ones_like(nll))
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    preds = logits.argmax(-1)
    acc = ((preds == labels) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    aux = dict(aux, accuracy=acc, frame_error_rate=1.0 - acc)
    return loss, aux

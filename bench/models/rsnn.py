"""The paper's RSNN as the harness builds it: the family module that
configurations with ``"reference": "rsnn"`` name.

The float weights are drawn on the accelerator in one compiled call.  The
program packs them (pruning masks, int4 codes, scales, LIF constants) on the
host CPU, as a deployment packs a model offline, and then places the packed
model on the accelerator; this keeps the accelerator's arithmetic out of the
packing, so the packed model is exactly what the configuration states.
Inputs are 8-bit feature frames (``FeatureBank``).  The module imports the
program at load, so a checkout without it fails when the harness looks the
family up, before any run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding
from scipy.signal import lfilter

from repro.core.compression.compress import (CompressionConfig,
                                             init_compression)
from repro.core.lif import LIFParams
from repro.core.rsnn import RSNNConfig
from repro.serving.stream import CompiledRSNN, EngineConfig, StreamLoop

SHAPES = ("l0_wx", "l0_wh", "l1_wx", "l1_wh", "fc_w")


def weight_seed(seed: int) -> int:
    """A 31-bit key for the weights, drawn from a run's seed of any size."""
    return int(np.random.SeedSequence([seed, 0x5EED]).generate_state(1)[0]
               & 0x7FFFFFFF)


def layer_shapes(model: dict) -> dict:
    d, h, c = model["input_dim"], model["hidden_dim"], model["fc_dim"]
    return {"l0_wx": (d, h), "l0_wh": (h, h), "l1_wx": (h, h),
            "l1_wh": (h, h), "fc_w": (h, c)}


def make_params(config: dict, seed: int, device) -> dict:
    """Float weights uniform in +-1/sqrt(fan_in) and the LIF parameters at
    their configured beta and V_th, in one compiled call on ``device``."""
    model = config["model"]
    shapes = layer_shapes(model)
    h = model["hidden_dim"]
    raw_beta = math.log(model["beta_init"] / (1.0 - model["beta_init"]))
    raw_vth = math.log(math.expm1(model["vth_init"]))

    def init(key):
        keys = jax.random.split(key, len(SHAPES))
        out = {}
        for k, name in zip(keys, SHAPES):
            bound = 1.0 / math.sqrt(shapes[name][0])
            out[name] = jax.random.uniform(k, shapes[name], jnp.float32,
                                           -bound, bound)
        for i in (0, 1):
            out[f"lif{i}"] = (jnp.full((h,), raw_beta, jnp.float32),
                              jnp.full((h,), raw_vth, jnp.float32))
        return out

    key = jax.device_put(jax.random.key(weight_seed(seed)), device)
    return jax.block_until_ready(jax.jit(init)(key))


class FeatureBank:
    """AR(1) feature frames on the 8-bit grid, drawn from a seed: 8-bit
    fixed-point codes at a power-of-two scale, the format the paper's input
    layer takes."""

    def __init__(self, spec: dict, input_dim: int, scale_log2: int,
                 rng: np.random.Generator):
        n, a = int(spec["bank_frames"]), float(spec["ar"])
        e = rng.standard_normal((n, input_dim))
        x = lfilter([math.sqrt(1.0 - a * a)], [1.0, -a], e, axis=0)
        codes = np.rint(x * spec["std"] * 2.0 ** -scale_log2)
        self.codes = np.clip(codes, -128, 127).astype(np.int8)
        self.scale = 2.0 ** scale_log2

    @property
    def size(self) -> int:
        return len(self.codes)

    def frames(self, offset: int, length: int) -> np.ndarray:
        idx = (offset + np.arange(length)) % self.size
        return self.codes[idx].astype(np.float32) * np.float32(self.scale)


def input_bank(config: dict, features: dict,
               rng: np.random.Generator) -> FeatureBank:
    """The traffic's feature frames at the configuration's input width and
    fixed-point scale."""
    return FeatureBank(features, config["model"]["input_dim"],
                       config["input"]["scale_log2"], rng)


def build_loop(config: dict, params: dict, accel, cpu):
    """The program's ``StreamLoop`` serving ``config`` with ``params``."""
    m, comp, srv = config["model"], config["compression"], config["serving"]
    cfg = RSNNConfig(input_dim=m["input_dim"], hidden_dim=m["hidden_dim"],
                     fc_dim=m["fc_dim"], num_ts=m["num_ts"],
                     beta_init=m["beta_init"], vth_init=m["vth_init"],
                     merged_spike=m["merged_spike"],
                     input_bits=m["input_bits"],
                     hw_rounded_lif=m["hw_rounded_lif"])
    host = jax.device_put(params, cpu)
    host = dict(host, **{f"lif{i}": LIFParams(*host[f"lif{i}"])
                         for i in (0, 1)})
    scale = 2.0 ** config["input"]["scale_log2"]
    with jax.default_device(cpu):
        if comp["weight_bits"]:
            ccfg = CompressionConfig(fc_prune_frac=comp["fc_prune_frac"],
                                     weight_bits=comp["weight_bits"])
            ec = EngineConfig(backend=srv["backend"], precision="int4",
                              sparse_fc=comp["fc_layout"] != "dense",
                              input_scale=scale)
            engine = CompiledRSNN(cfg, host, ec, ccfg,
                                  init_compression(host, ccfg))
        else:
            ec = EngineConfig(backend=srv["backend"], precision="float",
                              input_scale=scale)
            engine = CompiledRSNN(cfg, host, ec)
    engine.place_weights(SingleDeviceSharding(accel))
    with jax.default_device(accel):
        return StreamLoop(engine, batch_slots=srv["slots"],
                          pipeline_depth=srv["pipeline_depth"],
                          ring_frames=srv["ring_frames"])

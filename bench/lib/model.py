"""The system under test, built from a configuration file and a seed.

The float weights are drawn on the accelerator in one compiled call.  The
program packs them (pruning masks, int4 codes, scales, LIF constants) on the
host CPU, as a deployment packs a model offline, and then places the packed
model on the accelerator; this keeps the accelerator's arithmetic out of the
packing, so the packed model is exactly what the configuration states.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

SHAPES = ("l0_wx", "l0_wh", "l1_wx", "l1_wh", "fc_w")


def weight_seed(seed: int) -> int:
    """A 31-bit key for the weights, drawn from a run's seed of any size."""
    return int(np.random.SeedSequence([seed, 0x5EED]).generate_state(1)[0]
               & 0x7FFFFFFF)


def layer_shapes(model: dict) -> dict:
    d, h, c = model["input_dim"], model["hidden_dim"], model["fc_dim"]
    return {"l0_wx": (d, h), "l0_wh": (h, h), "l1_wx": (h, h),
            "l1_wh": (h, h), "fc_w": (h, c)}


def make_params(model: dict, seed: int, device) -> dict:
    """Float weights uniform in +-1/sqrt(fan_in) and the LIF parameters at
    their configured beta and V_th, in one compiled call on ``device``."""
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


def build_loop(config: dict, params: dict, accel, cpu):
    """The program's ``StreamLoop`` serving ``config`` with ``params``."""
    from repro.core.compression.compress import (CompressionConfig,
                                                 init_compression)
    from repro.core.lif import LIFParams
    from repro.core.rsnn import RSNNConfig
    from repro.serving.stream import CompiledRSNN, EngineConfig, StreamLoop

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

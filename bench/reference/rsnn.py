"""Plain reference of the paper's RSNN (arXiv:2503.21337, Eq. 1-3).

Written from the paper and the configuration file alone; it imports nothing
of the system under test.  Two recurrent layers of leaky integrate-and-fire
neurons run ``num_ts`` time steps per 10-ms frame:

    U[t][ts] = stimulus[t][ts] + beta * U[t][ts-1] * (1 - h[t][ts-1])
    h[t][ts] = 1 if U[t][ts] >= V_th else 0

The membrane chains from the last time step of frame t-1 into the first of
frame t.  Layer 0's stimulus at time step ts is ``x[t] @ W0x + h0[t-1][ts]
@ W0h``; layer 1's is ``h0[t][ts] @ W1x + h1[t-1][ts] @ W1h``.  The readout
sums layer 1's spikes over the time steps (merged spike) and applies one
matrix.  Inputs are 8-bit fixed point.  Compression, where the
configuration states it: global magnitude pruning of the FC to the stated
fraction, then symmetric per-output-column quantization of every matrix to
``weight_bits``, ``q = clip(round(w / s), -2^(b-1), 2^(b-1) - 1)`` with
``s = max|w| / (2^(b-1) - 1)``, served as ``q * s``.

``precision="highest"`` computes every product in float32.  The control,
``precision="high"``, computes each dot in three bfloat16 passes (the
split ``a = a_hi + a_lo`` into bfloat16 halves, dropping ``a_lo @ b_lo``):
the next precision below float32 that a TPU offers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYERS = ("l0_wx", "l0_wh", "l1_wx", "l1_wh", "fc_w")
BLOCK = 32  # utterances per reference pass
CHUNK = 64  # frames per compiled reference call


def effective_weights(params: dict, compression: dict) -> dict:
    """The weights the configuration serves, from the float ones."""
    w = {n: np.asarray(params[n], np.float32) for n in LAYERS}
    frac = float(compression.get("fc_prune_frac") or 0.0)
    if frac > 0.0:
        fc = w["fc_w"]
        keep = max(int(round(fc.size * (1.0 - frac))), 1)
        thresh = np.sort(np.abs(fc).ravel())[-keep]
        w["fc_w"] = fc * (np.abs(fc) >= thresh).astype(np.float32)
    bits = compression.get("weight_bits")
    if bits:
        qmax = np.float32(2.0 ** (bits - 1) - 1)
        for n in LAYERS:
            amax = np.abs(w[n]).max(axis=0, keepdims=True)
            scale = np.maximum(amax, np.float32(1e-8)) / qmax
            q = np.clip(np.round(w[n] / scale), -qmax - 1, qmax)
            w[n] = (q * scale).astype(np.float32)
    return w


def lif_constants(params: dict) -> dict:
    """beta = sigmoid(raw_beta), V_th = softplus(raw_vth), per layer."""
    out = {}
    for i in (0, 1):
        raw_beta, raw_vth = (np.asarray(a, np.float32)
                             for a in params[f"lif{i}"])
        out[f"beta{i}"] = np.asarray(jax.nn.sigmoid(raw_beta))
        out[f"vth{i}"] = np.asarray(jax.nn.softplus(raw_vth))
    return out


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _dot(a, b, precision: str):
    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.dot(a, b, precision=hp)
    if precision == "high":
        (ah, al), (bh, bl) = _split(a), _split(b)
        return (jnp.dot(ah, bh, precision=hp) + jnp.dot(ah, bl, precision=hp)
                + jnp.dot(al, bh, precision=hp))
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("num_ts", "precision"))
def _chunk(w, lif, carry, xq, *, num_ts: int, precision: str):
    """Advance ``carry`` over ``xq`` (F, B, D) -> (carry, logits (F, B, C))."""

    def lif_chain(stim, u, h, beta, vth):
        spikes = []
        for t in range(num_ts):
            u = stim[t] + beta * u * (1.0 - h)
            h = (u >= vth).astype(jnp.float32)
            spikes.append(h)
        return jnp.stack(spikes), u, h

    def frame(c, x):
        h0, u0, sp0, h1, u1, sp1 = c
        ff0 = _dot(x, w["l0_wx"], precision)
        stim0 = ff0[None] + _dot(h0, w["l0_wh"], precision)
        s0, u0, sp0 = lif_chain(stim0, u0, sp0, lif["beta0"], lif["vth0"])
        stim1 = _dot(s0, w["l1_wx"], precision) + _dot(h1, w["l1_wh"],
                                                       precision)
        s1, u1, sp1 = lif_chain(stim1, u1, sp1, lif["beta1"], lif["vth1"])
        logits = _dot(s1.sum(axis=0), w["fc_w"], precision)
        return (s0, u0, sp0, s1, u1, sp1), logits

    return jax.lax.scan(frame, carry, xq)


def logits(utts: list, params: dict, config: dict, precision: str,
           device) -> dict:
    """{i: logits (T_i, fc_dim)} for every utterance ``utts[i]`` (raw
    features, (T_i, input_dim)), each run from a zero state, with the
    weights that ``config`` serves made from the float ``params`` on the
    host CPU and every dot at ``precision`` on ``device``."""
    if config["compute"]["dtype"] != "float32":
        raise ValueError("the plain reference computes in float32 only, "
                         f"not {config['compute']['dtype']!r}")
    with jax.default_device(jax.devices("cpu")[0]):
        host_params = jax.device_get(params)
        weights = effective_weights(host_params, config["compression"])
        lif = lif_constants(host_params)
    return dict(_blocks(utts, weights, lif, config["model"],
                        2.0 ** config["input"]["scale_log2"], precision,
                        device))


def _blocks(utts: list, weights: dict, lif: dict, model: dict,
            input_scale: float, precision: str, device):
    """Yield ``(i, logits)`` for ``logits``, in blocks of similar length."""
    ts, h, d = model["num_ts"], model["hidden_dim"], model["input_dim"]
    qmax = 2.0 ** (model["input_bits"] - 1)
    w = jax.device_put(weights, device)
    lc = jax.device_put(lif, device)
    order = sorted(range(len(utts)), key=lambda i: len(utts[i]))
    for b0 in range(0, len(order), BLOCK):
        idx = order[b0:b0 + BLOCK]
        tmax = max(len(utts[i]) for i in idx)
        steps = -(-tmax // CHUNK)
        x = np.zeros((steps * CHUNK, BLOCK, d), np.float32)
        for j, i in enumerate(idx):
            x[:len(utts[i]), j] = utts[i]
        xq = np.clip(np.round(x / np.float32(input_scale)), -qmax, qmax - 1)
        zs, zu = np.zeros((ts, BLOCK, h), np.float32), np.zeros((BLOCK, h),
                                                                np.float32)
        carry = jax.device_put((zs, zu, zu, zs, zu, zu), device)
        out = []
        for c in range(steps):
            carry, lg = _chunk(w, lc, carry,
                               jax.device_put(xq[c * CHUNK:(c + 1) * CHUNK],
                                              device),
                               num_ts=ts, precision=precision)
            out.append(np.asarray(lg))
        block = np.concatenate(out)  # (steps * CHUNK, BLOCK, C)
        for j, i in enumerate(idx):
            yield i, block[:len(utts[i]), j]

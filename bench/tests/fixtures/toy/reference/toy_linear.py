"""Plain reference of the toy family: ``h[t] = decay * h[t-1] + x[t] @ W``,
``logits[t] = h[t] @ V`` from a zero state, in ``jax.numpy``.  Its own
comparison: the number held to the cell's limit is ``worst_err``, the
largest logit error of any compared frame over ``max(1, max |logit|)``."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("precision",))
def _scan(w, v, decay, x, *, precision: str):
    """x (T, B, D) -> logits (T, B, C)."""

    def step(h, xt):
        h = decay * h + jnp.dot(xt, w, precision=precision)
        return h, jnp.dot(h, v, precision=precision)

    h0 = jnp.zeros((x.shape[1], w.shape[1]), jnp.float32)
    return jax.lax.scan(step, h0, x)[1]


def logits(utts: list, params: dict, config: dict, precision: str,
           device) -> dict:
    host = jax.device_get(params)
    tmax = max(len(u) for u in utts)
    x = np.zeros((tmax, len(utts), config["model"]["input_dim"]),
                 np.float32)
    for i, u in enumerate(utts):
        x[:len(u), i] = u
    w, v, xd = jax.device_put((np.asarray(host["w"]), np.asarray(host["v"]),
                               x), device)
    out = np.asarray(_scan(w, v, jnp.float32(config["model"]["decay"]), xd,
                           precision=precision))
    return {i: out[:len(u), i] for i, u in enumerate(utts)}


@dataclasses.dataclass
class Outcome:
    worst_err: float
    frames: int

    @property
    def numbers(self) -> dict:
        return {"worst_err": self.worst_err}

    def describe(self) -> str:
        return f"frames={self.frames} worst_err={self.worst_err!r}"


def compare(served: list, reference: dict) -> Outcome:
    """``served``: [(frames, logits or None)]; a lost request or a NaN
    reads inf."""
    worst, frames = 0.0, 0
    for i, (x, got) in enumerate(served):
        want = reference[i]
        frames += len(x)
        if got is None or got.shape != want.shape:
            return Outcome(float("inf"), frames)
        err = np.abs(got - want).max() / max(1.0, float(np.abs(want).max()))
        worst = max(worst, float(np.nan_to_num(err, nan=np.inf)))
    return Outcome(worst, frames)

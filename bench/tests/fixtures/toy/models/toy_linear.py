"""A model family the harness takes from added files alone: a linear
recurrence per slot,

    h[t] = decay * h[t-1] + x[t] @ W,    logits[t] = h[t] @ V,

served one frame per step for every slot by a small numpy loop that keeps
to ``bench.lib.drive.ServedLoop``.  With ``serving.reset_at_refill`` false
the loop keeps a slot's state from its previous occupant: a lost reset,
which the check has to catch."""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np


def make_params(config: dict, seed: int, device) -> dict:
    m = config["model"]
    d, n, c = m["input_dim"], m["state_dim"], m["classes"]

    def init(key):
        kw, kv = jax.random.split(key)
        return {"w": jax.random.normal(kw, (d, n), jnp.float32) / d ** 0.5,
                "v": jax.random.normal(kv, (n, c), jnp.float32) / n ** 0.5}

    key = jax.device_put(jax.random.key(seed % 2**31), device)
    return jax.block_until_ready(jax.jit(init)(key))


class Bank:
    """Standard normal input frames drawn from the seed."""

    def __init__(self, size: int, dim: int, rng: np.random.Generator):
        self.x = rng.standard_normal((size, dim)).astype(np.float32)

    @property
    def size(self) -> int:
        return len(self.x)

    def frames(self, offset: int, length: int) -> np.ndarray:
        return self.x[(offset + np.arange(length)) % self.size]


def input_bank(config: dict, features: dict,
               rng: np.random.Generator) -> Bank:
    return Bank(int(features["bank_frames"]), config["model"]["input_dim"],
                rng)


class Request:
    def __init__(self, sid: int, frames: np.ndarray, classes: int):
        self.sid, self.frames, self.classes = sid, frames, classes
        self.logits: list = []

    def stacked_logits(self) -> np.ndarray:
        if not self.logits:
            return np.zeros((0, self.classes), np.float32)
        return np.stack(self.logits)


class Loop:
    pipeline_depth = 0  # a step's logits are on the host when it returns

    def __init__(self, w, v, decay: float, slots: int,
                 reset_at_refill: bool):
        self.w, self.v, self.decay = w, v, np.float32(decay)
        self.reset_at_refill = reset_at_refill
        self.h = np.zeros((slots, w.shape[1]), np.float32)
        self.queue: collections.deque = collections.deque()
        self.finished: list = []
        self.slot_req: list = [None] * slots
        self.pos = [0] * slots
        self.frames_served = 0
        self._sid = 0

    def submit(self, frames: np.ndarray) -> int:
        r = Request(self._sid, np.asarray(frames, np.float32),
                    self.v.shape[1])
        self._sid += 1
        self.queue.append(r)
        return r.sid

    def step_once(self) -> bool:
        for i, r in enumerate(self.slot_req):
            if r is None and self.queue:
                self.slot_req[i], self.pos[i] = self.queue.popleft(), 0
                if self.reset_at_refill:
                    self.h[i] = 0.0
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return False
        x = np.stack([self.slot_req[i].frames[self.pos[i]] for i in active])
        h = self.decay * self.h[active] + x @ self.w
        self.h[active] = h
        logits = h @ self.v
        for j, i in enumerate(active):
            r = self.slot_req[i]
            r.logits.append(logits[j])
            self.pos[i] += 1
            if self.pos[i] == len(r.frames):
                self.finished.append(r)
                self.slot_req[i] = None
        self.frames_served += len(active)
        return True

    def flush(self) -> None:
        pass

    def run(self) -> list:
        while self.step_once():
            pass
        return self.finished

    def reset_metrics(self) -> None:
        self.frames_served = 0


def build_loop(config: dict, params: dict, accel, cpu) -> Loop:
    host = jax.device_get(params)
    return Loop(np.asarray(host["w"]), np.asarray(host["v"]),
                config["model"]["decay"], config["serving"]["slots"],
                config["serving"]["reset_at_refill"])

"""Slot reuse: a slot's recurrent state is zeroed when its next occupant
is placed, by one compiled reset per refill that takes the slots as a mask.

A freed slot keeps its previous occupant's state (stale, never read) until
the refill; the next stream in that slot must still see a fresh membrane,
bit for bit, on every backend family and loop contract, and on the sharded
loop (4 virtual devices, in a subprocess: the device count is fixed when
JAX starts)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rsnn
from repro.serving import stream as S

FIRST, SECOND = 14, 9  # frames of the slot's first and next occupant


def _streams(cfg, seed=5):
    """The slot's first and next occupant, and the input scale
    ``rsnn.forward`` calibrates on the next one alone."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(FIRST + SECOND, cfg.input_dim)).astype(np.float32)
    first, second = x[:FIRST], x[FIRST:]
    return first, second, S.calibrate_input_scale(jnp.asarray(second))


def _nonzero(state) -> bool:
    return any(float(np.abs(np.asarray(a)).sum()) > 0
               for a in jax.tree.leaves(state))


@pytest.mark.parametrize("chunk", [1, 2], ids=["frame", "chunk2"])
@pytest.mark.parametrize("depth", [0, 2], ids=["sync", "pipelined"])
@pytest.mark.parametrize("backend", ["jnp", "fused", "delta"])
def test_next_occupant_sees_a_fresh_state(small_cfg, rng_key, backend,
                                          depth, chunk):
    params = rsnn.init_params(rng_key, small_cfg)
    first, second, scale = _streams(small_cfg)
    eng = S.CompiledRSNN(small_cfg, params,
                         S.EngineConfig(backend=backend, input_scale=scale))
    loop = S.StreamLoop(eng, batch_slots=1, pipeline_depth=depth,
                        ring_frames=4, chunk_frames=chunk)
    loop.submit(first)
    loop.run()
    # nothing zeroes a slot at completion: the first occupant's state stays
    assert _nonzero(loop.state)
    sid = loop.submit(second)
    got = {r.sid: r for r in loop.run()}[sid].stacked_logits()
    want, _, _ = rsnn.forward(params, jnp.asarray(second)[None], small_cfg)
    np.testing.assert_array_equal(got, np.asarray(want[0]))
    assert loop.reset_dispatches == loop.refills == 2


def test_refills_of_one_step_cost_one_reset_dispatch(small_cfg, rng_key):
    params = rsnn.init_params(rng_key, small_cfg)
    first, second, scale = _streams(small_cfg)
    eng = S.CompiledRSNN(small_cfg, params, S.EngineConfig(input_scale=scale))
    loop = S.StreamLoop(eng, batch_slots=4, pipeline_depth=2, ring_frames=8)
    for t in (3, 3, 3, 5):
        loop.submit(first[:t])
    loop.step_once()  # one refill places all four
    assert (loop.refills, loop.reset_dispatches) == (4, 1)
    for _ in range(3):
        loop.step_once()  # the three 3-frame streams complete together
    for t in (2, 4, 6):
        loop.submit(second[:t])
    loop.step_once()  # and their three slots refill in one dispatch
    assert (loop.refills, loop.reset_dispatches) == (7, 2)
    loop.run()
    assert loop.completions == 7


def test_one_reset_executable_per_slot_count(small_cfg, rng_key):
    params = rsnn.init_params(rng_key, small_cfg)
    first, _, scale = _streams(small_cfg)
    eng = S.CompiledRSNN(small_cfg, params, S.EngineConfig(input_scale=scale))
    loops = [S.StreamLoop(eng, batch_slots=n, pipeline_depth=2, ring_frames=4)
             for n in (2, 8)]
    resets = sorted(k for k in eng._aot_cache if k[0] == "reset")
    assert resets == [("reset", 2), ("reset", 8)]
    assert eng.compile_count == 4  # a step and a reset per slot count
    for loop in loops:  # every slot of both loops filled and refilled
        for t in range(2 * loop.slots):
            loop.submit(first[:1 + t % 5])
        loop.run()
        assert loop.refills == 2 * loop.slots
    assert eng.compile_count == 4
    assert eng._loop_reset._cache_size() == 0  # no lazy per-call builds


def test_reset_slot_takes_an_index_or_a_mask(small_cfg):
    st = jax.tree.map(lambda a: a + 1.0, rsnn.init_state(small_cfg, 4))
    by_index = S.reset_slot(st, 2)
    by_mask = S.reset_slot(st, np.array([False, False, True, False]))
    for a, b in zip(jax.tree.leaves(by_index), jax.tree.leaves(by_mask)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    two = S.reset_slot(st, np.array([True, False, True, False]))
    np.testing.assert_array_equal(np.asarray(two.lif0.u)[:, 0],
                                  [0.0, 1.0, 0.0, 1.0])
    np.testing.assert_array_equal(np.asarray(two.h1)[:, :, 0],
                                  [[0.0, 1.0, 0.0, 1.0]] * 2)


_SHARDED = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
from repro.core import rsnn
from repro.core.rsnn import RSNNConfig
from repro.serving import stream as S
from repro.serving.sharded import ShardedStreamLoop

first, second = int(sys.argv[1]), int(sys.argv[2])
assert len(jax.devices()) == 4
cfg = RSNNConfig(input_dim=8, hidden_dim=16, fc_dim=12, num_ts=2)
params = rsnn.init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(5)
x = rng.normal(size=(4, first + second, 8)).astype(np.float32)
scale = S.calibrate_input_scale(jnp.asarray(x[:, first:]))
eng = S.CompiledRSNN(cfg, params, S.EngineConfig(input_scale=scale))
loop = ShardedStreamLoop(eng, batch_slots=4, max_frames=16, ring_frames=4)
spec = str(loop.state.h0.sharding.spec)
for b in range(4):
    loop.submit(x[b, :first])
loop.run()
stale = bool(np.abs(np.asarray(loop.state.lif1.u)).sum() > 0)
sids = [loop.submit(x[b, first:]) for b in range(4)]
got = {r.sid: r.stacked_logits() for r in loop.run()}
want, _, _ = rsnn.forward(params, jnp.asarray(x[:, first:]), cfg)
same = all(np.array_equal(got[s], np.asarray(want[b]))
           for b, s in enumerate(sids))
print(json.dumps({"same": same, "stale": stale, "spec": spec,
                  "spec_after": str(loop.state.h0.sharding.spec),
                  "resets": [loop.reset_dispatches, loop.refills]}))
"""


def test_next_occupant_sees_a_fresh_state_sharded():
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_SHARDED), str(FIRST),
         str(SECOND)], capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["stale"] and got["same"]
    assert "data" in got["spec"] and got["spec_after"] == got["spec"]
    assert got["resets"] == [2, 8]  # four slots placed per refill

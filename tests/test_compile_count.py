"""Zero steady-state compiles: after loop construction (which AOT-warms
the step and slot-reset executables and the per-slot eager ring helpers),
serving MUST NOT trigger any new XLA compilation.  This guards the
compile-storm class of bug permanently: a shape- or index-dependent op
on the hot path (the PR-6 regression was a ``ring[i, :fill]`` harvest
slice baking every (slot, length) pair into its own executable) shows up
here as a nonzero compile count instead of as multi-ms p99 outliers in
the load generator.

Counting uses ``jax.monitoring`` events (every lowering/compile records
``/jax/compilation_cache/compile_requests_use_cache``; cached
executable-cache hits record nothing), cross-checked against the engine's
own ``compile_count`` of AOT builds."""

import numpy as np
import pytest
from jax import monitoring

from repro.core import rsnn
from repro.serving import stream as S
from repro.serving.sharded import ShardedStreamLoop


class _CompileListener:
    """Collects jax compile events between __enter__ and __exit__."""

    def __init__(self):
        self.events = []

    def __call__(self, event, **kw):
        if "compile" in event:
            self.events.append(event)

    def __enter__(self):
        monitoring.register_event_listener(self)
        return self

    def __exit__(self, *exc):
        monitoring.unregister_event_listener(self)


def _utts(cfg, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [np.round(rng.normal(0, 20, (t, cfg.input_dim))
                     ).astype(np.float32) for t in lens]


@pytest.fixture
def engine(small_cfg, rng_key):
    params = rsnn.init_params(rng_key, small_cfg)
    return S.CompiledRSNN(small_cfg, params, S.EngineConfig(backend="jnp"))


@pytest.mark.parametrize("depth,chunk", [(0, 1), (2, 1), (0, 4), (2, 4)])
def test_zero_steady_state_compiles(engine, small_cfg, depth, chunk):
    """A full serve straight after construction — first serve included, no
    separate warmup run — compiles nothing, in every loop contract."""
    loop = S.StreamLoop(engine, batch_slots=2, pipeline_depth=depth,
                        ring_frames=8, chunk_frames=chunk)
    with _CompileListener() as listener:
        for u in _utts(small_cfg, (5, 9, 3, 7, 2, 8)):
            loop.submit(u)
        done = loop.run()
        if loop.track_sparsity:
            loop.sparsity_profile()
    assert listener.events == [], (
        f"steady-state serve compiled: {sorted(set(listener.events))}")
    assert len(done) == 6


def test_zero_steady_state_compiles_sharded(engine, small_cfg):
    """Sharded steady state: the submit frontend pins each utterance into
    its buffer row with a per-(slot, length) eager op, so one warmup serve
    over the workload's length distribution populates those executables;
    after it, a serve of fresh streams compiles nothing."""
    loop = ShardedStreamLoop(engine, batch_slots=2, max_frames=16,
                             pipeline_depth=2, ring_frames=8, chunk_frames=2)
    lens = (5, 9, 3, 7, 2, 8)
    for u in _utts(small_cfg, lens):  # warmup: same length distribution
        loop.submit(u)
    loop.run()
    loop.reset_metrics()
    with _CompileListener() as listener:
        for u in _utts(small_cfg, lens, seed=9):
            loop.submit(u)
        done = loop.run()
        loop.sparsity_profile()
    assert listener.events == [], (
        f"sharded steady-state serve compiled: {sorted(set(listener.events))}")
    assert len(done) == 12  # warmup's 6 finished streams + the 6 measured


def test_aot_cache_shared_across_loops(engine):
    """Two loops with the same (slots, chunk, ring) signature on one engine
    share the AOT executable — the second construction builds nothing."""
    S.StreamLoop(engine, batch_slots=2, pipeline_depth=2,
                 ring_frames=8, chunk_frames=2)
    before = engine.compile_count
    with _CompileListener() as listener:
        S.StreamLoop(engine, batch_slots=2, pipeline_depth=2,
                     ring_frames=8, chunk_frames=2)
    assert engine.compile_count == before
    assert listener.events == []


def test_aot_warmup_counts_builds(engine):
    """Distinct step signatures build distinct executables, visible in the
    engine's compile_count (the executable-cache counter assertion); a
    new slot count builds one slot-reset executable beside its step."""
    before = engine.compile_count
    S.StreamLoop(engine, batch_slots=3, pipeline_depth=2,
                 ring_frames=12, chunk_frames=3)
    assert engine.compile_count == before + 2  # step + reset
    S.StreamLoop(engine, batch_slots=3, pipeline_depth=2,
                 ring_frames=12, chunk_frames=4)  # new chunk -> new build
    assert engine.compile_count == before + 3  # same slots: reset reused


def test_opt_out_still_serves(engine, small_cfg):
    """aot_warmup=False falls back to lazy jit compilation — same results,
    just no zero-compile guarantee."""
    loop = S.StreamLoop(engine, batch_slots=2, pipeline_depth=2,
                        ring_frames=8, chunk_frames=2, aot_warmup=False)
    warm = S.StreamLoop(engine, batch_slots=2, pipeline_depth=2,
                        ring_frames=8, chunk_frames=2)
    utts = _utts(small_cfg, (5, 9, 3))
    for u in utts:
        loop.submit(u)
        warm.submit(u)
    for a, b in zip(loop.run(), warm.run()):
        np.testing.assert_array_equal(a.stacked_logits(), b.stacked_logits())

"""Event-driven spike-broadcast kernels vs oracle + bit-identity properties.

The central contract: the matmul over each row's event list is
BIT-IDENTICAL to the dense matmul on the same input
(``np.testing.assert_array_equal``, not allclose).  It holds by
construction: the accumulate over the kept events is the dense dot over
the row with the truncated tail zeroed, so no tolerance is needed at any
precision.  The finite-queue truncation is also checked against a numpy
loop over integer codes, whose sums are exact in any order.
``hypothesis`` is optional (try-import); a deterministic density sweep
keeps the property running on bare installs.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import complexity as C
from repro.kernels import ops, ref
from repro.kernels import spike_broadcast as sb

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on bare installs
    HAVE_HYPOTHESIS = False


def _spikes(rng, shape, density):
    return jnp.asarray(rng.random(shape) < density, jnp.float32)


# ------------------------------------------------------------- compaction


def test_compact_spikes_ascending_events():
    x = jnp.asarray([[0.0, 2.0, 0.0, 3.0, 1.0],
                     [0.0, 0.0, 0.0, 0.0, 0.0],
                     [1.0, 1.0, 1.0, 1.0, 1.0]])
    idx, vals = sb.compact_spikes(x, capacity=5)
    np.testing.assert_array_equal(np.asarray(idx[0, :3]), [1, 3, 4])
    np.testing.assert_array_equal(np.asarray(vals[0]), [2, 3, 1, 0, 0])
    np.testing.assert_array_equal(np.asarray(vals[1]), np.zeros(5))
    np.testing.assert_array_equal(np.asarray(idx[2]), np.arange(5))
    np.testing.assert_array_equal(np.asarray(vals[2]), np.ones(5))


def test_compact_spikes_overflow_truncates_tail():
    """Rows past capacity drop their HIGHEST-index events (finite queue)."""
    x = jnp.zeros((1, 8)).at[0, jnp.asarray([1, 3, 6])].set(1.0)
    idx, vals = sb.compact_spikes(x, capacity=2)
    np.testing.assert_array_equal(np.asarray(idx[0]), [1, 3])
    np.testing.assert_array_equal(np.asarray(vals[0]), [1, 1])


# ------------------------------------------- kernel vs oracle / dense parity


@pytest.mark.parametrize("density", [0.0, 0.1, 0.38, 0.46, 0.9, 1.0])
@pytest.mark.parametrize("rows,k,n", [(8, 16, 12), (128, 128, 256),
                                      (64, 256, 64)])
def test_kernel_bit_identical_to_dense(density, rows, k, n):
    """Density sweep incl. all-zero (0.0) and all-one (1.0) spike rows."""
    rng = np.random.default_rng(int(density * 100) + rows + k)
    x = _spikes(rng, (rows, k), density)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    out = ops.spike_broadcast(x, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x @ w))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.spike_broadcast_ref(x, w)))


def test_kernel_matches_oracle_under_overflow():
    """capacity < population count: kernel and oracle agree on the
    truncated tail (both drop the highest-index events)."""
    rng = np.random.default_rng(3)
    x = _spikes(rng, (32, 64), 0.7)  # ~45 events per row >> capacity
    w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    for cap in (1, 8, 32):
        out = ops.spike_broadcast(x, w, capacity=cap)
        want = ref.spike_broadcast_ref(x, w, capacity=cap)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    # lossless capacity == dense, even via the explicit capacity arg
    out = ops.spike_broadcast(x, w, capacity=64)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x @ w))


@pytest.mark.parametrize("capacity", [1, 5, 17, 64])
def test_event_queue_matches_numpy_loop_on_int_codes(capacity):
    """Independent reference: keep each row's first ``capacity`` events in
    a Python loop, then an integer matmul.  Int4 codes times spike counts
    sum exactly in f32, so the kernel must match bit for bit."""
    rng = np.random.default_rng(capacity)
    x = (rng.random((24, 64)) < 0.4) * rng.integers(1, 3, (24, 64))
    w = rng.integers(-8, 8, (64, 40))
    kept = np.zeros_like(x)
    for r in range(x.shape[0]):
        cols = np.flatnonzero(x[r])[:capacity]
        kept[r, cols] = x[r, cols]
    got = ops.spike_broadcast(jnp.asarray(x, jnp.float32),
                              jnp.asarray(w, jnp.float32), capacity=capacity)
    np.testing.assert_array_equal(np.asarray(got), (kept @ w).astype(np.float32))


def test_merged_union_path():
    """3-D (TS, B, H) input merges over TS in VMEM — the FC readout's
    merged-spike-union variant (values in {0..TS})."""
    rng = np.random.default_rng(4)
    s = _spikes(rng, (2, 16, 32), 0.4)
    w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
    out = ops.spike_broadcast(s, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(s.sum(0) @ w))
    want = ref.spike_broadcast_ref(s, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_gathered_values_not_assumed_binary():
    """The event values are gathered, not assumed 1: arbitrary magnitudes
    ride through (the merged {0..TS} counts are the serving case)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(8, 16)) * _spikes(rng, (8, 16), 0.5),
                    jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    np.testing.assert_array_equal(np.asarray(ops.spike_broadcast(x, w)),
                                  np.asarray(x @ w))


# ------------------------------------------------------------- spike_cell


@pytest.mark.parametrize("ts", [1, 2])
@pytest.mark.parametrize("b,h", [(6, 32), (128, 128)])
def test_spike_cell_bit_identical_to_ref(ts, b, h):
    rng = np.random.default_rng(ts * 100 + b + h)
    stim = jnp.asarray(rng.normal(size=(ts, b, h)), jnp.float32)
    s_prev = _spikes(rng, (ts, b, h), 0.38)
    w = jnp.asarray(rng.normal(size=(h, h)) * 0.1, jnp.float32)
    u0 = jnp.asarray(rng.normal(size=(b, h)), jnp.float32)
    h0 = _spikes(rng, (b, h), 0.5)
    beta = jnp.asarray(rng.uniform(0.5, 0.99, h), jnp.float32)
    vth = jnp.asarray(rng.uniform(0.5, 1.5, h), jnp.float32)
    sp_k, u_k = ops.spike_cell(stim, s_prev, w, u0, h0, beta, vth)
    sp_r, u_r = ref.rsnn_cell_ref(stim, s_prev, w, u0, h0, beta, vth)
    np.testing.assert_array_equal(np.asarray(sp_k), np.asarray(sp_r))
    np.testing.assert_array_equal(np.asarray(u_k), np.asarray(u_r))


# --------------------------------------------------------- megastep spike


def test_megastep_spike_mode_bit_identical():
    rng = np.random.default_rng(9)
    ts, b, h, d, fc, frames = 2, 4, 16, 8, 12, 3
    x = jnp.asarray(rng.integers(-10, 10, (frames, b, d)), jnp.float32)
    s0 = _spikes(rng, (ts, b, h), 0.4)
    s1 = _spikes(rng, (ts, b, h), 0.4)
    u0 = jnp.asarray(rng.normal(size=(b, h)), jnp.float32)
    u1 = jnp.asarray(rng.normal(size=(b, h)), jnp.float32)
    wargs = tuple(jnp.asarray(rng.normal(size=(d if i == 0 else h, h)) * 0.3,
                              jnp.float32) for i in range(4))
    fcw = jnp.asarray(rng.normal(size=(h, fc)), jnp.float32)
    beta0 = jnp.asarray(rng.uniform(0.5, 0.99, h), jnp.float32)
    beta1 = jnp.asarray(rng.uniform(0.5, 0.99, h), jnp.float32)
    vth = jnp.ones((h,), jnp.float32)
    kw = dict(precision="float", fc_mode="dense_float", input_bits=8)
    want = ref.megastep_ref(x, s0, u0, s0[-1], s1, u1, s1[-1], beta0, vth,
                            beta1, vth, wargs, (fcw,), **kw)
    got = ops.megastep(x, s0, u0, s0[-1], s1, u1, s1[-1], beta0, vth,
                       beta1, vth, wargs, (fcw,), spike=True, **kw)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


# ------------------------------------------------- serving capacity contract


def test_engine_config_capacity_validation():
    from repro.serving.stream import EngineConfig

    with pytest.raises(ValueError, match="spike_capacity must be >= 1"):
        EngineConfig(backend="spike", spike_capacity=0)
    with pytest.raises(ValueError, match="event-queue knob"):
        EngineConfig(backend="jnp", spike_capacity=8)
    EngineConfig(backend="spike", spike_capacity=8)  # ok
    EngineConfig(backend="delta", spike_capacity=8)  # ok


def test_spike_backend_capacity_lossless_vs_truncating():
    """A capacity >= H serves bit-identically to jnp; capacity=1 runs (and
    truncates, so logits may drift) — the finite-event-queue model."""
    from repro.core import rsnn
    from repro.serving.stream import CompiledRSNN, EngineConfig, StreamLoop

    cfg = rsnn.RSNNConfig(input_dim=8, hidden_dim=16, fc_dim=12, num_ts=2)
    params = rsnn.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    utt = rng.normal(size=(6, cfg.input_dim)).astype(np.float32)

    def serve(engine_cfg):
        loop = StreamLoop(CompiledRSNN(cfg, params, engine_cfg),
                          batch_slots=2, pipeline_depth=0)
        loop.submit(utt)
        return loop.run()[0].stacked_logits()

    base = serve(EngineConfig(backend="jnp", input_scale=0.05))
    lossless = serve(EngineConfig(backend="spike", input_scale=0.05,
                                  spike_capacity=cfg.hidden_dim))
    np.testing.assert_array_equal(np.asarray(lossless), np.asarray(base))
    tight = serve(EngineConfig(backend="spike", input_scale=0.05,
                               spike_capacity=1))
    assert tight.shape == base.shape and np.isfinite(tight).all()


# ------------------------------------------------ complexity accounting


def test_spike_broadcast_report():
    cfg = dataclasses.replace  # noqa: F841 (keep import honest)
    from repro.core.rsnn import RSNNConfig

    cfg = RSNNConfig(input_dim=40, hidden_dim=128, fc_dim=1920, num_ts=2)
    rep = C.spike_broadcast_report(cfg, 2)  # analytic Fig. 18 defaults
    assert rep["gathered"] < rep["dense"]
    assert 0.0 < rep["skip_fraction"] < 1.0
    dense_prof = C.SparsityProfile(1.0, (1.0, 1.0), (1.0, 1.0),
                                   (1.0, 1.0), 1.0)
    rep1 = C.spike_broadcast_report(cfg, 2, sparsity=dense_prof)
    assert rep1["gathered"] == rep1["dense"]
    assert rep1["skip_fraction"] == 0.0


# -------------------------------------------- property: gather == dense
# (deterministic tier always runs; hypothesis fuzzes it when installed)


def _property(rows, k, n, density, seed):
    rng = np.random.default_rng(seed)
    x = _spikes(rng, (rows, k), density)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    got = np.asarray(ops.spike_broadcast(x, w))
    np.testing.assert_array_equal(got, np.asarray(
        jnp.dot(x, w, preferred_element_type=jnp.float32)))


@pytest.mark.parametrize("seed", range(6))
def test_gather_equals_dense_deterministic(seed):
    rng = np.random.default_rng(seed)
    rows = int(rng.integers(1, 48))
    k = int(rng.integers(2, 96))
    n = int(rng.integers(1, 64))
    _property(rows, k, n, float(rng.uniform()), seed + 1000)


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(rows=st.integers(1, 32), k=st.integers(2, 64),
           n=st.integers(1, 32), density=st.floats(0.0, 1.0),
           seed=st.integers(0, 2**16))
    def test_gather_equals_dense_fuzzed(rows, k, n, density, seed):
        _property(rows, k, n, density, seed)

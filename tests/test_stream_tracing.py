"""The serving loop's profiler spans and counters.

``StreamLoop`` records its step path as ``rsnn.*`` annotations and counts
at the same boundaries (docs/serving.md, "Tracing a serving loop").  Here
a tiny loop serves, on the CPU under ``jax.profiler.trace``, utterances
longer than the ring (watermark flushes) and more of them than slots
(refills), on the pipelined frame path, the chunked path and the sharded
loop on 4 virtual devices (a subprocess: the device count is fixed when
JAX starts).  The counters must equal what the lengths give, the spans
must nest as documented and carry the request's ``sid``, and tracing must
not change a logit.
"""

import glob
import json
import math
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import rsnn
from repro.serving import stream as S

LENS = [5, 9, 3, 7, 6, 10, 4, 8]
RING = 4
FC_BYTES = 12 * 4  # small_cfg's fc_dim, float32

# span -> the spans it may be a direct child of (None: outside every span)
PARENTS = {
    "rsnn.step": {None},
    "rsnn.refill": {"rsnn.step"},
    "rsnn.assemble": {"rsnn.step"},
    "rsnn.dispatch": {"rsnn.step"},
    "rsnn.complete": {"rsnn.step"},
    "rsnn.reset_slot": {"rsnn.refill"},
    "rsnn.fence": {"rsnn.step"},
    "rsnn.retire": {"rsnn.step", None},  # None: flush() after the run
    "rsnn.fence_wait": {"rsnn.retire"},
    "rsnn.egress": {"rsnn.retire"},
}
PER_REQUEST = ("rsnn.complete", "rsnn.reset_slot", "rsnn.egress")


def _utterances(lens, dim=8, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(t, dim)).astype(np.float32) for t in lens]


def expected_counts(lens, ring, fc_bytes):
    """What the counters must read after serving ``lens``: one block per
    ``ring`` frames of each utterance, the last one its completion's."""
    blocks = sum(math.ceil(n / ring) for n in lens)
    return {"refills": len(lens), "completions": len(lens),
            "watermark_flushes": blocks - len(lens),
            "egress_bytes": blocks * ring * fc_bytes,
            "egress_valid_bytes": sum(lens) * fc_bytes}


def program_spans(trace_dir):
    """[(name, stats, parent name)] of the ``rsnn.*`` host events of the
    newest trace under ``trace_dir``, nested per thread."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                              dict(e.stats)) for e in line.events
                             if e.name.startswith("rsnn.")),
                            key=lambda e: (e[1], -e[2]))
            stack = []
            for name, a, b, stats in events:
                while stack and stack[-1][1] <= a:
                    stack.pop()
                out.append((name, stats, stack[-1][0] if stack else None))
                stack.append((name, b))
    return out


def check_spans(spans, counts, sids):
    names = {n for n, _, _ in spans}
    assert names == set(PARENTS), names
    for name, stats, parent in spans:
        assert parent in PARENTS[name], (name, parent)
        if name in PER_REQUEST:
            assert stats["sid"] in sids, (name, stats)
        if name in ("rsnn.complete", "rsnn.reset_slot"):
            assert "slot" in stats
    per = {n: [s for m, s, _ in spans if m == n] for n in names}
    # one reset per refill, none at completion; one egress per request
    resets = [p for n, _, p in spans if n == "rsnn.reset_slot"]
    assert resets == ["rsnn.refill"] * counts["refills"]
    assert len(per["rsnn.complete"]) == (counts["completions"]
                                         + counts["watermark_flushes"])
    assert sorted(s["sid"] for s in per["rsnn.egress"]) == sorted(sids)
    assert sum(s["bytes"] for s in per["rsnn.egress"]) == (
        counts["egress_bytes"])
    assert sum(s["valid_bytes"] for s in per["rsnn.egress"]) == (
        counts["egress_valid_bytes"])
    assert len(per["rsnn.fence_wait"]) == len(per["rsnn.retire"])


@pytest.fixture
def engine(small_cfg, rng_key):
    params = rsnn.init_params(rng_key, small_cfg)
    utts = _utterances(LENS, small_cfg.input_dim)
    scale = S.calibrate_input_scale(jnp.asarray(np.concatenate(utts, 0)))
    return lambda: S.CompiledRSNN(small_cfg, params,
                                  S.EngineConfig(input_scale=scale)), utts


def check_reset_dispatches(dispatches, refills, steps):
    """One compiled reset per refill that placed a slot: at least one, and
    no more than the slots placed or the steps taken."""
    assert 1 <= dispatches <= min(refills, steps), (dispatches, refills,
                                                    steps)


def _serve(loop, utts):
    sids = [loop.submit(u) for u in utts]
    done = loop.run()
    return sids, {r.sid: r.stacked_logits() for r in done}


@pytest.mark.parametrize("chunk", [1, 2], ids=["frame", "chunked"])
def test_spans_and_counters_of_the_pipelined_loop(engine, tmp_path, chunk):
    make, utts = engine
    loop = S.StreamLoop(make(), batch_slots=2, pipeline_depth=2,
                        ring_frames=RING, chunk_frames=chunk)
    with jax.profiler.trace(str(tmp_path)):
        sids, traced = _serve(loop, utts)
    counts = expected_counts(LENS, RING, FC_BYTES)
    assert {k: getattr(loop, k) for k in counts} == counts
    check_spans(program_spans(str(tmp_path)), counts, set(sids))
    check_reset_dispatches(loop.reset_dispatches, loop.refills, loop.steps)

    plain = S.StreamLoop(make(), batch_slots=2, pipeline_depth=2,
                         ring_frames=RING, chunk_frames=chunk)
    _, untraced = _serve(plain, utts)
    assert {k: getattr(plain, k) for k in counts} == counts
    for sid in sids:
        np.testing.assert_array_equal(traced[sid], untraced[sid])


def test_reset_metrics_zeroes_the_counters(engine):
    make, utts = engine
    loop = S.StreamLoop(make(), batch_slots=2, ring_frames=RING)
    _serve(loop, utts)
    assert loop.watermark_flushes > 0 and loop.egress_bytes > 0
    assert loop.reset_dispatches > 0
    loop.reset_metrics()
    assert [loop.refills, loop.completions, loop.watermark_flushes,
            loop.egress_bytes, loop.egress_valid_bytes,
            loop.reset_dispatches] == [0] * 6


_SHARDED = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
from repro.core import rsnn
from repro.core.rsnn import RSNNConfig
from repro.serving import stream as S
from repro.serving.sharded import ShardedStreamLoop

trace_dir, lens, ring = sys.argv[1], json.loads(sys.argv[2]), int(sys.argv[3])
assert len(jax.devices()) == 4
cfg = RSNNConfig(input_dim=8, hidden_dim=16, fc_dim=12, num_ts=2)
params = rsnn.init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(7)
utts = [rng.normal(size=(t, 8)).astype(np.float32) for t in lens]
scale = S.calibrate_input_scale(jnp.asarray(np.concatenate(utts, 0)))

def serve(traced):
    eng = S.CompiledRSNN(cfg, params, S.EngineConfig(input_scale=scale))
    loop = ShardedStreamLoop(eng, batch_slots=4, max_frames=16,
                             ring_frames=ring)
    sids = [loop.submit(u) for u in utts]
    if traced:
        with jax.profiler.trace(trace_dir):
            done = loop.run()
    else:
        done = loop.run()
    counts = {k: getattr(loop, k) for k in (
        "refills", "completions", "watermark_flushes", "egress_bytes",
        "egress_valid_bytes")}
    resets = [loop.reset_dispatches, loop.refills, loop.steps]
    return sids, {r.sid: r.stacked_logits() for r in done}, counts, resets

sids, a, counts, resets = serve(True)
_, b, counts_b, _ = serve(False)
same = all(np.array_equal(a[s], b[s]) for s in sids)
print(json.dumps({"sids": sids, "counts": counts, "counts_untraced":
                  counts_b, "resets": resets, "same": same}))
"""


def test_spans_and_counters_of_the_sharded_loop(tmp_path):
    lens = LENS + [11, 2]  # more requests than the 4 slots
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_SHARDED), str(tmp_path),
         json.dumps(lens), str(RING)],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    counts = expected_counts(lens, RING, FC_BYTES)
    assert got["counts"] == counts and got["counts_untraced"] == counts
    assert got["same"]
    check_reset_dispatches(*got["resets"])
    check_spans(program_spans(str(tmp_path)), counts, set(got["sids"]))

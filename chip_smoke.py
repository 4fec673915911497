"""Serve the paper-width int4 RSNN on one TPU and check it against the CPU.

    python chip_smoke.py               # one chip: every registered backend
    python chip_smoke.py --four-chips  # four chips: the sharded loop only

The model is the paper's ``PRUNED`` configuration (``configs/rsnn_timit.py``:
40/128/1920, TS=2) with int4 weights and 40% unstructured FC pruning stored
as padded CSC — the recipe ``examples/stream_asr.py`` serves by default —
built from a seed, and the FC readout runs through the CSC layout.  The
utterances are ``TimitLikeStream`` features of 40-100 frames.

One chip: the ``fused`` backend serves every utterance through
``StreamLoop(pipeline_depth=2)``, once at ``SLOTS`` slots (streams refill
freed slots) and once at ``CEILING_SLOTS`` (the most slots whose mega-step
fits the chip's VMEM); every other registered backend then serves a few
frames of a few streams.  Each phase also steps the engine frame by frame
to compare spikes.  Four chips: ``ShardedStreamLoop`` on a 4-device
``stream_mesh`` against a one-chip ``StreamLoop`` on the same streams,
after checking that the state, frame buffer, ring and weights are spread
over all four devices.

The reference is the ``ref`` backend run on the host CPU in this process.
Each phase prints one line; the last line of stdout is one JSON object,
and the exit code is 0 only when every phase passed.  Without a TPU the
script exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

# the reference runs on the host CPU, so keep that platform next to the TPU
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.rsnn_timit import PRUNED  # noqa: E402
from repro.core import rsnn  # noqa: E402
from repro.core.compression.compress import (CompressionConfig,  # noqa: E402
                                             init_compression)
from repro.data.synthetic import SpeechDataConfig, TimitLikeStream  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import backends  # noqa: E402
from repro.serving.sharded import ShardedStreamLoop, stream_mesh  # noqa: E402
from repro.serving.stream import (CompiledRSNN, EngineConfig,  # noqa: E402
                                  StreamLoop, calibrate_input_scale)

SEED = 0
STREAMS = 48  # fused phases: utterances of 40-100 frames
SLOTS = 32  # fused phase: fewer slots than streams, so slots refill
CEILING_SLOTS = 512  # 1024 slots exceed the mega-step's VMEM (compile check)
SMALL = dict(streams=8, frames=12, slots=8)  # every other backend
STEP_FRAMES = 12  # frame-by-frame spike comparison, 8 slots
FOUR_CHIP_SLOTS = 64

# Tolerances.  The chip accumulates each dot in another order than the CPU
# (the MXU's f32 passes), so a logit differs from the reference by float
# rounding, ~1e-6 of its scale: ATOL_REL bounds that with margin.  A spike
# is a threshold of the membrane, so a membrane within rounding distance of
# the threshold flips on one side and not the other, and the recurrence
# carries the flip to the end of that stream.  Such flips are rare but not
# absent, so every check over a whole run is a share, not an equality:
# MIN_AGREE of the frames must keep every logit within ATOL_REL and the
# same argmax, and MIN_SPIKE_AGREE of the spikes must match (over a dozen
# frames, one diverged slot of eight costs at most a few percent).  A wrong
# kernel misses these by far: it changes most frames, and spikes by about
# the spike density (5-40% here).
# One check is exact in kind: a slot-frame whose L1 spikes all match must
# have every logit within ATOL_REL, since the readout sees nothing else.
ATOL_REL = 1e-4
MIN_AGREE = 0.9
MIN_SPIKE_AGREE = 0.98
REFERENCE_BACKENDS = ("ref", "jnp")  # pure jnp: no Pallas kernel to find


def build_model(cfg, seed: int):
    """The stream_asr recipe: int4 weights, 40% FC pruning stored as CSC."""
    params = rsnn.init_params(jax.random.PRNGKey(seed), cfg)
    ccfg = CompressionConfig(fc_prune_frac=0.4, weight_bits=4)
    return params, ccfg, init_compression(params, ccfg)


def utterances(cfg, n: int, seed: int) -> list[np.ndarray]:
    """``n`` TimitLikeStream utterances of 40-100 frames (stream_asr's)."""
    data = TimitLikeStream(SpeechDataConfig(input_dim=cfg.input_dim,
                                            seed=seed))
    rng = np.random.default_rng(seed)
    return [data.batch(1, step=i)["features"][0][:int(rng.integers(40, 101))]
            for i in range(n)]


def make_engine(model, cfg, backend: str, scale) -> CompiledRSNN:
    params, ccfg, cstate = model
    ec = EngineConfig(backend=backend, precision="int4", sparse_fc=True,
                      input_scale=scale)
    return CompiledRSNN(cfg, params, ec, ccfg, cstate)


def serve(engine, utts, slots: int):
    """Serve ``utts`` through a pipelined StreamLoop -> (per-stream logits,
    compile seconds, loop)."""
    t0 = time.perf_counter()
    loop = StreamLoop(engine, batch_slots=slots, pipeline_depth=2)
    compile_s = time.perf_counter() - t0
    for u in utts:
        loop.submit(u)
    logits = [r.stacked_logits() for r in loop.run()]
    return logits, compile_s, loop


def step_spikes(engine, utts, frames: int):
    """Step the engine frame by frame over the first ``frames`` frames of
    each utterance (one slot each) -> (L0 spikes, L1 spikes, logits), each
    stacked over frames."""
    x = np.stack([u[:frames] for u in utts], axis=1)  # (T, B, D)
    state = engine.init_state(x.shape[1])
    s0, s1, out = [], [], []
    for xt in x:
        state, logits, _ = engine.step(state, engine.quantize_features(xt))
        core = getattr(state, "rsnn", state)  # the delta backend's carry
        s0.append(np.asarray(core.h0))
        s1.append(np.asarray(core.h1))
        out.append(np.asarray(logits))
    return np.stack(s0), np.stack(s1), np.stack(out)


def compare_logits(got: list, want: list) -> dict:
    """Per-frame logit agreement of two runs over the same streams."""
    if [g.shape for g in got] != [w.shape for w in want]:
        return {"ok": False, "why": "logit shapes differ"}
    g, w = np.concatenate(got), np.concatenate(want)
    if not np.isfinite(g).all():
        return {"ok": False, "why": "non-finite logits"}
    atol = ATOL_REL * max(1.0, float(np.abs(w).max()))
    dmax = np.abs(g - w).max(axis=-1)
    within = float((dmax <= atol).mean())
    argmax = float((g.argmax(-1) == w.argmax(-1)).mean())
    return {"ok": within >= MIN_AGREE and argmax >= MIN_AGREE,
            "frames": int(len(g)), "max_dlogit": float(dmax.max()),
            "frames_within_atol": within, "argmax_agree": argmax}


def compare_steps(got, want) -> dict:
    """Spike agreement of two frame-by-frame runs, and the readout check
    on every slot-frame whose L1 spikes match."""
    (g0, g1, gl), (w0, w1, wl) = got, want
    spikes = float(np.concatenate([(g0 == w0).ravel(),
                                   (g1 == w1).ravel()]).mean())
    same = (g1 == w1).all(axis=(1, 3))  # (T, B): all TS x H spikes match
    atol = ATOL_REL * max(1.0, float(np.abs(wl).max()))
    readout = bool((np.abs(gl - wl).max(axis=-1)[same] <= atol).all())
    return {"ok": spikes >= MIN_SPIKE_AGREE and readout,
            "spike_agree": spikes,
            "slot_frames_same_l1": float(same.mean()), "readout_exact": readout}


def _has_kernel(loop) -> bool:
    return "tpu_custom_call" in loop.step_executable.as_text()


def _memory(loop) -> dict:
    ma = loop.step_executable.memory_analysis()
    return {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes") if ma is not None and hasattr(ma, k)}


def backend_phase(name, model, cfg, scale, utts, slots, *, device,
                  ref_device, require_kernels: bool, ref_logits=None,
                  tag=None) -> dict:
    """Serve ``utts`` with backend ``name`` on ``device`` and compare with
    the ``ref`` backend on ``ref_device``; step both to compare spikes."""
    with jax.default_device(ref_device):
        ref = make_engine(model, cfg, "ref", scale)
        if ref_logits is None:
            ref_logits = serve(ref, utts, min(slots, 8))[0]
        ref_steps = step_spikes(ref, utts[:8], STEP_FRAMES)
    with jax.default_device(device):
        eng = make_engine(model, cfg, name, scale)
        logits, compile_s, loop = serve(eng, utts, slots)
        steps = step_spikes(eng, utts[:8], STEP_FRAMES)
        res = {"phase": tag or name, "slots": slots,
               "compile_s": round(compile_s, 3),
               "frames_served": int(loop.frames_served),
               "step_memory": _memory(loop)}
    res.update(compare_logits(logits, ref_logits))
    st = compare_steps(steps, ref_steps)
    res["ok"] = res["ok"] and st.pop("ok")
    res.update(st)
    if require_kernels and name not in REFERENCE_BACKENDS:
        res["tpu_custom_call"] = _has_kernel(loop)
        res["ok"] = res["ok"] and res["tpu_custom_call"]
    return res


def one_chip_phases(cfg, *, device, ref_device, require_kernels: bool,
                    streams: int = STREAMS, slots: int = SLOTS,
                    ceiling_slots: int = CEILING_SLOTS,
                    small: dict = SMALL, seed: int = SEED, report=print):
    """Every registered backend on ``device`` against ``ref`` on
    ``ref_device``; ``report`` sees each phase's result as it lands."""
    model = build_model(cfg, seed)
    utts = utterances(cfg, streams, seed)
    scale = calibrate_input_scale(np.concatenate(utts), cfg.input_bits)
    with jax.default_device(ref_device):
        ref_logits = serve(make_engine(model, cfg, "ref", scale), utts,
                           8)[0]
    kw = dict(device=device, ref_device=ref_device,
              require_kernels=require_kernels)
    results = []
    for n_slots, tag in ((slots, "fused"), (ceiling_slots, "fused_ceiling")):
        results.append(backend_phase("fused", model, cfg, scale, utts,
                                     n_slots, ref_logits=ref_logits,
                                     tag=tag, **kw))
        report(results[-1])
    few = [u[:small["frames"]] for u in utts[:small["streams"]]]
    for name in backends.available():
        if name == "fused":
            continue
        results.append(backend_phase(name, model, cfg, scale, few,
                                     small["slots"], **kw))
        report(results[-1])
    return results


def _devices_of(tree) -> set:
    return {d for a in jax.tree_util.tree_leaves(tree)
            for d in a.sharding.device_set}


def four_chip_phase(cfg, devices, *, streams: int = STREAMS,
                    slots: int = FOUR_CHIP_SLOTS, seed: int = SEED) -> dict:
    """``ShardedStreamLoop`` over ``devices`` against a one-chip
    ``StreamLoop`` on ``devices[0]``, both with the fused backend."""
    model = build_model(cfg, seed)
    utts = utterances(cfg, streams, seed)
    scale = calibrate_input_scale(np.concatenate(utts), cfg.input_bits)
    with jax.default_device(devices[0]):
        want, _, _ = serve(make_engine(model, cfg, "fused", scale), utts,
                           slots)
    eng = make_engine(model, cfg, "fused", scale)
    t0 = time.perf_counter()
    loop = ShardedStreamLoop(eng, batch_slots=slots,
                             mesh=stream_mesh(devices),
                             max_frames=max(len(u) for u in utts),
                             pipeline_depth=2)
    compile_s = time.perf_counter() - t0
    placed = {"state": _devices_of(loop.state), "frame_buffer":
              _devices_of(loop._buf), "ring": _devices_of(loop._ring),
              "weights": _devices_of((eng._ctx.quant, eng._ctx.sparse))}
    spread = {k: len(v) for k, v in placed.items()}
    for u in utts:
        loop.submit(u)
    got = [r.stacked_logits() for r in loop.run()]
    res = {"phase": "four_chips", "slots": slots, "devices": len(devices),
           "compile_s": round(compile_s, 3),
           "frames_served": int(loop.frames_served), "placed_on": spread}
    res.update(compare_logits(got, want))
    res["bit_identical"] = all(np.array_equal(g, w)
                               for g, w in zip(got, want))
    res["ok"] = res["ok"] and all(n == len(devices)
                                  for n in spread.values())
    return res


def _print_phase(res: dict) -> None:
    print(" ".join(f"{k}={v}" for k, v in res.items()), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the ShardedStreamLoop-vs-one-chip phase "
                         "on four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform}); "
              "this check runs only on the chip", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 TPU devices, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"device kind={devices[0].device_kind} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache}", flush=True)
    if args.four_chips:
        devices = devices[:4]
        results = [four_chip_phase(PRUNED, devices)]
        _print_phase(results[0])
    else:
        results = one_chip_phases(PRUNED, device=devices[0],
                                  ref_device=jax.devices("cpu")[0],
                                  require_kernels=True, report=_print_phase)
    ok = all(r["ok"] for r in results)
    print(json.dumps({"ok": ok, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

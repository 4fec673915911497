"""Stream speech through the compressed RSNN in real time.

  PYTHONPATH=src python examples/stream_asr.py [--precision int4] \
      [--backend jnp|ref|pallas|sparse|fused|delta|spike|fused_spike] \
      [--layout dense|csc|nm] \
      [--slots 4] [--streams 8] [--sharded] [--pipeline-depth 2] \
      [--artifact DIR | --save-artifact DIR] [--frames N]

Builds the paper's model (optionally packed to the pruned/int4 deployment
artifact via core/sparse.py), submits a queue of unequal-length synthetic
utterances to the slot-based StreamLoop, and reports throughput, the
measured sparsity profile, and the zero-skip MMAC/s the served traffic
would cost on the accelerator (paper Fig. 13).

``--artifact DIR`` serves straight from an on-disk deployment artifact
(core/artifact.py — e.g. the output of
``python -m repro.training.rsnn_pipeline --artifact DIR``): model config,
precision, preferred backend, and the static input scale all come from the
manifest, and the logits are bit-identical to serving the same model
packed in-process.  ``--save-artifact DIR`` writes the in-process model
out as such an artifact instead.  ``--frames N`` truncates every utterance
to N frames (the CI smoke serves 3 frames from a pipeline-built artifact).

``--layout`` picks the packed-weight recipe (docs/layouts.md): ``csc``
(default) is the paper's 40% unstructured FC pruning stored as padded
CSC; ``nm`` prunes the FC 2:4 and packs it into the group-packed N:M
layout (no index padding), serving the readout through the layout's
zero-skip path; ``dense`` skips pruning entirely (int4 only).  With
``--save-artifact`` the layout choice lands in the manifest, so
``--artifact`` serves the same path back.

``--sharded`` serves the same queue through serving/sharded.py instead:
the slot batch and recurrent state shard over every local device (set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a CPU mesh),
weights replicate, and an ``AsyncFeaturizer`` thread quantizes utterances
ahead of the slot loop.

``--pipeline-depth`` selects the step-lifecycle contract (docs/serving.md):
0 is the v1 synchronous loop (per-frame logit + counter fetches), >= 1 the
double-buffered contract-v2 loop — logits stay in a device-side ring until
stream completion and counters accumulate on device, so the report's
"host syncs/frame" drops from 2 to ~1/stream-length.
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro.core import complexity as C
from repro.core import rsnn, sparse
from repro.core.compression.compress import (CompressionConfig,
                                             init_compression,
                                             pack_for_inference)
from repro.core import spike_ops
from repro.core.rsnn import RSNNConfig
from repro.data.featurize import AsyncFeaturizer
from repro.data.synthetic import SpeechDataConfig, TimitLikeStream
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import backends
from repro.serving.sharded import ShardedStreamLoop
from repro.serving.stream import (CompiledRSNN, EngineConfig, StreamLoop,
                                  calibrate_input_scale)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    choices=list(backends.available()),
                    help="execution backend (default: jnp, or the "
                         "artifact's preferred backend)")
    ap.add_argument("--precision", default="int4", choices=["float", "int4"],
                    help="ignored with --artifact (manifest decides)")
    ap.add_argument("--layout", default="csc",
                    choices=["dense", "csc", "nm"],
                    help="packed-weight recipe: csc = 40%% unstructured FC "
                         "pruning in padded CSC (paper), nm = 2:4 FC "
                         "pruning in the group-packed N:M layout served "
                         "zero-skip, dense = no pruning; ignored with "
                         "--artifact (manifest decides)")
    ap.add_argument("--hidden", type=int, default=128,
                    help="paper's pruned width; ignored with --artifact")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--frames", type=int, default=None,
                    help="truncate every utterance to this many frames")
    ap.add_argument("--artifact", default=None, metavar="DIR",
                    help="serve from an on-disk deployment artifact "
                         "(config/precision/scale from its manifest)")
    ap.add_argument("--save-artifact", default=None, metavar="DIR",
                    help="write the in-process model out as an artifact")
    ap.add_argument("--sharded", action="store_true",
                    help="shard the slot batch over all local devices with "
                         "an async featurization front-end")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="in-flight device steps (0 = v1 synchronous loop)")
    args = ap.parse_args()

    if args.artifact:
        if args.save_artifact:
            ap.error("--save-artifact conflicts with --artifact (the model "
                     "already lives on disk)")
        engine = CompiledRSNN.from_artifact(args.artifact,
                                            backend=args.backend)
        cfg = engine.cfg
        scale = engine._input_scale
        if scale is None:
            raise SystemExit("artifact carries no input scale; re-export it "
                             "with calibration")
        print(f"serving from artifact {args.artifact} "
              f"(precision {engine.engine.precision}, "
              f"backend {engine.engine.backend})")
    else:
        cfg = RSNNConfig(hidden_dim=args.hidden)
        params = rsnn.init_params(jax.random.PRNGKey(0), cfg)
        if args.layout == "dense":
            ccfg = CompressionConfig(weight_bits=4)
        elif args.layout == "nm":
            from repro.core.compression.compress import PruneSpec
            ccfg = CompressionConfig(weight_bits=4, prune_specs=(
                ("fc_w", PruneSpec(kind="nm", n=2, m=4)),))
        else:
            ccfg = CompressionConfig(fc_prune_frac=0.4, weight_bits=4)
        # the nm layout is there to be *executed*: route the readout
        # through the packed layout's zero-skip path (int4 only)
        sparse_fc = args.layout == "nm" and args.precision == "int4"
        cstate = init_compression(params, ccfg)

    data = TimitLikeStream(SpeechDataConfig())
    rng = np.random.default_rng(0)
    utts = []
    for i in range(args.streams):
        feats = data.batch(1, step=i)["features"][0]
        n = int(rng.integers(40, 101))  # 0.4-1.0 s
        if args.frames is not None:
            n = min(n, args.frames)
        utts.append(feats[:n])

    if not args.artifact:
        scale = calibrate_input_scale(np.concatenate(utts, axis=0),
                                      cfg.input_bits)
        engine = CompiledRSNN(
            cfg, params,
            EngineConfig(backend=args.backend or "jnp",
                         precision=args.precision, sparse_fc=sparse_fc,
                         input_scale=scale),
            ccfg=ccfg, cstate=cstate)
        if args.save_artifact:
            from repro.core import artifact as artifact_lib
            if engine.packed is not None:
                artifact_lib.save_artifact(
                    args.save_artifact, cfg=cfg, packed=engine.packed,
                    ccfg=ccfg, input_scale=scale,
                    backend=args.backend or "jnp", sparse_fc=sparse_fc)
            else:
                artifact_lib.save_artifact(
                    args.save_artifact, cfg=cfg, params=params,
                    input_scale=scale, backend=args.backend or "jnp")
            print(f"wrote deployment artifact to {args.save_artifact}")
    feat = None
    if args.sharded:
        # quantize ahead of the loop on a host thread; starts now, so the
        # front-end overlaps model packing and engine compilation below
        # (depth per data.featurize.prefetch_depth: slots + pipeline depth)
        from repro.data.featurize import prefetch_depth
        feat = AsyncFeaturizer(
            utts, lambda u: np.asarray(
                spike_ops.quantize_input(u, cfg.input_bits, scale)[0]),
            depth=prefetch_depth(args.slots, args.pipeline_depth))

    if engine.packed is not None:
        rep = sparse.packed_size_report(engine.packed)
        tags = ", ".join(f"{n}={v['layout']}" for n, v in rep.items()
                         if isinstance(v, dict) and "layout" in v)
        print(f"packed model: {rep['broadcast_total_bytes'] / 1e6:.3f} MB "
              f"nonzero int4 (paper Fig. 12: 0.10 MB); "
              f"{rep['total_bytes'] / 1e6:.3f} MB packed layout "
              f"({tags or 'all dense'})")

    if args.sharded:
        max_frames = max(len(u) for u in utts)
        loop = ShardedStreamLoop(engine, batch_slots=args.slots,
                                 max_frames=max_frames,
                                 pipeline_depth=args.pipeline_depth)
        print(f"sharded over {loop.mesh.shape['data']} devices "
              f"({args.slots} slots, pipeline depth {args.pipeline_depth}, "
              f"async featurization front-end)")
        # submit_stream serves while the featurizer drains, so the timed
        # region must cover it — its steps count toward the totals below
        t0 = time.time()
        loop.submit_stream(feat, quantized=True)
    else:
        loop = StreamLoop(engine, batch_slots=args.slots,
                          pipeline_depth=args.pipeline_depth)
        for u in utts:
            loop.submit(u)
        t0 = time.time()
    done = loop.run()
    dt = time.time() - t0

    frames = int(loop.counters.frames)
    print(f"\nserved {len(done)} streams / {frames} frames in {dt:.2f}s over "
          f"{loop.steps} engine steps ({args.slots} slots, "
          f"pipeline depth {args.pipeline_depth}, "
          f"{loop.host_syncs / frames:.3f} host syncs/frame)")
    dev = jax.devices()[0]
    print(f"  {frames / dt:.0f} frames/s on {dev.platform} "
          f"({dev.device_kind}) -> "
          f"{frames / dt / C.FRAMES_PER_SECOND:.1f} concurrent real-time streams")
    prof = loop.sparsity_profile()
    print(f"  measured sparsity: input bits {1 - prof.input_bit_density:.0%}, "
          f"L0 spikes {1 - np.mean(prof.l0_density):.0%}, "
          f"L1 spikes {1 - np.mean(prof.l1_density):.0%} "
          f"(paper Fig. 18: 57% / 60-71%)")
    mmac = loop.mmac_per_second()  # at the engine's deployed FC pruning
    dense = C.mmac_per_second(cfg, cfg.num_ts,
                              fc_prune_frac=engine.fc_prune_frac)
    print(f"  zero-skip complexity of this traffic: {mmac:.2f} MMAC/s "
          f"(dense {dense:.2f}; paper's operating point 13.86)")
    top = done[0]
    preds = top.stacked_logits().argmax(-1)
    print(f"  stream {top.sid}: {len(top.frames)} frames -> "
          f"first predictions {preds[:8].tolist()}")


if __name__ == "__main__":
    enable_compile_cache()
    main()

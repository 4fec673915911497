"""Streaming compressed-RSNN inference engine (frames -> slots -> state).

This is the serving path for the paper's actual workload: always-on speech
recognition over 10-ms audio frames from a pruned/int4 0.1 MB model — the
recurrent-state analogue of the token-LM continuous batching in
``serving/engine.py`` (both loops run on ``serving.slots.SlotScheduler``).

Lifecycle (contract v2 — pipelined)
-----------------------------------
1. **Frames.** Audio arrives as per-utterance feature sequences
   ``(T, input_dim)``.  Features are quantized to the 8-bit fixed-point
   input format with a *static* calibrated scale (hardware has no per-chunk
   calibration), so chunked streaming is bit-identical to a one-shot pass.
2. **Slots.** ``StreamLoop`` packs N concurrent utterances into a fixed
   decode batch of ``batch_slots`` slots.  Every engine step advances each
   active slot by one frame; a finished slot is refilled from the queue
   without stopping the batch, its recurrent state zeroed (``reset_slot``)
   as the next utterance takes it — continuous batching with membrane
   potentials instead of KV rows.
3. **State.** ``CompiledRSNN`` carries ``RSNNState`` (per-ts spikes + LIF
   membrane chain) across frames; parity with ``core.rsnn.forward`` over the
   concatenated utterance is the engine's correctness contract
   (tests/test_stream.py, tests/test_stream_pipeline.py).
4. **Pipelining (v2).** ``step_once`` *dispatches* device step ``t`` and
   returns without a device->host transfer: per-slot logits are written into
   a device-side ring (``(slots, ring_frames, fc_dim)``) inside the jitted
   step, and the packed sparsity-counter vector is accumulated into a
   device-side running sum.  Up to ``pipeline_depth`` steps stay in flight;
   the host only blocks on step ``t - pipeline_depth + 1`` (a fence, not a
   transfer), so the host-side frame assembly/scheduling of step ``t+1``
   overlaps device execution of step ``t`` — the serving analogue of the
   paper's parallel time-step datapath and EdgeDRNN's continuous DMA-fed
   pipeline.  A stream's logits cross to the host **once per stream** (on
   completion, or on a ring-watermark flush for streams longer than
   ``ring_frames``), and the counter accumulator crosses **once per
   drain** (``flush()`` / metrics read), not once per frame.
   ``pipeline_depth=0`` preserves the v1 synchronous contract — one logit
   fetch and one counter fetch per step — and is the bit-parity comparator.

Scheduling (which frame each step serves, refill/reset order) is identical
in both contracts: completion is decided by host-side frame counts, never
by logit values, so the pipelined loop can advance its bookkeeping at
dispatch time.  Logits are bit-identical between v1 and v2 on float and
int4 paths (tests/test_stream_pipeline.py).

Execution paths (``EngineConfig``): ``backend`` names a registered entry in
``serving/backends.py`` — ``ref``/``jnp`` (oracles), ``pallas`` (fused
kernels), ``sparse`` (pallas + the fused zero-skip CSC FC of
``kernels/sparse_fc.py``) — which resolves to a uniform op table
(``rsnn_cell`` / ``ff_matmul`` / ``fc``) per layer and per precision;
``precision`` selects float weights or the packed int4 model from
``core/sparse.py``; ``sparse_fc`` additionally routes the pruned FC through
the zero-skipping CSC path of the chosen backend.  New kernels plug in by
registering a backend; the engine itself never selects kernels.
``CompiledRSNN.from_artifact`` builds the engine from the versioned
on-disk artifact of ``core/artifact.py`` (the compression pipeline's
output) with logits bit-identical to packing in-process.

Scaling out: ``serving/sharded.py`` runs this same loop with the slot
batch, recurrent state, pinned frame buffer, and logit ring sharded over a
device mesh (weights replicated via ``place_weights``), and
``data/featurize.py`` prefetches quantized frames ahead of the slot loop
(``AsyncFeaturizer.for_loop`` sizes its queue to ``batch_slots +
pipeline_depth`` so refills never wait on featurization).

Sparsity counters -> MMAC/s
---------------------------
Each step emits per-slot spike/bit counters (L0/L1 per-ts spike counts, the
merged-spike union count, input one-bits), masked to *active* slots and
reduced on device.  In the pipelined contract they accumulate on device and
fold into ``core.complexity.SparsityCounters`` on drain; ``profile()`` is
the measured ``SparsityProfile`` and ``mmac_per_second()`` evaluates the
paper's zero-skip complexity table (Fig. 13 / the 13.86 MMAC/s operating
point) on live traffic instead of the published Fig. 18 constants.  Pass
``track_sparsity=False`` to detach the sink: the loop then dispatches a
counter-free step (no per-step counter math, no fetch, ever).
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import complexity, rsnn, sparse, spike_ops
from repro.core import lif as lif_lib
from repro.core.compression.compress import (CompressionConfig,
                                             CompressionState,
                                             init_compression)
from repro.core.lif import LIFState
from repro.core.rsnn import RSNNConfig, RSNNState
from repro.serving import backends
from repro.serving.slots import SlotScheduler


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Execution-path selection for CompiledRSNN."""

    backend: str = "jnp"  # registered name in serving/backends.py
    precision: str = "float"  # "float" | "int4" (packed model from sparse.py)
    sparse_fc: bool = False  # zero-skip CSC path for the pruned FC
    input_scale: float | jax.Array | None = None  # static 8-bit calibration
    delta_threshold: float = 0.0  # delta backend: |x_t - x_prev| gate (LSBs)
    spike_capacity: int | None = None  # spike/delta: event-list slots per
    # row (None = sized to the contraction dim, lossless and bit-identical;
    # smaller values model a finite hardware event queue and truncate each
    # row's highest-index spike events)

    def __post_init__(self):
        if self.backend not in backends.available():
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"available: {backends.available()}")
        if self.precision not in ("float", "int4"):
            raise ValueError(f"unknown precision {self.precision!r}")
        if self.wants_sparse_fc and self.precision != "int4":
            raise ValueError("the zero-skip CSC FC runs over the packed "
                             "int4 model (set precision='int4')")
        if self.delta_threshold < 0.0:
            raise ValueError(
                f"delta_threshold must be >= 0, got {self.delta_threshold}")
        if self.delta_threshold != 0.0 and self.backend != "delta":
            raise ValueError(
                "delta_threshold is the 'delta' backend's knob; backend "
                f"{self.backend!r} would silently ignore it")
        if self.spike_capacity is not None:
            if self.spike_capacity < 1:
                raise ValueError(
                    f"spike_capacity must be >= 1, got {self.spike_capacity}")
            if self.backend not in ("spike", "delta"):
                raise ValueError(
                    "spike_capacity is the event-queue knob of the 'spike'"
                    " and 'delta' backends; backend "
                    f"{self.backend!r} would silently ignore it")

    @property
    def wants_sparse_fc(self) -> bool:
        """The CSC zero-skip readout: the flag, or the dedicated backend."""
        return self.sparse_fc or self.backend == "sparse"


# Every dot of the served model runs at full f32 precision, XLA's and the
# kernels' alike.  The TPU's default f32 matmul is one bf16 pass, which
# rounds each weight to 8 mantissa bits; spikes are thresholds of the
# membrane those weights sum to, and a flipped spike carries forward
# through the recurrence.
MATMUL_PRECISION = "highest"


def calibrate_input_scale(features: jax.Array, bits: int = 8) -> jax.Array:
    """Static input quantization scale from calibration audio (max-abs)."""
    return spike_ops.quantize_input(features, bits)[1]


class DeltaRSNNState(NamedTuple):
    """Per-slot step state of the ``delta`` backend: the core recurrent
    state plus EdgeDRNN-style delta carries — ``x_prev`` the *held* input
    vector (skipped elements keep their last-propagated value) and ``pre``
    the cached input-layer pre-activation row reused when a slot has no
    propagated delta.  A NamedTuple, so it is a pytree: ``lax.scan``
    carries it, ``distributed.sharding.stream_state_specs`` shards its
    2-D (slots, ...) leaves on the slot dim like the LIF membrane chains.
    """

    rsnn: RSNNState
    x_prev: jax.Array  # (B, input_dim) held input
    pre: jax.Array  # (B, hidden_dim) cached x_hat @ l0_wx


def reset_slot(state, slots):
    """Zero the recurrent state of ``slots`` (fresh utterance boundary):
    one slot index, or a (B,) bool mask.  Traced with the mask as an
    operand, one executable zeroes any set of slots (the slot loops'
    compiled reset)."""
    if isinstance(state, DeltaRSNNState):
        # delta carries reset with the core state: a fresh utterance must
        # not inherit the previous occupant's held inputs/pre-activations
        return DeltaRSNNState(rsnn=reset_slot(state.rsnn, slots),
                              x_prev=_zero_slots(state.x_prev, slots, 0),
                              pre=_zero_slots(state.pre, slots, 0))

    def zl(s: LIFState) -> LIFState:
        return LIFState(u=_zero_slots(s.u, slots, 0),
                        spike=_zero_slots(s.spike, slots, 0))

    return RSNNState(h0=_zero_slots(state.h0, slots, 1),
                     h1=_zero_slots(state.h1, slots, 1),
                     lif0=zl(state.lif0), lif1=zl(state.lif1))


def _zero_slots(x: jax.Array, slots, axis: int) -> jax.Array:
    """``x`` with the ``slots`` entries of its slot axis ``axis`` zeroed
    (``slots``: an index or a bool mask over that axis)."""
    b = x.shape[axis]
    mask = jnp.asarray(slots)
    if mask.dtype != jnp.bool_:
        mask = jnp.arange(b) == mask
    shape = [1] * x.ndim
    shape[axis] = b
    return jnp.where(mask.reshape(shape), jnp.zeros((), x.dtype), x)


class CompiledRSNN:
    """One RSNN compiled for streaming inference on a chosen execution path.

    Owns the (possibly packed) weights, the static input scale, and a jitted
    per-frame step; state threads through explicitly so callers control the
    frame/slot lifecycle.
    """

    def __init__(self, cfg: RSNNConfig, params: dict | None,
                 engine: EngineConfig = EngineConfig(),
                 ccfg: CompressionConfig | None = None,
                 cstate: CompressionState | None = None, *,
                 packed: sparse.PackedRSNN | None = None):
        self.cfg = cfg
        self.engine = engine
        self.packed: sparse.PackedRSNN | None = None

        if engine.precision == "int4":
            if packed is not None:
                # pre-packed deployment payload (core/artifact.py): no
                # float params needed, the packer already ran elsewhere
                self.packed = packed
            else:
                if params is None:
                    raise ValueError("int4 precision needs params to pack "
                                     "(or a pre-packed model via packed=)")
                if ccfg is None or ccfg.quant_spec is None:
                    raise ValueError("int4 precision needs a CompressionConfig "
                                     "with weight_bits set")
                if cstate is None:
                    cstate = init_compression(params, ccfg)
                self.packed = sparse.pack_model(params, cfg, ccfg, cstate)
            if engine.wants_sparse_fc and "fc_w" not in self.packed.sparse:
                raise ValueError("sparse_fc needs a mask-pruned fc_w (set "
                                 "ccfg.fc_prune_frac > 0 or give fc_w a "
                                 "PruneSpec)")
            missing = set(cfg.layer_shapes) - set(self.packed.quant)
            if missing:
                raise ValueError(
                    f"int4 engine needs every layer weight quantized; "
                    f"missing from ccfg.quant_names: {sorted(missing)}")
            # dense-dequant copies only where the backend consumes dense
            # weights: the recurrent cell always does (paper type-D: no skip
            # at TS=2); backends that declare dense_stimulus (the ref
            # oracles) need the feedforward weights too.  Dequant is
            # bit-exact with QAT fake-quant.
            dense_needed = {"l0_wh", "l1_wh"}
            if backends.needs_dense_stimulus(engine.backend):
                dense_needed |= {"l0_wx", "l1_wx"}
            dense = {n: sparse.dequantize(self.packed.quant[n])
                     for n in dense_needed}
            quant, csc = dict(self.packed.quant), dict(self.packed.sparse)
            self._lif = self.packed.lif
        else:
            if params is None:
                raise ValueError("float precision needs the parameter tree")
            dense = {n: params[n] for n in cfg.layer_shapes}
            quant, csc = {}, {}
            self._lif = {}
            for i in (0, 1):
                beta, vth = lif_lib.inference_constants(params[f"lif{i}"],
                                                        cfg.hw_rounded_lif)
                self._lif[f"beta{i}"] = beta
                self._lif[f"vth{i}"] = vth

        self._ctx = backends.BackendContext(
            cfg=cfg, precision=engine.precision,
            sparse_fc=engine.wants_sparse_fc, dense=dense, quant=quant,
            sparse=csc, delta_threshold=engine.delta_threshold,
            spike_capacity=engine.spike_capacity)
        self.ops = backends.resolve(engine.backend, self._ctx)
        self._w = self._ctx.dense

        # deployed FC pruning fraction, for measured-MMAC/s accounting
        self.fc_prune_frac = (ccfg.fc_prune_fraction
                              if engine.precision == "int4" and ccfg is not None
                              else 0.0)
        scale = engine.input_scale
        self._input_scale = None if scale is None else jnp.asarray(scale)
        self._compile()

    def _compile(self) -> None:
        self._step = jax.jit(self._frame_step)
        self._step_masked = jax.jit(self._masked_frame_step)
        self._step_ring = jax.jit(self._ring_frame_step_fused)
        self._step_ring_quiet = jax.jit(self._ring_frame_step_fused_quiet)
        self._run = jax.jit(self._run_scan)
        # Donated hot-loop variants: the slot loops thread every
        # loop-carried buffer (recurrent/delta state, logit ring, counter
        # accumulator) through these, and donate_argnums lets XLA alias
        # each output onto its input buffer — the ring update is in-place
        # instead of an allocate+copy per step.  Donated argnums cover
        # exactly the buffers with a same-shaped output (state / ring /
        # aux_acc); the staged frame batch is consumed, not carried, so
        # donating it could never alias.  These are separate jits from the
        # public step/step_masked/step_ring API, whose callers may
        # legitimately reuse their input arrays after the call.
        self._loop_step_masked = jax.jit(
            self._masked_frame_step_fused, donate_argnums=(0,))
        self._loop_step_masked_chunk = jax.jit(
            self._masked_chunk_step_fused, donate_argnums=(0,))
        self._loop_step_ring = jax.jit(
            self._ring_frame_step_fused, donate_argnums=(0, 3, 4))
        self._loop_step_ring_quiet = jax.jit(
            self._ring_frame_step_fused_quiet, donate_argnums=(0, 3))
        self._loop_step_ring_chunk = jax.jit(
            self._ring_chunk_step_fused, donate_argnums=(0, 3, 4))
        self._loop_step_ring_chunk_quiet = jax.jit(
            self._ring_chunk_step_fused_quiet, donate_argnums=(0, 3))
        self._loop_reset = jax.jit(self._reset_slots, donate_argnums=(0,))
        # AOT executable cache (jax.jit(...).lower().compile() results),
        # shared by every loop over this engine; ``compile_count`` moves
        # only on a real build, so the compile-count regression test can
        # assert a steady-state serve triggers zero new compiles
        self._aot_cache: dict = {}
        self.compile_count = getattr(self, "compile_count", 0)

    def aot_compile(self, key: tuple, jitted, *args):
        """Ahead-of-time compile ``jitted`` for the given abstract args
        (``jax.ShapeDtypeStruct`` trees, or concrete arrays — ``lower``
        never executes), cached under ``key``.  ``jax.jit``'s call cache
        and ``lower().compile()`` do not share entries, so a loop that
        warms here must also *dispatch* through the returned executable;
        the loops bind it at construction (``aot_warmup=True``) and
        steady-state serving then never compiles."""
        exe = self._aot_cache.get(key)
        if exe is None:
            exe = jitted.lower(*args).compile()
            self._aot_cache[key] = exe
            self.compile_count += 1
        return exe

    def place_weights(self, sharding) -> None:
        """``jax.device_put`` every deployed array (dense/quant/CSC weights,
        LIF constants, input scale) with ``sharding`` — e.g. replicated over
        a serving mesh — then re-resolve the op table and re-jit so the
        compiled steps capture the placed copies.  A ``NamedSharding``'s
        mesh goes to the op table too: the mega-step kernel then runs on
        each device over that device's slot shard."""
        put = lambda tree: jax.device_put(tree, sharding)  # noqa: E731
        self._ctx = dataclasses.replace(
            self._ctx, dense=put(self._ctx.dense), quant=put(self._ctx.quant),
            sparse=put(self._ctx.sparse),
            mesh=getattr(sharding, "mesh", None))
        self.ops = backends.resolve(self.engine.backend, self._ctx)
        self._w = self._ctx.dense
        self._lif = put(self._lif)
        if self._input_scale is not None:
            self._input_scale = put(self._input_scale)
        self._compile()

    @classmethod
    def from_artifact(cls, path, engine: EngineConfig | None = None, *,
                      backend: str | None = None) -> "CompiledRSNN":
        """Build an engine straight from an on-disk deployment artifact
        (``core/artifact.py``) — the serving end of the train→compress→
        pack→serve loop.  Logits are bit-identical to serving the same
        model packed in-process (tests/test_artifact.py).

        ``engine=None`` derives the execution path from the manifest: the
        artifact's precision, its preferred backend (overridable via
        ``backend=``), its zero-skip FC preference (``sparse_fc``), and
        its stored static input scale.  An explicit ``engine`` is used
        verbatim and must match the artifact's precision.
        """
        from repro.core import artifact as artifact_lib

        art = artifact_lib.load_artifact(path)
        if engine is None:
            engine = EngineConfig(
                backend=backend or art.backend or "jnp",
                precision=art.precision,
                sparse_fc=art.sparse_fc,
                input_scale=art.input_scale)
        elif engine.precision != art.precision:
            raise ValueError(
                f"engine precision {engine.precision!r} does not match the "
                f"artifact's {art.precision!r} payload")
        if art.precision == "int4":
            return cls(art.cfg, None, engine, ccfg=art.ccfg,
                       packed=art.packed)
        return cls(art.cfg, art.params, engine, ccfg=art.ccfg)

    # ------------------------------------------------------------ frontend

    def init_state(self, batch: int):
        if self.ops.mxu_aligned:
            # MXU tiling contract of the fused kernels: a batch over 128
            # must be a multiple of the 128-row block (rsnn_cell's b-grid;
            # the int4 path also folds TS into the matmul M dim).
            dims = [("batch", batch)]
            if self.packed is not None:
                dims.append(("num_ts*batch", self.cfg.num_ts * batch))
            for what, m in dims:
                if m > 128 and m % 128 != 0:
                    raise ValueError(
                        f"pallas backend needs {what} <= 128 or a multiple "
                        f"of 128, got {m}; use backend='jnp' or pad the "
                        f"slot count")
        state = rsnn.init_state(self.cfg, batch)
        if self.ops.delta_gate is not None:
            # zero delta carries: frame 1 of every stream propagates all
            # its nonzero elements against the zero held vector
            return DeltaRSNNState(
                rsnn=state,
                x_prev=jnp.zeros((batch, self.cfg.input_dim), jnp.float32),
                pre=jnp.zeros((batch, self.cfg.hidden_dim), jnp.float32))
        return state

    def quantize_features(self, x: jax.Array) -> jax.Array:
        """8-bit fixed-point input quantization with the static scale.

        ``input_scale=None`` means the features are already integer-valued
        (pre-quantized upstream); that contract is validated eagerly, since
        raw floats would truncate to garbage in the bit-sparsity counters.
        """
        if self._input_scale is None:
            if bool(jnp.any(x != jnp.round(x))):
                raise ValueError(
                    "input_scale=None requires integer-valued features; "
                    "pass input_scale=calibrate_input_scale(features)")
            return x
        return spike_ops.quantize_input(x, self.cfg.input_bits,
                                        self._input_scale)[0]

    # ------------------------------------------------------- layer dispatch

    def _frame_step(self, state, x_t: jax.Array):
        """One quantized frame x_t (B, input_dim) -> (state, logits, aux).

        Every kernel choice goes through ``self.ops`` (the op table the
        backend registry resolved at construction) — the engine itself is
        backend-agnostic.
        """
        if self.ops.megastep is not None:
            # single-dispatch mega-step: both cells, the layout-resolved
            # FC, and the sparsity counters run inside one kernel with
            # state/weights VMEM-resident (kernels/megastep.py); every
            # loop contract (v1, v2 ring, scan, sharded) funnels here, so
            # they all inherit the collapsed dispatch.  The binding is
            # chunk-native — (F, B, input_dim) in, leading frame axis out —
            # and one frame is its F=1 special case.
            state, logits, aux = self._chunk_step(state, x_t[None])
            return state, logits[0], {k: v[0] for k, v in aux.items()}
        with jax.default_matmul_precision(MATMUL_PRECISION):
            if self.ops.delta_gate is None:
                return self._compose_step(state, x_t)
            # delta-temporal gating (EdgeDRNN): propagate only elements
            # with |x_t - x_prev| > threshold, hold the rest, and reuse
            # the cached L0 pre-activation for slots with no delta; the
            # held x_hat also feeds the bit counters, so at threshold>0
            # they measure the stimulus the step actually used
            x_hat, pre, mask = self.ops.delta_gate(x_t, state.x_prev,
                                                   state.pre)
            core, logits, aux = self._compose_step(state.rsnn, x_hat,
                                                   ff0=pre)
        prop = mask.sum(axis=1)
        aux = dict(aux, delta_propagated=prop,
                   delta_skipped=x_t.shape[1] - prop)
        return DeltaRSNNState(rsnn=core, x_prev=x_hat, pre=pre), logits, aux

    def _compose_step(self, state: RSNNState, x_t: jax.Array,
                      ff0: jax.Array | None = None):
        """Per-op frame step (the non-collapsed backends): both cells, the
        readout, and the host-side counters composed from the op table.
        ``ff0`` overrides the L0 feedforward stimulus (the delta route's
        cached/gated pre-activation)."""
        cell, ff, fc = self.ops.rsnn_cell, self.ops.ff_matmul, self.ops.fc
        w = self._w
        lif = self._lif
        ts = state.h0.shape[0]
        b = x_t.shape[0]
        h = self.cfg.hidden_dim

        # L0: feedforward stimulus once per frame, shared across time steps
        if ff0 is None:
            ff0 = ff(x_t, "l0_wx")  # (B, H)
        stim0 = jnp.broadcast_to(ff0[None], (ts, b, h))
        s0, u0 = cell(stim0, state.h0, w["l0_wh"], state.lif0.u,
                      state.lif0.spike, lif["beta0"], lif["vth0"])
        lif0 = LIFState(u=u0, spike=s0[-1])

        # L1: per-ts feedforward from L0 spikes + recurrent
        stim1 = ff(s0.reshape(ts * b, h), "l1_wx").reshape(ts, b, h)
        s1, u1 = cell(stim1, state.h1, w["l1_wh"], state.lif1.u,
                      state.lif1.spike, lif["beta1"], lif["vth1"])
        lif1 = LIFState(u=u1, spike=s1[-1])

        logits = fc(s1)

        aux = _frame_counters(x_t, s0, s1, self.cfg.input_bits)
        return RSNNState(h0=s0, h1=s1, lif0=lif0, lif1=lif1), logits, aux

    def _masked_frame_step(self, state: RSNNState, x_t: jax.Array,
                           active: jax.Array):
        state, logits, aux = self._frame_step(state, x_t)
        return state, logits, pack_step_aux(aux, active)

    def _masked_frame_step_fused(self, state: RSNNState, x_raw: jax.Array,
                                 active: jax.Array):
        """v1 loop step with input quantization fused into the dispatch
        (bit-exact with the eager quantize — see ``_quantize_in_graph``;
        the integer contract of ``input_scale=None`` is enforced at submit
        time instead)."""
        return self._masked_frame_step(state, self._quantize_in_graph(x_raw),
                                       active)

    # -------------------------------------------------------- chunked steps

    def _chunk_step(self, state, x_chunk: jax.Array):
        """Advance every slot by a chunk of F frames inside one traced
        computation: ``x_chunk`` (F, B, input_dim) -> (state, logits
        (F, B, fc_dim), aux with a leading frame axis).  The mega-step
        backends run the whole chunk as ONE kernel dispatch over the
        kernel's native frame-chunk grid axis (weights stay VMEM-resident
        across the chunk); per-op tables scan the frame step, which still
        amortizes the Python->device dispatch to one per chunk.  Frame
        semantics are sequential either way, so a C-frame chunk is
        bit-identical to C single-frame steps."""
        if self.ops.megastep is not None:
            with jax.default_matmul_precision(MATMUL_PRECISION):
                return self.ops.megastep(state, x_chunk, self._lif)

        def body(st, x_t):
            st, logits, aux = self._frame_step(st, x_t)
            return st, (logits, aux)

        state, (logits, aux) = jax.lax.scan(body, state, x_chunk)
        return state, logits, aux

    def _masked_chunk_step(self, state, x_chunk: jax.Array,
                           active: jax.Array):
        """Chunked ``_masked_frame_step``: ``active`` is the (F, slots)
        per-sub-step fill mask — False tail rows are idle padding (a ragged
        stream tail or a mid-chunk completion), which advance state with
        zero frames exactly like an idle slot in per-frame stepping and are
        masked out of the packed counters."""
        state, logits, aux = self._chunk_step(state, x_chunk)
        return state, logits, jax.vmap(pack_step_aux)(aux, active).sum(axis=0)

    def _masked_chunk_step_fused(self, state, x_raw: jax.Array,
                                 active: jax.Array):
        return self._masked_chunk_step(state, self._quantize_in_graph(x_raw),
                                       active)

    def _ring_write(self, ring: jax.Array, ring_idx: jax.Array,
                    logits: jax.Array) -> jax.Array:
        """Scatter each slot's logits row into its ring position."""
        return ring.at[jnp.arange(logits.shape[0]), ring_idx].set(logits)

    def _quantize_in_graph(self, x: jax.Array) -> jax.Array:
        """Traced input quantization for the fused pipelined step — the
        same elementwise round/clip as ``quantize_features`` (bit-exact
        under jit), minus the eager integer-contract check: with
        ``input_scale=None`` the caller validates at submit time instead,
        so the step dispatch stays transfer-free."""
        if self._input_scale is None:
            return x
        return spike_ops.quantize_input(x, self.cfg.input_bits,
                                        self._input_scale)[0]

    def _ring_frame_step(self, state: RSNNState, x_t: jax.Array,
                         active: jax.Array, ring: jax.Array,
                         ring_idx: jax.Array, aux_acc: jax.Array):
        state, logits, aux = self._frame_step(state, x_t)
        return (state, self._ring_write(ring, ring_idx, logits),
                aux_acc + pack_step_aux(aux, active))

    def _ring_frame_step_quiet(self, state: RSNNState, x_t: jax.Array,
                               ring: jax.Array, ring_idx: jax.Array):
        state, logits, _ = self._frame_step(state, x_t)
        return state, self._ring_write(ring, ring_idx, logits)

    def _ring_frame_step_fused(self, state: RSNNState, x_raw: jax.Array,
                               ctrl: jax.Array, ring: jax.Array,
                               aux_acc: jax.Array):
        """Raw-frame variant: quantization fused into the same dispatch (one
        jit call per step instead of an eager quantize + a jitted step).
        ``ctrl`` is the packed (2, slots) int32 control word — row 0 the
        active mask, row 1 the ring write index — so the host ships one
        small transfer per step instead of one per operand."""
        return self._ring_frame_step(state, self._quantize_in_graph(x_raw),
                                     ctrl[0], ring, ctrl[1], aux_acc)

    def _ring_frame_step_fused_quiet(self, state: RSNNState,
                                     x_raw: jax.Array, ctrl: jax.Array,
                                     ring: jax.Array):
        return self._ring_frame_step_quiet(
            state, self._quantize_in_graph(x_raw), ring, ctrl[1])

    def _ring_write_chunk(self, ring: jax.Array, ring_idx: jax.Array,
                          logits: jax.Array) -> jax.Array:
        """Scatter an (F, B, fc) chunk of logit rows into per-slot ring
        positions (``ring_idx`` (F, B)).  Idle sub-steps carry
        ``ring_frames`` — one past the last ring row — and ``mode="drop"``
        discards those writes, so the idle tail after a mid-chunk
        completion can never clobber the completed stream's
        still-harvestable ring rows."""
        f, b, fc = logits.shape
        rows = jnp.broadcast_to(jnp.arange(b)[None], (f, b)).reshape(-1)
        return ring.at[rows, ring_idx.reshape(-1)].set(
            logits.reshape(f * b, fc), mode="drop")

    def _ring_chunk_step(self, state, x_chunk: jax.Array, active: jax.Array,
                         ring: jax.Array, ring_idx: jax.Array,
                         aux_acc: jax.Array):
        state, logits, aux = self._chunk_step(state, x_chunk)
        ring = self._ring_write_chunk(ring, ring_idx, logits)
        return state, ring, aux_acc + jax.vmap(pack_step_aux)(
            aux, active).sum(axis=0)

    def _ring_chunk_step_quiet(self, state, x_chunk: jax.Array,
                               ring: jax.Array, ring_idx: jax.Array):
        state, logits, _ = self._chunk_step(state, x_chunk)
        return state, self._ring_write_chunk(ring, ring_idx, logits)

    def _ring_chunk_step_fused(self, state, x_raw: jax.Array,
                               ctrl: jax.Array, ring: jax.Array,
                               aux_acc: jax.Array):
        """Chunked ``_ring_frame_step_fused``: ``ctrl`` is the packed
        (2, F, slots) int32 control word — row 0 the per-sub-step fill
        mask, row 1 the per-sub-step ring write index (``ring_frames``,
        i.e. dropped, when idle)."""
        return self._ring_chunk_step(state, self._quantize_in_graph(x_raw),
                                     ctrl[0], ring, ctrl[1], aux_acc)

    def _ring_chunk_step_fused_quiet(self, state, x_raw: jax.Array,
                                     ctrl: jax.Array, ring: jax.Array):
        return self._ring_chunk_step_quiet(
            state, self._quantize_in_graph(x_raw), ring, ctrl[1])

    def _reset_slots(self, state, mask: jax.Array):
        """The slot loops' reset: the module's ``reset_slot`` (looked up
        when traced) over a (slots,) bool mask."""
        return reset_slot(state, mask)

    # ------------------------------------------------------------ execution

    def step(self, state: RSNNState, x_q: jax.Array):
        """Advance every slot by one quantized frame. x_q: (B, input_dim)."""
        return self._step(state, x_q)

    def step_masked(self, state: RSNNState, x_q: jax.Array,
                    active: jax.Array):
        """``step`` with device-side idle-slot masking of the counters:
        returns (state, logits, packed counter vector) where the vector is
        already masked to active slots and reduced — one small host
        transfer per step instead of one per counter key (see
        ``pack_step_aux``/``unpack_step_aux``)."""
        return self._step_masked(state, x_q, active)

    def step_ring(self, state: RSNNState, x_raw: jax.Array,
                  ctrl: jax.Array, ring: jax.Array, aux_acc: jax.Array):
        """Contract-v2 pipelined step over *raw* frames: input quantization,
        the frame step, the logit write into ``ring`` at the per-slot ring
        row ``ctrl[1]``, and the ``ctrl[0]``-masked packed-counter add into
        ``aux_acc`` all run inside one jitted dispatch — the call returns
        device arrays only, so the host never blocks here.  Returns
        (state, ring, aux_acc)."""
        return self._step_ring(state, x_raw, ctrl, ring, aux_acc)

    def step_ring_quiet(self, state: RSNNState, x_raw: jax.Array,
                        ctrl: jax.Array, ring: jax.Array):
        """``step_ring`` without sparsity counters (no counter math at all:
        XLA dead-code-eliminates the unused aux reductions).  Returns
        (state, ring)."""
        return self._step_ring_quiet(state, x_raw, ctrl, ring)

    def _run_scan(self, state: RSNNState, xq: jax.Array):
        def body(st, x_t):
            st, logits, aux = self._frame_step(st, x_t)
            return st, (logits, aux)

        state, (logits, aux) = jax.lax.scan(body, state, jnp.swapaxes(xq, 0, 1))
        return state, jnp.swapaxes(logits, 0, 1), aux

    def run(self, x: jax.Array, state: RSNNState | None = None):
        """Batch-run a chunk of raw frames x (B, T_chunk, input_dim), carrying
        state across calls. Returns (logits (B, T_chunk, fc_dim), state, aux);
        aux counters are stacked per frame, already summed over slots."""
        if state is None:
            state = self.init_state(x.shape[0])
        xq = self.quantize_features(x)
        state, logits, aux = self._run(state, xq)
        aux = {k: v.sum(axis=-1) for k, v in aux.items()}  # sum slots
        return logits, state, aux


def _frame_counters(x_t: jax.Array, s0: jax.Array, s1: jax.Array,
                    input_bits: int) -> dict:
    """Per-slot zero-skip counters for one frame (see module docstring)."""
    one_bits = spike_ops.bitplanes(x_t, input_bits).sum(axis=(1, 2))  # (B,)
    zero = jnp.zeros_like(one_bits, dtype=jnp.float32)
    return {
        "spikes_l0": s0.sum(axis=2),  # (TS, B)
        "spikes_l1": s1.sum(axis=2),  # (TS, B)
        "union_l1": s1.max(axis=0).sum(axis=1),  # (B,)
        "input_one_bits": one_bits.astype(jnp.float32),  # (B,)
        # delta-temporal gating counters: zero unless the delta route
        # overrides them (zero totals read back as density 1.0 — "not
        # measured" — in complexity.SparsityCounters.profile)
        "delta_propagated": zero,  # (B,)
        "delta_skipped": zero,  # (B,)
    }


def pack_step_aux(aux: dict, active: jax.Array) -> jax.Array:
    """Mask the per-slot counters of one step by ``active`` and reduce over
    slots, packed into one flat device vector: ``[spikes_l0 (TS,),
    spikes_l1 (TS,), union_l1, input_one_bits, delta_propagated,
    delta_skipped]``.  The slot loops fetch this single vector per step
    (v1) or accumulate it on device and fetch once per drain (v2) instead
    of one host round-trip per counter key.
    """
    act = active.astype(jnp.float32)
    return jnp.concatenate([
        (aux["spikes_l0"] * act).sum(axis=-1),
        (aux["spikes_l1"] * act).sum(axis=-1),
        (aux["union_l1"] * act).sum(axis=-1)[None],
        (aux["input_one_bits"] * act).sum(axis=-1)[None],
        (aux["delta_propagated"] * act).sum(axis=-1)[None],
        (aux["delta_skipped"] * act).sum(axis=-1)[None],
    ])


def unpack_step_aux(vec, num_ts: int) -> dict:
    """Host-side inverse of ``pack_step_aux`` -> the dict
    ``complexity.SparsityCounters.update`` consumes.  The packed layout is
    linear in frames, so a device-side sum of per-step vectors unpacks the
    same way as a single step's vector."""
    v = np.asarray(vec)
    return {"spikes_l0": v[:num_ts], "spikes_l1": v[num_ts:2 * num_ts],
            "union_l1": v[2 * num_ts], "input_one_bits": v[2 * num_ts + 1],
            "delta_propagated": v[2 * num_ts + 2],
            "delta_skipped": v[2 * num_ts + 3]}


# ---------------------------------------------------------------------------
# Slot-based continuous batching over audio streams
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StreamRequest:
    """One utterance: its frames in, its per-frame logits out.

    In the pipelined contract, harvested logit blocks arrive as
    ``(device_block, fill)`` pairs in ``pending`` (one per stream
    completion or watermark flush; the block is the stream's statically
    shaped ring row, ``fill`` the number of valid leading frames) and
    materialize into ``logits`` rows when the pipeline retires the
    completing step — or lazily, on the first ``stacked_logits`` call.
    Harvesting whole ring rows keeps the harvest op's shape independent of
    the utterance length: a ``ring[i, :fill]`` slice would bake every
    distinct (slot, length) pair into its own compiled executable — a
    mid-serve compile storm under mixed-length load (multi-ms p99
    outliers in ``benchmarks/loadgen.py``); the trim to ``fill`` happens
    on the host after the block crosses.

    Lifecycle timestamps (``StreamLoop.clock``, monotonic seconds) feed the
    load-generator latency accounting (``benchmarks/loadgen.py``):
    ``t_submit`` at enqueue, ``t_start`` when the stream takes a slot,
    ``t_done`` when its last frame is scheduled (slot freed), ``t_harvest``
    when its logits are host-resident — completion latency is
    ``t_harvest - t_submit``, queue wait ``t_start - t_submit``.  In the
    synchronous contract ``t_done == t_harvest``; pipelined, harvest lands
    when the completing step retires.
    """

    sid: int
    frames: np.ndarray  # (T, input_dim) raw features
    fc_dim: int = 0  # logit width, stamped by StreamLoop.submit
    logits: list = dataclasses.field(default_factory=list)
    done: bool = False
    pending: list = dataclasses.field(default_factory=list, repr=False)
    t_submit: float | None = None
    t_start: float | None = None
    t_done: float | None = None
    t_harvest: float | None = None

    def _materialize(self) -> int:
        """Fetch pending device-side logit blocks into ``logits`` rows
        (each ring-row block host-trimmed to its ``fill`` valid frames);
        returns the number of device->host transfers performed."""
        n = len(self.pending)
        for chunk, fill in self.pending:
            self.logits.extend(np.asarray(chunk)[:fill])
        self.pending.clear()
        return n

    def stacked_logits(self) -> np.ndarray:
        self._materialize()
        if not self.logits:
            return np.zeros((0, self.fc_dim), np.float32)
        return np.stack(self.logits)


class _InflightStep:
    """One dispatched-but-unretired device step: a fence handle plus the
    requests whose completion rode on this step."""

    __slots__ = ("handle", "completed")

    def __init__(self, handle, completed):
        self.handle = handle  # device array produced by the step (fence)
        self.completed = completed  # list[StreamRequest]


class StreamLoop(SlotScheduler):
    """Continuous batching of audio streams over recurrent-state slots.

    N submitted utterances share a fixed decode batch of ``batch_slots``
    rows.  Each ``step_once`` advances every active slot by one frame; a
    slot whose utterance ends is refilled from the queue mid-batch, so
    throughput never drops to the shortest stream.  Idle slots carry zero
    frames and are excluded from the sparsity counters.

    A slot's recurrent state is zeroed when its next occupant is placed:
    every slot ``_refill`` fills in a step is reset by ONE compiled
    dispatch that takes the slots as a (slots,) bool mask operand, before
    the step is assembled.  Until then a freed slot's state is stale and
    never read — its counters are masked and its ring row was sliced for
    harvest at completion.

    ``pipeline_depth`` selects the step-lifecycle contract (module
    docstring): ``0`` is the v1 synchronous loop (one logit + one counter
    fetch per step); ``>= 1`` is the v2 pipelined loop with at most
    ``pipeline_depth`` device steps in flight, logits retained in a
    device-side ring of ``ring_frames`` rows per slot, and counters
    accumulated on device.  Scheduling and logits are identical across
    contracts; only *when data crosses to the host* changes.

    ``chunk_frames=C`` amortizes dispatch: each ``step_once`` advances
    every active slot by up to C frames in ONE jitted device call (the
    mega-step backends run the chunk as one kernel dispatch; per-op tables
    scan it).  Per chunk, slot i serves ``min(C, remaining frames)``
    frames and idles for the rest (the ragged tail of a stream whose
    length is not a multiple of C) — no mid-chunk refill; completions,
    refills, and the ring watermark are decided at the chunk boundary,
    and idle sub-steps are masked out of the ring writes and the counters
    while the next occupant's state is reset at its refill — so
    per-stream logits, final state, and counters are bit-identical to
    ``chunk_frames=1``, which remains the bit-parity comparator the same
    way ``pipeline_depth=0`` is.  The pipelined contract requires
    ``ring_frames`` to be a multiple of C so a *live* slot never idles
    mid-chunk on ring capacity (its state would silently advance through
    frames it never received).

    Every loop-carried device buffer (recurrent/delta state, logit ring,
    counter accumulator) is *donated* to the step dispatch, so XLA updates
    it in place, and ``aot_warmup=True`` (the default) pre-compiles the
    loop's step executables at construction (``jax.jit(...).lower()
    .compile()``) and dispatches through them — steady-state serving
    performs zero compiles (tests/test_compile_count.py).

    ``host_syncs`` counts device->host transfers the loop performs — the
    quantity the pipelined contract minimizes (``bench_stream_pipeline``
    reports it per frame).  ``dispatches`` counts jitted device dispatches
    and ``frames_served`` slot-frames advanced, so ``dispatches /
    frames_served`` exposes the 1 -> 1/C amortization under full slots.
    ``track_sparsity=False`` detaches the sparsity-counter sink entirely:
    no counter math, no counter fetches.

    The step path records profiler spans (``rsnn.step``, ``rsnn.refill``,
    ``rsnn.complete``, ``rsnn.egress``, ...; docs/serving.md, "Tracing a
    serving loop"), which cost nothing unless ``jax.profiler`` is tracing,
    and counts at the same boundaries: ``refills``, ``completions``,
    ``watermark_flushes``, ``egress_bytes`` and ``egress_valid_bytes``;
    ``reset_dispatches`` counts the compiled slot resets, so ``refills /
    reset_dispatches`` is the slots one reset zeroes.
    """

    def __init__(self, engine: CompiledRSNN, batch_slots: int = 4,
                 pipeline_depth: int = 2, ring_frames: int = 256,
                 track_sparsity: bool = True, chunk_frames: int = 1,
                 aot_warmup: bool = True):
        super().__init__(batch_slots)
        if pipeline_depth < 0:
            raise ValueError(f"pipeline_depth must be >= 0, "
                             f"got {pipeline_depth}")
        if ring_frames < 1:
            raise ValueError(f"ring_frames must be >= 1, got {ring_frames}")
        if chunk_frames < 1:
            raise ValueError(f"chunk_frames must be >= 1, got {chunk_frames}")
        if (chunk_frames > 1 and pipeline_depth >= 1
                and ring_frames % chunk_frames != 0):
            # a live stream's ring fill advances in whole chunks, so with
            # ring_frames % chunk_frames == 0 its capacity at a chunk
            # boundary is never less than a full chunk and only *completed*
            # (freed) slots ever idle mid-chunk.  A non-multiple ring
            # would force a live slot to idle mid-chunk on ring-capacity,
            # advancing its recurrent state through zero frames it never
            # received — silently breaking chunk/per-frame bit parity.
            raise ValueError(
                f"ring_frames ({ring_frames}) must be a multiple of "
                f"chunk_frames ({chunk_frames}) in the pipelined contract")
        self.engine = engine
        self.pipeline_depth = pipeline_depth
        self.ring_frames = ring_frames
        self.track_sparsity = track_sparsity
        self.chunk_frames = chunk_frames
        self.aot_warmup = aot_warmup
        # monotonic clock behind the request lifecycle stamps; swappable
        # (deterministic tests, the load generator's virtual-time checks)
        self.clock = time.monotonic
        self.state = engine.init_state(batch_slots)
        self._flushed = [0] * batch_slots  # frames already harvested, per slot
        self._inflight: collections.deque[_InflightStep] = collections.deque()
        self._ring = self._init_ring() if pipeline_depth >= 1 else None
        self._to_reset = np.zeros(batch_slots, bool)  # filled this refill
        self.reset_metrics()
        self._bind_step_fns()
        if aot_warmup:
            self._warm_executables()

    def _init_ring(self):
        """Device-side per-slot logit ring (overridden to shard on a mesh)."""
        return jnp.zeros(
            (self.slots, self.ring_frames, self.engine.cfg.fc_dim),
            jnp.float32)

    def _zero_aux_acc(self):
        """Zeroed packed-counter accumulator (overridden to place on mesh)."""
        return jnp.zeros((2 * self.engine.cfg.num_ts + 4,), jnp.float32)

    # -------------------------------------------------- executables / warmup

    def _bind_step_fns(self) -> None:
        """Bind the dispatch callables this loop's contract uses — the
        donated jitted variants, replaced by AOT-compiled executables when
        ``aot_warmup`` runs.  (Overridden by the sharded loop, which
        dispatches its own device-resident-buffer jits.)"""
        eng = self.engine
        if self.chunk_frames == 1:
            self._fn_step = eng._loop_step_masked
            self._fn_ring = (eng._loop_step_ring if self.track_sparsity
                             else eng._loop_step_ring_quiet)
        else:
            self._fn_step = eng._loop_step_masked_chunk
            self._fn_ring = (eng._loop_step_ring_chunk if self.track_sparsity
                             else eng._loop_step_ring_chunk_quiet)
        self._fn_reset = eng._loop_reset

    def _warm_executables(self) -> None:
        """AOT-compile the step executable this loop dispatches
        (``jax.jit(...).lower().compile()`` via the engine's keyed cache —
        loops sharing an engine share executables).  Slot count, chunk
        size, and ring shape are fixed at construction, so after this a
        steady-state serve never compiles — the class of bug PR 6 caught
        as a mid-serve compile storm, now guarded by
        tests/test_compile_count.py."""
        eng = self.engine
        sds = jax.ShapeDtypeStruct
        st = jax.tree.map(lambda a: sds(a.shape, a.dtype), self.state)
        b, c, d = self.slots, self.chunk_frames, eng.cfg.input_dim
        if self.pipeline_depth == 0:
            if c == 1:
                self._fn_step = eng.aot_compile(
                    ("v1", b), eng._loop_step_masked, st,
                    sds((b, d), jnp.float32), sds((b,), jnp.bool_))
            else:
                self._fn_step = eng.aot_compile(
                    ("v1-chunk", b, c), eng._loop_step_masked_chunk, st,
                    sds((c, b, d), jnp.float32), sds((c, b), jnp.bool_))
        else:
            ring = sds(self._ring.shape, self._ring.dtype)
            if c == 1:
                x, ctrl = sds((b, d), jnp.float32), sds((2, b), jnp.int32)
                if self.track_sparsity:
                    self._fn_ring = eng.aot_compile(
                        ("v2", b, self.ring_frames), eng._loop_step_ring,
                        st, x, ctrl, ring,
                        sds(self._aux_acc.shape, self._aux_acc.dtype))
                else:
                    self._fn_ring = eng.aot_compile(
                        ("v2-quiet", b, self.ring_frames),
                        eng._loop_step_ring_quiet, st, x, ctrl, ring)
            else:
                x = sds((c, b, d), jnp.float32)
                ctrl = sds((2, c, b), jnp.int32)
                if self.track_sparsity:
                    self._fn_ring = eng.aot_compile(
                        ("v2-chunk", b, c, self.ring_frames),
                        eng._loop_step_ring_chunk, st, x, ctrl, ring,
                        sds(self._aux_acc.shape, self._aux_acc.dtype))
                else:
                    self._fn_ring = eng.aot_compile(
                        ("v2-chunk-quiet", b, c, self.ring_frames),
                        eng._loop_step_ring_chunk_quiet, st, x, ctrl, ring)
        self._warm_slot_ops()

    @property
    def step_executable(self):
        """The step this loop dispatches: after ``aot_warmup`` the
        compiled executable (``as_text()``, ``memory_analysis()``), else
        the jitted function."""
        return self._fn_ring if self.pipeline_depth >= 1 else self._fn_step

    def _warm_slot_ops(self) -> None:
        """Build the one slot-reset executable, and touch the eager ring
        helpers: each static slot index bakes its own tiny ring-row
        harvest slice, and the retire fence slice one more, so warming
        them here keeps mid-serve compiles at zero."""
        self._fn_reset = self._compile_reset()
        if self._ring is not None:
            for i in range(self.slots):
                jax.block_until_ready(self._ring[i])
            jax.block_until_ready(self._ring_fence())

    def _compile_reset(self):
        """AOT-compile the slot reset over this loop's state and a
        (slots,) mask, in the engine's keyed cache: one executable per
        slot count, whatever slots it zeroes.  (Overridden by the sharded
        loop, which compiles against its placed state.)"""
        eng, sds = self.engine, jax.ShapeDtypeStruct
        st = jax.tree.map(lambda a: sds(a.shape, a.dtype), self.state)
        return eng.aot_compile(("reset", self.slots), eng._loop_reset, st,
                               sds((self.slots,), jnp.bool_))

    def _slot_mask(self, mask: np.ndarray):
        """The reset's (slots,) mask operand (overridden to place it with
        the slot sharding)."""
        return mask

    def _ring_fence(self):
        """A tiny eager slice of the just-dispatched ring, used as the
        retire-time fence handle.  The ring array itself can no longer be
        the handle: the *next* dispatch donates (deletes) it, and blocking
        on a deleted buffer raises — the slice owns its own buffer and
        becomes ready exactly when the step's ring output does."""
        with TraceAnnotation("rsnn.fence"):
            return self._ring[0, 0, 0]

    # ------------------------------------------------------------- frontend

    def submit(self, frames: np.ndarray) -> int:
        return self._enqueue(self._validate_frames(frames))

    def _validate_frames(self, frames) -> np.ndarray:
        frames = np.asarray(frames)
        d = self.engine.cfg.input_dim
        if frames.ndim != 2 or frames.shape[-1] != d:
            # fail at submit time, not as a broadcast error deep in step_once
            raise ValueError(
                f"frames must have shape (T, input_dim={d}); "
                f"got {frames.shape}")
        if (self.engine._input_scale is None
                and frames.size and np.any(frames != np.round(frames))):
            # every loop contract now fuses quantization into the jitted
            # dispatch (v1 included), so the eager integer-contract check
            # cannot run per step — enforce it here, once per utterance
            raise ValueError(
                "input_scale=None requires integer-valued features; "
                "pass input_scale=calibrate_input_scale(features)")
        return frames

    def _enqueue(self, frames: np.ndarray) -> int:
        sid = self._new_sid()
        req = StreamRequest(sid, frames, fc_dim=self.engine.cfg.fc_dim)
        req.t_submit = self.clock()
        if len(req.frames) == 0:  # empty utterance: nothing to stream
            req.done = True
            req.t_start = req.t_done = req.t_harvest = req.t_submit
            self.finished.append(req)
        else:
            self.queue.append(req)
        return sid

    def _refill(self) -> None:
        """Fill free slots from the queue, then zero the recurrent state of
        every slot just filled in one compiled dispatch."""
        super()._refill()
        if self._to_reset.any():
            mask, self._to_reset = self._to_reset, np.zeros(self.slots, bool)
            self.state = self._fn_reset(self.state, self._slot_mask(mask))
            self.reset_dispatches += 1

    def _on_slot_filled(self, i: int, req: StreamRequest) -> None:
        """Fresh utterance boundary: zero the slot's harvest cursor and
        mark its state for ``_refill``'s reset.  (The previous occupant's
        un-materialized logit blocks, if any, were already sliced out of
        the ring at its completion — ring rows are dead once harvested, so
        the new stream may overwrite them while those blocks are still in
        flight.)"""
        req.t_start = self.clock()
        self.refills += 1
        self._flushed[i] = 0
        with TraceAnnotation("rsnn.reset_slot", sid=req.sid, slot=i):
            self._to_reset[i] = True

    def _finish_slot(self, i: int) -> StreamRequest:
        req = super()._finish_slot(i)
        self.completions += 1
        req.t_done = self.clock()
        if self.pipeline_depth == 0:
            # synchronous contract: logits were fetched this step, so the
            # stream is fully host-resident the moment it finishes
            req.t_harvest = req.t_done
        return req

    # ------------------------------------------------------------ step path

    def _gather_host_frames(self) -> np.ndarray | None:
        """Host-side frame assembly: idle slots carry zero frames (the
        counter masking keys off the active mask, not this zeroing).
        (The sharded loop gathers on device and returns None.)"""
        d = self.engine.cfg.input_dim
        x = np.zeros((self.slots, d), np.float32)
        for i, r in enumerate(self.slot_req):
            if r is not None:
                x[i] = r.frames[self.slot_pos[i]]
        return x

    def _dispatch_step(self, x: np.ndarray, active: np.ndarray):
        """v1 path: advance the engine one frame over all slots through the
        donated (and, with ``aot_warmup``, pre-compiled) step — input
        quantization fused into the dispatch, state updated in place.
        Returns (logits (slots, fc_dim) np, packed masked counter
        vector)."""
        self.state, logits, aux_vec = self._fn_step(self.state, x, active)
        return np.asarray(logits), aux_vec

    def _dispatch_ring_step(self, x: np.ndarray, ctrl: np.ndarray) -> None:
        """v2 path: dispatch one pipelined step (no host transfer; input
        quantization is fused into the jitted step, all scalar operands
        ride the packed ``ctrl`` word).  The state, ring, and counter
        accumulator are donated — XLA writes the ring row in place."""
        if self.counters is None:
            self.state, self._ring = self._fn_ring(
                self.state, x, ctrl, self._ring)
        else:
            self.state, self._ring, self._aux_acc = self._fn_ring(
                self.state, x, ctrl, self._ring, self._aux_acc)

    def step_once(self) -> bool:
        """One engine step over all slots; returns False when fully drained
        (empty queue, empty slots, and — in the pipelined contract — an
        empty in-flight pipeline).  Its phases are profiler spans
        (``rsnn.step`` and its children; docs/serving.md)."""
        with TraceAnnotation("rsnn.step"):
            with TraceAnnotation("rsnn.refill"):
                self._refill()
            active = self.active_mask()
            if not active.any():
                if self._inflight:  # shutdown drain: retire, no dispatch
                    self._retire()
                    return True
                return False
            if self.pipeline_depth == 0:
                if self.chunk_frames == 1:
                    return self._step_once_sync(active)
                return self._step_once_sync_chunk()
            if self.chunk_frames > 1:
                return self._step_once_chunk()

            with TraceAnnotation("rsnn.assemble"):
                x = self._gather_host_frames()
                ctrl = np.zeros((2, self.slots), np.int32)  # [mask; ring idx]
                ctrl[0] = active
                ctrl[1] = [self.slot_pos[i] - self._flushed[i]
                           if self.slot_req[i] is not None else 0
                           for i in range(self.slots)]
            with TraceAnnotation("rsnn.dispatch"):
                self._dispatch_ring_step(x, ctrl)
            return self._after_ring_dispatch([1] * self.slots,
                                             int(active.sum()))

    def _after_ring_dispatch(self, counts: list[int], served: int) -> bool:
        """Pipelined bookkeeping of a step just dispatched that advanced
        each occupied slot by ``counts[i]`` frames, ``served`` in all:
        count it, advance the slots, queue its fence, and retire down to
        ``pipeline_depth - 1`` steps in flight."""
        self.steps += 1
        self.dispatches += 1
        self.frames_served += served
        if self.counters is not None:
            self._frames_acc += float(served)
        completed = self._advance_slots(counts)
        self._inflight.append(_InflightStep(self._ring_fence(), completed))
        while len(self._inflight) > max(self.pipeline_depth - 1, 0):
            self._retire()
        return True

    # -------------------------------------------------- chunked step paths

    def _chunk_counts(self) -> list[int]:
        """Frames each slot serves in this chunk: bounded by the chunk
        size and the stream's remaining frames (ragged tail).  A slot that
        completes idles to the chunk boundary (no mid-chunk refill) with
        its sub-steps masked from the ring and the counters; its next
        occupant's state is reset at refill, so the idle advance is
        invisible.  In the pipelined contract a live slot never idles:
        ``ring_frames`` is a multiple of ``chunk_frames`` (constructor
        invariant), so fill advances in whole chunks, hits the watermark
        exactly at a chunk boundary, and the flush restores full capacity
        — which is also why a stream longer than ``ring_frames`` never
        deadlocks."""
        counts = []
        for i, r in enumerate(self.slot_req):
            if r is None:
                counts.append(0)
                continue
            n = min(self.chunk_frames, len(r.frames) - self.slot_pos[i])
            if self.pipeline_depth >= 1:
                cap = self.ring_frames - (self.slot_pos[i] - self._flushed[i])
                assert cap >= n, "live slot would idle mid-chunk (ring " \
                    "capacity below a chunk — the constructor invariant " \
                    "should make this unreachable)"
            counts.append(n)
        return counts

    def _stage_chunk(self, counts: list[int]) -> np.ndarray | None:
        """Host-side chunk staging: the next ``counts[i]`` frames of each
        slot into an (F, slots, input_dim) buffer; idle sub-steps stay
        zero (the fill mask, not this zeroing, keys the counters).  (The
        sharded loop gathers on device and returns None.)"""
        x = np.zeros((self.chunk_frames, self.slots, self.engine.cfg.input_dim),
                     np.float32)
        for i, r in enumerate(self.slot_req):
            if counts[i]:
                p = self.slot_pos[i]
                x[:counts[i], i] = r.frames[p:p + counts[i]]
        return x

    def _dispatch_step_chunk(self, x: np.ndarray, act: np.ndarray):
        """v1 chunked dispatch: (F, slots) fill mask ``act`` -> (logits
        (F, slots, fc_dim) np, packed masked counter vector)."""
        self.state, logits, aux_vec = self._fn_step(self.state, x, act)
        return np.asarray(logits), aux_vec

    def _step_once_sync_chunk(self) -> bool:
        """v1 synchronous contract at ``chunk_frames > 1``: one dispatch
        and one logit fetch per chunk, scheduling otherwise identical to
        per-frame stepping."""
        with TraceAnnotation("rsnn.assemble"):
            counts = self._chunk_counts()
            act = np.zeros((self.chunk_frames, self.slots), bool)
            for i, n in enumerate(counts):
                act[:n, i] = True
            x = self._stage_chunk(counts)
        with TraceAnnotation("rsnn.dispatch"):
            logits_np, aux_vec = self._dispatch_step_chunk(x, act)
        self.host_syncs += 1  # per-chunk logit fetch
        self.steps += 1
        self.dispatches += 1
        served = int(sum(counts))
        self.frames_served += served
        if self.counters is not None:
            self.counters.update(
                unpack_step_aux(aux_vec, self.engine.cfg.num_ts),
                active_frames=float(served))
            self.host_syncs += 1
        for i, r in enumerate(self.slot_req):
            if r is None or counts[i] == 0:
                continue
            r.logits.extend(logits_np[:counts[i], i])
            self.slot_pos[i] += counts[i]
            if self.slot_pos[i] == len(r.frames):
                self._finish_slot(i)
        return True

    def _dispatch_ring_chunk(self, x: np.ndarray, ctrl: np.ndarray) -> None:
        """v2 chunked dispatch (no host transfer): ``ctrl`` is the packed
        (2, F, slots) word of ``_ring_chunk_step_fused``."""
        if self.counters is None:
            self.state, self._ring = self._fn_ring(
                self.state, x, ctrl, self._ring)
        else:
            self.state, self._ring, self._aux_acc = self._fn_ring(
                self.state, x, ctrl, self._ring, self._aux_acc)

    def _step_once_chunk(self) -> bool:
        """v2 pipelined contract at ``chunk_frames > 1``: one in-flight
        pipeline entry per chunk."""
        with TraceAnnotation("rsnn.assemble"):
            counts = self._chunk_counts()
            c, b = self.chunk_frames, self.slots
            ctrl = np.zeros((2, c, b), np.int32)
            # default ring index is one past the end: idle sub-steps'
            # writes are dropped (mode="drop" in _ring_write_chunk)
            ctrl[1] = self.ring_frames
            for i, n in enumerate(counts):
                if n:
                    base = self.slot_pos[i] - self._flushed[i]
                    ctrl[0, :n, i] = 1
                    ctrl[1, :n, i] = base + np.arange(n)
            x = self._stage_chunk(counts)
        with TraceAnnotation("rsnn.dispatch"):
            self._dispatch_ring_chunk(x, ctrl)
        return self._after_ring_dispatch(counts, int(sum(counts)))

    def _advance_slots(self, counts: list[int]) -> list[StreamRequest]:
        """Dispatch-time bookkeeping: advance each occupied slot's cursor
        by ``counts[i]`` frames (one per step, or the chunk's fill),
        harvest completed or watermark-full slots, and return the
        completed requests.  Completion depends only on host-side frame
        counts, so this is safe to run while the step is still in flight
        — the schedule is identical to the synchronous contract's."""
        completed = []
        for i, r in enumerate(self.slot_req):
            if r is not None and counts[i] and self._advance_slot(
                    i, r, counts[i]):
                completed.append(r)
        return completed

    def _advance_slot(self, i: int, r: StreamRequest, n: int) -> bool:
        """Advance slot ``i`` by ``n`` frames.  On completion, or when the
        ring row is full (watermark flush), slice the row for harvest (a
        lazy device slice — the fetch happens at retire time); on
        completion also free the slot.  ``n`` is capped by the
        remaining ring capacity, so fill never exceeds ``ring_frames``.
        Returns whether ``r`` completed."""
        self.slot_pos[i] += n
        fill = self.slot_pos[i] - self._flushed[i]
        done = self.slot_pos[i] == len(r.frames)
        if not done and fill != self.ring_frames:
            return False
        with TraceAnnotation("rsnn.complete", sid=r.sid, slot=i):
            if fill > 0:
                r.pending.append((self._ring[i], fill))
            if done:
                self._finish_slot(i)
                self._flushed[i] = 0
            else:  # watermark flush: the ring row is full
                self.watermark_flushes += 1
                self._flushed[i] = self.slot_pos[i]
        return done

    def _retire(self) -> None:
        """Retire the oldest in-flight step: fence on its completion, then
        materialize the logit blocks of streams it completed."""
        with TraceAnnotation("rsnn.retire"):
            step = self._inflight.popleft()
            if step.handle is not None:
                with TraceAnnotation("rsnn.fence_wait"):
                    jax.block_until_ready(step.handle)  # fence, not a transfer
            for r in step.completed:
                self._egress(r)
                r.t_harvest = self.clock()

    def _egress(self, r: StreamRequest) -> None:
        """Fetch ``r``'s pending logit blocks to the host, counting the
        bytes that cross (whole ring rows) and the valid ones in them."""
        nbytes = valid = 0
        for block, fill in r.pending:
            nbytes += block.nbytes
            valid += fill * block.shape[-1] * block.dtype.itemsize
        with TraceAnnotation("rsnn.egress", sid=r.sid, bytes=nbytes,
                             valid_bytes=valid):
            self.host_syncs += r._materialize()
        self.egress_bytes += nbytes
        self.egress_valid_bytes += valid

    def _step_once_sync(self, active: np.ndarray) -> bool:
        """v1 synchronous contract: fetch logits (and counters, when a sink
        is attached) to the host every step."""
        with TraceAnnotation("rsnn.assemble"):
            x = self._gather_host_frames()
        with TraceAnnotation("rsnn.dispatch"):  # and the logit fetch
            logits_np, aux_vec = self._dispatch_step(x, active)
        self.host_syncs += 1  # per-frame logit fetch
        self.steps += 1
        self.dispatches += 1
        self.frames_served += int(active.sum())
        if self.counters is not None:
            # the packed-vector fetch is gated on an attached sink
            self.counters.update(
                unpack_step_aux(aux_vec, self.engine.cfg.num_ts),
                active_frames=float(active.sum()))
            self.host_syncs += 1
        for i, r in enumerate(self.slot_req):
            if r is None:
                continue
            r.logits.append(logits_np[i])
            self.slot_pos[i] += 1
            if self.slot_pos[i] == len(r.frames):
                self._finish_slot(i)
        return True

    @property
    def pending_steps(self) -> int:
        """Device steps dispatched but not yet retired."""
        return len(self._inflight)

    def flush(self) -> None:
        """Drain the pipeline deterministically: retire every in-flight step
        (materializing completed streams' logits) and fold the device-side
        counter accumulator into ``counters``.  After ``flush()``,
        ``pending_steps == 0`` and the metrics cover every dispatched step.
        In-progress streams keep their un-watermarked logits on device —
        those cross on completion, per the contract."""
        while self._inflight:
            self._retire()
        self._drain_aux()

    def run(self) -> list[StreamRequest]:
        """Drain queue, slots, and pipeline; returns finished requests in
        sid order, logits materialized."""
        while self.step_once():
            pass
        self.flush()
        return sorted(self.finished, key=lambda r: r.sid)

    # --------------------------------------------------- measured complexity

    def reset_metrics(self) -> None:
        """Zero the measured-traffic counters (e.g. after a warmup run)."""
        cfg = self.engine.cfg
        self.counters = (complexity.SparsityCounters(
            num_ts=cfg.num_ts, hidden_dim=cfg.hidden_dim,
            input_dim=cfg.input_dim, input_bits=cfg.input_bits)
            if self.track_sparsity else None)
        self._aux_acc = (self._zero_aux_acc()
                         if self.track_sparsity and self.pipeline_depth >= 1
                         else None)
        self._frames_acc = 0.0
        self.steps = 0
        self.host_syncs = 0
        self.dispatches = 0  # jitted device dispatches (1/chunk, not 1/frame)
        self.frames_served = 0  # slot-frames advanced across all dispatches
        # at the boundaries of the step path's profiler spans (rsnn.*)
        self.refills = 0  # requests placed into a slot
        self.completions = 0  # requests that left their slot, done
        self.watermark_flushes = 0  # ring rows sliced before completion
        self.egress_bytes = 0  # logit-block bytes fetched at retire
        self.egress_valid_bytes = 0  # of them, the frames' valid rows
        self.reset_dispatches = 0  # compiled slot resets (one per refill)

    def _drain_aux(self) -> None:
        """Fold the device-side counter accumulator into ``counters`` (one
        host transfer for all steps since the last drain)."""
        if self.counters is None or self._frames_acc == 0.0:
            return
        self.counters.update(
            unpack_step_aux(self._aux_acc, self.engine.cfg.num_ts),
            active_frames=self._frames_acc)
        self.host_syncs += 1
        self._frames_acc = 0.0
        self._aux_acc = self._zero_aux_acc()

    def _require_counters(self) -> complexity.SparsityCounters:
        if self.counters is None:
            raise ValueError(
                "sparsity tracking is disabled (track_sparsity=False); "
                "construct the loop with track_sparsity=True to measure "
                "profiles/MMAC/s")
        self._drain_aux()
        return self.counters

    def sparsity_profile(self) -> complexity.SparsityProfile:
        return self._require_counters().profile()

    def mmac_per_second(self, fc_prune_frac: float | None = None) -> float:
        """Zero-skip MMAC/s of the traffic served so far (paper Fig. 13).

        Defaults to the pruning fraction of the model the engine actually
        serves."""
        counters = self._require_counters()
        if fc_prune_frac is None:
            fc_prune_frac = self.engine.fc_prune_frac
        return counters.mmac_per_second(
            self.engine.cfg, merged_spike=self.engine.cfg.merged_spike,
            fc_prune_frac=fc_prune_frac)

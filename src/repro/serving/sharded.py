"""Sharded StreamLoop: the slot batch distributed over a device mesh.

``serving/stream.py``'s ``StreamLoop`` drives one device and assembles each
step's frame batch with a per-slot host loop.  This module scales the same
engine out:

  * **Placement.**  A 1-D ``data`` mesh over the serving devices
    (``stream_mesh``).  The packed weights replicate onto every device
    (``CompiledRSNN.place_weights`` — the paper's 0.1 MB model is the TPU
    analogue of everything-on-chip, so there is no tensor parallelism to
    pay for); the recurrent slot state shards on its slot dim with
    ``distributed.sharding.stream_state_specs``, and the on-device logit
    ring of the pipelined contract with
    ``distributed.sharding.stream_ring_spec``.
  * **Pinned frame buffer.**  Each slot owns a row of a device-resident
    ``(slots, max_frames, input_dim)`` buffer of *pre-quantized* frames,
    written once when the slot is (re)filled.  The per-step frame gather
    and idle-slot masking are device-side ops inside the jitted step — the
    host no longer touches frame data on the step path.
  * **Pipelining.**  The inherited contract-v2 loop applies unchanged: up
    to ``pipeline_depth`` jitted steps stay in flight, per-slot logits
    accumulate in the sharded ring and cross to the host once per stream
    (or watermark flush), and the packed counter vector accumulates on
    device, crossing once per drain.  ``pipeline_depth=0`` keeps the v1
    per-step fetch path.
  * **Front-end.**  ``data.featurize.AsyncFeaturizer`` quantizes utterances
    on a background thread ahead of the loop; ``submit(..., quantized=True)``
    accepts its output directly, and ``AsyncFeaturizer.for_loop`` sizes the
    prefetch queue to feed the pipeline (``slots + pipeline_depth``).
    Quantization is elementwise with a static scale, so the front-end is
    bit-transparent.

Scheduling (queue order, refill-at-step-start, reset-on-refill, pipeline
retirement) is *inherited* from ``StreamLoop`` — only the data path is
overridden — and the jitted step wraps the same ``_frame_step``, so logits
are identical to the single-device loop on the same utterance set
(tests/test_sharded_stream.py proves this on 8 virtual devices, pipelined
against the synchronous single-device baseline).

An engine built with ``CompiledRSNN.from_artifact`` (the on-disk
deployment artifact of ``core/artifact.py``) drops in unchanged: the
constructor replicates whatever weight payload the engine carries via
``place_weights``, so artifact-served sharded logits match the in-memory
model bit for bit (tests/test_artifact.py).
"""

from __future__ import annotations

from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shd
from repro.serving.stream import CompiledRSNN, StreamLoop, StreamRequest


def stream_mesh(devices=None) -> Mesh:
    """1-D ``data`` mesh over the serving devices (default: all local)."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs, ("data",))


class ShardedStreamLoop(StreamLoop):
    """Continuous batching over recurrent-state slots sharded on a mesh.

    Subclasses ``stream.StreamLoop``: the scheduling layer (submit queue,
    refill/finish bookkeeping, pipeline retirement, counters) is inherited
    verbatim — only the data path is overridden, so "same scheduling, same
    logits" is structural, not a convention to maintain by hand.  The
    decode batch, RSNN state, frame buffer, and logit ring live sharded
    across the mesh's ``data`` axis and every per-step data movement is a
    device-side op.
    """

    def __init__(self, engine: CompiledRSNN, batch_slots: int | None = None,
                 mesh: Mesh | None = None, max_frames: int = 1024,
                 pipeline_depth: int = 2, ring_frames: int | None = None,
                 track_sparsity: bool = True, chunk_frames: int = 1,
                 aot_warmup: bool = True):
        self.mesh = mesh if mesh is not None else stream_mesh()
        ndev = self.mesh.shape["data"]
        slots = batch_slots if batch_slots is not None else ndev
        if slots < 1 or slots % ndev != 0:
            raise ValueError(f"batch_slots={slots} must be a positive "
                             f"multiple of the mesh's {ndev} devices")
        self.max_frames = max_frames
        self._rep = NamedSharding(self.mesh, P())
        self._slot = NamedSharding(self.mesh, P("data"))
        self._ctrl = NamedSharding(self.mesh, P(None, "data"))
        self._ctrl3 = NamedSharding(self.mesh, P(None, None, "data"))
        engine.place_weights(self._rep)

        # streams are capped at max_frames, so the ring never needs more
        ring = min(ring_frames if ring_frames is not None else 256,
                   max_frames)
        super().__init__(engine, batch_slots=slots,
                         pipeline_depth=pipeline_depth, ring_frames=ring,
                         track_sparsity=track_sparsity,
                         chunk_frames=chunk_frames, aot_warmup=aot_warmup)
        self.state = jax.device_put(
            self.state, shd.stream_shardings(self.state, self.mesh))
        self._buf = jax.device_put(
            jnp.zeros((slots, max_frames, engine.cfg.input_dim), jnp.float32),
            NamedSharding(self.mesh, shd.stream_ring_spec()))
        # the slot reset keeps the state's placement: mask on the slot
        # sharding, every output leaf where its input leaf lives
        self._jit_reset = jax.jit(
            engine._reset_slots, donate_argnums=(0,),
            out_shardings=jax.tree.map(lambda a: a.sharding, self.state))
        # the loop-carried buffers (state, and for the pipelined contract
        # the ring + counter accumulator) are donated so their updates are
        # in-place; the pinned frame buffer is read-only in-step and reused
        # across steps, so it is NOT donated
        self._jit_step = jax.jit(self._device_step, donate_argnums=(0,))
        self._jit_ring_step = jax.jit(self._device_ring_step,
                                      donate_argnums=(0, 3, 4))
        self._jit_ring_quiet = jax.jit(self._device_ring_step_quiet,
                                       donate_argnums=(0, 3))
        self._jit_chunk_step = jax.jit(self._device_chunk_step,
                                       donate_argnums=(0,))
        self._jit_ring_chunk = jax.jit(self._device_ring_chunk,
                                       donate_argnums=(0, 3, 4))
        self._jit_ring_chunk_quiet = jax.jit(self._device_ring_chunk_quiet,
                                             donate_argnums=(0, 3))
        # the base constructor's binding/warmup ran before these jits (and
        # the placed buffers) existed and early-returned; do it for real now
        self._bind_step_fns()
        if aot_warmup:
            self._warm_executables()

    # --------------------------------------------------- sharded placement

    def _init_ring(self):
        return jax.device_put(
            jnp.zeros((self.slots, self.ring_frames, self.engine.cfg.fc_dim),
                      jnp.float32),
            NamedSharding(self.mesh, shd.stream_ring_spec()))

    def _zero_aux_acc(self):
        return jax.device_put(
            jnp.zeros((2 * self.engine.cfg.num_ts + 4,), jnp.float32),
            self._rep)

    # ------------------------------------------------------------- frontend

    def submit(self, frames: np.ndarray, *, quantized: bool = False) -> int:
        """Queue one utterance.  ``quantized=True`` marks frames already in
        the engine's 8-bit fixed-point format (e.g. from
        ``data.featurize.AsyncFeaturizer``); raw frames are quantized here,
        once, before they enter the pinned buffer."""
        frames = self._validate_frames(frames)
        if len(frames) > self.max_frames:
            raise ValueError(
                f"utterance of {len(frames)} frames exceeds the pinned "
                f"buffer ({self.max_frames}); raise max_frames")
        if not quantized and len(frames):
            frames = np.asarray(
                self.engine.quantize_features(jnp.asarray(frames)))
        return self._enqueue(frames)

    def submit_stream(self, utterances: Iterable[np.ndarray], *,
                      quantized: bool = False) -> list[int]:
        """Submit everything an iterable yields, serving while it drains.

        Once the queue backlog covers every slot, engine steps run between
        pulls — so with an ``AsyncFeaturizer`` source (pass
        ``quantized=True`` for its pre-quantized output), featurization of
        later utterances genuinely overlaps serving of earlier ones (the
        per-stream logits don't depend on packing, so this is
        result-transparent; call ``run()`` afterwards to drain).
        """
        sids = []
        try:
            for u in utterances:
                sids.append(self.submit(u, quantized=quantized))
                while len(self.queue) >= self.slots:
                    self.step_once()
        except BaseException:
            close = getattr(utterances, "close", None)
            if callable(close):  # stop an AsyncFeaturizer's worker thread
                close()
            raise
        return sids

    # ------------------------------------------------------------ step path

    def _gather_frames(self, buf, pos, active):
        """Device-side per-slot frame gather + idle masking."""
        idx = jnp.clip(pos, 0, self.max_frames - 1)
        x = jnp.take_along_axis(buf, idx[:, None, None], axis=1)[:, 0]
        return jnp.where(active[:, None], x, jnp.zeros_like(x))  # idle -> 0

    def _device_step(self, state, buf, pos, active):
        """(state, buffer, per-slot cursor, mask) -> (state, logits, aux)."""
        x = self._gather_frames(buf, pos, active)
        return self.engine._masked_frame_step(state, x, active)

    def _device_ring_step(self, state, buf, ctrl, ring, aux_acc):
        """Pipelined variant: logits into the sharded ring, counters into
        the device accumulator -> (state, ring, aux_acc).  ``ctrl`` is the
        packed (3, slots) int32 control word — frame cursor, active mask,
        ring write index — one small sharded transfer per step."""
        pos, active, ring_idx = ctrl[0], ctrl[1].astype(bool), ctrl[2]
        x = self._gather_frames(buf, pos, active)
        return self.engine._ring_frame_step(state, x, active, ring, ring_idx,
                                            aux_acc)

    def _device_ring_step_quiet(self, state, buf, ctrl, ring):
        pos, active, ring_idx = ctrl[0], ctrl[1].astype(bool), ctrl[2]
        x = self._gather_frames(buf, pos, active)
        return self.engine._ring_frame_step_quiet(state, x, ring, ring_idx)

    def _gather_chunk_frames(self, buf, pos, active):
        """Chunked device-side gather: per-sub-step cursors ``pos`` (F,
        slots) -> (F, slots, input_dim) frames, idle sub-steps zeroed."""
        idx = jnp.clip(pos, 0, self.max_frames - 1)
        x = jnp.take_along_axis(buf, idx.T[:, :, None], axis=1)
        x = jnp.swapaxes(x, 0, 1)
        return jnp.where(active[:, :, None], x, jnp.zeros_like(x))

    def _device_chunk_step(self, state, buf, pos, active):
        """Chunked ``_device_step``: F frames per slot in one dispatch."""
        x = self._gather_chunk_frames(buf, pos, active)
        return self.engine._masked_chunk_step(state, x, active)

    def _device_ring_chunk(self, state, buf, ctrl, ring, aux_acc):
        """Chunked ``_device_ring_step``: ``ctrl`` is the packed
        (3, F, slots) int32 word — per-sub-step frame cursor, fill mask,
        and ring write index (``ring_frames``, i.e. dropped, when idle)."""
        pos, active, ring_idx = ctrl[0], ctrl[1].astype(bool), ctrl[2]
        x = self._gather_chunk_frames(buf, pos, active)
        return self.engine._ring_chunk_step(state, x, active, ring, ring_idx,
                                            aux_acc)

    def _device_ring_chunk_quiet(self, state, buf, ctrl, ring):
        pos, active, ring_idx = ctrl[0], ctrl[1].astype(bool), ctrl[2]
        x = self._gather_chunk_frames(buf, pos, active)
        return self.engine._ring_chunk_step_quiet(state, x, ring, ring_idx)

    def _on_slot_filled(self, i: int, req: StreamRequest) -> None:
        """Pin the slot's quantized frames into its device buffer row.

        Only ``len(frames)`` rows transfer; stale rows past the utterance
        end are never read (an active slot's cursor stays < its length and
        idle slots are masked in the device step)."""
        super()._on_slot_filled(i, req)
        self._buf = self._buf.at[i, : len(req.frames)].set(
            jnp.asarray(req.frames, jnp.float32))

    def _gather_host_frames(self) -> None:
        return None  # frames are gathered on device from the pinned buffer

    def _stage_chunk(self, counts: list[int]) -> None:
        return None

    def _dispatch_step(self, x: None, active: np.ndarray):
        pos = jax.device_put(np.asarray(self.slot_pos, np.int32), self._slot)
        act = jax.device_put(active, self._slot)
        self.state, logits, aux_vec = self._fn_step(
            self.state, self._buf, pos, act)
        return np.asarray(logits), aux_vec

    def _dispatch_ring_step(self, x: None, ctrl: np.ndarray) -> None:
        word = np.empty((3, self.slots), np.int32)
        word[0] = self.slot_pos
        word[1:] = ctrl  # [active mask; ring idx] from the base loop
        word_d = jax.device_put(word, self._ctrl)
        if self.counters is None:
            self.state, self._ring = self._fn_ring(
                self.state, self._buf, word_d, self._ring)
        else:
            self.state, self._ring, self._aux_acc = self._fn_ring(
                self.state, self._buf, word_d, self._ring, self._aux_acc)

    def _chunk_cursors(self) -> np.ndarray:
        """Per-sub-step frame cursors (F, slots): the base cursor plus the
        sub-step offset.  Out-of-range rows (idle sub-steps) are clipped
        in-graph and masked by the fill mask."""
        return (np.asarray(self.slot_pos, np.int32)[None, :]
                + np.arange(self.chunk_frames, dtype=np.int32)[:, None])

    def _dispatch_step_chunk(self, x: None, act: np.ndarray):
        pos = jax.device_put(self._chunk_cursors(), self._ctrl)
        actd = jax.device_put(act, self._ctrl)
        self.state, logits, aux_vec = self._fn_step(
            self.state, self._buf, pos, actd)
        return np.asarray(logits), aux_vec

    def _dispatch_ring_chunk(self, x: None, ctrl: np.ndarray) -> None:
        word = np.empty((3, self.chunk_frames, self.slots), np.int32)
        word[0] = self._chunk_cursors()
        word[1:] = ctrl  # [fill mask; ring idx] from the base loop
        word_d = jax.device_put(word, self._ctrl3)
        if self.counters is None:
            self.state, self._ring = self._fn_ring(
                self.state, self._buf, word_d, self._ring)
        else:
            self.state, self._ring, self._aux_acc = self._fn_ring(
                self.state, self._buf, word_d, self._ring, self._aux_acc)

    # -------------------------------------------------- executables / warmup

    def _bind_step_fns(self) -> None:
        if not hasattr(self, "_jit_ring_quiet"):
            return  # called from super().__init__ before our jits exist
        if self.chunk_frames == 1:
            self._fn_step = self._jit_step
            self._fn_ring = (self._jit_ring_step if self.track_sparsity
                             else self._jit_ring_quiet)
        else:
            self._fn_step = self._jit_chunk_step
            self._fn_ring = (self._jit_ring_chunk if self.track_sparsity
                             else self._jit_ring_chunk_quiet)
        self._fn_reset = self._jit_reset

    def _compile_reset(self):
        """The slot reset compiled against the placed state; like the
        sharded steps it lives on the loop."""
        exe = self._jit_reset.lower(
            self.state, self._slot_mask(np.zeros(self.slots, bool))).compile()
        self.engine.compile_count += 1
        return exe

    def _slot_mask(self, mask: np.ndarray):
        return jax.device_put(mask, self._slot)

    def _warm_executables(self) -> None:
        """AOT-compile the sharded step this loop dispatches.  The jits
        close over this loop instance (mesh, placed buffers), so the
        compiled executable lives on the loop, not in the engine's keyed
        cache; ``lower`` never executes, so lowering against the live
        placed buffers is free."""
        if not hasattr(self, "_jit_ring_quiet"):
            return  # called from super().__init__ before our jits exist
        c, b = self.chunk_frames, self.slots
        if self.pipeline_depth == 0:
            if c == 1:
                pos = jax.device_put(np.zeros(b, np.int32), self._slot)
                act = jax.device_put(np.zeros(b, bool), self._slot)
            else:
                pos = jax.device_put(np.zeros((c, b), np.int32), self._ctrl)
                act = jax.device_put(np.zeros((c, b), bool), self._ctrl)
            self._fn_step = self._fn_step.lower(
                self.state, self._buf, pos, act).compile()
        else:
            if c == 1:
                word = jax.device_put(np.zeros((3, b), np.int32), self._ctrl)
            else:
                word = jax.device_put(np.zeros((3, c, b), np.int32),
                                      self._ctrl3)
            args = (self.state, self._buf, word, self._ring)
            if self.track_sparsity:
                args += (self._aux_acc,)
            self._fn_ring = self._fn_ring.lower(*args).compile()
        self.engine.compile_count += 1
        self._warm_slot_ops()

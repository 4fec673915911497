"""Named execution backends for the streaming RSNN engine.

``CompiledRSNN`` used to hard-code its per-layer kernel/oracle selection in
``__init__``/``_kernels``/``_ff_matmul``; this module is that logic as a
dispatch layer.  A *backend* is a named recipe that, given the deployed
weight bundle (``BackendContext``), returns a uniform ``OpTable``:

  * ``rsnn_cell``   — fused recurrent-spiking-layer step (TS parallel);
  * ``ff_matmul``   — per-layer feedforward stimulus ``x @ W`` (resolved
    per layer name and per precision: dense float, dense dequant, or the
    int4 Pallas matmul on the packed nibbles);
  * ``fc``          — the readout over the TS spike trains (merged-spike
    dense, per-ts int4, or the zero-skip sparse path).

The zero-skip readout is *layout-dispatched*: the packed FC tensor's type
resolves its ``core/layouts`` ``WeightLayout`` (padded CSC, group-packed
N:M, ...), and the backend binds either the layout's jnp oracle (``ref``)
or its fused Pallas kernel (``pallas``/``sparse``) — a new layout plugs in
without a backend edit, and a new backend without naming any layout.

Built-in backends:

  ``ref`` (alias ``jnp``)  — the jnp oracles in ``kernels/ref``; with
      ``sparse_fc`` the readout is the packed layout's jnp oracle (the
      materializing reference, e.g. ``core.layouts.csc.sparse_matmul``).
  ``pallas``               — the fused Pallas kernels in ``kernels/ops``
      (interpret mode on CPU, Mosaic on TPU).
  ``sparse``               — ``pallas`` cells/stimulus plus the packed FC
      layout's fused zero-skip kernel (``kernels/sparse_fc`` for CSC,
      ``kernels/nm_fc`` for N:M-group).
  ``fused``                — the single-dispatch mega-step: the op table
      collapses to one ``megastep`` call (``kernels/megastep.py``) that
      runs both cells, the layout-resolved zero-skip FC (bound via each
      layout's ``megastep_fc``), and the sparsity counters in one Pallas
      dispatch with state and packed weights resident in VMEM.
      Bit-identical to ``jnp`` at every loop contract.
  ``delta``                — EdgeDRNN-style delta-temporal zero skipping:
      the op table gains a ``delta_gate`` entry (``kernels/delta_step.py``)
      that holds the previous frame's inputs and input-layer
      pre-activations in the per-slot step state and recomputes the
      stimulus only where ``|x_t - x_prev| > ctx.delta_threshold``;
      measured delta sparsity feeds ``core/complexity.py``.  At
      ``threshold=0`` bit-identical to ``jnp`` at every loop contract
      (tests/test_delta_backend.py).  The recurrent operand is gated too:
      the cell runs through ``kernels/spike_broadcast.spike_cell`` — for a
      binary spike train the event list *is* the delta list (a spiking
      neuron's recurrent contribution changes exactly when it spikes), so
      the same compaction primitive covers EdgeDRNN's second operand.
  ``spike``                — event-driven spike-broadcast path (the
      paper's input-broadcasting scheme as executed compute): every
      spike-consuming matmul — L0-recurrent via
      ``kernels/spike_broadcast.spike_cell``, L1-feedforward via the
      event-gather matmul, and the dense-FC readouts via its
      merged-spike-union variant — compacts the binary spike matrix into
      ascending-index event lists and accumulates only the gathered rows
      of W.  Bit-identical to ``jnp`` at lossless capacity (the default);
      ``ctx.spike_capacity`` models a finite hardware event queue.
  ``fused_spike``          — the ``fused`` mega-step with its spike mode
      on: the same single dispatch, with the three spike matmuls and the
      dense FC modes running over compacted event lists.

New kernels plug in via ``register`` without touching the engine: the
engine resolves a table once at construction and calls through it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import layouts, spike_ops
from repro.core.lif import LIFState
from repro.core.rsnn import RSNNConfig, RSNNState
from repro.kernels import ops, ref


@dataclasses.dataclass(frozen=True)
class BackendContext:
    """The deployed weight bundle an OpTable is resolved against.

    ``dense`` holds float matrices for ops that consume dense weights (the
    full parameter set at float precision; the bit-exact dequant copies at
    int4).  ``quant`` holds the packed int4 layout and ``sparse`` each
    masked tensor's layout-resolved packed form (int4 precision only).
    Resolution happens once per engine build, so the returned closures
    capture concrete arrays and stay jit-friendly.
    """

    cfg: RSNNConfig
    precision: str  # "float" | "int4"
    sparse_fc: bool  # zero-skip layout readout instead of the dense FC
    dense: dict  # name -> (K, N) float32
    quant: dict  # name -> layouts.dense.QuantTensor
    sparse: dict  # name -> layout tensor (SparseColumns / NMGroupPacked)
    delta_threshold: float = 0.0  # delta backend's |x_t - x_prev| gate
    spike_capacity: int | None = None  # event-list slots (None = lossless)
    mesh: Mesh | None = None  # serving mesh whose "data" axis shards slots


class OpTable(NamedTuple):
    """Uniform per-backend op set consumed by ``CompiledRSNN``.

    ``megastep``, when set, supersedes the per-op fields: the engine's
    frame step becomes that one call.  The binding is *chunk-native* —
    ``(state, x_chunk (F, B, input_dim), lif) -> (new_state, logits
    (F, B, fc_dim), aux)`` with every ``aux`` value carrying a leading
    frame axis over ``stream._frame_counters``'s per-frame shapes — so the
    serving loops feed the kernel's F-frame chunk axis directly (one
    dispatch per ``chunk_frames``); a single-frame step is the ``F=1``
    special case.  The per-op entries are never invoked.

    ``delta_gate``, when set, makes the engine carry delta step state
    (``stream.DeltaRSNNState``: held inputs + cached input-layer
    pre-activation per slot) and call ``(x_t, x_prev, pre_prev) ->
    (x_hat, pre, mask)`` before the per-op composition: ``pre`` replaces
    the L0 feedforward stimulus and ``mask``'s reduction feeds the delta
    sparsity counters.
    """

    name: str
    rsnn_cell: Callable  # (stim, s_prev, w, u0, h0, beta, vth) -> (s, u)
    ff_matmul: Callable  # (x2d (M, K), layer_name) -> (M, N)
    fc: Callable  # (spikes_ts (TS, B, H)) -> (B, fc_dim)
    mxu_aligned: bool  # True: batch must satisfy the 128-row MXU tiling
    megastep: Callable | None = None  # whole-frame single-dispatch step
    delta_gate: Callable | None = None  # delta-temporal input gating


class _Entry(NamedTuple):
    builder: Callable  # BackendContext -> OpTable
    dense_stimulus: bool  # int4 ff_matmul consumes dense dequant weights


_REGISTRY: dict[str, _Entry] = {}


def register(name: str, *aliases: str, dense_stimulus: bool = False):
    """Decorator: register an OpTable builder under ``name`` (+ aliases).

    ``dense_stimulus=True`` declares that at int4 precision the backend's
    ``ff_matmul`` reads dense dequantized weights (so the engine must
    materialize them) rather than the packed nibbles.
    """

    def deco(builder: Callable[[BackendContext], OpTable]):
        for key in (name, *aliases):
            _REGISTRY[key] = _Entry(builder, dense_stimulus)
        return builder

    return deco


def available() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def unregister(name: str) -> None:
    """Remove a registered backend (for bench/test-local plugins)."""
    _REGISTRY.pop(name, None)


def needs_dense_stimulus(name: str) -> bool:
    """Whether backend ``name``'s int4 feedforward path wants dense weights."""
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown backend {name!r}; available: {available()}")
    return _REGISTRY[name].dense_stimulus


def resolve(name: str, ctx: BackendContext) -> OpTable:
    """Build the op table of backend ``name`` over the weight bundle."""
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown backend {name!r}; available: {available()}")
    return _REGISTRY[name].builder(ctx)


# ------------------------------------------------------------ op resolution


def _dense_ff(ctx: BackendContext) -> Callable:
    def ff(x2d: jax.Array, name: str) -> jax.Array:
        return x2d @ ctx.dense[name]

    return ff


def _fc_op(ctx: BackendContext, *, mfc: Callable, i4mm: Callable,
           fused: bool) -> Callable:
    """Resolve the readout: layout zero-skip > packed int4 > dense float.

    The zero-skip path dispatches on the packed FC tensor's *layout*
    (``core/layouts`` registry): whatever ``pack_model`` resolved from the
    tensor's ``PruneSpec`` — padded CSC or group-packed N:M — binds here
    without the backend naming it.  ``fused=True`` binds the layout's
    Pallas kernel, ``False`` its jnp oracle.
    """
    if ctx.sparse_fc:
        t = ctx.sparse["fc_w"]
        layout = layouts.layout_of(t)
        fc_fn = layout.fc_kernel if fused else layout.fc_oracle
        return lambda s1: fc_fn(s1, t)
    if ctx.precision == "int4":
        qt = ctx.quant["fc_w"]
        scale = qt.scale.reshape(-1)
        if ctx.cfg.merged_spike:
            return lambda s1: mfc(s1, qt.packed, scale)
        return lambda s1: sum(i4mm(s1[t], qt.packed, scale)
                              for t in range(ctx.cfg.num_ts))
    w = ctx.dense["fc_w"]
    if ctx.cfg.merged_spike:
        return lambda s1: spike_ops.merged_spike_fc(s1, w)
    return lambda s1: (s1 @ w).sum(axis=0)


# ------------------------------------------------------- built-in backends


@register("ref", "jnp", dense_stimulus=True)
def _build_ref(ctx: BackendContext) -> OpTable:
    fc = _fc_op(ctx, mfc=ref.merged_spike_fc_ref, i4mm=ref.int4_matmul_ref,
                fused=False)
    return OpTable(name="ref", rsnn_cell=ref.rsnn_cell_ref,
                   ff_matmul=_dense_ff(ctx), fc=fc, mxu_aligned=False)


@register("delta", dense_stimulus=True)
def _build_delta(ctx: BackendContext) -> OpTable:
    """EdgeDRNN-style delta-temporal zero skipping over the ref table.

    The table is the ``ref`` oracles plus a ``delta_gate`` closure over the
    dense (dequantized-at-int4, bit-exact) L0 feedforward weights: the
    engine carries each slot's held input vector and cached input-layer
    pre-activation (``stream.DeltaRSNNState``) and only recomputes the
    stimulus row for slots with a propagated delta.  ``threshold=0``
    propagates every numeric change, so logits/state/counters are
    bit-identical to ``jnp``; ``threshold>0`` trades stimulus drift for
    measured temporal sparsity (the ``delta_*`` counters -> MMAC/s).

    EdgeDRNN gates *both* operands; the recurrent one is covered by
    running the cell through the spike-event compaction
    (``kernels/spike_broadcast.spike_cell``): a binary spike train's delta
    list between consecutive time steps IS its event list — a recurrent
    column contributes exactly when its neuron spikes — so skipping
    zero-spike rows is the spike-domain form of delta-gating the state
    operand.  Bit-identical, so the ``threshold=0`` contract is untouched.
    """
    table = _build_ref(ctx)
    w0x = ctx.dense["l0_wx"]
    thr = jnp.float32(ctx.delta_threshold)
    cap = ctx.spike_capacity

    def delta_gate(x_t: jax.Array, x_prev: jax.Array, pre_prev: jax.Array):
        return ops.delta_step(x_t, x_prev, pre_prev, w0x, thr)

    def cell(stim, s_prev, w, u0, h0, beta, vth):
        return ops.spike_cell(stim, s_prev, w, u0, h0, beta, vth,
                              capacity=cap)

    return table._replace(name="delta", rsnn_cell=cell,
                          delta_gate=delta_gate)


@register("pallas")
def _build_pallas(ctx: BackendContext) -> OpTable:
    if ctx.precision == "int4":
        def ff(x2d: jax.Array, name: str) -> jax.Array:
            qt = ctx.quant[name]
            return ops.int4_matmul(x2d, qt.packed, qt.scale.reshape(-1))
    else:
        ff = _dense_ff(ctx)

    fc = _fc_op(ctx, mfc=ops.merged_spike_fc, i4mm=ops.int4_matmul,
                fused=True)
    return OpTable(name="pallas", rsnn_cell=ops.rsnn_cell, ff_matmul=ff,
                   fc=fc, mxu_aligned=True)


@register("sparse")
def _build_sparse(ctx: BackendContext) -> OpTable:
    """Pallas cells/stimulus + the packed layout's fused zero-skip readout."""
    ctx = dataclasses.replace(ctx, sparse_fc=True)
    return _build_pallas(ctx)._replace(name="sparse")


@register("spike", dense_stimulus=True)
def _build_spike(ctx: BackendContext) -> OpTable:
    """Event-driven spike-broadcast path: input-side zero skipping.

    Every spike-consuming matmul runs over compacted ascending-index event
    lists (``kernels/spike_broadcast``): the two recurrent cells through
    ``spike_cell``, the L1 feedforward through the event-gather matmul,
    and the dense readouts through its merged-spike-union variant — only
    the rows of W named by actual spikes are fetched and accumulated (the
    paper's input-broadcasting scheme; EdgeDRNN's activation-side skip).
    The analog L0 stimulus is not spike-consuming and stays a dense
    matmul over the (dequantized-at-int4, bit-exact) weights, and a
    layout-packed FC keeps its own weight-side zero-skip kernel.  At the
    default lossless ``ctx.spike_capacity`` the gather accumulates in the
    same partial-sum order as the dense dots, so logits/state/counters are
    bit-identical to ``jnp`` at every loop contract
    (tests/test_backend_conformance.py); a finite capacity truncates each
    row's highest-index events (a hardware event-queue model).
    """
    cfg = ctx.cfg
    cap = ctx.spike_capacity
    dense = ctx.dense

    def cell(stim, s_prev, w, u0, h0, beta, vth):
        return ops.spike_cell(stim, s_prev, w, u0, h0, beta, vth,
                              capacity=cap)

    def ff(x2d: jax.Array, name: str) -> jax.Array:
        if name == "l1_wx":  # spike-consuming: gather over spike events
            return ops.spike_broadcast(x2d, dense[name], capacity=cap)
        return x2d @ dense[name]  # analog input stimulus: dense

    if ctx.sparse_fc:
        t = ctx.sparse["fc_w"]
        layout = layouts.layout_of(t)
        fc_fn = layout.fc_kernel  # weight-side zero-skip, already fused
        fc = lambda s1: fc_fn(s1, t)  # noqa: E731
    else:
        if ctx.precision == "int4":
            qt = ctx.quant["fc_w"]
            # bit-exact dequant (ref.int4_matmul_ref's weight), built once
            w_fc = (ref.unpack_int4_ref(qt.packed).astype(jnp.float32)
                    * qt.scale.reshape(-1).astype(jnp.float32))
        else:
            w_fc = ctx.dense["fc_w"]
        if cfg.merged_spike:
            # 3-D input -> the kernel's merged-spike-union path (§II-D2)
            fc = lambda s1: ops.spike_broadcast(s1, w_fc,  # noqa: E731
                                                capacity=cap)
        elif ctx.precision == "int4":
            # mirror _fc_op's per-ts sum composition bit for bit
            fc = lambda s1: sum(  # noqa: E731
                ops.spike_broadcast(s1[t], w_fc, capacity=cap)
                for t in range(cfg.num_ts))
        else:
            fc = lambda s1: jnp.stack(  # noqa: E731
                [ops.spike_broadcast(s1[t], w_fc, capacity=cap)
                 for t in range(cfg.num_ts)]).sum(axis=0)

    return OpTable(name="spike", rsnn_cell=cell, ff_matmul=ff, fc=fc,
                   mxu_aligned=False)


@register("fused")
def _build_fused(ctx: BackendContext) -> OpTable:
    """Single-dispatch mega-step: the op table collapses to one call.

    Both cells, the layout-resolved zero-skip FC, and the sparsity
    counters execute inside one ``kernels/megastep.py`` dispatch with the
    packed weights and recurrent state resident in VMEM; the per-op table
    entries are never invoked (they raise to catch accidental use).  The
    FC operands come from the packed tensor's ``WeightLayout.megastep_fc``
    binding, so a new layout plugs into the mega-step without a backend
    edit.  Bit-identical to ``jnp`` (tests/test_megastep.py).
    """
    return _fused_table(ctx, spike=False)


@register("fused_spike")
def _build_fused_spike(ctx: BackendContext) -> OpTable:
    """The mega-step with its spike mode on: one dispatch per chunk, with
    the three spike-consuming matmuls and the dense FC modes running over
    compacted event lists (``kernels/spike_broadcast.gather_matmul``) —
    input-side zero skipping inside the single-dispatch frame step, still
    bit-identical to ``jnp``.
    """
    return _fused_table(ctx, spike=True)


def _fused_table(ctx: BackendContext, *, spike: bool) -> OpTable:
    name = "fused_spike" if spike else "fused"
    cfg = ctx.cfg
    if not cfg.merged_spike:
        raise ValueError(
            f"the {name!r} backend's mega-step kernel implements the "
            "merged-spike readout (paper §II-D2); per-ts readout needs "
            "another backend")
    names = ("l0_wx", "l0_wh", "l1_wx", "l1_wh")
    if ctx.precision == "int4":
        # the layer weights ride into VMEM as packed nibbles + scales and
        # dequantize next to the MACs (bit-exact with ctx.dense's copies)
        wargs = tuple(a for n in names
                      for a in (ctx.quant[n].packed, ctx.quant[n].scale))
    else:
        wargs = tuple(ctx.dense[n] for n in names)
    if ctx.sparse_fc:
        fct = ctx.sparse["fc_w"]
    elif ctx.precision == "int4":
        fct = ctx.quant["fc_w"]
    else:
        fct = None
    if fct is None:
        fc_mode, fcargs, statics = "dense_float", (ctx.dense["fc_w"],), {}
    else:
        fc_mode, fcargs, statics = layouts.layout_of(fct).megastep_fc(fct)

    def kernel(x_chunk, s0, u0, h0, s1, u1, h1, lif, wargs, fcargs):
        return ops.megastep(
            x_chunk, s0, u0, h0, s1, u1, h1,
            lif["beta0"], lif["vth0"], lif["beta1"], lif["vth1"],
            wargs, fcargs, precision=ctx.precision, fc_mode=fc_mode,
            input_bits=cfg.input_bits, spike=spike, **statics)

    if ctx.mesh is not None:
        # a Mosaic kernel cannot be partitioned by the compiler: every
        # device runs it on its own slot shard (slots are independent),
        # with the weights and LIF constants replicated
        slots, rows, rep = P(None, "data"), P("data"), P()
        kernel = jax.shard_map(
            kernel, mesh=ctx.mesh,
            in_specs=(slots, slots, rows, rows, slots, rows, rows, rep, rep,
                      rep),
            out_specs=(slots, rows, slots, rows, slots,
                       P(None, None, "data"), P(None, None, "data"), slots,
                       slots),
            check_vma=False)

    def megastep(state: RSNNState, x_chunk: jax.Array, lif: dict):
        # chunk-native: x_chunk is (F, B, input_dim) and maps onto the
        # kernel's frame-chunk grid axis — F frames advance in ONE Pallas
        # dispatch with the weights staying VMEM-resident across the chunk
        s0, u0, s1, u1, logits, sp0, sp1, union, bits = kernel(
            x_chunk, state.h0, state.lif0.u, state.lif0.spike,
            state.h1, state.lif1.u, state.lif1.spike, lif, wargs, fcargs)
        new_state = RSNNState(h0=s0, h1=s1,
                              lif0=LIFState(u=u0, spike=s0[-1]),
                              lif1=LIFState(u=u1, spike=s1[-1]))
        zero = jnp.zeros_like(bits)  # no delta gating in the mega-step
        aux = {"spikes_l0": sp0, "spikes_l1": sp1,
               "union_l1": union, "input_one_bits": bits,
               "delta_propagated": zero, "delta_skipped": zero}
        return new_state, logits, aux

    def _collapsed(op: str) -> Callable:
        def call(*_a, **_k):
            raise RuntimeError(
                f"the {name!r} backend executes the whole frame step as "
                f"one megastep dispatch; {op!r} is not separately callable")

        return call

    return OpTable(name=name, rsnn_cell=_collapsed("rsnn_cell"),
                   ff_matmul=_collapsed("ff_matmul"), fc=_collapsed("fc"),
                   mxu_aligned=False, megastep=megastep)

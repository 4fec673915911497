"""The main path's Pallas kernels compile for a TPU v5e at the paper's widths.

Interpret mode accepts constructs the chip's compiler (Mosaic) refuses:
an int8 shift, a cumsum, a dynamic slice, a block not tiled by (8, 128),
too much VMEM.  These tests compile each kernel for a *described* v5e —
the compiler runs here, no chip is attached — at the ``PRUNED`` widths
(``configs/rsnn_timit.py``: 40/128/1920, TS=2; a 40%-pruned 128-row FC
keeps at most ~90 rows per column) and check that the executable holds
the kernel (``tpu_custom_call``), not an interpreted copy.

The topology is described inside a module fixture, never at import:
only one process may load the TPU compiler's library, and every test
worker imports this file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.rsnn_timit import PRUNED
from repro.kernels import delta_step as ds
from repro.kernels import int4_matmul as i4
from repro.kernels import megastep as mg
from repro.kernels import merged_spike_fc as mf
from repro.kernels import nm_fc as nf
from repro.kernels import rsnn_cell as rc
from repro.kernels import sparse_fc as sf
from repro.kernels import spike_broadcast as sb
from repro.serving.stream import MATMUL_PRECISION

TS, D, H, FC = PRUNED.num_ts, PRUNED.input_dim, PRUNED.hidden_dim, PRUNED.fc_dim
NNZ = 90  # padded CSC rows of the 40%-pruned FC
SLOTS = 8
CEILING_SLOTS = 512  # the fused csc mega-step's VMEM fits 512 slots


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without the chip: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: no compiler
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topology
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def spec(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


def _compile(fn, *args) -> str:
    with jax.default_matmul_precision(MATMUL_PRECISION):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _mega_operands(spec, fc_mode, slots):
    f32, i8 = jnp.float32, jnp.int8
    state = (spec((1, slots, D)), spec((TS, slots, H)), spec((slots, H)),
             spec((slots, H)), spec((TS, slots, H)), spec((slots, H)),
             spec((slots, H)), *(spec((H,)) for _ in range(4)))
    if fc_mode == "dense_float":
        wargs = (spec((D, H)), spec((H, H)), spec((H, H)), spec((H, H)))
    else:
        wargs = tuple(a for k in (D, H, H, H)
                      for a in (spec((k // 2, H), i8), spec((1, H))))
    fcargs = {"dense_float": (spec((H, FC)),),
              "dense_int4": (spec((H // 2, FC), i8), spec((1, FC))),
              "csc": (spec((NNZ, FC), jnp.int32), spec((NNZ, FC), f32),
                      spec((1, FC))),
              "nm": (spec((H // 2, FC), i8), spec((1, FC)))}[fc_mode]
    return state, wargs, fcargs


def _megastep(fc_mode, spike, n_w):
    statics = {"nm_n": 2, "nm_m": 4} if fc_mode == "nm" else {}
    precision = "float" if fc_mode == "dense_float" else "int4"

    def fn(*a):
        return mg.megastep(*a[:11], a[11:11 + n_w], a[11 + n_w:],
                           precision=precision, fc_mode=fc_mode,
                           input_bits=8, spike=spike, interpret=False,
                           **statics)

    return fn


@pytest.mark.parametrize("spike", [False, True])
@pytest.mark.parametrize("fc_mode", ["dense_float", "dense_int4", "csc",
                                     "nm"])
def test_megastep_compiles(spec, fc_mode, spike):
    state, wargs, fcargs = _mega_operands(spec, fc_mode, SLOTS)
    _compile(_megastep(fc_mode, spike, len(wargs)), *state, *wargs, *fcargs)


def test_megastep_csc_fits_ceiling_slots(spec):
    """The fused backend's CSC mega-step at the slot ceiling that
    ``chip_smoke.py`` serves: the whole slot batch sits in VMEM."""
    state, wargs, fcargs = _mega_operands(spec, "csc", CEILING_SLOTS)
    _compile(_megastep("csc", False, len(wargs)), *state, *wargs, *fcargs)


def test_delta_step_compiles(spec):
    _compile(lambda *a: ds.delta_step(*a, interpret=False),
             spec((SLOTS, D)), spec((SLOTS, D)), spec((SLOTS, H)),
             spec((D, H)), spec(()))


def test_sparse_fc_compiles(spec):
    _compile(lambda *a: sf.sparse_fc(*a, interpret=False),
             spec((TS, SLOTS, H)), spec((NNZ, FC), jnp.int32),
             spec((NNZ, FC)), spec((1, FC)))


def test_nm_fc_compiles(spec):
    _compile(lambda *a: nf.nm_fc(*a, n=2, m=4, interpret=False),
             spec((TS, SLOTS, H)), spec((H // 2, FC), jnp.int8),
             spec((1, FC)))


@pytest.mark.parametrize("k", [D, H])
def test_int4_matmul_compiles(spec, k):
    _compile(lambda *a: i4.int4_matmul(*a, interpret=False),
             spec((TS * SLOTS, k)), spec((k // 2, H), jnp.int8), spec((H,)))


def test_merged_spike_fc_compiles(spec):
    _compile(lambda *a: mf.merged_spike_fc(*a, interpret=False),
             spec((TS, SLOTS, H)), spec((H // 2, FC), jnp.int8),
             spec((FC,)))


@pytest.mark.parametrize("cell", ["rsnn_cell", "spike_cell"])
def test_cell_compiles(spec, cell):
    fn = {"rsnn_cell": rc.rsnn_cell, "spike_cell": sb.spike_cell}[cell]
    _compile(lambda *a: fn(*a, interpret=False),
             spec((TS, SLOTS, H)), spec((TS, SLOTS, H)), spec((H, H)),
             spec((SLOTS, H)), spec((SLOTS, H)), spec((H,)), spec((H,)))


@pytest.mark.parametrize("x_shape,w_shape,capacity", [
    ((TS * SLOTS, H), (H, H), None),  # L1 feedforward over L0 events
    ((TS, SLOTS, H), (H, FC), None),  # merged-union FC readout
    ((TS * SLOTS, H), (H, H), 16),  # finite event queue: truncation
])
def test_spike_broadcast_compiles(spec, x_shape, w_shape, capacity):
    _compile(lambda *a: sb.spike_broadcast(*a, capacity=capacity,
                                           interpret=False),
             spec(x_shape), spec(w_shape))


def test_compact_spikes_compiles(spec):
    """The priority encoder lowers inside a kernel (no cumsum, no gather)."""
    rows = TS * SLOTS

    def kernel(x_ref, idx_ref, val_ref):
        idx_ref[...], val_ref[...] = sb.compact_spikes(x_ref[...], H)

    def fn(x):
        return pl.pallas_call(kernel, out_shape=[
            jax.ShapeDtypeStruct((rows, H), jnp.int32),
            jax.ShapeDtypeStruct((rows, H), jnp.float32)])(x)

    _compile(fn, spec((rows, H)))


def test_fused_table_compiles_on_four_chip_mesh(topo, monkeypatch):
    """The ``fused`` op table bound to a 4-device serving mesh runs the
    mega-step per device under ``shard_map``: a Mosaic kernel cannot be
    partitioned by the compiler, so without it this compile fails."""
    from repro.core import rsnn
    from repro.core.compression.compress import (CompressionConfig,
                                                 init_compression)
    from repro.distributed import sharding as shd
    from repro.kernels import ops
    from repro.serving import backends
    from repro.serving.stream import CompiledRSNN, EngineConfig

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = PRUNED
    params = rsnn.init_params(jax.random.PRNGKey(0), cfg)
    ccfg = CompressionConfig(fc_prune_frac=0.4, weight_bits=4)
    eng = CompiledRSNN(cfg, params, EngineConfig(
        backend="fused", precision="int4", sparse_fc=True, input_scale=0.05),
        ccfg, init_compression(params, ccfg))
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    table = backends.resolve("fused", dataclasses.replace(eng._ctx,
                                                          mesh=mesh))
    slots = 4 * SLOTS
    state = eng.init_state(slots)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        state, shd.stream_state_specs(state),
        is_leaf=lambda s: isinstance(s, P))
    x = jax.ShapeDtypeStruct((1, slots, D), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "data")))
    _compile(lambda st, xc: table.megastep(st, xc, eng._lif), state, x)

"""Distribution: sharding rules, multi-device train step, gradient
compression, elastic reshard. Multi-device cases run in a subprocess with 8
fake CPU devices (the main test process keeps 1 device)."""

import json
import subprocess
import sys
import textwrap

import jax
import pytest

# multi-device cases spawn fresh 8-fake-device subprocesses that re-JIT the
# train step (minutes on CPU) — slow tier, run with --runslow
pytestmark = pytest.mark.slow
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed import compression as gc_lib
from repro.distributed import sharding as shd


def _run_subprocess(code: str) -> str:
    # the parent may hold the accelerator; the child stays on the CPU
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src",
           "PATH": "/usr/bin:/bin"}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, cwd=".",
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------------------ spec rules


def test_param_spec_rules():
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    m = FakeMesh()
    assert shd.param_spec("['layers']['attn']['w_q']", (26, 2304, 2048), m) \
        == P(None, "data", "model")
    assert shd.param_spec("['layers']['attn']['w_o']", (26, 2048, 2304), m) \
        == P(None, "model", "data")
    assert shd.param_spec("['layers']['moe']['w_gate']", (58, 256, 7168, 2048), m) \
        == P(None, "model", "data", None)
    assert shd.param_spec("['embed']['tok']", (92672, 6144), m) == P("model", "data")
    # indivisible dims degrade to replication
    assert shd.param_spec("['layers']['attn']['w_q']", (26, 33, 17), m) \
        == P(None, None, None)
    assert shd.param_spec("['final_norm']['scale']", (2304,), m) == P(None)


def test_cache_spec_rules():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    m = FakeMesh()
    # batched decode: batch over data + heads over model
    s = shd.cache_spec("['layers'].k", (26, 128, 32768, 32, 128), m, batch=128)
    assert tuple(s)[1] == "data" or "data" in str(s)
    # B=1 long-context: sequence over data (context parallelism)
    s1 = shd.cache_spec(".k", (1, 524288, 4, 256), m, batch=1)
    assert "data" in str(s1)


# ------------------------------------------------------- grad compression


def test_compress_decompress_error_feedback():
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)), jnp.float32)}
    res = gc_lib.init_error_feedback(g)
    comp, res2 = gc_lib.compress_grads(g, res)
    back = gc_lib.decompress_grads(comp)
    # int8 roundtrip error small relative to signal
    rel = float(jnp.linalg.norm(back["w"] - g["w"]) / jnp.linalg.norm(g["w"]))
    assert rel < 0.02
    # residual carries exactly the quantization error
    np.testing.assert_allclose(np.asarray(res2["w"]),
                               np.asarray(g["w"] - back["w"]), atol=1e-6)
    # error feedback: two identical steps -> accumulated bias shrinks
    comp2, res3 = gc_lib.compress_grads(g, res2)
    back2 = gc_lib.decompress_grads(comp2)
    total = back["w"] + back2["w"]
    rel2 = float(jnp.linalg.norm(total - 2 * g["w"]) / jnp.linalg.norm(2 * g["w"]))
    assert rel2 < rel


def test_compressed_psum_multidevice():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        mesh = jax.make_mesh((8,), ("d",))
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 32)), jnp.float32)
        f = jax.shard_map(lambda x: compressed_psum(x[0], "d")[None],
                      mesh=mesh, in_specs=P("d", None), out_specs=P("d", None))
        got = np.asarray(f(x))
        want = np.asarray(x.sum(0))
        rel = np.linalg.norm(got[0] - want) / np.linalg.norm(want)
        assert rel < 0.03, rel
        print("psum ok", rel)
    """)
    assert "psum ok" in out


def test_multidevice_train_step_and_elastic_reshard():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import registry
        from repro.distributed import sharding as shd
        from repro.launch import steps as steps_lib
        from repro.training import optimizer as opt_lib
        from repro.training.optimizer import OptimizerConfig
        from repro.runtime.elastic import make_elastic_mesh, reshard_state

        cfg = registry.reduce_config(registry.get_model("yi-6b").cfg)
        api = registry.get_model("yi-6b", cfg)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        shd.set_activation_axes(mesh)
        params = api.init(jax.random.PRNGKey(0))
        pspecs = shd.tree_param_specs(params, mesh)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, pspecs,
            is_leaf=lambda x: isinstance(x, jax.Array))
        ocfg = OptimizerConfig(warmup_steps=1, decay_steps=10)
        state = {"params": params, "opt": opt_lib.init_opt_state(params, ocfg)}
        step = jax.jit(steps_lib.make_train_step(api, ocfg))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)}
        with mesh:
            state2, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        print("train ok", float(metrics["loss"]))

        # elastic: resume on 4 devices instead of 8
        small = make_elastic_mesh(preferred_model=2, devices=jax.devices()[:4])
        p2 = reshard_state(state2["params"], small)
        n_dev = {len(l.sharding.device_set) for l in jax.tree.leaves(p2)}
        assert max(n_dev) <= 4
        print("elastic ok")
    """)
    assert "train ok" in out and "elastic ok" in out


def test_constrain_helpers_no_mesh():
    shd.set_activation_axes(None)
    x = jnp.ones((4, 8))
    np.testing.assert_array_equal(np.asarray(shd.constrain_batch(x)), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(shd.constrain_last_dim(x)), np.asarray(x))

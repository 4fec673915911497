"""``chip_smoke.py``'s body at tiny widths on the CPU.

The script itself runs only on a TPU; these tests steer its phase
functions onto the CPU (kernels in interpret mode, so no Pallas custom
call to look for) and check that its comparisons pass on a correct
engine, fail on a wrong one, and that without a TPU it exits non-zero
and prints no result.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.rsnn import RSNNConfig

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

CFG = RSNNConfig(input_dim=8, hidden_dim=16, fc_dim=12, num_ts=2)


def test_one_chip_phases_pass_at_tiny_widths():
    """Every registered backend serves and matches the CPU ref backend."""
    cpu = jax.devices("cpu")[0]
    seen = []
    results = chip_smoke.one_chip_phases(
        CFG, device=cpu, ref_device=cpu, require_kernels=False, streams=5,
        slots=2, ceiling_slots=4, small=dict(streams=3, frames=4, slots=2),
        report=seen.append)
    assert seen == results
    from repro.serving import backends
    assert {r["phase"] for r in results} == (
        set(backends.available()) | {"fused_ceiling"})
    for r in results:
        assert r["ok"], r
        assert r["frames_within_atol"] == 1.0 and r["spike_agree"] == 1.0


def test_wrong_backend_fails_its_phase():
    """A backend whose readout is off by a constant fails the comparison."""
    import jax.numpy as jnp
    from repro.serving import backends

    @backends.register("off_by_one", dense_stimulus=True)
    def _build(ctx):
        table = backends.resolve("ref", ctx)
        return table._replace(fc=lambda s1: table.fc(s1) + jnp.float32(1))

    try:
        cpu = jax.devices("cpu")[0]
        model = chip_smoke.build_model(CFG, 0)
        utts = chip_smoke.utterances(CFG, 3, 0)
        scale = chip_smoke.calibrate_input_scale(np.concatenate(utts), 8)
        res = chip_smoke.backend_phase(
            "off_by_one", model, CFG, scale, [u[:4] for u in utts], 2,
            device=cpu, ref_device=cpu, require_kernels=False)
    finally:
        backends.unregister("off_by_one")
    assert not res["ok"] and not res["readout_exact"]
    assert res["frames_within_atol"] == 0.0


def test_main_without_tpu_exits_nonzero_and_prints_no_result(capsys):
    assert chip_smoke.main([]) == 2
    assert capsys.readouterr().out == ""


def test_compare_logits_fails_beyond_tolerance():
    rng = np.random.default_rng(0)
    want = [rng.normal(size=(t, 12)).astype(np.float32) for t in (5, 7)]
    assert chip_smoke.compare_logits(want, want)["ok"]
    wrong = [w + 0.5 for w in want]
    res = chip_smoke.compare_logits(wrong, want)
    assert not res["ok"] and res["frames_within_atol"] == 0.0
    nan = [w.copy() for w in want]
    nan[0][0, 0] = np.nan
    assert not chip_smoke.compare_logits(nan, want)["ok"]
    assert not chip_smoke.compare_logits(want[:1], want)["ok"]


def test_compare_steps_checks_spikes_and_readout():
    rng = np.random.default_rng(1)
    s0 = (rng.random((6, 2, 3, 16)) < 0.3).astype(np.float32)
    s1 = (rng.random((6, 2, 3, 16)) < 0.3).astype(np.float32)
    logits = rng.normal(size=(6, 3, 12)).astype(np.float32)
    want = (s0, s1, logits)
    assert chip_smoke.compare_steps(want, want)["ok"]
    # same spikes, different logits: the readout is wrong
    res = chip_smoke.compare_steps((s0, s1, logits + 1.0), want)
    assert not res["ok"] and not res["readout_exact"]
    # a wrong cell: spikes differ by about their density
    res = chip_smoke.compare_steps((1 - s0, s1, logits), want)
    assert not res["ok"] and res["spike_agree"] < 0.9


_FOUR_DEVICES = """
import sys
sys.path.insert(0, {root!r})
import jax
import chip_smoke
from repro.core.rsnn import RSNNConfig
cfg = RSNNConfig(input_dim=8, hidden_dim=16, fc_dim=12, num_ts=2)
res = chip_smoke.four_chip_phase(cfg, jax.devices()[:4], streams=6, slots=8)
assert res["ok"] and res["bit_identical"], res
assert set(res["placed_on"].values()) == {{4}}, res
print("FOUR_OK")
"""


def test_four_chip_phase_on_virtual_devices():
    """The sharded phase spreads state, buffers and weights over all four
    devices and matches the one-device loop (4 virtual CPU devices)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(
            _FOUR_DEVICES.format(root=str(ROOT)))],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_OK" in out.stdout


def test_serving_path_imports_no_launch_tool():
    """``launch/rsnn_cells.py`` and ``launch/dryrun.py`` rewrite XLA_FLAGS
    when imported; nothing the chip smoke loads may import them."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        bad = [m for m in ("repro.launch.rsnn_cells", "repro.launch.dryrun")
               if m in sys.modules]
        assert not bad, bad
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """The environment's directory when set, else one fixed directory
    inside the checkout (tests never turn the cache on)."""
    from repro.runtime import compile_cache

    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.compile_cache_dir() == str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.compile_cache_dir() == str(ROOT / ".jax_cache")

"""A run of the harness at a size the CPU holds, with the accelerator
lookup and the compile cache left out."""

import copy
import json
import os

from bench.lib import harness, spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


def tiny_config(name="rsnn_pruned_int4_csc"):
    conf = load("configs", name + ".json")
    conf["model"].update(input_dim=8, hidden_dim=32, fc_dim=24)
    conf["serving"].update(slots=4, ring_frames=8)
    return conf


def tiny_traffic(name):
    mix = load("traffic", name + ".json")
    mix["lengths"].update(min=4, max=30)
    if mix["lengths"]["dist"] == "lognormal":
        mix["lengths"]["median"] = 12
    mix["features"]["bank_frames"] = 512
    mix["check"]["sample"] = 64
    if mix["loop"] == "closed":
        mix["pool"] = 64
    else:
        mix.update(rate_per_s=12.0, preroll_s=0.5, drain_timeout_s=30.0)
    return mix


def tiny_cell(traffic="timit_backlog", config="rsnn_pruned_int4_csc",
              limit=None):
    """The cell of ``config`` under ``traffic`` at the CPU size, held to
    the cell's own limit unless ``limit`` is given."""
    bench = spec.Bench(ROOT)
    cell = next(w for w in bench.doc["workloads"]
                if w["traffic"] == traffic and w["config"] == config)
    real = bench.cell(cell["name"])
    real.config = tiny_config(config)
    real.traffic = tiny_traffic(traffic)
    real.limits = copy.deepcopy(real.limits)
    if limit is not None:
        real.limits["off_share"]["limit"] = limit
    return real


def run(monkeypatch, capsys, cell, seed=3, seconds=1.0, trace=0, root=None):
    """Drive ``harness.main`` over ``cell`` on the CPU -> (exit code, the
    last stdout line as a dict or None, stderr).  ``root``, where given, is
    the checkout whose ``BENCHMARK.json`` and ``paths`` the run finds its
    files in."""
    import jax

    if root is not None:
        monkeypatch.setattr(harness, "ROOT", str(root))

    monkeypatch.setattr(harness, "find_accelerator",
                        lambda chips: jax.devices("cpu")[0])
    monkeypatch.setattr(harness, "enable_cache", lambda: "off")
    monkeypatch.setattr(harness, "load_peaks",
                        lambda kind: {"bf16_flops": 1e12, "int8_ops": 2e12,
                                      "hbm_bytes_per_s": 1e11,
                                      "hbm_bytes": 1e9})
    monkeypatch.setattr(spec.Bench, "cell", lambda self, name: cell)
    rc = harness.main(["--workload", cell.name, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      t_start=0.0)
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err

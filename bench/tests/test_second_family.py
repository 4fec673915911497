"""A model family of another kind runs through the harness from added files
alone: a checkout whose ``BENCHMARK.json`` appends one configuration and
one cell of the toy family in ``fixtures/toy`` (configuration, family
module, reference with its own comparison, traffic and limits), with the fixture's directory among
its ``paths`` and nothing under ``bench/`` changed."""

import json
import os
import re
import shutil

import jax
import numpy as np
import pytest

from bench.lib import drive, spec
from bench.tests import tiny

FIXTURE = os.path.join(tiny.HERE, "fixtures", "toy")
CELL = "toy_linear.backlog"
# the RSNN's own names, which only its family and reference modules know
RSNN_NAMES = re.compile(r"input_dim|hidden_dim|fc_dim|scale_log2|\blif(?!e)|"
                        r"RSNNConfig|CompiledRSNN|effective_weights")


def checkout(tmp_path, reset_at_refill=True):
    """A root holding the benchmark's own files, the fixture as added files
    and ``BENCHMARK.json`` with the toy configuration and cell appended."""
    os.symlink(os.path.join(tiny.ROOT, "bench"), tmp_path / "bench")
    shutil.copytree(FIXTURE, tmp_path / "toy")
    conf_path = tmp_path / "toy" / "configs" / "toy_linear.json"
    conf = json.loads(conf_path.read_text())
    conf["serving"]["reset_at_refill"] = reset_at_refill
    conf_path.write_text(json.dumps(conf))
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"] = doc["paths"] + ["toy"]
    doc["configs"].append({"name": "toy_linear", "source": conf["source"],
                           "file": "toy/configs/toy_linear.json",
                           "reduced": [], "why": "a second model family"})
    doc["workloads"].append({"name": CELL, "config": "toy_linear",
                             "traffic": "toy_backlog", "chips": 1,
                             "why": "short utterances, all slots busy"})
    for m in doc["end_to_end"]:
        if m["name"] == "frames_per_s":
            m["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp_path


@pytest.mark.parametrize("reset_at_refill", [True, False],
                         ids=["sound", "state_kept_at_refill"])
def test_second_family_runs_from_added_files(monkeypatch, capsys, tmp_path,
                                              reset_at_refill):
    root = checkout(tmp_path, reset_at_refill)
    cell = spec.Bench(str(root)).cell(CELL)
    rc, result, err = tiny.run(monkeypatch, capsys, cell, root=root)
    assert rc == 0, err
    assert result["correct"] is reset_at_refill, err
    assert result["attempted"] > 0
    assert result["metrics"]["frames_per_s"]["value"] > 0
    # the toy reference's own comparison decides, not the RSNN's
    assert "off_share" not in result["check"]
    worst = result["check"]["worst_err"]
    assert (worst["value"] <= worst["limit"]) is reset_at_refill


@pytest.mark.parametrize("name", ["harness.py", "drive.py", "spec.py"])
def test_the_harness_names_nothing_of_the_rsnn(name):
    with open(os.path.join(tiny.ROOT, "bench", "lib", name)) as f:
        assert RSNN_NAMES.findall(f.read()) == []


@pytest.mark.parametrize("root,config", [
    (tiny.ROOT, "rsnn_pruned_int4_csc"), (None, "toy_linear")])
def test_served_loops_keep_to_the_contract(tmp_path, root, config):
    bench = spec.Bench(root or str(checkout(tmp_path)))
    conf = (tiny.tiny_config(config) if root else
            bench.cell(CELL).config)
    model = bench.family(conf["reference"])
    cpu = jax.devices("cpu")[0]
    loop = model.build_loop(conf, model.make_params(conf, 5, cpu), cpu, cpu)
    assert isinstance(loop, drive.ServedLoop)
    sid = loop.submit(np.zeros((3, 8), np.float32))
    loop.step_once()
    held = [r for r in loop.slot_req if r is not None]
    assert [r.sid for r in held] == [sid]
    assert isinstance(held[0], drive.ServedRequest)
    loop.run()
    assert len(loop.finished[0].stacked_logits()) == 3

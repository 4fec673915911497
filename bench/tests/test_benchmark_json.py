"""``BENCHMARK.json`` keeps to the benchmark's contract, and every file it
implies exists."""

import json
import os
import re

import pytest

from bench.lib import spec
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")

with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as _f:
    DOC = json.load(_f)


def test_top_level_keys_and_command():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(DOC["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in DOC["paths"])
    assert isinstance(DOC["run_seconds"], int)
    assert 1 <= DOC["run_seconds"] <= 51


def test_every_name_and_unit_uses_allowed_characters():
    names = ([c["name"] for c in DOC["configs"]]
             + [w["name"] for w in DOC["workloads"]]
             + [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
             + [w["traffic"] for w in DOC["workloads"]]
             + [k for c in DOC["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    units = [m["unit"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    assert all(UNIT.match(u) for u in units), units
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({e["name"] for e in DOC[group]}) == len(DOC[group])
    texts = ([e["why"] for e in DOC["configs"] + DOC["workloads"]]
             + [m["layer"] for m in DOC["per_layer"]])
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)


def test_entries_have_exactly_the_contract_keys():
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1
    for m in DOC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in DOC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in DOC["end_to_end"]}
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert m["better"] in ("lower", "higher")


def test_every_cell_finds_its_files_and_reports_enough():
    bench = spec.Bench(tiny.ROOT)
    for w in DOC["workloads"]:
        cell = bench.cell(w["name"])
        assert "off_share" in cell.limits
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(bench.reader(m["name"]))
            assert m["moves"] in e2e


@pytest.mark.parametrize("conf", DOC["configs"], ids=lambda c: c["name"])
def test_configuration_files_are_run_as_stated(conf):
    assert conf["file"].startswith(DOC["paths"][0] + "/")
    with open(os.path.join(tiny.ROOT, conf["file"])) as f:
        body = json.load(f)
    assert body["name"] == conf["name"] and body["source"] == conf["source"]
    assert conf["reduced"] == []  # published widths and depth


@pytest.mark.parametrize("conf", DOC["configs"], ids=lambda c: c["name"])
def test_configuration_finds_its_family_and_reference(conf):
    bench = spec.Bench(tiny.ROOT)
    with open(os.path.join(tiny.ROOT, conf["file"])) as f:
        family = json.load(f)["reference"]
    model = bench.family(family)
    for name in ("make_params", "build_loop", "input_bank"):
        assert callable(getattr(model, name)), name
    assert callable(bench.reference(family).logits)


def test_a_full_check_fits_its_time():
    cells = 24  # what later changes may grow the benchmark to
    runs = 2 + 14 * cells
    total = runs * (DOC["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200

"""The control: the reference computed one precision below the
configuration's (three bfloat16 passes per float32 dot) comes out not
correct under every cell's limit.  At the configurations' own widths, over
fewer and shorter utterances than a run compares."""

import functools

import jax
import pytest

from bench.lib import check, harness, spec, traffic
from bench.tests import tiny

BENCH = spec.Bench(tiny.ROOT)
CELLS = [w["name"] for w in BENCH.doc["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_cells_limit(name):
    cell = BENCH.cell(name)
    conf = cell.config
    mix = dict(cell.traffic, lengths=dict(cell.traffic["lengths"],
                                          max=min(cell.traffic["lengths"]
                                                  ["max"], 160)))
    cpu = jax.devices("cpu")[0]
    model = BENCH.family(conf["reference"])
    plan = traffic.Plan(mix, slots=conf["serving"]["slots"], seconds=10.0,
                        seed=11,
                        make_bank=functools.partial(model.input_bank, conf))
    reqs = plan.first if mix["loop"] == "closed" else plan.arrivals
    utts = [plan.frames(r) for r in reqs[:12]]
    params = model.make_params(conf, 11, cpu)
    want = harness.reference_logits(conf, params, utts, cpu, "highest")
    same = check.compare([(x, want[i]) for i, x in enumerate(utts)], want)
    assert same.off_share == 0.0
    ctrl = harness.reference_logits(conf, params, utts, cpu, "high")
    got = check.compare([(x, ctrl[i]) for i, x in enumerate(utts)], want)
    assert got.off_share > float(cell.limits["off_share"]["limit"])

"""Required work from the configurations' shapes, against
``core/complexity.py``'s dense accounting."""

import pytest

from bench.lib import work
from bench.tests import tiny
from repro.core import complexity
from repro.core.rsnn import RSNNConfig

CONFIGS = ["rsnn_pruned_int4_csc", "rsnn_baseline_f32"]


def _cfg(m):
    return RSNNConfig(input_dim=m["input_dim"], hidden_dim=m["hidden_dim"],
                      fc_dim=m["fc_dim"], num_ts=m["num_ts"])


@pytest.mark.parametrize("name", CONFIGS)
def test_macs_match_complexity_with_one_input_pass(name):
    conf = tiny.load("configs", name + ".json")
    m, c = conf["model"], conf["compression"]
    dense = complexity.accumulates_per_frame(
        _cfg(m), m["num_ts"], merged_spike=True,
        fc_prune_frac=c["fc_prune_frac"])
    # complexity.py counts the 8-bit input layer bit-serially (8 passes);
    # the chip multiplies each 8-bit input once
    bit_serial = (m["input_bits"] - 1) * m["input_dim"] * m["hidden_dim"]
    assert work.macs_per_frame(m, c) == dense - bit_serial
    assert work.ops_per_frame(m, c) == 2 * work.macs_per_frame(m, c)


def test_weight_bytes_of_the_paper_models():
    pruned = tiny.load("configs", "rsnn_pruned_int4_csc.json")
    base = tiny.load("configs", "rsnn_baseline_f32.json")
    cfg = _cfg(pruned["model"])
    # int4 kept weights: the paper's 0.1 MB, plus the column scales
    kept = complexity.model_size_bytes(cfg, 4, fc_prune_frac=0.4)
    scales = 4 * (4 * 128 + 1920)
    assert work.weight_bytes(pruned["model"],
                             pruned["compression"]) == kept + scales
    assert work.weight_bytes(base["model"], base["compression"]) == (
        complexity.model_size_bytes(_cfg(base["model"]), 32))


def test_least_step_time_is_the_larger_bound():
    conf = tiny.load("configs", "rsnn_pruned_int4_csc.json")
    m, c = conf["model"], conf["compression"]
    t, bound = work.least_step_seconds(m, c, 512, 393e12, 819e9)
    assert bound == "memory"
    assert t == pytest.approx(work.bytes_per_step(m, c, 512) / 819e9)
    t, bound = work.least_step_seconds(m, c, 512, 1e9, 819e9)
    assert bound == "compute"
    assert t == pytest.approx(512 * work.ops_per_frame(m, c) / 1e9)

"""The readers of the program's spans, on a synthetic run and trace, and
in a traced run of the harness at a size the CPU holds."""

import types

import pytest

from bench.lib import harness
from bench.lib import program_trace as pt
from bench.lib import spec
from bench.tests import tiny
from bench.tests.test_program_trace import _events

BENCH = spec.Bench(tiny.ROOT)
NEW = {"host_self_ms_per_step.backlog": pytest.approx(16.0 / 3),
       "refill_ms_per_request.backlog": pytest.approx(8.0),
       "complete_ms_per_request.backlog": pytest.approx(8.0),
       "egress_ms_per_request.backlog": pytest.approx(4.0),
       "egress_useful_share.backlog": pytest.approx(72.0),
       "fence_wait_share.backlog": pytest.approx(17.0),
       "fence_wait_share.ptt": pytest.approx(17.0)}


def _run(monkeypatch, tmp_path, events, cell="cell", traced=True):
    """A run whose trace directory holds a trace that loads as
    ``events``."""
    (tmp_path / cell).mkdir()
    (tmp_path / cell / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(pt, "load", lambda trace_dir: events)
    return types.SimpleNamespace(reduction=object() if traced else None,
                                 cell=types.SimpleNamespace(name=cell))


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_on_a_synthetic_trace(monkeypatch, tmp_path, metric):
    run = _run(monkeypatch, tmp_path, _events())
    assert BENCH.reader(metric)(run) == NEW[metric]


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_without_program_spans_is_none(monkeypatch, tmp_path,
                                              metric):
    ev = _events()
    ev.threads = [[s for s in ev.threads[0]
                   if not s.name.startswith(pt.PREFIX)]]
    run = _run(monkeypatch, tmp_path, ev, cell="bare")
    assert BENCH.reader(metric)(run) is None


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_of_an_untraced_run_is_none(monkeypatch, tmp_path, metric):
    run = _run(monkeypatch, tmp_path, _events(), cell="untraced",
               traced=False)
    assert BENCH.reader(metric)(run) is None


@pytest.mark.parametrize("traffic", ["timit_backlog", "timit_ptt_rate"])
def test_a_traced_run_prints_every_new_metric_of_its_cell(
        monkeypatch, capsys, tmp_path, traffic):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    cell = tiny.tiny_cell(traffic)
    rc, result, err = tiny.run(monkeypatch, capsys, cell, trace=1)
    assert rc == 0 and result["correct"] is True, err
    mine = {m["name"] for m in cell.per_layer} & set(NEW)
    assert mine and mine <= set(result["metrics"])
    for name in mine:
        assert result["metrics"][name]["value"] >= 0.0
    if "egress_useful_share.backlog" in mine:
        assert 0 < result["metrics"]["egress_useful_share.backlog"][
            "value"] <= 100

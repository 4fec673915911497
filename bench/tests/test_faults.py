"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the program before the run builds its step, and
the rest of the run goes as ``bench/run.py`` drives it, at a size the CPU
holds (the accelerator lookup and the compile cache are left out)."""

import jax
import jax.numpy as jnp
import pytest

from bench.tests import tiny
from repro.serving import stream
from repro.serving.stream import CompiledRSNN

_ring_frame_step = CompiledRSNN._ring_frame_step
_ring_write = CompiledRSNN._ring_write


def state_unchanged(self, state, x_t, active, ring, ring_idx, aux_acc):
    _, ring, aux_acc = _ring_frame_step(self, state, x_t, active, ring,
                                        ring_idx, aux_acc)
    return state, ring, aux_acc


def half_batch_left_out(self, ring, ring_idx, logits):
    half = logits.shape[0] // 2
    return _ring_write(self, ring, ring_idx,
                       logits.at[half:].set(0.0))


def answer_altered(self, ring, ring_idx, logits):
    return _ring_write(self, ring, ring_idx, logits.at[:, 7].add(0.01))


def harvest_skipped(self):
    """Retire the oldest step, but leave every fifth completion's logits on
    the device, stamped as harvested all the same."""
    step = self._inflight.popleft()
    if step.handle is not None:
        jax.block_until_ready(step.handle)
    for r in step.completed:
        if r.sid % 5:
            r._materialize()
        r.t_harvest = self.clock()


def _step_skipped():
    step_once = stream.StreamLoop.step_once
    calls = [0]

    def skipping(self):
        """Every third call dispatches nothing and says it did."""
        calls[0] += 1
        return True if calls[0] % 3 == 0 else step_once(self)
    return skipping


FAULTS = {
    "state_unchanged": (CompiledRSNN, "_ring_frame_step", state_unchanged),
    "half_batch_left_out": (CompiledRSNN, "_ring_write",
                            half_batch_left_out),
    "answer_altered": (CompiledRSNN, "_ring_write", answer_altered),
    # a fresh copy: the state's leaves must stay distinct buffers, since
    # the step donates them
    "harvest_skipped": (stream.StreamLoop, "_retire", harvest_skipped),
    "step_skipped": (stream.StreamLoop, "step_once", _step_skipped()),
    "reset_skipped": (stream, "reset_slot",
                      lambda state, i: jax.tree.map(jnp.copy, state)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_not_correct(monkeypatch, capsys, fault):
    obj, name, broken = FAULTS[fault]
    monkeypatch.setattr(obj, name, broken)
    rc, result, err = tiny.run(monkeypatch, capsys,
                               tiny.tiny_cell("timit_backlog"))
    assert rc == 0
    assert result["correct"] is False, err
    assert "check " in err.strip().splitlines()[-1]
    if fault == "step_skipped":
        assert result["check"]["slot_steps_off"]["value"] > 0
    if fault == "harvest_skipped":
        assert result["failed"] > 0
        assert result["check"]["unfinished"]["value"] == result["failed"]


def test_sound_run_is_correct_and_prints_its_limits(monkeypatch, capsys):
    rc, result, err = tiny.run(monkeypatch, capsys,
                               tiny.tiny_cell("timit_backlog"))
    assert rc == 0 and result["correct"] is True, err
    assert list(result)[-1] == "check"
    for name, entry in result["check"].items():
        assert set(entry) == {"value", "limit"}
        assert f"check {name}=" in err
    assert {"setup_s", "frames_per_s"} <= set(result["metrics"])
    assert result["attempted"] > 0 and result["failed"] == 0


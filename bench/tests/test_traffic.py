"""The traffic generator: the same work in every run; the seed changes the
inputs and which slot serves what."""

import numpy as np
import pytest

from bench.lib import spec, traffic
from bench.tests import tiny

SEEDS = (1, 2**31 + 7, 98765432109)
RSNN = spec.Bench(tiny.ROOT).family("rsnn")


def _plan(name, seed, seconds=10.0, slots=512):
    return traffic.Plan(tiny.load("traffic", name + ".json"), slots=slots,
                        seconds=seconds, seed=seed,
                        make_bank=lambda features, rng: RSNN.FeatureBank(
                            features, 40, -5, rng))


@pytest.mark.parametrize("name", ["timit_backlog", "commands_backlog"])
def test_closed_pool_lengths_same_multiset_every_seed(name):
    pools = []
    for seed in SEEDS:
        plan = _plan(name, seed)
        n = int(plan.traffic["pool"])
        pools.append([plan.next_pooled().length for _ in range(n)])
    assert all(p == pools[0] for p in pools)  # one order, every seed
    assert sorted(pools[0]) == sorted(traffic.stratified_lengths(
        plan.lengths, n))


def _completion_steps(plan, steps):
    """Completions per step of a closed loop whose slots always hold work
    and advance one frame per step, the queue taking the pool's order."""
    left = [r.length for r in plan.first]
    out = []
    for _ in range(steps):
        done = 0
        for i in range(len(left)):
            left[i] -= 1
            if left[i] == 0:
                done += 1
                left[i] = plan.next_pooled().length
        out.append(done)
    return out


@pytest.mark.parametrize("name", ["timit_backlog", "commands_backlog"])
def test_closed_loop_completes_at_the_same_steps_every_seed(name):
    plans = [_plan(name, seed, slots=64) for seed in SEEDS]
    firsts = [[r.length for r in p.first] for p in plans]
    assert firsts[0] != firsts[1]  # the seed deals the first occupants
    runs = [_completion_steps(p, 400) for p in plans]
    assert all(r == runs[0] for r in runs)
    assert sum(runs[0]) > 64


def test_ptt_window_lengths_and_arrivals_same_every_seed():
    windows = []
    for seed in SEEDS:
        plan = _plan("timit_ptt_rate", seed, seconds=10.0)
        win = [r for r in plan.arrivals if r.segment == "window"]
        assert all(plan.preroll_s <= r.due < plan.preroll_s + 10.0
                   for r in win)
        windows.append(win)
    rate = tiny.load("traffic", "timit_ptt_rate.json")["rate_per_s"]
    assert len(windows[0]) == round(rate * 10.0)
    end = windows[0][0].due + 10.0

    def gaps(w):  # to the next arrival, and from the last to the end
        return np.sort(np.diff([r.due for r in w] + [end]))

    for w in windows[1:]:
        assert [r.length for r in w] == [r.length for r in windows[0]]
        assert np.allclose(gaps(w), gaps(windows[0]))
        assert [r.due for r in w] == [r.due for r in windows[0]]
    assert sorted(r.length for r in windows[0]) == list(
        traffic.stratified_lengths(plan.lengths, len(windows[0])))


def test_timit_lengths_follow_the_stated_distribution():
    lengths = traffic.stratified_lengths(
        tiny.load("traffic", "timit_backlog.json")["lengths"], 6300)
    assert lengths.min() >= 100 and lengths.max() <= 800
    assert abs(np.median(lengths) - 286) <= 1
    assert 300 <= lengths.mean() <= 320  # about 3.1 s at 100 frames/s


@pytest.mark.parametrize("name", ["timit_backlog", "commands_backlog"])
def test_first_occupants_are_residual_lives(name):
    spec = tiny.load("traffic", name + ".json")["lengths"]
    firsts = [sorted(r.length for r in _plan(name, s).first) for s in SEEDS]
    assert all(f == firsts[0] for f in firsts)
    r = np.array(firsts[0])
    assert r.min() >= 1 and r.max() <= spec["max"]
    # renewal theory: the mean residual life is E[L^2] / (2 E[L]) + 1/2
    lengths = traffic.stratified_lengths(spec, 100_000).astype(float)
    want = (lengths ** 2).mean() / (2 * lengths.mean()) + 0.5
    assert abs(r.mean() - want) / want < 0.02


def test_features_lie_on_the_8_bit_grid_and_follow_the_seed():
    a, b = _plan("timit_backlog", 5), _plan("timit_backlog", 5)
    req = a.first[0]
    x = a.frames(req)
    assert x.shape == (req.length, 40) and x.dtype == np.float32
    codes = x / 2.0 ** -5
    assert np.array_equal(codes, np.round(codes))
    assert codes.min() >= -128 and codes.max() <= 127
    assert np.array_equal(x, b.frames(b.first[0]))
    assert not np.array_equal(a.bank.codes, _plan("timit_backlog",
                                                   6).bank.codes)

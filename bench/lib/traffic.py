"""The general traffic generator: a traffic file's parameters and a seed in,
the requests of one run out.

Every run of a mix serves the same utterance lengths: the stratified
quantiles ``(k + 0.5) / N`` of the mix's length distribution, with the
arrival gaps fixed the same way, in one order that the generator draws
from a constant stream (``ORDER``).  The seed draws the features and the
weights, and deals the closed loop's first occupants to the slots.  A
run's lengths, its arrivals and, in the closed loop, the steps at which
its slots complete are therefore the same in every run; only the inputs
and which slot serves what change.

Two loops:

* ``closed``: a queue that never runs dry.  The first occupant of each
  slot is an utterance tail whose length follows the residual-life
  distribution of the mix, so occupancy is steady from the first step.
* ``open``: arrivals at a fixed rate, due at fixed offsets from the
  generator's start, in three segments: a pre-roll before the window, the
  window, and the load kept on after it until every request due in the
  window has its logits on the host.

Each utterance reads a run of frames at its own offset from a bank of
inputs that the configuration's model family makes from the mix's
``features`` and the seed (``models/<name>.py``, ``input_bank``): an object
with ``size`` and ``frames(offset, length)``, which wraps round ``size``.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Callable

import numpy as np

_FINE = 100_000  # quantiles that stand for a length distribution
ORDER = 0x0DE7  # entropy of the one order of lengths and gaps of every run


def stratified_lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, ascending.

    >>> stratified_lengths({"dist": "uniform", "min": 60, "max": 100}, 4)
    array([ 65,  75,  85,  95])
    """
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        inv = statistics.NormalDist().inv_cdf
        z = np.array([inv(float(p)) for p in q])
        x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
        return np.clip(x, lo, hi).astype(np.int64)
    if spec["dist"] == "uniform":
        return (lo + np.floor(q * (hi - lo + 1))).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def residual_life_lengths(spec: dict, n: int) -> np.ndarray:
    """Stratified quantiles of the frames left in the utterance a slot holds
    at a random instant of a queue that never runs dry: ``P(R = r)`` is
    ``P(L >= r) / E[L]`` for ``r >= 1``."""
    lengths = stratified_lengths(spec, _FINE)
    counts = np.bincount(lengths)
    survival = counts[::-1].cumsum()[::-1]  # survival[r] = #{L >= r}
    pmf = survival[1:] / lengths.sum()  # r = 1 .. max
    cdf = np.cumsum(pmf)
    q = (np.arange(n) + 0.5) / n
    return (np.searchsorted(cdf, q * cdf[-1]) + 1).astype(np.int64)


def stratified_gaps(rate: float, n: int, span: float) -> np.ndarray:
    """``n`` exponential gaps at ``rate`` by stratified quantiles, scaled so
    that they add up to ``span`` exactly (so ``n`` arrivals fill it)."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return gaps * (span / gaps.sum())


@dataclasses.dataclass
class Request:
    """One utterance of a run: its frames come from the bank at ``offset``.
    ``due`` is the offset in seconds from the generator's start (open
    loop only); ``segment`` is ``first``/``pool`` (closed) or
    ``preroll``/``window``/``post`` (open)."""

    idx: int
    length: int
    offset: int
    segment: str
    due: float | None = None


class Plan:
    """The requests of one run of a traffic mix."""

    def __init__(self, traffic: dict, *, slots: int, seconds: float,
                 seed: int,
                 make_bank: Callable[[dict, np.random.Generator], object]):
        """``make_bank(traffic["features"], rng)`` is the family's
        ``input_bank`` for the configuration served."""
        self.traffic = traffic
        self.slots = slots
        self.seconds = float(seconds)
        ss = np.random.SeedSequence(seed)
        deal_ss, feat_ss = ss.spawn(2)
        self.rng = np.random.default_rng(deal_ss)  # offsets, first occupants
        self.order = np.random.default_rng(ORDER)  # lengths and gaps
        self.bank = make_bank(traffic["features"],
                              np.random.default_rng(feat_ss))
        self.lengths = traffic["lengths"]
        self._next_idx = 0
        if traffic["loop"] == "closed":
            # any deal of the first occupants gives the same completion
            # steps under a fixed queue order, so the seed deals them
            self.first = self._requests(self.rng.permutation(
                residual_life_lengths(self.lengths, slots)), "first")
            self._pool_size = int(traffic["pool"])
            self._pool: list[Request] = []
            self._pool_order = None
        elif traffic["loop"] == "open":
            rate = float(traffic["rate_per_s"])
            self.rate = rate
            self.preroll_s = float(traffic["preroll_s"])
            segs = [("preroll", 0.0, self.preroll_s),
                    ("window", self.preroll_s, self.seconds),
                    ("post", self.preroll_s + self.seconds,
                     float(traffic["drain_timeout_s"]))]
            self.arrivals: list[Request] = []
            for name, start, span in segs:
                n = max(int(round(rate * span)), 1)
                reqs = self._requests(self.order.permutation(
                    stratified_lengths(self.lengths, n)), name)
                gaps = self.order.permutation(stratified_gaps(rate, n, span))
                due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
                for r, d in zip(reqs, due):
                    r.due = float(d)
                self.arrivals.extend(reqs)
        else:
            raise ValueError(f"unknown loop {traffic['loop']!r}")

    def _requests(self, lengths: np.ndarray, segment: str) -> list[Request]:
        offsets = self.rng.integers(0, self.bank.size, len(lengths))
        out = []
        for n, off in zip(lengths, offsets):
            out.append(Request(self._next_idx, int(n), int(off), segment))
            self._next_idx += 1
        return out

    def next_pooled(self) -> Request:
        """The next utterance of the closed loop's queue: the pool's lengths
        in the one fixed order, again from the start when used up."""
        if not self._pool:
            if self._pool_order is None:
                self._pool_order = self.order.permutation(
                    stratified_lengths(self.lengths, self._pool_size))
            self._pool = self._requests(self._pool_order, "pool")
            self._pool.reverse()
        return self._pool.pop()

    def frames(self, req: Request) -> np.ndarray:
        return self.bank.frames(req.offset, req.length)

    @property
    def queue_floor(self) -> int:
        return int(self.traffic["queue_floor_per_slot"]) * self.slots

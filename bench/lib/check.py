"""The comparison that decides ``correct``.

Served logits are compared frame by frame with the plain reference run on
the host CPU over the same utterances.  A frame's error is its largest
logit error over ``max(1, max |reference logit|)``.  The chip accumulates
each float32 dot in another order than the CPU, so a sound frame's error is
float rounding: at most about 5e-7 on a TPU v5 lite.  Three bfloat16 passes
per dot leave 2e-6 to 6e-6 on nearly every frame.
A frame is off when its error exceeds ``FRAME_TOL``, between the two, or
when it never reached the host.  The number compared is ``off_share``: the
share of compared frames that are off.  This is the comparison of a family
whose reference module supplies no ``compare`` of its own; an outcome of
either kind gives ``numbers``, each held to the cell's limit of that name
(``limits/<cell>.json``), and ``describe()``, a line of diagnostics.

A spike is a threshold of the membrane, so a membrane within rounding
distance of the threshold fires on one side and not on the other, and the
recurrence carries the difference on through the utterance: those frames
are off too (their errors are about the size of a logit).  Sound runs
therefore lose a small share of frames; a wrong step, a lost reset, a
misplaced ring row or a lower precision loses most of them.  The frames
off by more than ``ATOL_REL`` (``diverged``) are printed beside it, to tell
a spike flip from rounding.
"""

from __future__ import annotations

import dataclasses

import numpy as np

FRAME_TOL = 1e-6
ATOL_REL = 1e-4


@dataclasses.dataclass
class Outcome:
    utterances: int
    frames: int
    diverged: int
    utterances_diverged: int
    first_divergence: list  # frame index of each diverged utterance
    errors: np.ndarray  # relative error of every compared frame (inf: lost)

    @property
    def diverged_share(self) -> float:
        return self.diverged / max(self.frames, 1)

    def off_share_at(self, tol: float) -> float:
        return float((~(self.errors <= tol)).mean()) if self.frames else 1.0

    @property
    def off_share(self) -> float:
        return self.off_share_at(FRAME_TOL)

    def agreeing_quantiles(self) -> list:
        e = self.errors[self.errors <= ATOL_REL]
        return ([float(np.quantile(e, q)) for q in (0.5, 0.9, 0.99, 1.0)]
                if e.size else [])

    @property
    def numbers(self) -> dict:
        return {"off_share": self.off_share}

    def describe(self) -> str:
        return (f"utterances={self.utterances} frames={self.frames}"
                f" diverged_frames={self.diverged} utterances_diverged="
                f"{self.utterances_diverged} first_divergence="
                f"{sorted(self.first_divergence)[:12]} "
                f"agreeing_error_quantiles={self.agreeing_quantiles()}")


def frame_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """(T,) largest logit error of each frame over ``max(1, max |want|)``;
    NaN reads as infinite."""
    err = np.abs(got - want).max(axis=-1) / np.maximum(
        1.0, np.abs(want).max(axis=-1))
    return np.where(np.isnan(err), np.inf, err)


def compare(served: list, reference: dict) -> Outcome:
    """``served``: [(frames (T, D), logits (T', C) or None)];
    ``reference``: {i: reference logits (T, C)} for every ``served[i]``."""
    frames = diverged = utt_bad = 0
    first, errors = [], []
    for i, (x, got) in enumerate(served):
        want = reference[i]
        t = len(x)
        frames += t
        if got is None or got.shape != want.shape:
            err = np.full(t, np.inf)
        else:
            err = frame_errors(got, want)
        errors.append(err)
        bad = ~(err <= ATOL_REL)
        n = int(bad.sum())
        diverged += n
        if n:
            utt_bad += 1
            first.append(int(np.argmax(bad)))
    return Outcome(len(served), frames, diverged, utt_bad, first,
                   np.concatenate(errors) if errors else np.zeros(0))


def sample(window_served: list, k: int, rng: np.random.Generator) -> list:
    """``k`` of the window's requests drawn by ``rng``, with the longest."""
    if len(window_served) <= k:
        return list(window_served)
    longest = max(range(len(window_served)),
                  key=lambda i: window_served[i].req.length)
    rest = [i for i in range(len(window_served)) if i != longest]
    pick = rng.choice(len(rest), size=k - 1, replace=False)
    return [window_served[longest]] + [window_served[rest[j]]
                                       for j in sorted(pick)]

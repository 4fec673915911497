"""The program's own host time per step outside every child span, in ms:
the self time of ``rsnn.step`` (``StreamLoop.step_once``: the O(slots)
Python bookkeeping between refill, assembly, dispatch, completions, the
fence and retire) over the number of steps in the window."""

from bench.lib import program_trace


def read(run):
    red = program_trace.of_run(run)
    if red is None or red.count("rsnn.step") == 0:
        return None
    _, self_s, n = red.spans["rsnn.step"]
    return 1e3 * self_s / n

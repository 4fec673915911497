"""Host time of one completion or watermark flush at dispatch, in ms: the
seconds of ``rsnn.complete`` (``StreamLoop._advance_slot``: the ring-row
slice and, on completion, the slot's release; the slot's state is reset
when its next occupant is placed, under ``rsnn.refill``) over its count,
which is ``completions + watermark_flushes``."""

from bench.lib import program_trace


def read(run):
    red = program_trace.of_run(run)
    if red is None or red.count("rsnn.complete") == 0:
        return None
    return 1e3 * red.seconds("rsnn.complete") / red.count("rsnn.complete")

"""Host time spent filling free slots from the queue, per request placed,
in ms: the seconds of ``rsnn.refill`` (``StreamLoop._refill`` in
``step_once``: the scheduler's refill, which marks each new occupant's
slot, and the step's one compiled reset dispatch for every slot marked)
over the requests it placed, one ``rsnn.reset_slot`` child each (the
``refills`` counter's boundary)."""

from bench.lib import program_trace


def read(run):
    red = program_trace.of_run(run)
    if red is None:
        return None
    refills = red.children.get(("rsnn.refill", "rsnn.reset_slot"), 0)
    if refills == 0:
        return None
    return 1e3 * red.seconds("rsnn.refill") / refills

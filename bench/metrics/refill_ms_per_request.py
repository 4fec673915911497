"""Host time spent filling free slots from the queue, per request placed,
in ms: the seconds of ``rsnn.refill`` (``SlotScheduler._refill`` in
``StreamLoop.step_once``, the eager ``reset_slot`` of each new occupant
included) over the requests it placed, one ``rsnn.reset_slot`` child
each (the ``refills`` counter's boundary)."""

from bench.lib import program_trace


def read(run):
    red = program_trace.of_run(run)
    if red is None:
        return None
    refills = red.children.get(("rsnn.refill", "rsnn.reset_slot"), 0)
    if refills == 0:
        return None
    return 1e3 * red.seconds("rsnn.refill") / refills

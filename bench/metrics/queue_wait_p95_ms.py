"""Time a request due in the window waited in the scheduler's queue
(``serving/slots.py``) for a free slot: the 95th percentile of the
program's ``StreamRequest.t_start - t_submit``, in ms."""

from bench.lib.stats import nearest_rank


def read(run):
    wait = [(s.handle.t_start - s.handle.t_submit) * 1e3
            for s in run.window.measured if s.handle is not None
            and s.handle.t_start is not None]
    return nearest_rank(wait, 95) if wait else None

"""How late the load generator submitted requests due in the window: the
95th percentile of the harness clock's submit time minus the due time, in
ms.  A late generator delays requests before the server sees them; the
latency still counts from the due time."""

from bench.lib.stats import nearest_rank


def read(run):
    late = [(s.t_submit - s.t_due) * 1e3 for s in run.window.measured
            if s.t_due is not None]
    return nearest_rank(late, 95) if late else None

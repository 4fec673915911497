"""Share of the window in which no operation ran on the device, in %, from
the profiler trace of the window.  One reader for every cell's split of
the metric (``device_idle_share.backlog``, ``device_idle_share.ptt``)."""


def read(run):
    r = run.reduction
    if r is None or r.busy_s <= 0.0:
        return None
    return 100.0 * r.idle_share

"""The mega-step kernel's share of its roofline, in %: the least time the
chip could take for one step's required work (``bench/lib/work.py``: the
model's operations and bytes for a step over every slot, at the peaks of
``bench/peaks.json``) over the kernel's device time per call, found in the
trace by its ``pallas_call`` name, ``megastep``."""

from bench.lib import work


def read(run):
    r = run.reduction
    if r is None:
        return None
    secs, calls = r.kernel("megastep")
    if calls == 0 or secs <= 0.0:
        return None
    conf = run.cell.config
    least, _ = work.least_step_seconds(
        conf["model"], conf["compression"], conf["serving"]["slots"],
        run.peak_ops(), run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (secs / calls)

"""The whole step's share of the chip's peak, in %: the model's required
operations per slot-frame (``bench/lib/work.py``) times the slot-frames
per second of the traced run, over the peak the configuration names in
``bench/peaks.json``."""

from bench.lib import work


def read(run):
    if run.reduction is None or run.window.frames == 0:
        return None
    conf = run.cell.config
    ops = (work.ops_per_frame(conf["model"], conf["compression"])
           * run.frames_per_s)
    return 100.0 * ops / run.peak_ops()

"""Share of the logit bytes fetched to the host that hold a served frame,
in %: the ``valid_bytes`` over the ``bytes`` that the window's
``rsnn.egress`` spans carry (``egress_valid_bytes`` over ``egress_bytes``
of ``StreamLoop``).  Whole ring rows cross, so it is set by the lengths
against ``ring_frames``."""

from bench.lib import program_trace


def read(run):
    red = program_trace.of_run(run)
    if red is None:
        return None
    sums = red.stat_sums.get("rsnn.egress", {})
    if not sums.get("bytes"):
        return None
    return 100.0 * sums.get("valid_bytes", 0) / sums["bytes"]

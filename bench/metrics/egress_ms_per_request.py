"""Host time fetching one request's logit blocks to the host at retire,
in ms: the seconds of ``rsnn.egress`` (``StreamLoop._egress``, a blocking
device-to-host copy of each whole ring row) over its count."""

from bench.lib import program_trace


def read(run):
    red = program_trace.of_run(run)
    if red is None or red.count("rsnn.egress") == 0:
        return None
    return 1e3 * red.seconds("rsnn.egress") / red.count("rsnn.egress")

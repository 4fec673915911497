"""Share of the window the host spent waiting on the device, in %: the
seconds of ``rsnn.fence_wait`` (the ``block_until_ready`` on the oldest
in-flight step in ``StreamLoop._retire``) over the window's.  One reader
for ``fence_wait_share.backlog`` and ``fence_wait_share.ptt``."""

from bench.lib import program_trace


def read(run):
    red = program_trace.of_run(run)
    if red is None or red.count("rsnn.fence_wait") == 0:
        return None
    return 100.0 * red.seconds("rsnn.fence_wait") / red.window_s

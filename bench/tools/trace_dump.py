"""Print the structure of a profiler trace: planes, lines, and the most
frequent event names of each line.

    python3 bench/tools/trace_dump.py .bench_trace/<cell>
"""

import collections
import glob
import os
import sys

from jax.profiler import ProfileData


def main(trace_dir: str) -> int:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            span = ((events[0].start_ns, events[-1].start_ns)
                    if events else None)
            print("  line", repr(line.name), len(events), span,
                  names.most_common(8))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

"""A traced run of one cell, as ``bench/run.py --trace 1`` makes it, and
what the program's own spans say of its window, in one process.

    python3 bench/tools/host_spans.py --workload <cell> --seed <n> \
        --seconds <s> [--out <file.json>]

The run prints its result line as ``bench/run.py`` does.  Then one JSON
line (also written to ``--out``): the program spans' seconds, self seconds
and count in the window; idle seconds by gap name (gaps of 1 ms or more,
``bench/lib/program_trace.py``) and the longest gaps; the longest program
spans with their stats; the lag from each ``rsnn.dispatch`` to the start
of its ``megastep`` call on the device; and the share of fetched logit
bytes that hold a frame, from the spans and from the lengths of the
window's measured requests.
"""

import argparse
import json
import math
import os
import statistics
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib import env  # noqa: E402

env.prepare()

from bench.lib import harness, program_trace  # noqa: E402


def _quantile(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else None


def summary(m: harness.Measured, trace_dir: str) -> dict:
    ev = program_trace.load(trace_dir)
    red = program_trace.reduce(ev, n_gaps=12)
    window = m.run.window
    ring = int(m.run.cell.config["serving"]["ring_frames"])
    lengths = [s.req.length for s in window.measured]
    lags = program_trace.dispatch_lags(ev)
    lo = min(s.start for t in ev.threads for s in t if s.name == "window")
    hi = lo + red.window_s * 1e9
    prog = [s for t in ev.threads for s in t
            if s.name.startswith(program_trace.PREFIX)
            and s.end > lo and s.start < hi]
    longest = sorted(prog, key=lambda s: s.start - s.end)[:12]
    egress = red.stat_sums.get("rsnn.egress", {})
    idle = sum(red.idle_by_name.values())
    return {
        "workload": m.run.cell.name, "window_s": red.window_s,
        "steps": window.steps, "requests": len(lengths),
        "spans": {n: list(v) for n, v in sorted(red.spans.items())},
        "children": {f"{p}>{c}": n for (p, c), n in
                     sorted(red.children.items())},
        "idle_s_gaps_1ms": idle,
        "idle_by_name": dict(sorted(red.idle_by_name.items(),
                                    key=lambda kv: -kv[1])),
        "idle_gaps": red.idle_gaps,
        "longest_spans": [[s.name, (s.end - s.start) * 1e-9,
                           (s.start - lo) * 1e-9, s.stats]
                          for s in longest],
        "dispatch_lag_s": {
            "pairs": len(lags), "negative": sum(x < 0 for x in lags),
            "min": min(lags, default=None),
            "median": statistics.median(lags) if lags else None,
            "p95": _quantile(lags, 0.95), "max": max(lags, default=None)},
        "egress_useful_share": {
            "spans": (100.0 * egress.get("valid_bytes", 0) / egress["bytes"]
                      if egress.get("bytes") else None),
            "lengths": (100.0 * sum(lengths)
                        / (ring * sum(math.ceil(n / ring) for n in lengths))
                        if lengths else None)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seen = []
    measure = harness.measure

    def keep(*a, **kw):
        seen.append(measure(*a, **kw))
        return seen[-1]

    harness.measure = keep
    rc = harness.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1"],
                      t_start=T_START)
    if rc or not seen:
        return rc or 1
    out = summary(seen[0], os.path.join(harness.TRACE_DIR, args.workload))
    out["seed"] = args.seed
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

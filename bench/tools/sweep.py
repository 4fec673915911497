"""Find the push-to-talk knee: latency against arrival rate, on the chip.

    python3 bench/tools/sweep.py --workload pruned_csc.ptt_rate \
        --rates 10,20,40 --seconds 10 --seeds 1,2

Runs the cell's open loop once per rate and seed in one process and
prints, per run, the latency percentiles, queue wait, the mean step period and the
slot-frames per step.  The rate the cell's traffic file fixes is chosen
from this sweep once, by hand; the benchmark never searches for it.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib import env  # noqa: E402

env.prepare()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.lib import drive, harness, spec, stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    bench = spec.Bench(ROOT)
    cell = bench.cell(args.workload)
    accel = harness.find_accelerator(cell.chips)
    if accel is None:
        print("sweep: no accelerator", file=sys.stderr)
        return 2
    cpu = jax.devices("cpu")[0]
    harness.enable_cache()
    counter = drive.CompileCounter()
    peaks = harness.load_peaks(accel.device_kind)
    runs = [(float(r), int(s)) for r in args.rates.split(",")
            for s in args.seeds.split(",")]
    for rate, seed in runs:
        cell.traffic["rate_per_s"] = rate
        t0 = time.monotonic()
        m = harness.measure(bench, cell, seed, args.seconds, False, accel,
                            cpu, counter, peaks, t0)
        w = m.run.window
        lat = m.run.latencies_ms()
        wait = [(s.handle.t_start - s.handle.t_submit) * 1e3
                for s in w.measured if s.handle is not None
                and s.handle.t_start is not None]
        row = {"rate_per_s": rate, "seed": seed,
               "requests": len(w.measured),
               "unfinished": w.unfinished,
               "p50_ms": stats.nearest_rank(lat, 50) if lat else None,
               "p95_ms": stats.nearest_rank(lat, 95) if lat else None,
               "queue_wait_p95_ms": (stats.nearest_rank(wait, 95)
                                     if wait else None),
               "step_period_mean_ms": 1e3 * float(np.mean(w.step_periods)),
               "step_period_max_ms": 1e3 * float(np.max(w.step_periods)),
               "slot_frames_per_step": w.frames / max(w.steps, 1),
               "off_share": m.outcome.off_share}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

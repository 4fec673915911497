"""Readings that set a cell's correctness limit, on the chip, in one process.

    python3 bench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 10 [--control]

For each seed it runs the cell's window as ``bench/run.py`` does and reads
the number compared (``off_share``): the lower readings.  With
``--control`` it also puts the reference, computed at the next precision
below the configuration's (three bfloat16 passes per dot), in the program's
place over the same utterances on the accelerator and reads the same
number: the upper readings.  One JSON line per seed goes to stdout.
"""

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib import env  # noqa: E402

env.prepare()

import jax  # noqa: E402

from bench.lib import check, drive, harness, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    bench = spec.Bench(ROOT)
    cell = bench.cell(args.workload)
    accel = harness.find_accelerator(cell.chips)
    if accel is None:
        print("readings: no accelerator", file=sys.stderr)
        return 2
    cpu = jax.devices("cpu")[0]
    harness.enable_cache()
    counter = drive.CompileCounter()
    peaks = harness.load_peaks(accel.device_kind)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        m = harness.measure(bench, cell, seed, args.seconds, False, accel, cpu,
                            counter, peaks, t0)
        row = {"workload": args.workload, "seed": seed,
               "program": m.outcome.off_share,
               "diverged_share": m.outcome.diverged_share,
               "frames": m.outcome.frames,
               "utterances_diverged": m.outcome.utterances_diverged,
               "utterances": m.outcome.utterances,
               "first_divergence": sorted(m.outcome.first_divergence),
               "unfinished": m.run.window.unfinished,
               "slot_steps_off": m.run.window.slot_steps_off,
               "agreeing_err_q": m.outcome.agreeing_quantiles(),
               "off_share_at": {t: m.outcome.off_share_at(float(t))
                                for t in ("3e-7", "1e-6", "2e-6")},
               "setup_s": m.run.window.t_open - t0}
        if args.control:
            t1 = time.monotonic()
            ctrl = harness.reference_logits(
                cell.config, m.params, [x for x, _ in m.served], accel,
                "high", bench)
            got = check.compare([(x, ctrl[i]) for i, (x, _) in
                                 enumerate(m.served)], m.reference)
            row.update(control=got.off_share,
                       control_diverged_share=got.diverged_share,
                       control_agreeing_err_q=got.agreeing_quantiles(),
                       control_off_share_at={
                           t: got.off_share_at(float(t))
                           for t in ("3e-7", "1e-6", "2e-6")},
                       control_utterances_diverged=got.utterances_diverged,
                       control_s=time.monotonic() - t1)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one cell, end to end (entry point: ``bench/run.py``)."""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import shutil
import sys
import time

import jax
import numpy as np

from bench.lib import check, drive, spec, stats, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def find_accelerator(chips: int):
    """The first accelerator device, or None where JAX finds none or fewer
    than ``chips``."""
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < chips:
        return None
    return devices[0]


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed place in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program,
    however quick to compile, so that a warm run loads them all."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


WARM_UTTERANCES = 4


def warm(loop: drive.ServedLoop, plan: traffic.Plan) -> None:
    """Serve a few short utterances, so that the step, refill, reset,
    harvest and retire have all run; then forget them.  (The loop built
    every per-slot executable when it was made.)"""
    short = traffic.Request(-1, 3, 0, "warm")
    for _ in range(WARM_UTTERANCES):
        loop.submit(plan.frames(short))
    loop.run()
    loop.finished.clear()
    loop.reset_metrics()


@dataclasses.dataclass
class Run:
    """What a metric reader sees of a run."""

    cell: spec.Cell
    plan: traffic.Plan
    window: drive.Window
    reduction: object  # trace.DeviceReduction, or None without --trace 1
    peaks: dict  # this device kind's row of peaks.json

    @property
    def frames_per_s(self) -> float:
        return self.window.frames / self.window.seconds

    def latencies_ms(self) -> list:
        """Due time to the last logit on the host, on the harness's clock."""
        return [(s.t_host - s.t_due) * 1e3 for s in self.window.measured
                if s.t_host is not None]

    def peak_ops(self) -> float:
        return self.peaks[self.cell.config["compute"]["peak"]]


def _latency(p):
    def metric(run: Run):
        lat = run.latencies_ms()
        return stats.nearest_rank(lat, p) if lat else None
    return metric


END_TO_END = {
    "frames_per_s": lambda run: (run.frames_per_s
                                 if run.cell.traffic["loop"] == "closed"
                                 else None),
    "utt_latency_p95_ms": _latency(95),
    "utt_latency_p50_ms": _latency(50),
}


def load_peaks(kind: str) -> dict:
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        kinds = json.load(f)["kinds"]
    if kind not in kinds:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return kinds[kind]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Measured:
    """One run's window, its check and what the check needs again."""

    run: Run
    outcome: check.Outcome
    served: list  # [(frames, served logits or None)] that were compared
    reference: dict  # {i: reference logits} of ``served``
    params: dict  # the float weights the seed made (on the accelerator)
    marks: dict  # set-up milestones on the host clock
    memory_peak: int
    setup_counts: tuple  # (compiles, cache loads) before the window


def measure(bench: spec.Bench, cell: spec.Cell, seed: int, seconds: float,
            trace: bool, accel, cpu, counter: drive.CompileCounter,
            peaks: dict, t_start: float) -> Measured:
    """Build, warm, run the window, and check it against the reference
    (the reference module's ``compare``, else ``check.compare``)."""
    conf, mix = cell.config, cell.traffic
    family = bench.family(conf["reference"])
    marks = {"start": t_start, "jax": time.monotonic()}
    plan = traffic.Plan(mix, slots=conf["serving"]["slots"], seconds=seconds,
                        seed=seed,
                        make_bank=functools.partial(family.input_bank, conf))
    marks["traffic"] = time.monotonic()
    params = family.make_params(conf, seed, accel)
    marks["weights"] = time.monotonic()
    loop = family.build_loop(conf, params, accel, cpu)
    marks["loop"] = time.monotonic()
    warm(loop, plan)
    marks["warm"] = time.monotonic()
    setup_counts = (counter.compiles, counter.cache_hits)

    host = drive.Host(tracing=trace, clock=time.monotonic)
    trace_dir = os.path.join(TRACE_DIR, cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    gc.collect()
    gc.freeze()
    driver = {"closed": drive.closed_loop, "open": drive.open_loop}
    window = driver[mix["loop"]](loop, plan, seconds, host, counter)
    gc.unfreeze()
    reduction = None
    if trace:
        jax.profiler.stop_trace()
        from bench.lib import trace as trace_lib
        reduction = trace_lib.reduce(trace_lib.load(trace_dir))
    memory_peak = int((accel.memory_stats() or {}).get("peak_bytes_in_use",
                                                       0))

    # the check: the window's requests (a seeded sample) against the
    # reference, once the program's device state is freed
    pick = check.sample(window.measured, int(mix["check"]["sample"]),
                        np.random.default_rng([seed, 0xC4EC]))
    served = [(plan.frames(s.req), s.handle.stacked_logits()
               if s.t_host is not None else None) for s in pick]
    del loop
    gc.collect()
    want = reference_logits(conf, params, [x for x, _ in served], cpu,
                            conf["compute"]["matmul_precision"], bench)
    compare = getattr(bench.reference(conf["reference"]), "compare",
                      check.compare)
    outcome = compare(served, want)
    return Measured(Run(cell, plan, window, reduction, peaks), outcome,
                    served, want, params, marks, memory_peak, setup_counts)


def reference_logits(conf: dict, params: dict, utts: list, device,
                     precision: str, bench: spec.Bench | None = None) -> dict:
    """{i: logits} of the configuration's plain reference
    (``reference/<conf["reference"]>.py`` under ``bench``'s paths) over
    ``utts``, from the float weights ``params``, every dot at ``precision``
    on ``device`` (``compute.matmul_precision`` for the check, the next one
    below for the control)."""
    ref = (bench or spec.Bench(ROOT)).reference(conf["reference"])
    return dict(ref.logits(utts, params, conf, precision, device))


def main(argv, t_start: float) -> int:
    args = parse(argv)
    bench = spec.Bench(ROOT)
    cell = bench.cell(args.workload)
    try:  # a family module imports the program it builds
        bench.family(cell.config["reference"])
    except ImportError as e:
        say(f"bench: the system under test is missing: {e}")
        return 2
    accel = find_accelerator(cell.chips)
    if accel is None:
        say(f"bench: no accelerator with {cell.chips} chip(s): JAX sees "
            f"{len(jax.devices())} {jax.devices()[0].platform} device(s)")
        return 2
    peaks = load_peaks(accel.device_kind)
    cpu = jax.devices("cpu")[0]
    cache = enable_cache()
    counter = drive.CompileCounter()
    m = measure(bench, cell, args.seed, args.seconds, bool(args.trace),
                accel, cpu, counter, peaks, t_start)
    run, outcome, window, marks = m.run, m.outcome, m.run.window, m.marks
    checks = {name: (value, float(cell.limits[name]["limit"]))
              for name, value in outcome.numbers.items()}
    checks.update(unfinished=(window.unfinished, 0),
                  slot_steps_off=(window.slot_steps_off, 0))
    correct = (all(value <= limit for value, limit in checks.values())
               and len(window.measured) > 0)

    if args.trace:
        metrics = {}
        for spec_m in cell.per_layer:
            value = bench.reader(spec_m["name"])(run)
            if value is not None:
                metrics[spec_m["name"]] = {"value": value,
                                           "unit": spec_m["unit"]}
    else:
        metrics = {"setup_s": {"value": window.t_open - t_start, "unit": "s"}}
        for spec_m in cell.end_to_end:
            if spec_m["name"] in END_TO_END:
                value = END_TO_END[spec_m["name"]](run)
                if value is not None:
                    metrics[spec_m["name"]] = {"value": value,
                                               "unit": spec_m["unit"]}

    lengths = [s.req.length for s in window.measured]
    periods = window.step_periods or [0.0]
    longest = max(window.host_longest.items(), key=lambda kv: kv[1],
                  default=("none", 0.0))
    say(f"diag seed={args.seed} workload={args.workload} "
        f"requests_in_window={len(window.measured)} "
        f"served_len_p50={stats.nearest_rank(lengths, 50) if lengths else 0} "
        f"served_len_p95={stats.nearest_rank(lengths, 95) if lengths else 0} "
        f"unfinished={window.unfinished}")
    say(f"diag steps={window.steps} window_s={window.seconds:.4f} "
        f"slot_frames={window.frames} "
        f"program_slot_frames={window.program_frames} "
        f"step_period_mean_ms={1e3 * float(np.mean(periods)):.3f} "
        f"step_period_max_ms={1e3 * max(periods):.3f} "
        f"host_longest={longest[0]}:{1e3 * longest[1]:.3f}ms")
    say(f"diag compiles_in_window={window.compiles} "
        f"setup_compiles={m.setup_counts[0]} "
        f"setup_cache_loads={m.setup_counts[1]} cache={cache}")
    say("diag setup " + " ".join(
        f"{b}={marks[b] - marks[a]:.3f}s" for a, b in zip(
            ["start", "jax", "traffic", "weights", "loop"],
            ["jax", "traffic", "weights", "loop", "warm"]))
        + f" window_open_after_warm={window.t_open - marks['warm']:.3f}s "
        + " ".join(f"{k}={v:.3f}" for k, v in window.prep.items()))
    say("diag check " + outcome.describe())
    for name, (value, limit) in checks.items():
        say(f"check {name}={value!r} limit={limit!r}")

    result = {
        "correct": bool(correct),
        "attempted": len(window.measured),
        "failed": window.unfinished,
        "metrics": metrics,
        "device": {"platform": accel.platform, "kind": accel.device_kind,
                   "count": cell.chips, "memory_peak_bytes": m.memory_peak},
    }
    if run.reduction is not None:
        red = run.reduction
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in red.top_ops(10)],
            "idle_gaps": [list(g) for g in red.idle_gaps[:10]]}
    result["check"] = {name: {"value": value, "limit": limit}
                       for name, (value, limit) in checks.items()}
    print(json.dumps(result), flush=True)
    return 0

"""Benchmark driver: one entry per paper table/figure + roofline summary.

Prints ``name,us_per_call,derived`` CSV lines (us_per_call only for the
timed entries; analytic tables report 0).  ``--only SUBSTR`` restricts the
run to matching entries (the CI smoke runs ``--only bench_stream_pipeline``
to keep the pipelined-serving row honest on every push); ``--list`` prints
the available names so ``--only`` isn't guess-and-check.  A ``--only``
that matches nothing exits non-zero listing the available names — a typo
in a CI smoke must fail the job, not print a bare CSV header and pass.

For persisted latency/throughput trajectories (rather than one-off CSV
rows), see ``benchmarks/loadgen.py`` / ``benchmarks/trajectory.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import paper_tables as T  # noqa: E402

ANALYTIC = ("table1_dimensions", "fig12_model_size", "fig13_complexity",
            "fig14_error_ablation", "fig16_time_steps", "fig17_cycles",
            "fig18_sparsity", "table2_weight_access", "table3_power")

TIMED = (("bench_rsnn_forward", "bench_rsnn_forward"),
         ("bench_merged_spike_fc", "bench_kernels"),
         ("bench_sparse_fc", "bench_sparse_fc"),
         ("bench_nm_fc", "bench_nm_fc"),
         ("bench_stream_engine", "bench_stream_engine"),
         ("bench_stream_sharded", "bench_stream_sharded"),
         ("bench_stream_pipeline", "bench_stream_pipeline"),
         ("bench_artifact_roundtrip", "bench_artifact_roundtrip"),
         ("bench_megastep", "bench_megastep"),
         ("bench_delta", "bench_delta"),
         ("bench_spike_broadcast", "bench_spike_broadcast"))


def _emit(name: str, us: float, derived) -> None:
    print(f"{name},{us:.2f},{json.dumps(derived, default=str)}")


def all_names() -> tuple[str, ...]:
    """Every runnable bench name (the values ``--only`` matches against)."""
    return ANALYTIC + tuple(n for n, _ in TIMED) + ("roofline_summary",)


def list_entries() -> None:
    """Print every runnable bench name (the values ``--only`` matches)."""
    for name in ANALYTIC:
        print(f"{name}  [analytic]")
    for name, _ in TIMED:
        print(f"{name}  [timed]")
    print("roofline_summary  [derived]")


def _run_roofline() -> None:
    # roofline summary (reads results/dryrun)
    try:
        from benchmarks import roofline

        rows = roofline.table("pod")
        ok = [r for r in rows if "roofline_fraction" in r]
        worst = sorted(ok, key=lambda r: r["roofline_fraction"])[:3]
        _emit("roofline_summary", 0.0, {
            "cells": len(rows),
            "ok": len(ok),
            "worst": [f"{r['arch']}/{r['shape']}={r['roofline_fraction']:.4f}"
                      for r in worst]})
    except Exception as e:  # dry-run artifacts absent
        _emit("roofline_summary", 0.0, {"error": str(e)})


def main(only: str | None = None) -> int:
    """Run every entry whose name contains ``only`` (all when None).

    Returns the number of entries run.  Zero matches is an error: the old
    driver silently printed only the CSV header and exited 0 — a typo in
    ``--only`` (e.g. the CI smoke's entry name) passed green running
    nothing.  The roofline row goes through the same name match as every
    other entry (the old ``only not in "roofline_summary"`` test matched
    any substring of the *literal* — ``--only o`` ran it spuriously even
    while skipping entries it was meant to select).
    """
    matches = lambda name: not only or only in name  # noqa: E731
    selected = [n for n in all_names() if matches(n)]
    if only and not selected:
        print(f"error: --only {only!r} matches no benchmark entry; "
              f"available:", file=sys.stderr)
        for name in all_names():
            print(f"  {name}", file=sys.stderr)
        raise SystemExit(2)

    print("name,us_per_call,derived")
    for name in ANALYTIC:
        if not matches(name):
            continue
        rows, derived = getattr(T, name)()
        _emit(name, 0.0, {"rows": rows, **derived})

    for name, fn in TIMED:
        if not matches(name):
            continue
        us, d = getattr(T, fn)()
        _emit(name, us, d)

    if matches("roofline_summary"):
        _run_roofline()
    return len(selected)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only entries whose name contains this substring")
    ap.add_argument("--list", action="store_true",
                    help="print available bench names and exit")
    args = ap.parse_args()
    if args.list:
        list_entries()
    else:
        main(args.only)

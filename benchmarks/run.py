"""Benchmark suite entry point: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--engine=jax]

--full replays the 526x150 FB-scale fabric (minutes on one CPU core);
the default quick fabric preserves every qualitative claim. Every
driver runs through `repro.api.run`, so --engine is plain Scenario data
threaded to the Saath side uniformly. Machine-readable perf records
accumulate in BENCH_api.json (benchmarks.common.record). The slow
roofline pass (`python -m benchmarks.roofline --all`) writes
experiments/roofline/; this runner prints its cached table if present.
"""
from __future__ import annotations

import argparse
import glob
import json
import sys
import time

from benchmarks import (fig2_out_of_sync, fig3_offline_policies,
                        fig9_speedup, fig10_breakdown, fig11_bins,
                        fig13_fct_deviation, fig14_sensitivity,
                        table2_coordinator_latency)
from benchmarks.common import Bench
from repro.launch.entry import enable_compile_cache

SUITES = [
    ("fig2", fig2_out_of_sync),
    ("fig3", fig3_offline_policies),
    ("fig9", fig9_speedup),
    ("fig10", fig10_breakdown),
    ("fig11", fig11_bins),
    ("fig13", fig13_fct_deviation),
    ("fig14", fig14_sensitivity),
    ("table2", table2_coordinator_latency),
]


def print_cached_roofline(path="experiments/roofline"):
    files = sorted(glob.glob(f"{path}/*.json"))
    if not files:
        print("# roofline: no cached results "
              "(run: python -m benchmarks.roofline --all)")
        return
    from benchmarks.roofline import HEADER, fmt_row
    print("# roofline (cached from experiments/roofline/)")
    print(HEADER)
    for f in files:
        rec = json.load(open(f))
        if "error" in rec:
            print(f"| {rec['arch']} | {rec['shape']} | ERROR |")
        else:
            print(fmt_row(rec))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="FB-scale fabric (526 coflows x 150 ports)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--engine", choices=("numpy", "jax"), default="numpy",
                    help="replay engine for the Saath-side Scenarios")
    args = ap.parse_args()
    enable_compile_cache()
    bench = Bench(quick=not args.full)
    t0 = time.time()
    failures = []
    for name, mod in SUITES:
        if args.only and name != args.only:
            continue
        t1 = time.time()
        try:
            mod.run(bench, engine=args.engine)
        except AssertionError as e:
            failures.append((name, str(e)))
            print(f"# {name} CLAIM-CHECK FAILED: {e}", file=sys.stderr)
        print(f"# {name} done in {time.time() - t1:.1f}s", file=sys.stderr)
    print_cached_roofline()
    print(f"# total {time.time() - t0:.1f}s; "
          f"{len(failures)} claim-check failures")
    if failures:
        sys.exit(1)


def run_all(quick=True, engine="numpy"):
    bench = Bench(quick=quick)
    return {name: mod.run(bench, engine=engine) for name, mod in SUITES}


if __name__ == "__main__":
    main()

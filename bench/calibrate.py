#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip.

    python bench/calibrate.py --workload fb150.steady --seconds 10 \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 3

For each seed, in one process: one run of the cell as `bench/run.py`
makes it (a shorter window), whose compared numbers are the sound
readings; and, for the first `--control-seeds` seeds, the control: the
reference with work conservation (D4) switched off put in the
program's place on the same submitted stream, whose numbers must fail.
Prints one JSON line per reading; the limits in the configuration are
set between the largest sound reading and the smallest control one.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def control_readings(cfg, keep) -> dict:
    """The control's numbers: its own completions, as the front door
    would have handed them out, against the reference."""
    import numpy as np

    from bench import compare

    sub, t0, t1 = keep["submitted"], keep["t0"], keep["t1"]
    until = t1 + float(cfg["limits"]["margin_s"])
    ref = compare.reference_ccts(cfg, sub, until)
    ctl = compare.reference_ccts(cfg, sub, t1, work_conservation=False)
    win, anyd = {}, set()
    for i, specs in sub.items():
        for s in specs:
            c = ctl[i][s.cid]
            if np.isfinite(c):
                anyd.add((i, s.cid))
                if t0 < s.arrival + c <= t1:
                    win[i, s.cid] = float(c)
    return compare.readings(cfg, sub, win, anyd, t0, t1, ref)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

    import jax

    from bench import harness, spec

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    cell = spec.Cell(args.workload)
    counter = harness.CompileCounter().install()
    seeds = [int(s) for s in args.seeds.split(",")]
    for k, seed in enumerate(seeds):
        keep: dict = {}
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), counter,
                               devs[:cell.chips], keep=keep)
        line = {"seed": seed, "side": "program", "correct": out["correct"],
                **keep["readings"],
                **{m: v["value"] for m, v in out["metrics"].items()}}
        print(json.dumps(line), flush=True)
        if k < args.control_seeds:
            vals = control_readings(cell.config, keep)
            print(json.dumps({"seed": seed, "side": "control", **vals}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

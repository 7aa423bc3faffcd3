#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload fb150.steady --seed 7 --seconds 30 \
        --trace 0

Set-up builds the cell's `CoflowServer`, generates its traffic from
the seed and fast-forwards the first part of the stream (compiling, or
loading from the persistent cache, every program the window uses). The
window then runs coordinator rounds back to back for `--seconds`; with
`--trace 1` it runs under the profiler and reports the per-layer
metrics instead of the end-to-end ones. Afterwards the completions are
compared with the float64 reference (`bench/compare.py`).

Earlier lines give the device, the kernel paths, the compilations in
the window, the pool's transfer counters and the generator settings;
the last lines of standard error give each compared number beside its
limit; the last line of standard output is the JSON result. Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "bench" / ".trace"


def fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no program under {ROOT / 'src'}: run from a checkout of "
             f"the repository")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)

    from bench import harness, spec

    try:
        cell = spec.Cell(args.workload)
    except (FileNotFoundError, KeyError) as e:
        fail(str(e))

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: jax found only {devs[0].platform} devices; the "
             f"benchmark runs on the chip only", 3)
    if len(devs) < cell.chips:
        fail(f"{args.workload} needs {cell.chips} chips, jax sees "
             f"{len(devs)}", 3)
    devices = devs[:cell.chips]
    counter = harness.CompileCounter().install()
    harness.log(f"jax={jax.__version__} device_kind={devs[0].device_kind} "
                f"platform={devs[0].platform} count={len(devices)} "
                f"cache={jax.config.jax_compilation_cache_dir}")
    harness.log(f"workload={cell.name} seed={args.seed} "
                f"seconds={args.seconds} trace={args.trace}")
    harness.log(f"config={json.dumps(cell.config, sort_keys=True)}")
    harness.log(f"traffic={json.dumps(cell.traffic, sort_keys=True)}")
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, counter, devices, TRACE_DIR)
    harness.print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

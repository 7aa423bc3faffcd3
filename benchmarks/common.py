"""Shared benchmark machinery: Scenario cache, CSV + BENCH_api.json emit.

Every driver goes through `Bench.run`, which builds a `repro.api.Scenario`
from the bench fabric spec and caches the normalized `Result` by scenario
hash — the engine is plain scenario data, so drivers never branch on it.
Uncached runs are appended to BENCH_api.json (scenario hash, engine,
wall-clock, compile time, CCT stats) so the perf trajectory is recorded
across PRs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from typing import Dict, Optional, Tuple

import numpy as np

from repro.api import Result, Scenario
from repro.api import run as api_run
from repro.core.params import SchedulerParams
from repro.launch.entry import enable_compile_cache

# default benchmark fabric: FB-like (paper: 526 coflows / 150 ports);
# --quick shrinks it so the full suite stays minutes on one CPU core.
FULL = dict(num_coflows=526, num_ports=150, seed=0)
QUICK = dict(num_coflows=240, num_ports=100, seed=0)

BENCH_JSON = os.environ.get("SAATH_BENCH_JSON", "BENCH_api.json")


def record(name: str, result: Result, row: int = 0, **extra) -> dict:
    """Append one machine-readable perf record to BENCH_api.json
    (idempotent per (bench, scenario, engine, row) key)."""
    rec = {"bench": name, **result.summary(row), **extra}
    rec = {k: (None if isinstance(v, float) and not math.isfinite(v)
               else v) for k, v in rec.items()}
    key = (rec["bench"], rec["scenario"], rec["engine"], rec["row"])
    existing = []
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as fh:
                existing = json.load(fh)
        except (json.JSONDecodeError, OSError):
            existing = []
    existing = [r for r in existing
                if (r.get("bench"), r.get("scenario"), r.get("engine"),
                    r.get("row")) != key]
    existing.append(rec)
    with open(BENCH_JSON, "w") as fh:
        json.dump(existing, fh, indent=1)
    return rec


@dataclasses.dataclass
class Bench:
    quick: bool = True
    _cache: Dict[str, Result] = dataclasses.field(default_factory=dict)
    _trace_kw: dict = None

    def __post_init__(self):
        self._trace_kw = QUICK if self.quick else FULL

    def scenario(self, policy: str = "saath", *, engine: str = "numpy",
                 params: SchedulerParams | None = None,
                 mechanisms: dict | None = None,
                 policy_kwargs: dict | None = None,
                 label: str = "", **trace_overrides) -> Scenario:
        """A Scenario over the bench fabric (QUICK/FULL synth spec plus
        per-driver overrides)."""
        synth = dict(self._trace_kw)
        synth.update(trace_overrides)
        return Scenario(policy=policy, engine=engine,
                        params=params or SchedulerParams(), synth=synth,
                        mechanisms=mechanisms, policy_kwargs=policy_kwargs,
                        label=label)

    def run(self, policy: str = "saath", *,
            scenario: Optional[Scenario] = None, record_as: str = "",
            **kw) -> Result:
        """Run (or fetch the cached) Result for a scenario. `record_as`
        names the BENCH_api.json record for uncached headline runs."""
        sc = scenario if scenario is not None else \
            self.scenario(policy, **kw)
        key = sc.hash()
        if key not in self._cache:
            t0 = time.perf_counter()
            self._cache[key] = api_run(sc)
            print(f"#   ran {sc.policy}[{sc.engine}]"
                  f"{'/' + sc.label if sc.label else ''} in "
                  f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
            if record_as:
                record(record_as, self._cache[key])
        return self._cache[key]

    def trace(self, **overrides):
        """The bench fabric trace itself (for drivers that inspect it)."""
        from repro.traces import fb_like_trace

        kw = dict(self._trace_kw)
        kw.update(overrides)
        return fb_like_trace(**kw)

def cli_bench(argv=None) -> "Tuple[Bench, str]":
    """Common driver CLI: --full fabric scale, --engine numpy|jax.

    The engine is scenario DATA, not a code path: drivers put it in the
    Saath-side Scenario and the repro.api dispatcher routes it.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="FB-scale fabric (526 coflows x 150 ports)")
    ap.add_argument("--engine", choices=("numpy", "jax"), default="numpy",
                    help="replay engine for the Saath side")
    args = ap.parse_args(argv)
    enable_compile_cache()
    return Bench(quick=not args.full), args.engine


def emit(name: str, rows):
    """CSV rows: list of dicts with consistent keys."""
    if not rows:
        print(f"{name},EMPTY")
        return
    keys = list(rows[0])
    print(f"# {name}")
    print(",".join(["bench"] + keys))
    for r in rows:
        print(",".join([name] + [_fmt(r[k]) for k in keys]))


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def pctl(x, q):
    return float(np.nanpercentile(np.asarray(x, float), q))

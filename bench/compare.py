"""What decides `correct`: the served completions against the reference.

After the window has closed, every tenant is replayed by the float64
numpy reference (`bench.reference`) on exactly the coflows the run
submitted, up to the window's end plus a margin. The coflows whose
completion the front door handed out during the window are compared
with the reference:

- `cct_median_rel_err`: the median over matched coflows of the
  per-coflow relative CCT gap. Per-coflow CCTs of an f32 coordinator
  and the f64 reference fork chaotically under contention (a schedule
  decision that ties to the last ulp cascades to the coflows that
  contend with it), so the median, and not the widest gap, is compared.
- `unmatched_share`: coflows that one side finished well inside the
  window and the other did not finish near it, over all compared.

The relative gap of the mean CCT (`avg_cct_rel_err`) is printed and
not compared: one fork on a large coflow moves it by percents (PERF.md,
section 6).

The run is correct when both compared numbers are at or under the
configuration's `limits` and the window handed out at least
`min_window_coflows` completions.
"""
from __future__ import annotations

import multiprocessing
import os

import numpy as np

from bench import reference

# the reference's processes for a fleet: a 1024-tenant window replays
# within the comparison's two minutes
MAX_WORKERS = 8


# the numbers held to a limit of the configuration's `limits`
COMPARED = ("cct_median_rel_err", "unmatched_share")


def replay(config: dict, coflows: list, until: float,
           work_conservation=None) -> np.ndarray:
    return reference.run(coflows, config["params"], config["num_ports"],
                         until=until, work_conservation=work_conservation,
                         topology=config.get("topology"))


def readings(config: dict, submitted: dict, served_window: dict,
             served_any: set, t0: float, t1: float,
             cct_ref: dict) -> dict:
    """The compared numbers. `submitted[i]` lists tenant i's submitted
    coflow specs, `served_window` maps (i, cid) -> CCT handed out in the
    window [t0, t1], `served_any` holds every (i, cid) handed out at
    all, `cct_ref[i]` the reference CCTs (NaN: not finished)."""
    m = float(config["limits"]["margin_s"])
    rel, got_sum, ref_sum = [], 0.0, 0.0
    unmatched = matched = 0
    for i, specs in submitted.items():
        ref = cct_ref[i]
        for s in specs:
            r = ref[s.cid]
            end_ref = s.arrival + r if np.isfinite(r) else np.inf
            got = served_window.get((i, s.cid))
            if got is not None:
                if np.isfinite(r) and end_ref >= t0 - m:
                    matched += 1
                    rel.append(abs(got - r) / max(abs(r), 1e-12))
                    got_sum += got
                    ref_sum += r
                else:
                    unmatched += 1
            elif t0 + m <= end_ref <= t1 - m and (i, s.cid) not in served_any:
                unmatched += 1
    n = matched + unmatched
    return {
        "window_coflows": matched,
        "cct_median_rel_err": float(np.median(rel)) if rel else np.inf,
        "avg_cct_rel_err": abs(got_sum - ref_sum) / ref_sum
        if ref_sum > 0 else np.inf,
        "unmatched_share": unmatched / n if n else 1.0,
    }


def checks(config: dict, vals: dict) -> dict:
    """{name: {"value", "limit"}} for every compared number."""
    lim = config["limits"]
    out = {k: {"value": vals[k], "limit": lim[k]} for k in COMPARED}
    out["window_coflows"] = {"value": vals["window_coflows"],
                             "limit": lim["min_window_coflows"]}
    return out


def passed(chk: dict) -> bool:
    ok = all(v["value"] <= v["limit"] for k, v in chk.items()
             if k != "window_coflows")
    return ok and chk["window_coflows"]["value"] >= \
        chk["window_coflows"]["limit"]


def reference_ccts(config: dict, submitted: dict, until: float,
                   work_conservation=None) -> dict:
    """{tenant: reference CCTs}. A fleet's tenants are replayed in a
    pool of at most `MAX_WORKERS` processes (started fresh, numpy only:
    none touches the chip), one tenant in the calling process."""
    workers = min(MAX_WORKERS, os.cpu_count() or 1, len(submitted))
    if workers <= 1:
        return {i: replay(config, specs, until, work_conservation)
                for i, specs in submitted.items()}
    jobs = [(config, specs, until, work_conservation)
            for specs in submitted.values()]
    with multiprocessing.get_context("spawn").Pool(workers) as pool:
        ccts = pool.starmap(replay, jobs, chunksize=1)
    pool.join()         # the pool's exit ended its processes: reap them
    return dict(zip(submitted, ccts))

"""Dispatch auditor: jaxpr-level gate on the hot entrypoints.

Traces each hot entrypoint (the session while_loop block, the planning
tick, the offline chunk scan, the slab scatter/gather) on a tiny
canonical slab and checks:

* HARD invariants (always enforced, even on `--update`): zero host
  callback primitives and zero float64 sites anywhere in the traced
  extent — a `pure_callback`/`debug_callback` or an f64
  `convert_element_type` in the hot loop means a host round-trip or a
  dtype drift shipped;
* DRIFT against the committed golden ``analysis/dispatch_manifest.json``:
  input avals (the jit cache signature — changes here are exactly the
  changes that trigger fresh compiles for existing callers) are
  compared always; primitive counts are compared exactly only when the
  manifest was generated under the SAME jax version (across versions
  they are reported as warnings — lowering details move between
  releases).

Usage::

    python -m repro.analysis.audit            # gate (CI)
    python -m repro.analysis.audit --update   # refresh the manifest

`make audit` / `make audit-update` wrap these. Keep manifest diffs in
review: a new primitive in `session_advance` is a reviewable artifact,
not a silent recompile trigger.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.jaxpr_scan import (aval_signature, callback_primitives,
                                       f64_sites, primitive_counts)

__all__ = ["ENTRYPOINTS", "build_manifest", "check_manifest",
           "default_manifest_path", "main"]

# canonical slab: tiny on purpose — the auditor only traces (no
# compile, no execution), so shapes just need to exercise the real
# code paths (B>1 rows, padding present)
B, F, C, P = 2, 8, 4, 4
CHUNK = 4
FEATURES = (True, True, False, False)
# leaf-spine canonical slab: P ports over Lf leaves (2 hosts per leaf)
LF = 2


def _canonical_slab(leaf_links: int = 0, sampling: bool = False, *,
                    b: int = B, f: int = F, c: int = C, p: int = P):
    """A blank (b rows, f flows, c coflows, p ports) session slab and its
    parameters; tests/test_tpu_compile.py compiles the same entrypoint
    at serving sizes."""
    B, F, C, P = b, f, c, p
    from repro.core import jax_coordinator as jc
    from repro.core.params import SchedulerParams
    from repro.fabric.jax_engine import EngineParams, EngineState
    from repro.traces.batch import empty_batch

    tb = empty_batch(B, flow_capacity=F, coflow_capacity=C,
                     port_capacity=P, leaf_links=leaf_links,
                     sampling=sampling)
    # the sampling slab carries the pilot leaf and a CONCRETE traced
    # clairvoyant scalar (learned row); the default slab compiles both
    # out (empty subtrees — the pre-ISSUE-10 structure, bit for bit)
    ep1 = EngineParams.from_scheduler(
        SchedulerParams(dynamics_requeue=True, clairvoyant=False)
        if sampling else SchedulerParams())
    ep_rows = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * B), ep1)
    coord = jc.CoordState(np.full((B, C), -1, np.int32),
                          np.full((B, C), np.inf, np.float32),
                          np.zeros((B, C), bool))
    state = EngineState(
        coord=coord,
        sent=np.zeros((B, F), np.float32),
        done=np.ones((B, F), bool),
        fct=np.zeros((B, F), np.float32),
        finished=np.ones((B, C), bool),
        cct=np.full((B, C), np.nan, np.float32),
        t0=np.zeros((B,), np.float32),
        tick=np.zeros((B,), np.int32),
        rate=np.zeros((B, F), np.float32),
        pend_sent=np.zeros((B, F), np.float32),
        pend_tick=np.zeros((B,), np.float32),
        pend_next=np.zeros((B,), np.float32))
    return tb, ep1, ep_rows, state


def _entry_session_advance():
    """The while_loop block `session_advance` dispatches (the pool's
    one-dispatch-per-fleet-advance hot path)."""
    from repro.fabric.jax_engine import _run_session_block

    tb, _, ep_rows, state = _canonical_slab()
    ne = np.full((B,), 4.0, np.float32)
    return jax.make_jaxpr(
        lambda s, t, e, n, m: _run_session_block(
            s, t, e, n, m, kernel=None, features=FEATURES))(
        state, tb, ep_rows, ne, np.int32(64))


def _entry_session_plan_tick():
    from repro.fabric.jax_engine import session_plan_tick

    tb, _, ep_rows, state = _canonical_slab()
    mask = np.zeros((B,), bool)
    mask[0] = True
    return jax.make_jaxpr(
        lambda s, t, e, m: session_plan_tick(
            s, t, e, kernel=None, features=(True, False, False, False),
            row_mask=m))(state, tb, ep_rows, mask)


def _entry_simulate_sweep():
    """The offline chunk scan both `simulate_batch` and
    `simulate_sweep` drive (`sweep=False` — the sweep axis only adds a
    vmap in_axes, not structure)."""
    from repro.fabric.jax_engine import _run_chunk

    tb, ep1, _, state = _canonical_slab()
    offline = state._replace(rate=None, pend_sent=None,
                             pend_tick=None, pend_next=None)
    return jax.make_jaxpr(
        lambda s, t, e: _run_chunk(
            s, t, e, chunk=CHUNK, kernel=None, sweep=False,
            features=FEATURES))(offline, tb, ep1)


def _entry_session_advance_leafspine():
    """The same while_loop block on a leaf-spine slab (Lf link leaves
    present, the link admission/WC machinery compiled in) — the
    topology-pinned pool's hot path."""
    from repro.fabric.jax_engine import _run_session_block

    tb, _, ep_rows, state = _canonical_slab(leaf_links=LF)
    ne = np.full((B,), 4.0, np.float32)
    return jax.make_jaxpr(
        lambda s, t, e, n, m: _run_session_block(
            s, t, e, n, m, kernel=None, features=FEATURES))(
        state, tb, ep_rows, ne, np.int32(64))


def _entry_session_advance_sampling():
    """The while_loop block with the non-clairvoyant machinery compiled
    in (pilot leaf + traced clairvoyant switch) — the sampling-pinned
    pool's hot path. The clairvoyant entrypoints above never contain
    these leaves: their manifests staying fixed is the bitwise proof
    that sampling is free when compiled out."""
    from repro.fabric.jax_engine import _run_session_block

    tb, _, ep_rows, state = _canonical_slab(sampling=True)
    ne = np.full((B,), 4.0, np.float32)
    return jax.make_jaxpr(
        lambda s, t, e, n, m: _run_session_block(
            s, t, e, n, m, kernel=None,
            features=FEATURES + (True,)))(
        state, tb, ep_rows, ne, np.int32(64))


def _entry_scatter_rows():
    """The dirty-row upload: one row scattered into the state slab."""
    from repro.fabric.jax_engine import scatter_rows

    _, _, _, state = _canonical_slab()
    idx = np.zeros((1,), np.int32)
    rows = jax.tree_util.tree_map(lambda a: a[:1], state)
    return jax.make_jaxpr(scatter_rows)(state, idx, rows)


def _entry_gather_rows():
    from repro.fabric.jax_engine import gather_rows

    _, _, _, state = _canonical_slab()
    idx = np.zeros((1,), np.int32)
    return jax.make_jaxpr(gather_rows)(state, idx)


ENTRYPOINTS: Dict[str, Callable] = {
    "session_advance": _entry_session_advance,
    "session_advance_leafspine": _entry_session_advance_leafspine,
    "session_advance_sampling": _entry_session_advance_sampling,
    "session_plan_tick": _entry_session_plan_tick,
    "simulate_sweep": _entry_simulate_sweep,
    "scatter_rows": _entry_scatter_rows,
    "gather_rows": _entry_gather_rows,
}


def default_manifest_path() -> Path:
    """`analysis/dispatch_manifest.json` at the repo root (resolved
    relative to the live package so it works from any cwd)."""
    import repro
    src_root = Path(list(repro.__path__)[0]).resolve().parent
    return src_root.parent / "analysis" / "dispatch_manifest.json"


def build_manifest(entrypoints: Optional[Dict[str, Callable]] = None
                   ) -> dict:
    entrypoints = ENTRYPOINTS if entrypoints is None else entrypoints
    entries = {}
    for name, build in sorted(entrypoints.items()):
        jaxpr = build()
        entries[name] = {
            "in_avals": aval_signature(jaxpr.in_avals),
            "primitives": dict(sorted(primitive_counts(jaxpr).items())),
            "callbacks": callback_primitives(jaxpr),
            "f64_sites": f64_sites(jaxpr),
        }
    return {"jax_version": jax.__version__, "entrypoints": entries}


def check_manifest(manifest: dict,
                   entrypoints: Optional[Dict[str, Callable]] = None
                   ) -> List[str]:
    """Gate the CURRENT entrypoints against a committed manifest.
    Returns hard failures; version-mismatched primitive drift is
    reported to stderr as a warning instead."""
    fresh = build_manifest(entrypoints)
    problems: List[str] = []
    same_jax = manifest.get("jax_version") == fresh["jax_version"]
    old_entries = manifest.get("entrypoints", {})
    for name, cur in fresh["entrypoints"].items():
        # hard invariants on the LIVE code, independent of the manifest
        if cur["callbacks"]:
            problems.append(
                f"{name}: host callback primitive(s) in the hot loop: "
                f"{cur['callbacks']}")
        if cur["f64_sites"]:
            problems.append(
                f"{name}: float64 site(s) in the hot loop: "
                f"{cur['f64_sites']}")
        old = old_entries.get(name)
        if old is None:
            problems.append(
                f"{name}: not in the manifest — run `make audit-update` "
                f"and review the diff")
            continue
        if old["in_avals"] != cur["in_avals"]:
            problems.append(
                f"{name}: input signature drift (recompile trigger for "
                f"existing callers)\n"
                + "\n".join(_aval_diff(old["in_avals"],
                                       cur["in_avals"])))
        if old["primitives"] != cur["primitives"]:
            diff = _prim_diff(old["primitives"], cur["primitives"])
            if same_jax:
                problems.append(
                    f"{name}: primitive-count drift\n{diff}")
            else:
                print(f"audit: {name}: primitive counts differ from "
                      f"manifest but jax version changed "
                      f"({manifest.get('jax_version')} -> "
                      f"{fresh['jax_version']}): {diff}",
                      file=sys.stderr)
    for name in old_entries:
        if name not in fresh["entrypoints"]:
            problems.append(
                f"{name}: in the manifest but no longer audited — run "
                f"`make audit-update`")
    return problems


def _prim_diff(old: dict, new: dict) -> str:
    """Per-entrypoint primitive delta, grouped into added / removed /
    count-changed so a reviewer sees WHAT entered the hot loop, not a
    raw manifest dump."""
    added, removed, changed = [], [], []
    for k in sorted(set(old) | set(new)):
        a, b = old.get(k, 0), new.get(k, 0)
        if a == b:
            continue
        if a == 0:
            added.append(f"{k} x{b}")
        elif b == 0:
            removed.append(f"{k} (was x{a})")
        else:
            changed.append(f"{k}: {a} -> {b}")
    out = []
    if added:
        out.append(f"  added:   {', '.join(added)}")
    if removed:
        out.append(f"  removed: {', '.join(removed)}")
    if changed:
        out.append(f"  changed: {', '.join(changed)}")
    return "\n".join(out)


def _aval_diff(old: list, new: list) -> List[str]:
    """Positional input-signature delta: only the argument slots that
    actually drifted, `<absent>` marking arity changes."""
    out = []
    for i in range(max(len(old), len(new))):
        a = old[i] if i < len(old) else "<absent>"
        b = new[i] if i < len(new) else "<absent>"
        if a != b:
            out.append(f"  [{i}] {a} -> {b}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.audit",
        description="jaxpr-level dispatch audit of the hot entrypoints")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the golden manifest (hard invariants "
                         "still enforced)")
    ap.add_argument("--manifest", type=Path,
                    default=None, help="manifest path override")
    args = ap.parse_args(argv)
    path = args.manifest or default_manifest_path()
    if args.update:
        manifest = build_manifest()
        hard = [p for name, cur in manifest["entrypoints"].items()
                for p in
                ([f"{name}: callbacks {cur['callbacks']}"]
                 if cur["callbacks"] else []) +
                ([f"{name}: f64 {cur['f64_sites']}"]
                 if cur["f64_sites"] else [])]
        if hard:
            for p in hard:
                print(f"audit: REFUSING to bless: {p}", file=sys.stderr)
            return 1
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(manifest, indent=2, sort_keys=True)
                        + "\n")
        print(f"audit: wrote {path}", file=sys.stderr)
        return 0
    if not path.exists():
        print(f"audit: no manifest at {path} — run `make audit-update` "
              f"and commit it", file=sys.stderr)
        return 1
    manifest = json.loads(path.read_text())
    problems = check_manifest(manifest)
    for p in problems:
        print(f"audit: {p}")
    if problems:
        print(f"audit: {len(problems)} problem(s) — review the diff "
              f"above, then bless intended drift with "
              f"`python -m repro.analysis.audit --update` "
              f"(make audit-update)", file=sys.stderr)
        return 1
    print(f"audit: {len(manifest['entrypoints'])} entrypoints clean "
          f"(jax {jax.__version__})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

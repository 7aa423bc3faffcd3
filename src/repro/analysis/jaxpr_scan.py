"""Recursive jaxpr traversal for the dispatch auditor.

A jitted entrypoint lowers to a closed jaxpr whose equations may hold
sub-jaxprs (jit bodies, while/scan/cond branches, custom_jvp calls …)
inside `eqn.params`. The helpers here flatten that tree so the auditor
can ask global questions about an entrypoint's whole traced extent:

* `primitive_counts(jaxpr)` — histogram of primitive names, the drift
  signal recorded in ``analysis/dispatch_manifest.json``;
* `callback_primitives(jaxpr)` — occurrences of host-callback
  primitives (`pure_callback`, `debug_callback`, …): a non-empty list
  means the "hot loop never leaves the device" contract is broken;
* `f64_sites(jaxpr)` — equations producing float64 values, including
  `convert_element_type` casts: any hit means weak-type promotion is
  dragging the f32 slab to f64 (the drift class PR 4's epoch rebasing
  exists to avoid).
"""
from __future__ import annotations

from collections import Counter
from typing import Iterator, List

import jax.extend.core as jax_core

# Host-callback primitive names across jax versions. Matched by name so
# the set survives primitive-object churn between releases.
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "debug_callback", "callback", "io_callback",
    "host_callback_call", "outside_call",
})


def iter_eqns(jaxpr) -> Iterator:
    """Yield every equation in `jaxpr` and, recursively, in any
    sub-jaxpr reachable through equation params (jit/scan/while/cond
    bodies, closed and open alike)."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr -> Jaxpr
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _sub_jaxprs(eqn) -> List:
    subs = []
    for val in eqn.params.values():
        subs.extend(_jaxprs_in(val))
    return subs


def _jaxprs_in(val) -> List:
    if isinstance(val, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
        return [val]
    if isinstance(val, (tuple, list)):
        out = []
        for item in val:
            out.extend(_jaxprs_in(item))
        return out
    return []


def primitive_counts(jaxpr) -> Counter:
    """Histogram of primitive names over the whole (recursive) jaxpr."""
    return Counter(eqn.primitive.name for eqn in iter_eqns(jaxpr))


def callback_primitives(jaxpr) -> List[str]:
    """Names of host-callback equations anywhere in the jaxpr."""
    return [eqn.primitive.name for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name in CALLBACK_PRIMITIVES]


def _is_f64(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and str(dtype) == "float64"


def f64_sites(jaxpr) -> List[str]:
    """Human-readable descriptions of equations that PRODUCE float64:
    explicit f64 `convert_element_type` casts and any other primitive
    with an f64 output aval. Input avals are not reported on their own
    — flagging every consumer of one bad producer would bury the root
    site in noise."""
    sites = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "convert_element_type":
            new = eqn.params.get("new_dtype")
            if new is not None and str(new) == "float64":
                sites.append(f"{name} -> float64")
                continue
        if any(_is_f64(var.aval) for var in eqn.outvars):
            sites.append(f"{name} (f64 output)")
    return sites


def aval_signature(avals) -> List[str]:
    """Stable string form of a list of abstract values — the jit cache
    signature recorded in the manifest (shape/dtype changes here are
    exactly the changes that trigger fresh compiles)."""
    out = []
    for aval in avals:
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None and dtype is None:
            out.append(repr(aval))
        else:
            out.append(f"{dtype}{list(shape) if shape is not None else ''}")
    return out

"""Trace-safety static analysis + dispatch auditing (DESIGN.md §9–§10).

The serving plane's performance story rests on invariants nothing used
to check: one dispatch per fleet advance, zero clean-row uploads, no
host round-trips inside traced scope, no f64 drift into the f32 slab.
This package makes those contracts machine-checked:

* ``repro.analysis.lint``  — stdlib-`ast` lint: JAX trace-safety rules
  (host calls / Python casts / Python branches inside traced scope,
  implicit-dtype conversions), repo-contract rules (TraceBatch /
  EngineState leaf coverage in the pack/scatter machinery, no
  module-level Simulator imports in `repro.api`, unaccounted host
  pulls in the pool), and hygiene rules (unused imports / variables).
  ``python -m repro.analysis.lint src tests``; suppressions are
  ``# saath: lint-ok(rule): reason`` comments.
* ``repro.analysis.audit`` — traces the hot entrypoints to jaxprs,
  asserts zero host callbacks and zero f64 casts in the hot loop, and
  diffs jit signatures + primitive counts against the committed golden
  ``analysis/dispatch_manifest.json`` (``make audit`` /
  ``make audit-update``).
* ``repro.analysis.sanitize`` — runtime sanitizers:
  `assert_no_recompiles` / `assert_no_transfers` context managers
  (jit-cache-miss counting, transfer-guard enforcement with
  `accounted_transfer` carve-outs for the pool's io-counted paths).
* ``repro.analysis.coherence`` — the slab coherence checker: the
  async serving plane's cache protocol (dirty flags, deferred ctl
  handle, host mirrors, the folded parameter copy, the io ledger) as a
  machine-readable declaration, typestate-checked per method against
  the committed golden ``analysis/coherence_manifest.json``
  (``make coherence`` / ``make coherence-update``); ``--selftest``
  re-checks six seeded single-line coherence bugs.
* ``repro.analysis.explore`` — the interleaving race detector:
  deterministic random schedules over the full pool API, replayed on
  async sharded pools against the blocking 1-shard oracle with
  bitwise comparison at sync points; divergences print a reproducer.
"""
from repro.analysis.sanitize import (RecompileError, accounted_transfer,
                                     assert_no_recompiles,
                                     assert_no_transfers)

__all__ = ["assert_no_recompiles", "assert_no_transfers",
           "accounted_transfer", "RecompileError"]

"""Dispatch auditor: golden-manifest round-trip, drift detection, and
the hard gates — a callback or f64 site injected into the real
`session_advance` hot path must fail the audit."""
import json

import jax
import jax.numpy as jnp
import numpy as np

import repro.analysis.audit as au


# ---- toy entrypoints (cheap; exercise the manifest machinery) ------------

def _toy_entry():
    return jax.make_jaxpr(lambda x: x * 2.0 + 1.0)(
        np.ones((3,), np.float32))


def _toy_entry_drifted():
    return jax.make_jaxpr(lambda x: jnp.sin(x * 2.0 + 1.0))(
        np.ones((3,), np.float32))


def _toy_entry_reshaped():
    return jax.make_jaxpr(lambda x: x * 2.0 + 1.0)(
        np.ones((4,), np.float32))


def _toy_entry_callback():
    def f(x):
        jax.debug.callback(lambda *_: None, x)
        return x * 2.0
    return jax.make_jaxpr(f)(np.ones((3,), np.float32))


def test_manifest_round_trip_is_clean():
    reg = {"toy": _toy_entry}
    manifest = au.build_manifest(reg)
    assert manifest["jax_version"] == jax.__version__
    assert manifest["entrypoints"]["toy"]["callbacks"] == []
    assert manifest["entrypoints"]["toy"]["f64_sites"] == []
    assert au.check_manifest(manifest, reg) == []


def test_primitive_drift_is_flagged_under_same_jax_version():
    manifest = au.build_manifest({"toy": _toy_entry})
    problems = au.check_manifest(manifest, {"toy": _toy_entry_drifted})
    assert any("primitive-count drift" in p and "sin" in p
               for p in problems), problems


def test_primitive_drift_diff_is_grouped_by_direction():
    # toy -> drifted adds `sin`; drifted -> toy removes it. The diff
    # must say WHICH, not dump both manifests.
    manifest = au.build_manifest({"toy": _toy_entry})
    problems = au.check_manifest(manifest, {"toy": _toy_entry_drifted})
    drift = next(p for p in problems if "primitive-count drift" in p)
    assert "added:" in drift and "sin x1" in drift
    assert "removed:" not in drift
    back = au.check_manifest(au.build_manifest(
        {"toy": _toy_entry_drifted}), {"toy": _toy_entry})
    drift = next(p for p in back if "primitive-count drift" in p)
    assert "removed:" in drift and "sin" in drift


def test_aval_signature_drift_is_flagged():
    manifest = au.build_manifest({"toy": _toy_entry})
    problems = au.check_manifest(manifest, {"toy": _toy_entry_reshaped})
    assert any("input signature drift" in p for p in problems), problems


def test_aval_drift_diff_is_positional():
    manifest = au.build_manifest({"toy": _toy_entry})
    problems = au.check_manifest(manifest, {"toy": _toy_entry_reshaped})
    drift = next(p for p in problems if "input signature drift" in p)
    # only the drifted slot, by position, old -> new
    assert "[0]" in drift and "->" in drift
    assert "float32[3]" in drift and "float32[4]" in drift


def test_aval_diff_marks_arity_changes():
    assert au._aval_diff(["f32[3]"], ["f32[3]", "i32[]"]) == \
        ["  [1] <absent> -> i32[]"]
    assert au._aval_diff(["f32[3]", "i32[]"], ["f32[3]"]) == \
        ["  [1] i32[] -> <absent>"]


def test_gate_failure_prints_the_update_hint(tmp_path, monkeypatch,
                                             capsys):
    monkeypatch.setattr(au, "ENTRYPOINTS", {"toy": _toy_entry})
    path = tmp_path / "manifest.json"
    assert au.main(["--update", "--manifest", str(path)]) == 0
    monkeypatch.setattr(au, "ENTRYPOINTS", {"toy": _toy_entry_drifted})
    capsys.readouterr()
    assert au.main(["--manifest", str(path)]) == 1
    captured = capsys.readouterr()
    assert "audit-update" in captured.err        # the one-line hint
    assert "added:" in captured.out              # the structured diff


def test_missing_and_stale_entries_are_flagged():
    manifest = au.build_manifest({"toy": _toy_entry})
    problems = au.check_manifest(
        manifest, {"other": _toy_entry})
    assert any(p.startswith("other: not in the manifest")
               for p in problems), problems
    assert any("toy" in p and "no longer audited" in p
               for p in problems), problems


def test_update_refuses_to_bless_callbacks(tmp_path, monkeypatch):
    """`--update` must never launder a hard-invariant violation into
    the golden manifest."""
    monkeypatch.setattr(au, "ENTRYPOINTS",
                        {"toy": _toy_entry_callback})
    path = tmp_path / "manifest.json"
    assert au.main(["--update", "--manifest", str(path)]) == 1
    assert not path.exists()


def test_cli_round_trip_update_then_gate(tmp_path, monkeypatch):
    monkeypatch.setattr(au, "ENTRYPOINTS", {"toy": _toy_entry})
    path = tmp_path / "manifest.json"
    assert au.main(["--manifest", str(path)]) == 1   # no manifest yet
    assert au.main(["--update", "--manifest", str(path)]) == 0
    written = json.loads(path.read_text())
    assert "toy" in written["entrypoints"]
    assert au.main(["--manifest", str(path)]) == 0


# ---- the real hot path ---------------------------------------------------

def test_committed_manifest_matches_live_entrypoints():
    """The golden manifest in analysis/ must stay in sync with the real
    hot entrypoints — this is `make audit` run as a test."""
    path = au.default_manifest_path()
    assert path.exists(), (
        f"no committed manifest at {path}; run `make audit-update`")
    manifest = json.loads(path.read_text())
    problems = au.check_manifest(manifest)
    assert problems == [], "\n".join(problems)


def _session_advance_inputs():
    tb, _, ep_rows, state = au._canonical_slab()
    ne = np.full((au.B,), 4.0, np.float32)
    return state, tb, ep_rows, ne, np.int32(64)


def test_callback_injected_into_session_advance_fails_gate():
    """If a host callback sneaks into the session block (e.g. a debug
    print left in the while_loop body), the audit must fail."""
    from repro.fabric.jax_engine import _run_session_block

    def poisoned():
        def noisy(s, t, e, n, m):
            out = _run_session_block(s, t, e, n, m, kernel=None,
                                     features=au.FEATURES)
            jax.debug.callback(lambda *_: None,
                               jax.tree_util.tree_leaves(out)[0])
            return out
        return jax.make_jaxpr(noisy)(*_session_advance_inputs())

    manifest = json.loads(au.default_manifest_path().read_text())
    problems = au.check_manifest(
        manifest, {"session_advance": poisoned})
    assert any("session_advance" in p and "callback" in p
               for p in problems), problems


def test_f64_cast_injected_into_session_advance_fails_gate():
    """An f64 convert in the hot loop (dtype drift) must fail the
    audit.  Tracing runs under enable_x64 because with x64 disabled the
    cast is silently dropped from the jaxpr — the exact failure mode
    the gate exists to catch before it ships to an x64-enabled host."""
    from repro.fabric.jax_engine import _run_session_block

    def poisoned():
        def drifted(s, t, e, n, m):
            out = _run_session_block(s, t, e, n, m, kernel=None,
                                     features=au.FEATURES)
            leaf = jax.tree_util.tree_leaves(out)[0]
            bad = jax.lax.convert_element_type(leaf, jnp.float64)
            return out, bad
        with jax.enable_x64(True):
            return jax.make_jaxpr(drifted)(*_session_advance_inputs())

    manifest = json.loads(au.default_manifest_path().read_text())
    problems = au.check_manifest(
        manifest, {"session_advance": poisoned})
    assert any("session_advance" in p and "float64" in p
               for p in problems), problems

"""`correct` comes out false when the timed path is broken underneath,
and for the control; true for the program as it is. Every run but the
look for a chip, on the CPU, at a size a test run holds: the cell's
own configuration with fewer ports, a short window, and its own
limits."""
import dataclasses
import json
import time
from pathlib import Path


BENCH = Path(__file__).resolve().parents[1]
SEED = 2**31 + 77
ROUNDS = 250            # 2 s of virtual time


class _Cell:
    def __init__(self, config, traffic, **over):
        self.name = "test"
        self.config = dict(json.loads(
            (BENCH / "configs" / f"{config}.json").read_text()), **over)
        self.traffic = json.loads(
            (BENCH / "traffic" / f"{traffic}.json").read_text())
        self.chips = 1
        self.end_to_end, self.per_layer = [], []


def _cell():
    """fb150.steady cut to what a test run holds; fewer completions fall
    in a short window, hence the lower count."""
    cell = _Cell("fb150", "steady", num_ports=32,
                 fast_forward_s=[1.0, 1.0], warm_rounds=20)
    cell.config["limits"] = dict(cell.config["limits"],
                                 min_window_coflows=5)
    return cell


def _run(cell, keep=None):
    import jax

    from bench import harness

    return harness.run_cell(cell, SEED, 0.0, False, time.perf_counter(),
                            harness.CompileCounter(), jax.devices()[:1],
                            keep=keep, rounds=ROUNDS)


def test_sound_run_is_correct_and_control_is_not():
    from bench import calibrate, compare

    cell = _cell()
    keep = {}
    out = _run(cell, keep)
    assert out["correct"], out["checks"]
    ctl = compare.checks(cell.config,
                         calibrate.control_readings(cell.config, keep))
    assert not compare.passed(ctl), ctl


def test_state_left_unchanged_is_caught(monkeypatch):
    from repro.api.pool import SessionPool

    monkeypatch.setattr(SessionPool, "_advance", lambda self, targets: None)
    assert not _run(_cell())["correct"]


def test_half_the_batch_left_out_is_caught(monkeypatch):
    from repro.api.session import SaathSession

    sub = SaathSession.submit      # half of each submitted batch dropped
    monkeypatch.setattr(SaathSession, "submit",
                        lambda self, cfs: sub(self, list(cfs)[::2]))
    assert not _run(_cell())["correct"]


def test_answer_altered_where_produced_is_caught(monkeypatch):
    from repro.api.session import SaathSession

    poll = SaathSession.poll

    def late(self):     # every completion reported one tick late
        return [dataclasses.replace(d, cct=d.cct + self.params.delta)
                for d in poll(self)]

    monkeypatch.setattr(SaathSession, "poll", late)
    assert not _run(_cell())["correct"]

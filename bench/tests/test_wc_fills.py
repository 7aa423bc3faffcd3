"""The `tick.wc_fills` reader: fills per traced round from the pool's
`wc_fills` counter, and nothing from a program without the counter."""
import types

import pytest

from bench import spec


def _ctx(io0, io1, rounds=10):
    return types.SimpleNamespace(rounds=rounds, io0=io0, io1=io1)


def test_fills_per_round():
    read = spec.layer_reader("tick.wc_fills")
    ctx = _ctx({"wc_trips": 100, "wc_fills": 7},
               {"wc_trips": 53_100, "wc_fills": 817})
    assert read(ctx) == pytest.approx(81.0)


def test_a_program_without_the_counter_reads_nothing():
    read = spec.layer_reader("tick.wc_fills")
    assert read(_ctx({"wc_trips": 100}, {"wc_trips": 980})) is None
    assert read(_ctx({"wc_fills": 0}, {"wc_fills": 5}, rounds=0)) is None

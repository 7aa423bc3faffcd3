"""The traffic generator: deterministic by seed, one population
permuted by the seed, and the declared load."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import gen

BENCH = Path(__file__).resolve().parents[1]


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _traffic(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _take(stream, t):
    return [(c.cid, c.arrival, c.src.tolist(), c.dst.tolist(),
             c.size.tolist()) for c in stream.until(t)]


def test_same_seed_same_stream_however_read():
    cfg, tr = _cfg("fb150"), _traffic("steady")
    a = gen.streams(dict(cfg, tenants=3), tr, 2**31 + 11)
    b = gen.streams(dict(cfg, tenants=3), tr, 2**31 + 11)
    whole = [_take(s, 20.0) for s in a]
    parts = [_take(s, 5.0) + _take(s, 12.0) + _take(s, 20.0) for s in b]
    assert whole == parts
    assert all(len(w) > 10 for w in whole)


def test_seeds_permute_one_population():
    """Another seed offers the same coflows per block, in another order."""
    cfg, tr = _cfg("fb150"), _traffic("steady")
    n = gen.BLOCK * 3
    got = []
    for seed in (1, 2):
        s = gen.streams(cfg, tr, seed)[0]
        cfs = []
        while len(cfs) < n:
            cfs += s.until(s._t + 1.0)
        got.append(cfs[:n])
    a, b = got
    assert [c.arrival for c in a] != [c.arrival for c in b]
    for k in range(3):
        blk = slice(k * gen.BLOCK, (k + 1) * gen.BLOCK)
        assert sorted(c.total_bytes for c in a[blk]) == \
            pytest.approx(sorted(c.total_bytes for c in b[blk]))
        span_a = a[blk][-1].arrival
        span_b = b[blk][-1].arrival
        assert span_a == pytest.approx(span_b)


@pytest.mark.parametrize("max_width,tenant", [(2000, 0), (64, 0),
                                              (2000, 1)])
def test_declared_load_over_the_rate_sample(max_width, tenant):
    """Offered bytes over the span of the first `RATE_COFLOWS` coflows
    are `load` of the fabric's capacity, as for one synthesized trace
    (the gaps are exponential, so within a few percent); the width cap
    splits coflows into fewer flows, not fewer bytes; every tenant
    offers the mix's load."""
    cfg, tr = _cfg("fb150"), _traffic("steady")
    cfg = dict(cfg, coflows=dict(cfg["coflows"], max_width=max_width))
    s = gen.streams(dict(cfg, tenants=2), tr, 5)[tenant]
    cfs = []
    while len(cfs) < gen.RATE_COFLOWS:
        cfs += s.until(s._t + 1.0)
    cfs = cfs[:gen.RATE_COFLOWS]
    span = cfs[-1].arrival
    load = sum(c.total_bytes for c in cfs) / (
        span * cfg["num_ports"] * cfg["params"]["port_bw"])
    assert load == pytest.approx(tr["load"], rel=0.1)


def test_flows_keep_the_coflow_total_and_the_width_cap():
    cfg, tr = _cfg("fb150"), _traffic("steady")
    P = cfg["num_ports"]
    s = gen.streams(cfg, tr, 9)[0]
    cfs = s.until(10.0)
    assert max(len(c.size) for c in cfs) <= cfg["coflows"]["max_width"]
    assert min(c.size.min() for c in cfs) > 0
    for c in cfs:
        assert ((c.src >= 0) & (c.src < P) & (c.dst >= 0)
                & (c.dst < P)).all()
    single = np.mean([len(c.size) == 1 for c in cfs])
    assert 0.1 < single < 0.4


def test_every_seed_offers_each_tenant_the_mix_load():
    """Each tenant's stream runs at the mix's load, at every seed: the
    offered bytes over its rate sample are that load of the fabric."""
    cfg = dict(_cfg("fb150"), tenants=4, num_ports=24)
    rates = None
    for seed in (3, 2**31 + 99):
        ss = gen.streams(cfg, _traffic("steady"), seed)
        assert rates is None or [s.rate for s in ss] == rates
        rates = [s.rate for s in ss]
    for s in ss:
        cfs = []
        while len(cfs) < gen.RATE_COFLOWS:
            cfs += s.until(s._t + 1.0)
        cfs = cfs[:gen.RATE_COFLOWS]
        got = sum(c.total_bytes for c in cfs) / (
            cfs[-1].arrival * 24 * cfg["params"]["port_bw"])
        assert got == pytest.approx(0.9, rel=0.1)

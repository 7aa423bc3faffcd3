"""The benchmark's reference is a faithful copy of the program's numpy
plane: on the same coflows both give the same CCTs."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import gen, reference

BENCH = Path(__file__).resolve().parents[1]


def _program(cfg, specs, until, topology=None):
    from repro.core.coflow import Coflow, Flow, Trace
    from repro.core.params import SchedulerParams
    from repro.core.policies.saath import Saath
    from repro.fabric.engine import Simulator
    from repro.fabric.state import FlowTable
    from repro.fabric.topology import LeafSpine

    P = cfg["num_ports"]
    tr = Trace(P, [Coflow(s.cid, s.arrival,
                          [Flow(j, int(a), int(b), float(z)) for j, (a, b, z)
                           in enumerate(zip(s.src, s.dst, s.size))])
                   for s in specs])
    p = SchedulerParams(**cfg["params"])
    t = FlowTable.from_trace(tr, p.port_bw)
    fabric = None if topology is None else LeafSpine(
        hosts_per_leaf=topology["hosts_per_leaf"],
        oversub=topology["oversub"], wc_fill=topology["wc_fill"])
    Simulator(p, topology=fabric).run(t, Saath(p))
    return t.cct


def _leaf_spine(oversub, wc_fill):
    return {"kind": "leaf_spine", "hosts_per_leaf": 4, "oversub": oversub,
            "wc_fill": wc_fill}


@pytest.mark.parametrize("ports,max_width", [(32, 64), (24, 2000)])
def test_reference_matches_the_program_numpy_plane(ports, max_width):
    cfg = json.loads((BENCH / "configs" / "fb150.json").read_text())
    cfg = dict(cfg, num_ports=ports,
               coflows=dict(cfg["coflows"], max_width=max_width))
    traffic = json.loads((BENCH / "traffic" / "steady.json").read_text())
    specs = gen.streams(cfg, traffic, 4)[0].until(3.0)
    assert len(specs) > 20
    got = reference.run(specs, cfg["params"], ports, until=np.inf)
    want = _program(cfg, specs, np.inf)
    assert np.isfinite(want).all()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("ports,max_width", [(32, 64), (24, 2000)])
@pytest.mark.parametrize("wc_fill", ["greedy", "maxmin"])
@pytest.mark.parametrize("oversub", [1.0, 2.0, 4.0])
def test_leaf_spine_reference_matches_the_program_numpy_plane(
        oversub, wc_fill, ports, max_width):
    """Leaf links in admission and in the greedy walk, and the max-min
    fill, as the program's numpy plane has them under `LeafSpine`."""
    cfg = json.loads((BENCH / "configs" / "fb150.json").read_text())
    cfg = dict(cfg, num_ports=ports,
               coflows=dict(cfg["coflows"], max_width=max_width))
    traffic = json.loads((BENCH / "traffic" / "steady.json").read_text())
    specs = gen.streams(cfg, traffic, 4)[0].until(3.0)
    topo = _leaf_spine(oversub, wc_fill)
    got = reference.run(specs, cfg["params"], ports, until=np.inf,
                        topology=topo)
    want = _program(cfg, specs, np.inf, topo)
    assert np.isfinite(want).all()
    np.testing.assert_array_equal(got, want)
    if oversub > 1.0:   # the links bind
        big = reference.run(specs, cfg["params"], ports, until=np.inf)
        assert not np.array_equal(got, big)


@pytest.mark.parametrize("ports", [24, 32])
def test_full_bisection_leaf_spine_is_the_big_switch(ports):
    """At oversub 1 a leaf's uplink holds at least what its ports have
    left, so the links never bind: the greedy walk's CCTs are the big
    switch's, bit for bit."""
    cfg = json.loads((BENCH / "configs" / "fb150.json").read_text())
    cfg = dict(cfg, num_ports=ports)
    traffic = json.loads((BENCH / "traffic" / "steady.json").read_text())
    specs = gen.streams(cfg, traffic, 4)[0].until(3.0)
    big = reference.run(specs, cfg["params"], ports, until=np.inf)
    ls = reference.run(specs, cfg["params"], ports, until=np.inf,
                       topology=_leaf_spine(1.0, "greedy"))
    assert np.isfinite(big).all()
    np.testing.assert_array_equal(ls, big)


def test_until_stops_at_the_horizon():
    cfg = json.loads((BENCH / "configs" / "fb150.json").read_text())
    cfg = dict(cfg, num_ports=24)
    traffic = json.loads((BENCH / "traffic" / "steady.json").read_text())
    specs = gen.streams(cfg, traffic, 4)[0].until(3.0)
    full = reference.run(specs, cfg["params"], 24, until=np.inf)
    part = reference.run(specs, cfg["params"], 24, until=2.0)
    ends = np.array([s.arrival for s in specs]) + full
    done = np.isfinite(part)
    assert done.any() and (~done).any()
    np.testing.assert_array_equal(part[done], full[done])
    assert (ends[~done] > 2.0 - 1e-9).all()

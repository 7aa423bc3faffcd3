"""The benchmark's reference is a faithful copy of the program's numpy
plane: on the same coflows both give the same CCTs."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import gen, reference

BENCH = Path(__file__).resolve().parents[1]


def _program(cfg, specs, until):
    from repro.core.coflow import Coflow, Flow, Trace
    from repro.core.params import SchedulerParams
    from repro.core.policies.saath import Saath
    from repro.fabric.engine import Simulator
    from repro.fabric.state import FlowTable

    P = cfg["num_ports"]
    tr = Trace(P, [Coflow(s.cid, s.arrival,
                          [Flow(j, int(a), int(b), float(z)) for j, (a, b, z)
                           in enumerate(zip(s.src, s.dst, s.size))])
                   for s in specs])
    p = SchedulerParams(**cfg["params"])
    t = FlowTable.from_trace(tr, p.port_bw)
    Simulator(p).run(t, Saath(p))
    return t.cct


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

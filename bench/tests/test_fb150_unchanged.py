"""fb150.steady runs the harness exactly as before fleets could be
stated: its streams, the reference's CCTs, the roofline share of one
chip and the server's arguments against values recorded from the
harness before leaf links and shards were added
(`data/fb150_golden.json`)."""
import dataclasses
import hashlib
import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import fleet, gen, reference, roofline

BENCH = Path(__file__).resolve().parents[1]
GOLDEN = json.loads((BENCH / "tests" / "data" / "fb150_golden.json")
                    .read_text())
CFG = json.loads((BENCH / "configs" / "fb150.json").read_text())
TRAFFIC = json.loads((BENCH / "traffic" / "steady.json").read_text())
N_COFLOWS = 2000


@pytest.mark.parametrize("seed", sorted(GOLDEN["streams"]))
def test_streams(seed):
    s = gen.streams(CFG, TRAFFIC, int(seed))[0]
    cfs = []
    while len(cfs) < N_COFLOWS:
        cfs += s.until(s._t + 1.0)
    h = hashlib.sha256()
    for c in cfs[:N_COFLOWS]:
        h.update(np.int64(c.cid).tobytes())
        h.update(np.float64(c.arrival).tobytes())
        h.update(np.asarray(c.src, np.int64).tobytes())
        h.update(np.asarray(c.dst, np.int64).tobytes())
        h.update(np.asarray(c.size, np.float64).tobytes())
    assert h.hexdigest() == GOLDEN["streams"][seed]


@pytest.mark.parametrize("case", ["wc", "no_wc"])
def test_reference_ccts(case):
    cfg = dict(CFG, num_ports=32)
    specs = gen.streams(cfg, TRAFFIC, 4)[0].until(3.0)
    if case == "wc":
        got = reference.run(specs, cfg["params"], 32, until=np.inf)
    else:
        got = reference.run(specs, cfg["params"], 32, until=2.0,
                            work_conservation=False)
    assert [float(x).hex() for x in got] == GOLDEN["reference_32"][case]


def test_roofline_share_of_one_chip():
    ctx = types.SimpleNamespace(
        kernel_events=lambda m: [
            ("/device:TPU:0", "contention_pallas.3", 1.0 + k * 1e-3,
             1.0 + k * 1e-3 + 4.9e-7) for k in range(10)],
        kernel_shapes={"contention": (256, 150)},
        device_kind="TPU v5 lite", lanes=1, n_devices=1)
    got = roofline.share(ctx, "contention", "contention_pallas")
    assert float(got).hex() == GOLDEN["share_one_device"]


def test_server_arguments(monkeypatch):
    import repro.launch.serve as serve

    calls = []

    class Recorder:
        def __init__(self, *args, **kwargs):
            calls.append(([dataclasses.asdict(a) for a in args], kwargs))

        def register(self, name):
            pass

    monkeypatch.setattr(serve, "CoflowServer", Recorder)
    fleet.Fleet(CFG, TRAFFIC, 7, shards=1)
    assert len(calls) == 1
    args, kwargs = calls[0]
    assert {"args": args, "kwargs": kwargs} == GOLDEN["server_call"]

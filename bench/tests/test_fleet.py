"""A fleet cell stated in data only: tenants of leaf-spine pods with
the max-min fill, on one chip's worth of rows or sharded over two.
Every run but the look for a chip, on the CPU, at a size a test run
holds."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SEED = 2**31 + 404
ROUNDS = 250            # 2 s of virtual time


class _Cell:
    """8 tenants of 8-port 2:1 leaf-spine pods with the max-min fill,
    the fb150 configuration's scheduler and coflow shapes."""

    def __init__(self):
        base = json.loads((BENCH / "configs" / "fb150.json").read_text())
        self.name = "test.fleet"
        self.config = dict(
            base, tenants=8, num_ports=8,
            topology={"kind": "leaf_spine", "hosts_per_leaf": 4,
                      "oversub": 2.0, "wc_fill": "maxmin"},
            fast_forward_s=[1.0, 1.0], warm_rounds=20,
            limits=dict(base["limits"], min_window_coflows=5))
        self.traffic = {"load": 0.4}
        self.chips = 1
        self.end_to_end, self.per_layer = [], []


def _run(devices, keep=None):
    from bench import harness

    return harness.run_cell(_Cell(), SEED, 0.0, False, time.perf_counter(),
                            harness.CompileCounter(), devices, keep=keep,
                            rounds=ROUNDS)


def test_fleet_run_is_correct():
    import jax

    keep = {}
    out = _run(jax.devices()[:1], keep)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert len(keep["submitted"]) == 8
    # the window served most tenants, not one
    assert len({i for i, _ in keep["served"]}) >= 4


_SHARDED = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench.tests.test_fleet import _run
assert len(jax.devices()) == 2, jax.devices()
out = {{}}
for n in (1, 2):
    keep = {{}}
    res = _run(jax.devices()[:n], keep)
    out[n] = {{"correct": res["correct"],
              "served": sorted([i, c, v.hex()] for (i, c), v
                               in keep["served"].items())}}
print(json.dumps(out))
"""


def test_two_shards_serve_what_one_does():
    """The pool's rows sharded over two devices (the cell's `chips`)
    hand out the same completions, bit for bit, as one device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run(
        [sys.executable, "-c", _SHARDED.format(root=str(ROOT),
                                                src=str(ROOT / "src"))],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    one, two = out["1"], out["2"]
    assert one["correct"] and two["correct"]
    assert one["served"] and one["served"] == two["served"]


def test_tenants_must_split_evenly_over_the_chips():
    from bench import fleet

    cell = _Cell()
    with pytest.raises(ValueError, match="multiple of the cell's chips"):
        fleet.Fleet(dict(cell.config, tenants=6), cell.traffic, 1,
                    shards=4)


def test_server_gets_the_fabric_and_the_shards(monkeypatch):
    import repro.launch.serve as serve
    from repro.fabric.topology import LeafSpine

    from bench import fleet

    calls = []

    class Recorder:
        def __init__(self, *args, **kwargs):
            calls.append(kwargs)

        def register(self, name):
            pass

    monkeypatch.setattr(serve, "CoflowServer", Recorder)
    cell = _Cell()
    fleet.Fleet(cell.config, cell.traffic, 1, shards=2)
    assert calls == [{"num_ports": 8, "max_tenants": 8, "shards": 2,
                      "topology": LeafSpine(hosts_per_leaf=4, oversub=2.0,
                                            wc_fill="maxmin")}]


_DATA_ONLY = """
import sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import harness, spec
cell = spec.Cell("fleet8.pods", root=__import__("pathlib").Path({root!r}))
out = harness.run_cell(cell, 2**31 + 9, 0.0, False, time.perf_counter(),
                       harness.CompileCounter(), jax.devices()[:cell.chips],
                       rounds={rounds})
print(out["checks"])
print(cell.chips, out["correct"], out["device"]["count"])
"""


def test_a_fleet_cell_is_data_only(tmp_path):
    """A configuration file with a topology, a mix and a four-chip
    workload entry, added to a copy of the benchmark as
    files and entries only, run through the harness."""
    import shutil

    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    cell = _Cell()
    (tmp_path / "bench" / "configs" / "fleet8.json").write_text(
        json.dumps(cell.config))
    (tmp_path / "bench" / "traffic" / "pods.json").write_text(
        json.dumps(cell.traffic))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "fleet8", "source": "test",
                             "file": "bench/configs/fleet8.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "fleet8.pods", "config": "fleet8",
                               "traffic": "pods", "chips": 4,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", _DATA_ONLY.format(
            root=str(tmp_path), src=str(ROOT / "src"), rounds=ROUNDS)],
        env=env, capture_output=True, text=True, timeout=900, cwd=tmp_path)
    assert p.returncode == 0, p.stderr[-4000:]
    assert p.stdout.strip().splitlines()[-1] == "4 True 4", \
        p.stdout.strip().splitlines()[-2]

"""Integration: train loop, checkpoint/restart determinism, elasticity."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, read_metadata, restore, save
from repro.launch.train import train


@pytest.mark.slow
def test_loss_decreases(tmp_path):
    out = train("starcoder2-3b", steps=30, smoke=True, batch=4, seq=64,
                ckpt_dir=None, log_every=1000, coflow_plan=False)
    losses = out["losses"]
    assert len(losses) == 30
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05, losses


@pytest.mark.slow
def test_checkpoint_restart_bitwise(tmp_path):
    """Crash-and-resume reproduces the uninterrupted run exactly: run to
    20 with periodic checkpoints, 'lose' everything after step 12 (the
    crash), resume, and compare the replayed losses (stateless data
    pipeline + saved train state)."""
    import shutil

    d = str(tmp_path / "ckpt")
    full = train("starcoder2-3b", steps=20, smoke=True, batch=4, seq=64,
                 ckpt_dir=d, ckpt_every=6, log_every=1000,
                 coflow_plan=False)
    assert latest_step(d) == 18
    shutil.rmtree(f"{d}/step_{18:08d}")  # the crash
    assert latest_step(d) == 12
    resumed = train("starcoder2-3b", steps=20, smoke=True, batch=4,
                    seq=64, ckpt_dir=d, ckpt_every=6, log_every=1000,
                    coflow_plan=False)
    assert resumed["final_step"] == 20
    # losses after resume equal the uninterrupted run's tail
    np.testing.assert_allclose(resumed["losses"], full["losses"][12:],
                               rtol=1e-6)


def test_checkpoint_atomic_and_metadata(tmp_path):
    tree = {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.zeros(3)}
    p = save(str(tmp_path), 7, tree, metadata={"arch": "x"})
    assert os.path.isdir(p)
    meta = read_metadata(str(tmp_path), 7)
    assert meta["step"] == 7 and meta["metadata"]["arch"] == "x"
    back = restore(str(tmp_path), 7, tree)
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.asarray(tree["w"]))


def test_elastic_reshard_roundtrip(tmp_path):
    """A checkpoint written replicated restores under a (1,1) mesh with
    explicit specs — the elastic-rescale path at CPU scale."""
    from jax.sharding import PartitionSpec as P

    tree = {"w": jnp.arange(64.0).reshape(8, 8)}
    save(str(tmp_path), 1, tree)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    back = restore(str(tmp_path), 1, tree, mesh=mesh,
                   specs={"w": P("data", "model")})
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.asarray(tree["w"]))
    assert back["w"].sharding.spec == P("data", "model")


def test_straggler_watchdog():
    from repro.launch.train import StragglerWatchdog

    dog = StragglerWatchdog(factor=3.0)
    for i in range(20):
        dog.observe(i, 0.1)
    assert not dog.events
    assert dog.observe(20, 1.0)   # 10x median -> flagged
    assert dog.events[0]["step"] == 20

"""The main path's Pallas kernels and the session step, compiled for a
TPU v5e that is described, not attached.

The chip's own compiler runs here, so these tests catch what interpret
mode cannot: tiles the chip cannot lay out, and kernels that want more
VMEM than they may use. Nothing runs, so they say nothing of results or
times. The topology is described inside a fixture (never at import):
only one process at a time may load the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.contention import contention_pallas
from repro.kernels.maxmin import maxmin_pallas

# the 256-tenant serving slab: 24 ports + 6 leaves of a
# LeafSpine(hosts_per_leaf=4), 16 coflows and 1024 flows per row
SERVE_B, SERVE_C, SERVE_P, SERVE_LF, SERVE_F = 256, 16, 24, 6, 1024


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels_in(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("C,P", [(526, 150), (4096, 512),
                                 (4096, ops.CONTENTION_MAX_P)],
                         ids=["fb_trace", "table2b", "domain_cap"])
def test_contention_compiles_for_v5e(one_chip, C, P):
    s = _spec(one_chip, (C, P))
    compiled = contention_pallas.lower(
        s, s, _spec(one_chip, (C,), jnp.bool_)).compile()
    assert _kernels_in(compiled) == 1


@pytest.mark.parametrize("P,F", [(SERVE_P + SERVE_LF, SERVE_F),
                                 (ops.MAXMIN_MAX_P, ops.MAXMIN_MAX_F)],
                         ids=["serving", "domain_cap"])
def test_maxmin_compiles_for_v5e(one_chip, P, F):
    a = _spec(one_chip, (P, F))
    bw = _spec(one_chip, (P,))
    compiled = maxmin_pallas.lower(
        a, a, _spec(one_chip, (F,), jnp.bool_), bw, bw).compile()
    assert _kernels_in(compiled) == 1


def test_contention_past_its_domain_is_refused(one_chip):
    """The VMEM bound behind CONTENTION_MAX_P: one port more pads the
    strips to the next 128 lanes, which does not fit."""
    s = _spec(one_chip, (4096, ops.CONTENTION_MAX_P + 1))
    with pytest.raises(Exception, match="vmem"):
        contention_pallas.lower(
            s, s, _spec(one_chip, (4096,), jnp.bool_)).compile()


def test_maxmin_past_its_domain_is_refused(one_chip):
    """The VMEM bound behind MAXMIN_MAX_P/F: twice the flows does not
    fit."""
    P, F = ops.MAXMIN_MAX_P, 2 * ops.MAXMIN_MAX_F
    a = _spec(one_chip, (P, F))
    bw = _spec(one_chip, (P,))
    with pytest.raises(Exception, match="vmem"):
        maxmin_pallas.lower(a, a, _spec(one_chip, (F,), jnp.bool_),
                            bw, bw).compile()


def test_session_advance_compiles_for_v5e_with_pallas(one_chip):
    """The served 256-tenant leaf-spine step with both kernels inside
    the device-side while loop (kernel="pallas", max-min fill on)."""
    from repro.analysis.audit import _canonical_slab
    from repro.fabric.jax_engine import _run_session_block

    tb, _, ep_rows, state = _canonical_slab(
        leaf_links=SERVE_LF, b=SERVE_B, f=SERVE_F, c=SERVE_C, p=SERVE_P)
    specs = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, np.shape(x), np.asarray(x).dtype),
        (state, tb, ep_rows))
    compiled = _run_session_block.lower(
        *specs, _spec(one_chip, (SERVE_B,)),
        _spec(one_chip, (), jnp.int32), kernel="pallas",
        features=(True, True, False, True, False)).compile()
    assert _kernels_in(compiled) == 2

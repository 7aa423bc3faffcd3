"""p95 over every round, and the kernels' roofline counts."""
import types

import numpy as np
import pytest

from bench import fleet, roofline, trace


@pytest.mark.parametrize("n", [1, 2, 19, 20, 21, 997])
def test_percentile_matches_numpy_over_all_rounds(n):
    xs = np.random.default_rng(n).lognormal(0, 1, n).tolist()
    for q in (50, 95, 99):
        assert fleet.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_p95_is_taken_over_the_whole_tail():
    xs = [1.0] * 95 + [100.0] * 5
    assert fleet.percentile(xs, 95) == pytest.approx(
        np.percentile(xs, 95))
    assert fleet.percentile(list(reversed(xs)), 95) == fleet.percentile(xs, 95)


def test_contention_counts():
    # two C x C x P products, threshold + row count; both incidences in,
    # mask in, counts out
    flops, nbytes = roofline.contention_work(128, 150)
    assert flops == 2 * 2 * 128 * 128 * 150 + 3 * 128 * 128
    assert nbytes == 2 * 128 * 150 * 4 + 128 + 128 * 4


def test_peaks_table_refuses_an_unknown_device():
    assert roofline.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_union_and_overlap():
    m = trace.union([(0, 2), (1, 3), (5, 6), (5.5, 5.7)])
    assert m == [[0, 3], [5, 6]]
    assert trace.length(m) == 4
    assert trace.overlap(m, 2, 5.5) == 1.5
    assert trace.overlap(m, -1, 10) == 4


def _ctx(planes, lanes, n_devices, op="contention", shape=(256, 24)):
    evs = [(f"/device:TPU:{d}", f"{op}_pallas.3", 1.0 + k * 1e-3 + d * 1e-5,
            1.0 + k * 1e-3 + d * 1e-5 + 2.5e-6 * (1 + 0.1 * d))
           for d in planes for k in range(10)]
    return types.SimpleNamespace(
        kernel_events=lambda m: [e for e in evs if m in e[1]],
        kernel_shapes={op: shape}, device_kind="TPU v5 lite", lanes=lanes,
        n_devices=n_devices)


@pytest.mark.parametrize("op,shape", [("contention", (256, 24)),
                                      ("maxmin", (30, 512))])
def test_share_counts_the_rows_of_one_chip(op, shape):
    """Four chips each run their quarter of the rows: the share over
    four planes' calls is the share of one plane's calls over a
    quarter of the lanes (here the planes take the same time)."""
    one = roofline.share(_ctx([0], 256, 1, op, shape), op, f"{op}_pallas")
    evs4 = _ctx([0, 0, 0, 0], 1024, 4, op, shape)
    assert roofline.share(evs4, op, f"{op}_pallas") == pytest.approx(one)
    # planes that differ in time read as their mean
    four = _ctx([0, 1, 2, 3], 1024, 4, op, shape)
    per_plane = [roofline.share(_ctx([d], 256, 1, op, shape), op,
                                f"{op}_pallas") for d in range(4)]
    assert roofline.share(four, op, f"{op}_pallas") == pytest.approx(
        4 / sum(1 / x for x in per_plane))


def test_maxmin_counts():
    # 2P + 2 rounds of six P x F mat-vecs, 8F and 22P elementwise; both
    # one-hots, the live mask and both bandwidths in, rates out
    P, F = 30, 512
    flops, nbytes = roofline.maxmin_work(P, F)
    assert flops == (2 * P + 2) * (12 * P * F + 8 * F + 22 * P)
    assert nbytes == 2 * P * F * 4 + F + 2 * P * 4 + F * 4
    assert roofline.WORK["maxmin"] is roofline.maxmin_work


@pytest.mark.parametrize("tenants", [1, 3])
def test_offered_load_is_the_mean_over_tenants(tenants):
    spec = types.SimpleNamespace(total_bytes=5e8)
    fl = types.SimpleNamespace(
        n=tenants, config={"num_ports": 4},
        params=types.SimpleNamespace(port_bw=1.25e8),
        submitted=[[spec] * 2 for _ in range(tenants)])
    # each tenant offered 1e9 bytes in 4 s of a 5e8 B/s fabric
    assert fleet.offered_load(fl, 4.0) == pytest.approx(0.5)

"""p95 over every round, and the kernels' roofline counts."""
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

"""The count of a decision window's least work, and the peak table."""
import pytest

from bench.work import decision_work, least_seconds, peaks


@pytest.mark.parametrize("R,I", [(1, 13), (8, 13), (64, 130)])
def test_decision_work_counts(R, I):
    N, D, M, k, tiers, trees, depth = 14886, 128, 4, 10, 4, 60, 3
    ops, nbytes = decision_work(R, I, N, D, M, k, tiers, trees, depth)
    want_ops = (2 * R * N * D + 4 * R * N + R * k * (4 + 4 * M)
                + I * trees * (depth + 1) + 26 * R * I)
    assert ops == want_ops
    words = (N * D + N + 2 * R * k * M + R * (D + 2) + 5 * I
             + tiers * trees * (2 * 7 + 8) + 3 * R + 3 * I)
    assert nbytes == 4 * words


def test_least_time_is_bytes_bound_on_v5e():
    ops, nbytes = decision_work(8, 13, 14886, 128, 4, 10, 4, 60, 3)
    t = least_seconds(ops, nbytes, "TPU v5 lite")
    assert t == pytest.approx(nbytes / 819e9)
    assert ops / 197e12 < t


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v4")

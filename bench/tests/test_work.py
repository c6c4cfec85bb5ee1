"""Work counts and the peak table, against values worked out by hand."""
import pytest
import tiny  # noqa: F401  (puts the bench directory on the path)

from harness import work


def test_blend_counts_by_hand():
    # m = 1, d = 2: the RBF entry is 4d + 2 = 10; a corner adds W k and
    # U k (4 m^2 = 4), their norms and the mean (6 m = 6) and k** - q + s
    # (2): 22; the blend adds 6 per corner and 3: 4 * 28 + 3
    assert work.rbf_flops(1, 1) == 10
    assert work.blend_point_flops(1) == 115
    # m = 2: corner = 20 + 16 + 12 + 2 = 50; 4 * 56 + 3
    assert work.blend_point_flops(2) == 227


def test_blend_bytes_by_hand():
    # per partition at m = 2: z 4, W 4, U 4, c 2, covariance 3, noise 1
    # = 18 floats; three partitions; 72 bytes each
    assert work.cache_bytes(3, 2) == 216
    # 10 points in (2 floats) and out (2): 160 bytes; the factors once
    # per batch, two batches
    assert work.blend_bytes(10, 2, 3, 2) == 160 + 2 * 216


def test_sgd_step_counts_by_hand():
    # B = 1, m = 1: Kmm 10, Cholesky 0, K(X, Z) 10, solves and (chol S)^T a
    # 4, residual/mean/norm 6, likelihood 8, KL 1 + 3 + 4
    assert work.elbo_forward_flops(1, 1) == 46
    assert work.params_per_partition(2) == 14
    # two partitions: forward + backward (2x) and Adam on 8 parameters each
    assert work.sgd_step_flops(2, 1, 1) == 2 * (3 * 46 + 10 * 8)


def test_peaks():
    p = work.peaks("TPU v5 lite", tiny.BENCH)
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary", tiny.BENCH)

"""Work counts from the algorithm's shapes, and the peaks they are
divided by. Nothing here reads the compiled program, so the counts stay
the same whatever implements the work.

Conventions: a multiply-add is 2 FLOPs, exp and compare count 1 each, a
dense (a x b) @ (b x c) product is 2abc, a Cholesky of m x m is m^3 / 3,
a triangular solve with r right-hand sides is m^2 r. d is the input
dimension (2).
"""
from __future__ import annotations

import json
import os

D = 2
FLOAT = 4  # bytes of a float32


def rbf_flops(rows: int, m: int, d: int = D) -> int:
    """K(x, Z) for ``rows`` points: per entry d differences, d scalings,
    d squares, d - 1 adds, the -1/2 scaling, exp and the variance."""
    return rows * m * (4 * d + 2)


def blend_point_flops(m: int, d: int = D) -> int:
    """One served point: for each of its 4 corner models the RBF row
    k (m), W k (2m^2) and its squared norm (2m), U k (2m^2) and its
    squared norm (2m), the mean k.c (2m) and k** - q + s (2); then the
    blend: per corner w*mean (2) and w*(var + mean^2) (4), and
    second - mean^2 and the clamp (3)."""
    corner = rbf_flops(1, m, d) + 4 * m * m + 6 * m + 2
    return 4 * (corner + 6) + 3


def cache_bytes(partitions: int, m: int, d: int = D) -> int:
    """The cached factors of every partition, each read once: z (m d),
    W and U (m^2 each), c (m), the covariance parameters (d + 1) and the
    noise (1)."""
    return partitions * (m * d + 2 * m * m + m + d + 2) * FLOAT


def blend_bytes(points: int, batches: int, partitions: int, m: int, d: int = D) -> int:
    """Queries in (d floats) and answers out (mean, var) for ``points``,
    and the cached factors read once per batch. The per-point copies of
    factors that an implementation may gather are not counted."""
    return points * (d + 2) * FLOAT + batches * cache_bytes(partitions, m, d)


def elbo_forward_flops(batch: int, m: int, d: int = D) -> int:
    """The mini-batch -ELBO of one partition (eq. 3), forward:
    Kmm (m^2 RBF) and its Cholesky; K(X_B, Z); L^-1 K_ZB and L^-T of it
    (2 B m^2); the diagonal residual (2Bm); the mean (2Bm); (chol S)^T a
    (2 B m^2) and its squared norm (2Bm); the Gaussian expected
    log-likelihood (8B); the KL: L^-1 chol S (m^3), its squared norm
    (2m^2), L^-1 m_star (m^2) and its norm (2m), the log-determinants
    (2m)."""
    b = batch
    return (rbf_flops(m, m, d) + m ** 3 // 3 + rbf_flops(b, m, d) + 4 * b * m * m + 6 * b * m
            + 8 * b + m ** 3 + 3 * m * m + 4 * m)


BACKWARD_FACTOR = 2  # reverse mode: about twice the forward pass
ADAM_FLOPS_PER_PARAM = 10


def params_per_partition(m: int, d: int = D) -> int:
    """m_star (m), chol S (m^2 stored dense), Z (m d), lengthscales (d),
    variance and noise (2)."""
    return m + m * m + m * d + d + 2


def sgd_step_flops(partitions: int, batch: int, m: int, d: int = D) -> int:
    """One SGD step of every partition: forward, backward
    (BACKWARD_FACTOR x forward) and the Adam update."""
    per = (1 + BACKWARD_FACTOR) * elbo_forward_flops(batch, m, d)
    per += ADAM_FLOPS_PER_PARAM * params_per_partition(m, d)
    return partitions * per


def peaks(device_kind: str, bench_dir: str) -> dict:
    """The peak table's row for this chip; an unknown chip is an error."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]

"""Pure-jnp oracles for the Pallas kernels (the allclose targets).

These mirror what ``repro.gp.covariances`` / ``repro.core.svgp`` compute, but
are kept dependency-free and in the exact input convention of the kernels so
tests compare kernel output to THIS file, and this file is itself covered by
tests against the gp/ implementations.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp


def rbf_cross_cov(
    x: jnp.ndarray, z: jnp.ndarray, log_lengthscale: jnp.ndarray, log_variance: jnp.ndarray
) -> jnp.ndarray:
    """ARD-RBF K(X,Z): exp(lv) * exp(-0.5 sum_d (x_d - z_d)^2 / l_d^2).

    x: (n, d), z: (m, d) -> (n, m).
    """
    inv_l = jnp.exp(-log_lengthscale)
    diff = x[:, None, :] * inv_l - z[None, :, :] * inv_l
    r2 = jnp.sum(diff * diff, axis=-1)
    return jnp.exp(log_variance) * jnp.exp(-0.5 * r2)


def svgp_projection(
    x: jnp.ndarray,
    z: jnp.ndarray,
    log_lengthscale: jnp.ndarray,
    log_variance: jnp.ndarray,
    w: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused SVGP projection (the O(B m^2) ELBO hot path).

    w: (m, m) = Lmm^{-1} (dense lower-triangular inverse of chol(Kmm)).
    Returns:
      knm    (B, m)  cross-covariance K(X, Z)
      lk_t   (B, m)  K(X,Z) @ W^T  (row i = (Lmm^{-1} k_i)^T)
      q_diag (B,)    ||Lmm^{-1} k_i||^2 = k_i^T Kmm^{-1} k_i
    """
    knm = rbf_cross_cov(x, z, log_lengthscale, log_variance)
    lk_t = jnp.dot(knm, w.T, precision="highest")
    q_diag = jnp.sum(lk_t * lk_t, axis=-1)
    return knm, lk_t, q_diag


def posterior_predict(
    x: jnp.ndarray,
    z: jnp.ndarray,
    log_lengthscale: jnp.ndarray,
    log_variance: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,
    c: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused cached-posterior prediction (the serving hot path).

    w: (m, m) = Lmm^{-1};  u: (m, m) = Sl^T A;  c: (m,) projected mean
    (see repro.core.posterior for the factor definitions). Returns:
      mean (Q,)  K(X*,Z) @ c
      fvar (Q,)  k_** - ||W k_*||^2 + ||U k_*||^2   (un-clamped)
    """
    knm = rbf_cross_cov(x, z, log_lengthscale, log_variance)
    # full-f32 matmuls like the kernels: an oracle at a TPU's default
    # precision would sit further from the truth than the code it checks
    mean = jnp.dot(knm, c, precision="highest")
    lk = jnp.dot(knm, w.T, precision="highest")
    su = jnp.dot(knm, u.T, precision="highest")
    fvar = jnp.exp(log_variance) - jnp.sum(lk * lk, axis=-1) + jnp.sum(su * su, axis=-1)
    return mean, fvar


def posterior_predict_slots(
    hx: jnp.ndarray,
    z: jnp.ndarray,
    log_lengthscale: jnp.ndarray,
    log_variance: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,
    c: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Slot-stacked ``posterior_predict``: hx (S, Q, d) -> (S, Q) pairs.

    One model, S stacked query blocks (the serving program's 9 halo
    slots) — the allclose target for the slot-stacked Pallas launch.
    """
    return jax.vmap(
        lambda xs: posterior_predict(xs, z, log_lengthscale, log_variance, w, u, c)
    )(hx)


def posterior_predict_slots_masked(
    hx: jnp.ndarray,
    qmask: jnp.ndarray,
    z: jnp.ndarray,
    log_lengthscale: jnp.ndarray,
    log_variance: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,
    c: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Masked slot-stacked oracle — the TWO-LEVEL routing contract.

    A two-level block mixes owner rows, spill rows (real queries hosted
    for an overflowing neighbor cell) and padded rows (qmask 0, cell-
    center placeholders). The kernel's guarantee that makes the mix safe
    is ROW INDEPENDENCE: every output row is a function of its own input
    row and the resident factors only, so spill rows compute exactly what
    they would as primaries and padded rows influence nothing.

    This oracle states that contract as math: it equals
    :func:`posterior_predict_slots` with masked rows forced to zero.
    Tests hold the Pallas kernel to it two ways — kernel * qmask must
    equal this oracle, and perturbing masked rows' INPUTS must leave
    valid rows bitwise unchanged (see tests/test_posterior.py).

    qmask: (S, Q) {0,1} row validity per slot block.
    """
    mean, fvar = posterior_predict_slots(
        hx, z, log_lengthscale, log_variance, w, u, c
    )
    return mean * qmask, fvar * qmask

"""Sparse Variational Gaussian Process (Hensman et al. 2013) — eq. (3).

One local model. The PSVGP layer (``repro.core.psvgp``) vmaps everything in
this file over a leading partition axis, so every function here is written
for a single un-batched model and must stay vmap-friendly (no python-level
data-dependent control flow).

Parameterization (all unconstrained, phi in the paper's notation):
  m_star     (m,)      variational mean of q(u)
  s_tril     (m, m)    unconstrained Cholesky of S_star: tril, diag via exp
  z          (m, d)    inducing point locations
  cov        CovarianceParams (ARD log-lengthscales, log-variance)
  log_beta   ()        log noise precision

``whitened=True`` reparameterizes q(u) = N(L v_m, L V L^T) with L = chol(Kmm),
a beyond-paper numerical option (KL becomes Kmm-free); default False matches
the paper / Hensman 2013 exactly.
"""
from __future__ import annotations

import math
from collections.abc import Callable
from typing import NamedTuple

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from repro.core.posterior import (
    build_cache,
    kmm_chol as _kmm_chol,
    predict_cached,
    projection as _projection,
    s_chol,
)
from repro.gp.covariances import CovarianceParams, init_covariance_params
from repro.gp.likelihoods import gaussian_expected_loglik

_LOG2PI = 1.8378770664093453


class SVGPParams(NamedTuple):
    m_star: jnp.ndarray  # (m,)
    s_tril: jnp.ndarray  # (m, m) unconstrained
    z: jnp.ndarray  # (m, d)
    cov: CovarianceParams
    log_beta: jnp.ndarray  # ()


class SVGPConfig(NamedTuple):
    num_inducing: int
    input_dim: int
    covariance: str = "rbf"
    jitter: float = 1e-5
    whitened: bool = False
    init_lengthscale: float = 1.0
    init_variance: float = 1.0
    init_beta: float = 1.0
    use_pallas: bool = False  # route the O(B m^2) hot path through kernels/
    likelihood: str = "gaussian"  # gaussian | poisson — the paper's §6
    # "extensions to non-Gaussian likelihoods ... count data" future work


def init_svgp_params(
    key: jax.Array,
    cfg: SVGPConfig,
    x_init: jnp.ndarray | None = None,
    mask: jnp.ndarray | None = None,
    dtype=jnp.float32,
) -> SVGPParams:
    """Initialize; inducing points from data subsample if provided, else N(0,1).

    mask: optional (n,) {0,1} row validity for ``x_init`` (the PSVGP layer's
    partitions are padded to a common n_max). Sampling is restricted to valid
    rows, uniformly WITHOUT replacement — padded slots replicate the
    partition's first point, and drawing them would stack duplicate inducing
    points there, making Kmm singular up to jitter (chaotic Cholesky
    gradients, wasted inducing capacity on exactly the small edge partitions
    that need it most). Partitions with fewer valid points than m still get
    duplicates (there is nothing else to sample); jitter handles those.
    """
    m, d = cfg.num_inducing, cfg.input_dim
    kz, = jax.random.split(key, 1)
    if x_init is not None:
        if mask is None:
            idx = jax.random.choice(kz, x_init.shape[0], (m,), replace=x_init.shape[0] < m)
        else:
            # Uniform top-k over valid rows (same idiom as the minibatch
            # sampler): distinct valid rows first, padded rows only when the
            # partition runs out of points. vmap-safe (no data-dependent
            # shapes), unlike random.choice with a probability vector.
            scores = jax.random.uniform(kz, (x_init.shape[0],)) + (mask - 1.0) * 1e9
            idx = jax.lax.top_k(scores, m)[1]
        z = x_init[idx].astype(dtype)
    else:
        z = jax.random.normal(kz, (m, d), dtype)
    return SVGPParams(
        m_star=jnp.zeros((m,), dtype),
        # exp(diag)=1 -> S_star initialized to the identity
        s_tril=jnp.zeros((m, m), dtype),
        z=z,
        cov=init_covariance_params(d, cfg.init_lengthscale, cfg.init_variance, dtype),
        log_beta=jnp.asarray(math.log(cfg.init_beta), dtype),
    )


# s_chol / _kmm_chol / _projection now live in repro.core.posterior (the
# shared prediction-math module); re-imported above so the ELBO below and
# external callers keep their historical access path.


def q_f(
    params: SVGPParams,
    cov_fn: Callable,
    x: jnp.ndarray,
    jitter: float = 1e-5,
    whitened: bool = False,
    use_pallas: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Marginal q(f_i) = N(fmean_i, fvar_i) at inputs x — the SVGP predictive.

    fmean = k_i^T Kmm^{-1} m_star              (unwhitened)
    fvar  = k~_ii + a_i^T S a_i  with a_i = Kmm^{-1} k_i
    """
    lk, kd, lmm = _projection(params, cov_fn, x, jitter, use_pallas)
    sl = s_chol(params.s_tril)  # (m, m)
    # full-f32 matmuls, like the served variance (posterior.predict_cached)
    if whitened:
        # u = L v, q(v)=N(m_star, S): fmean = lk^T m_star, a_i^T S a_i = ||sl^T lk||^2
        fmean = jnp.dot(lk.T, params.m_star, precision="highest")
        tmp = jnp.dot(sl.T, lk, precision="highest")  # (m, B)
        fvar = kd + jnp.sum(tmp * tmp, axis=0)
    else:
        a = jsl.solve_triangular(lmm.T, lk, lower=False)  # (m, B) = Kmm^{-1} k_i
        fmean = jnp.dot(a.T, params.m_star, precision="highest")
        tmp = jnp.dot(sl.T, a, precision="highest")
        fvar = kd + jnp.sum(tmp * tmp, axis=0)
    return fmean, jnp.maximum(fvar, 1e-12)


def kl_to_prior(params: SVGPParams, cov_fn: Callable, jitter: float, whitened: bool) -> jnp.ndarray:
    """KL( N(m_star, S_star) || p(u) ) — eq. (3)'s last term (times n/n = 1)."""
    m = params.m_star.shape[0]
    sl = s_chol(params.s_tril)
    logdet_s = 2.0 * jnp.sum(jnp.diagonal(params.s_tril))  # log|S| from exp-diag
    if whitened:
        # KL(N(m,S) || N(0,I))
        trace = jnp.sum(sl * sl)
        quad = jnp.sum(params.m_star**2)
        return 0.5 * (trace + quad - m - logdet_s)
    lmm = _kmm_chol(params, cov_fn, jitter)
    linv_sl = jsl.solve_triangular(lmm, sl, lower=True)
    trace = jnp.sum(linv_sl * linv_sl)  # tr(Kmm^{-1} S)
    linv_m = jsl.solve_triangular(lmm, params.m_star, lower=True)
    quad = jnp.sum(linv_m**2)  # m^T Kmm^{-1} m
    logdet_kmm = 2.0 * jnp.sum(jnp.log(jnp.diagonal(lmm)))
    return 0.5 * (trace + quad - m + logdet_kmm - logdet_s)


def elbo(
    params: SVGPParams,
    cov_fn: Callable,
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    mask: jnp.ndarray | None = None,
    n_total: jnp.ndarray | float | None = None,
    jitter: float = 1e-5,
    whitened: bool = False,
    use_pallas: bool = False,
    ll_weight: jnp.ndarray | float = 1.0,
    likelihood: str = "gaussian",
) -> jnp.ndarray:
    """Minibatch estimate of eq. (3):  (n/B) * sum_batch l_i  -  KL.

    mask: optional (B,) {0,1} — padded slots contribute nothing, and the
          scaling uses the effective batch size sum(mask). Required by the
          PSVGP layer whose partitions are ragged (8..222 obs in the paper).
    n_total: the "n" of eq. (3); for PSVGP this is n_eff,j of eq. (9).
             Defaults to the (effective) batch size, i.e. full-batch ELBO.
    ll_weight: importance weight applied to the LIKELIHOOD term only (the
          KL is deterministic, so weighting it would add pure variance) —
          used by the TPU-native synchronized-direction estimator.
    likelihood: "gaussian" (closed-form eq. 3) or "poisson" (log-link,
          closed-form expectation) — the paper's §6 count-data extension.
    """
    fmean, fvar = q_f(params, cov_fn, x, jitter, whitened, use_pallas)
    if likelihood == "gaussian":
        ll = gaussian_expected_loglik(y, fmean, fvar, params.log_beta)  # (B,)
    elif likelihood == "poisson":
        from repro.gp.likelihoods import poisson_expected_loglik

        ll = poisson_expected_loglik(y, fmean, fvar)
    else:
        raise ValueError(likelihood)
    if mask is not None:
        ll = ll * mask
        batch_n = jnp.maximum(jnp.sum(mask), 1.0)
    else:
        batch_n = jnp.asarray(float(x.shape[0]), ll.dtype)
    n_tot = batch_n if n_total is None else jnp.asarray(n_total, ll.dtype)
    scale = n_tot / batch_n
    return ll_weight * scale * jnp.sum(ll) - kl_to_prior(params, cov_fn, jitter, whitened)


def predict(
    params: SVGPParams,
    cov_fn: Callable,
    xstar: jnp.ndarray,
    jitter: float = 1e-5,
    whitened: bool = False,
    include_noise: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Predictive mean/variance at new locations (latent f by default).

    One-shot path: factorizes Kmm, predicts, discards the factors. Callers
    issuing MANY predictions against a fixed posterior should build a
    ``repro.core.posterior.PosteriorCache`` once and call ``predict_cached``
    (this function is exactly build + predict, so the two agree)."""
    cache = build_cache(params, cov_fn, jitter=jitter, whitened=whitened)
    return predict_cached(cache, cov_fn, xstar, include_noise=include_noise)

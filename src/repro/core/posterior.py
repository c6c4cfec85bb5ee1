"""Cached-posterior prediction — the serving-grade fast path.

Training optimizes q(u) per partition; once it converges, every prediction
against that posterior re-derives the same Kmm factorization. The seed code
paid that O(m^3) Cholesky (plus two triangular solves) on EVERY call —
``blend.predict_blended`` even paid it per query point per corner model.
Distributed low-rank spatial models get their serving speed precisely from
precomputing shared factors once and reusing them across predictions
(Katzfuss & Hammerling 2014; Peruzzi et al. 2020 use the same
cache-the-factorization pattern for partitioned prediction).

``PosteriorCache`` stores, per local model, everything S- and Kmm-dependent
that predictions reuse:

    w    (m, m)  Lmm^{-1}, Lmm = chol(Kmm+jI)  q_diag_i = ||W k_i||^2
    u    (m, m)  Sl^T A                        s_diag_i = ||U k_i||^2
    c    (m,)    projected variational mean    fmean_i  = k_i^T c

with A = Kmm^{-1}, c = Kmm^{-1} m_star for the standard parameterization and
A = Lmm^{-1}, c = Lmm^{-T} m_star for the whitened one — the whitening is
folded INTO the factors, so prediction itself is parameterization-agnostic.
A prediction at Q points then costs two (Q, m) x (m, m) matmuls and an
O(Q m) mean path instead of Q Choleskys: O(Q m^2) total, MXU-shaped.

Every function is vmap-friendly; the PSVGP layer stacks caches on a leading
partition axis (``build_cache_stacked``). The fused Pallas kernel variant of
``predict_cached`` lives in ``repro.kernels.predict`` (dispatch in
``kernels/ops.py``).

This module also owns the shared projection primitives (``s_chol``,
``kmm_chol``, ``projection``) that the training-time ELBO in
``repro.core.svgp`` builds on — one implementation of eq. (3)'s linear
algebra, used by both the training and the serving path.
"""
from __future__ import annotations

from collections.abc import Callable
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from repro.analysis.contracts import contract
from repro.gp.covariances import CovarianceParams, kdiag


class PosteriorCache(NamedTuple):
    """Per-model cached prediction factors (leaves stack/vmap over P).

    Only factors a prediction actually consumes live here — the blend path
    gathers every leaf per query point, so dead weight (e.g. Lmm itself,
    recoverable as w^{-1}) would be pure gather traffic on the hot path."""

    z: jnp.ndarray  # (m, d) inducing locations
    w: jnp.ndarray  # (m, m) Lmm^{-1}, Lmm = chol(Kmm + jitter I)
    u: jnp.ndarray  # (m, m) S-dependent variance factor (see module doc)
    c: jnp.ndarray  # (m,)   projected variational mean
    cov: CovarianceParams
    log_beta: jnp.ndarray  # ()


def s_chol(s_tril: jnp.ndarray) -> jnp.ndarray:
    """Constrained Cholesky factor of S_star: strictly-lower + exp(diag)."""
    ltri = jnp.tril(s_tril, -1)
    return ltri + jnp.diag(jnp.exp(jnp.diagonal(s_tril)))


def kmm_chol(params: Any, cov_fn: Callable, jitter: float) -> jnp.ndarray:
    """chol(Kmm + jitter I) for an SVGPParams-like bundle."""
    m = params.z.shape[0]
    kmm = cov_fn(params.cov, params.z, params.z)
    return jnp.linalg.cholesky(kmm + jitter * jnp.eye(m, dtype=kmm.dtype))


def projection(
    params: Any, cov_fn: Callable, x: jnp.ndarray, jitter: float, use_pallas: bool
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared O(B m^2) training hot path (the ELBO's eq. 3 projection).

    Returns (lk, kdiag_res, lmm) where
      lk   (m, B): Lmm^{-1} K_mz^T   (so a_i = Lmm^{-T} lk_i, A = Kmm^{-1}k_i)
      kdiag_res (B,): k~_ii = k_ii - ||lk_i||^2   (eq. 3's  k~ term)
      lmm  (m, m): chol(Kmm)
    When ``use_pallas`` is set, K(X,Z) and the triangular projection run in
    the fused Pallas kernel (repro.kernels); otherwise pure jnp.
    """
    lmm = kmm_chol(params, cov_fn, jitter)
    if use_pallas:
        from repro.kernels import ops as kops

        knm, lk_t, q_diag = kops.svgp_projection(
            x, params.z, params.cov.log_lengthscale, params.cov.log_variance, lmm
        )
        del knm
        lk = lk_t.T  # (m, B)
        kd = kdiag(params.cov, x) - q_diag
    else:
        knm = cov_fn(params.cov, x, params.z)  # (B, m)
        lk = jsl.solve_triangular(lmm, knm.T, lower=True)  # (m, B)
        kd = kdiag(params.cov, x) - jnp.sum(lk * lk, axis=0)
    return lk, kd, lmm


def build_cache(
    params: Any,
    cov_fn: Callable,
    *,
    jitter: float = 1e-5,
    whitened: bool = False,
) -> PosteriorCache:
    """Precompute the prediction factors for one model — O(m^3), once."""
    lmm = kmm_chol(params, cov_fn, jitter)
    m = lmm.shape[0]
    w = jsl.solve_triangular(lmm, jnp.eye(m, dtype=lmm.dtype), lower=True)
    sl = s_chol(params.s_tril)
    if whitened:
        # u = L v, q(v)=N(m_star, S): fmean = k^T Lmm^{-T} m_star
        c = jsl.solve_triangular(lmm.T, params.m_star, lower=False)
        u = jnp.dot(sl.T, w, precision="highest")
    else:
        c = jsl.solve_triangular(
            lmm.T, jsl.solve_triangular(lmm, params.m_star, lower=True), lower=False
        )
        kinv = jnp.dot(w.T, w, precision="highest")  # Kmm^{-1}, full f32
        u = jnp.dot(sl.T, kinv, precision="highest")
    return PosteriorCache(
        z=params.z, w=w, u=u, c=c, cov=params.cov, log_beta=params.log_beta
    )


def predict_cached(
    cache: PosteriorCache,
    cov_fn: Callable,
    xstar: jnp.ndarray,
    *,
    include_noise: bool = False,
    use_pallas: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Predictive mean/variance at xstar (Q, d) from cached factors.

    fmean = K(x*, Z) c
    fvar  = k_** - ||W k_*||^2 + ||U k_*||^2     (clamped to >= 1e-12)

    ``use_pallas`` routes K(x*,Z) + both projections + the reductions
    through the fused prediction kernel — RBF covariance only, and that is
    VALIDATED: the kernel computes the RBF whatever ``cov_fn`` is, so a
    non-RBF covariance raises instead of silently returning RBF answers.
    """
    if use_pallas:
        from repro.kernels import ops as kops

        fmean, fvar = kops.posterior_predict(
            xstar, cache.z, cache.cov.log_lengthscale, cache.cov.log_variance,
            cache.w, cache.u, cache.c, cov_fn=cov_fn,
        )
    else:
        knm = cov_fn(cache.cov, xstar, cache.z)  # (Q, m)
        # full-f32 matmuls: the variance terms cancel, and at a TPU's
        # default f32 precision the variance comes out ~10% off (PERF.md)
        fmean = jnp.dot(knm, cache.c, precision="highest")
        qd = jnp.sum(jnp.dot(knm, cache.w.T, precision="highest") ** 2, axis=-1)
        sd = jnp.sum(jnp.dot(knm, cache.u.T, precision="highest") ** 2, axis=-1)
        fvar = kdiag(cache.cov, xstar) - qd + sd
    fvar = jnp.maximum(fvar, 1e-12)
    if include_noise:
        fvar = fvar + jnp.exp(-cache.log_beta)
    return fmean, fvar


def build_cache_stacked(
    params: Any,
    cov_fn: Callable,
    *,
    jitter: float = 1e-5,
    whitened: bool = False,
) -> PosteriorCache:
    """vmap of ``build_cache`` over a leading partition axis — one batched
    O(P m^3) factorization for the whole partitioned model.

    Args:
      params: SVGPParams-like pytree whose every leaf has a leading (P, ...)
        partition axis (``psvgp.PSVGPState.params``).
      cov_fn / jitter / whitened: as in ``build_cache``.

    Returns a ``PosteriorCache`` with leaves z (P, m, d), w/u (P, m, m),
    c (P, m), cov (P, d)/(P,), log_beta (P,). The leading axis is what the
    sharded serving path partitions one-per-device over the mesh
    (``sharding.gp_stacked_pspecs`` / ``launch.serve_sharded``)."""
    return jax.vmap(
        lambda p: build_cache(p, cov_fn, jitter=jitter, whitened=whitened)
    )(params)


def predict_cached_stacked(
    cache: PosteriorCache,
    cov_fn: Callable,
    xstar: jnp.ndarray,
    *,
    include_noise: bool = False,
    use_pallas: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Each stacked model predicts at its own rows of xstar.

    Args:
      cache: P-stacked ``PosteriorCache`` (``build_cache_stacked``).
      cov_fn: covariance function (``repro.gp.covariances``).
      xstar: (P, Q, d) — model p sees only row p's Q query points.
      include_noise / use_pallas: as in ``predict_cached``.

    Returns (fmean (P, Q), fvar (P, Q)); fvar clamped to >= 1e-12."""
    return jax.vmap(
        lambda ca, xq: predict_cached(
            ca, cov_fn, xq, include_noise=include_noise, use_pallas=use_pallas
        )
    )(cache, xstar)


def resolve_slot_backend(use_pallas: bool, backend: str | None) -> str:
    """Normalize the (legacy ``use_pallas`` bool, ``backend`` name) pair to
    one kernel lane: "ref" | "pallas" | "fused". The ONE definition of the
    mapping — :func:`predict_cached_slots` and
    ``serve_sharded.make_sharded_blend`` both validate through it, so the
    lane vocabulary cannot drift between the prediction and serving layers.
    """
    if backend is None:
        return "fused" if use_pallas else "ref"
    if use_pallas:
        raise ValueError("pass either use_pallas or backend=, not both")
    if backend not in ("ref", "pallas", "fused"):
        raise ValueError(f"backend must be 'ref'|'pallas'|'fused', got {backend!r}")
    return backend


@contract(
    args={"xslots": "(S, Q, D)"},
    returns=("(S, Q)", "(S, Q)"),
    invariants=("outputs-f32",),
)
def predict_cached_slots(
    cache: PosteriorCache,
    cov_fn: Callable,
    xslots: jnp.ndarray,
    *,
    include_noise: bool = False,
    use_pallas: bool = False,
    backend: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """ONE model evaluated on S stacked query blocks: xslots (S, Q, d).

    This is the device-side serving hot path: the sharded blend evaluates
    the local model on all 9 halo slots at once. Three kernel lanes,
    selected by ``backend`` (the ``repro.api.ServeConfig`` vocabulary;
    the legacy ``use_pallas`` bool maps True -> "fused", False -> "ref"
    and may not be combined with an explicit ``backend``):

      "ref"    — pure jnp: a vmap of :func:`predict_cached` over the slot
                 axis (every covariance; the XLA-compiled CPU lane).
      "pallas" — the fused single-block Pallas predict kernel
                 (``kernels.ops.posterior_predict``) through a (S*Q, d)
                 reshape round-trip: one launch, but the factor tiles are
                 re-staged per q-block across the flattened stack.
      "fused"  — a SINGLE slot-stacked Pallas launch whose grid spans
                 (S x q-blocks) with W/U/c resident across the whole grid
                 (``repro.kernels.predict.posterior_predict_slots_pallas``)
                 — no reshape round-trip, no per-slot re-staging; the TPU
                 production lane.

    Returns (fmean (S, Q), fvar (S, Q)); fvar clamped to >= 1e-12.
    Non-RBF covariances raise on the Pallas lanes (see
    ``repro.kernels.ops.require_rbf``).
    """
    backend = resolve_slot_backend(use_pallas, backend)
    if backend == "ref":
        return jax.vmap(
            lambda xs: predict_cached(cache, cov_fn, xs, include_noise=include_noise)
        )(xslots)
    from repro.kernels import ops as kops

    if backend == "fused":
        fmean, fvar = kops.posterior_predict_slots(
            xslots, cache.z, cache.cov.log_lengthscale, cache.cov.log_variance,
            cache.w, cache.u, cache.c, cov_fn=cov_fn,
        )
    else:  # "pallas": flatten the stack through the single-block kernel
        S, Q, d = xslots.shape
        fmean, fvar = kops.posterior_predict(
            xslots.reshape(S * Q, d), cache.z,
            cache.cov.log_lengthscale, cache.cov.log_variance,
            cache.w, cache.u, cache.c, cov_fn=cov_fn,
        )
        fmean, fvar = fmean.reshape(S, Q), fvar.reshape(S, Q)
    fvar = jnp.maximum(fvar, 1e-12)
    if include_noise:
        fvar = fvar + jnp.exp(-cache.log_beta)
    return fmean, fvar


def take_cache(cache: PosteriorCache, ids: jnp.ndarray) -> PosteriorCache:
    """Gather stacked cache rows (e.g. one per query point or edge).

    ``ids`` is any int array; leaf p-axes are indexed by it, so the result
    stacks cache ids.shape[0] times (duplicates allowed — the blend path
    gathers one row per query per corner). The sharded serving path never
    calls this on the factors (that would be the all-gather it exists to
    avoid); it is the replicated path's tool."""
    return jax.tree.map(lambda a: jnp.take(a, ids, axis=0), cache)

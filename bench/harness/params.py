"""Model parameters made from the seed, in the state a converged fit
would leave them.

Per partition: m inducing points spread over the partition's cell (one
in each of m distinct cells of a k x k subgrid, k = ceil(sqrt m), away
from its edges, as fitted inducing points spread out),
ARD lengthscales, process variance and noise precision drawn from the
config's ranges, and q(u) = N(m_star, S) set to the optimum for those
hyperparameters on the partition's own observations,

    Sigma  = Kmm + beta Kmn Knm
    m_star = beta Kmm Sigma^-1 Kmn y
    S      = Kmm Sigma^-1 Kmm,

in float64, then rounded to the float32 the program serves. The program
and the reference both start from these numbers, so the reference never
uses what the program made.
"""
from __future__ import annotations

import numpy as np

from configs import psvgp_reference as ref


def _rbf64(a, b, ls, var):
    d = (a[..., :, None, :] - b[..., None, :, :]) / ls[..., None, None, :]
    return var[..., None, None] * np.exp(-0.5 * np.sum(d * d, -1))


def seed_params(cfg: dict, grid: ref.Grid, x: np.ndarray, y: np.ndarray, seed: int) -> dict:
    """The stacked float32 parameters (``ref.LEAVES``) for one seed."""
    rng = np.random.default_rng([seed, 1])
    m = int(cfg["num_inducing"])
    jitter = float(cfg["jitter"])
    hp = cfg["seed_params"]
    P = grid.gx * grid.gy
    parts = ref.partitions(grid, x, y)
    p = np.arange(P)
    ix, iy = p % grid.gx, p // grid.gx
    lo = np.stack([grid.x_edges[ix], grid.y_edges[iy]], 1)
    hi = np.stack([grid.x_edges[ix + 1], grid.y_edges[iy + 1]], 1)
    k = int(np.ceil(np.sqrt(m)))
    sub = np.argsort(rng.uniform(size=(P, k * k)), axis=1)[:, :m]
    frac = np.stack([sub % k, sub // k], -1) + 0.25 + 0.5 * rng.uniform(size=(P, m, 2))
    z = lo[:, None, :] + frac / k * (hi - lo)[:, None, :]

    def log_uniform(key, shape):
        a, b = hp[key]
        return rng.uniform(np.log(a), np.log(b), shape)

    log_ls = log_uniform("lengthscale", (P, 2))
    log_var = log_uniform("variance", (P,))
    log_beta = -2.0 * log_uniform("noise_sd", (P,))
    ls, var, beta = np.exp(log_ls), np.exp(log_var), np.exp(log_beta)

    kmm = _rbf64(z, z, ls, var) + jitter * np.eye(m)
    kmn = _rbf64(z, parts.x.astype(np.float64), ls, var) * parts.mask[:, None, :]
    sigma = kmm + beta[:, None, None] * kmn @ np.swapaxes(kmn, 1, 2)
    ky = kmn @ parts.y.astype(np.float64)[..., None]
    m_star = beta[:, None] * (kmm @ np.linalg.solve(sigma, ky))[..., 0]
    s = kmm @ np.linalg.solve(sigma, kmm)
    s = 0.5 * (s + np.swapaxes(s, 1, 2))
    ridge = 1e-9 * np.trace(s, axis1=1, axis2=2)[:, None, None] * np.eye(m)
    ls_chol = np.linalg.cholesky(s + ridge)
    s_tril = np.tril(ls_chol, -1) + np.eye(m) * np.log(np.diagonal(ls_chol, axis1=1, axis2=2))[:, None, :]
    out = {"m_star": m_star, "s_tril": s_tril, "z": z, "log_ls": log_ls,
           "log_var": log_var, "log_beta": log_beta}
    return {k: np.asarray(v, np.float32) for k, v in out.items()}

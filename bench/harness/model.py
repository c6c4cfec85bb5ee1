"""The system under test, wired from a configuration file: the program's
``FitConfig`` and a ``FittedPSVGP`` holding the seed-made parameters."""
from __future__ import annotations

import numpy as np


def fit_config(cfg: dict, fit_seed: int):
    from repro import api

    return api.FitConfig(
        grid=int(cfg["grid"][0]), m=int(cfg["num_inducing"]), delta=float(cfg["delta"]),
        train_iters=int(cfg["iters"]), batch_size=int(cfg["batch_size"]),
        learning_rate=float(cfg["learning_rate"]), seed=int(fit_seed), comm=cfg["comm"],
        covariance=cfg["covariance"], whitened=bool(cfg["whitened"]), jitter=float(cfg["jitter"]),
    )


def fitted(cfg: dict, x: np.ndarray, params: dict, fit_seed: int, adam=None):
    """A ``FittedPSVGP`` at ``params`` (``psvgp_reference.LEAVES`` layout),
    on the grid the program builds over ``x``. ``adam`` = (step, mu, nu)
    gives it the optimizer state of a fit that has run ``step`` steps:
    Adam's count there and its moments ``mu`` and ``nu`` (dicts like
    ``params``); without it the model has no moments, which is how
    ``Server`` serves a loaded artifact."""
    import jax.numpy as jnp

    from repro.api.fitted import FittedPSVGP, _psvgp_config
    from repro.core import psvgp, svgp
    from repro.core.partition import make_grid
    from repro.gp.covariances import CovarianceParams, make_covariance
    from repro.optim import AdamState

    fc = fit_config(cfg, fit_seed)
    leaves = _svgp_params(svgp, CovarianceParams, params)
    static = psvgp.PSVGPStatic(cfg=_psvgp_config(fc), cov_fn=make_covariance(fc.covariance),
                               dist=None, perms=None, p_dir=None)
    if adam is None:
        step = jnp.zeros((), jnp.int32)
        opt = AdamState(step=step, mu=None, nu=None)
    else:
        step = jnp.asarray(adam[0], jnp.int32)
        mu, nu = (_svgp_params(svgp, CovarianceParams, a) for a in adam[1:])
        opt = AdamState(step=step, mu=mu, nu=nu)
    state = psvgp.PSVGPState(params=leaves, opt=opt, step=step)
    return FittedPSVGP(fc, make_grid(x, fc.grid, fc.grid), static, state)


def _svgp_params(svgp, CovarianceParams, leaves: dict):
    import jax.numpy as jnp

    a = {k: jnp.asarray(v, jnp.float32) for k, v in leaves.items()}
    return svgp.SVGPParams(m_star=a["m_star"], s_tril=a["s_tril"], z=a["z"],
                           cov=CovarianceParams(a["log_ls"], a["log_var"]), log_beta=a["log_beta"])


def host_params(p) -> dict:
    """A program ``SVGPParams`` (parameters, or an Adam moment of them) as
    host arrays in the reference's layout."""
    leaves = (p.m_star, p.s_tril, p.z, p.cov.log_lengthscale, p.cov.log_variance, p.log_beta)
    return dict(zip(("m_star", "s_tril", "z", "log_ls", "log_var", "log_beta"),
                    (np.asarray(a) for a in leaves)))

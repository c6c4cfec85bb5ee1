#!/usr/bin/env python3
"""Where the configurations' ``seed_params`` ranges come from: an exact
GP fit, in float64 on the host, of one slice of the benchmark's field.

    python3 bench/fit_hyper.py [--seed 11] [--partitions 60]

For a seeded sample of the 20 x 20 partitions: the RBF (ARD) lengthscales,
the process variance and the noise that maximise the exact marginal
likelihood of the partition's observations of slice 0 (their mean taken
out), by L-BFGS-B from three starts. Prints the quantiles over the
sample; the configurations take the interquartile ranges. Runs on the
host CPU in a minute or two; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))


def neg_log_marginal(theta, x, y):
    ls, var, noise = np.exp(theta[:2]), np.exp(theta[2]), np.exp(theta[3])
    d = (x[:, None, :] - x[None, :, :]) / ls
    k = var * np.exp(-0.5 * np.sum(d * d, -1)) + noise * np.eye(len(x))
    try:
        chol = np.linalg.cholesky(k)
    except np.linalg.LinAlgError:
        return 1e10
    a = np.linalg.solve(chol, y)
    return 0.5 * a @ a + np.sum(np.log(np.diag(chol)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--partitions", type=int, default=60)
    ap.add_argument("--n-obs", type=int, default=48602)
    args = ap.parse_args()
    sys.path.insert(0, BENCH)
    from scipy.optimize import minimize

    from configs import psvgp_reference as ref
    from traffic.field import Field

    field = Field(args.n_obs, args.seed)
    x, y = field.x.astype(np.float64), field.slice(0).astype(np.float64)
    grid = ref.grid_of(field.x, 20, 20)
    pid = ref.cell_ids(grid, field.x)
    rng = np.random.default_rng(0)
    bounds = [(-4, 3), (-4, 3), (-8, 3), (-12, 0)]
    rows = []
    for p in rng.choice(grid.gx * grid.gy, args.partitions, replace=False):
        xp, yp = x[pid == p], y[pid == p]
        yp = yp - yp.mean()
        best = min((minimize(neg_log_marginal, [l0, l0, np.log(yp.var() + 1e-6), np.log(2e-3)],
                             args=(xp, yp), method="L-BFGS-B", bounds=bounds)
                    for l0 in (-1.5, -0.5, 0.5)), key=lambda r: r.fun)
        th = best.x
        rows.append([*np.exp(th[:2]), np.exp(th[2]), np.exp(th[3] / 2)])
    rows = np.asarray(rows)
    q = (5, 10, 25, 50, 75, 90, 95)
    for i, name in enumerate(("lengthscale_x", "lengthscale_y", "variance", "noise_sd")):
        print(name, dict(zip(q, np.round(np.percentile(rows[:, i], q), 4).tolist())))
    return 0


if __name__ == "__main__":
    sys.exit(main())

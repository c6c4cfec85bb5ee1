"""Plain reference for the PSVGP configurations: the blended posterior
and the paper's SGD step, written from the equations in plain
``jax.numpy`` (arXiv 2507.16771 eqs. 3, 8, 9; the bilinear four-corner
blend of the program's serving path). It imports nothing of the program.

Precision: ``mode="highest"`` is float32 with every matrix product at
``Precision.HIGHEST`` (what the configurations state). ``mode="high"``
is the control: every matrix product in three bfloat16 passes (hi*hi +
hi*lo + lo*hi, what XLA calls bf16_3x), written out with explicit
roundings to bfloat16 so that it computes the same on the host and on
the chip. Cholesky factors and triangular
solves are not matrix products and stay float32 in both modes.

Parameters are a dict of stacked (P, ...) arrays, one row per partition:
``m_star`` (P, m), ``s_tril`` (P, m, m) (strict lower part + log of the
diagonal of chol S), ``z`` (P, m, 2), ``log_ls`` (P, 2), ``log_var``
(P,), ``log_beta`` (P,). Leaves are ordered as ``LEAVES``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.scipy.linalg import solve_triangular

LEAVES = ("m_star", "s_tril", "z", "log_ls", "log_var", "log_beta")
MODES = ("highest", "high")
_HI = lax.Precision.HIGHEST
_LOG2PI = 1.8378770664093453
SLOTS = 5  # self, east, west, north, south


# -- geometry ----------------------------------------------------------------


class Grid(NamedTuple):
    x_edges: np.ndarray  # (gx + 1,)
    y_edges: np.ndarray  # (gy + 1,)

    @property
    def gx(self) -> int:
        return len(self.x_edges) - 1

    @property
    def gy(self) -> int:
        return len(self.y_edges) - 1


def grid_of(x: np.ndarray, gx: int, gy: int) -> Grid:
    """A regular grid over the bounding box of the data, its upper edges
    nudged by 1e-6 of the extent so the last points fall inside."""
    lo = x.min(axis=0).astype(np.float64)
    hi = x.max(axis=0).astype(np.float64)
    hi = hi + 1e-6 * np.maximum(hi - lo, 1.0)
    return Grid(np.linspace(lo[0], hi[0], gx + 1), np.linspace(lo[1], hi[1], gy + 1))


def cell_ids(grid: Grid, x: np.ndarray) -> np.ndarray:
    """Row-major (x fastest) cell of each point; outside points clip."""
    ix = np.clip(np.searchsorted(grid.x_edges, x[:, 0], side="right") - 1, 0, grid.gx - 1)
    iy = np.clip(np.searchsorted(grid.y_edges, x[:, 1], side="right") - 1, 0, grid.gy - 1)
    return (iy * grid.gx + ix).astype(np.int64)


def corners(grid: Grid, pts: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The four partitions whose cell centres surround each point (clipped
    at the edges) and their bilinear weights, which sum to 1."""
    cw = grid.x_edges[1] - grid.x_edges[0]
    ch = grid.y_edges[1] - grid.y_edges[0]
    u = (pts[:, 0] - grid.x_edges[0]) / cw - 0.5
    v = (pts[:, 1] - grid.y_edges[0]) / ch - 0.5
    i0 = np.clip(np.floor(u).astype(np.int64), 0, grid.gx - 1)
    j0 = np.clip(np.floor(v).astype(np.int64), 0, grid.gy - 1)
    i1 = np.clip(i0 + 1, 0, grid.gx - 1)
    j1 = np.clip(j0 + 1, 0, grid.gy - 1)
    fx = np.clip(u - i0, 0.0, 1.0)
    fy = np.clip(v - j0, 0.0, 1.0)
    ids = np.stack([j0 * grid.gx + i0, j0 * grid.gx + i1, j1 * grid.gx + i0, j1 * grid.gx + i1], 1)
    w = np.stack([(1 - fx) * (1 - fy), fx * (1 - fy), (1 - fx) * fy, fx * fy], 1)
    return ids, w.astype(np.float32)


def neighbours(grid: Grid) -> np.ndarray:
    """(P, 5) [self, east, west, north, south], -1 where there is none
    (the longitude does not wrap)."""
    P = grid.gx * grid.gy
    p = np.arange(P)
    ix, iy = p % grid.gx, p // grid.gx
    tbl = np.stack([
        p,
        np.where(ix + 1 < grid.gx, p + 1, -1),
        np.where(ix > 0, p - 1, -1),
        np.where(iy + 1 < grid.gy, p + grid.gx, -1),
        np.where(iy > 0, p - grid.gx, -1),
    ], 1)
    return tbl.astype(np.int32)


# -- linear algebra ----------------------------------------------------------


def mm(a, b, mode: str):
    """a @ b at the mode's precision (see the module docstring)."""
    if mode == "highest":
        return jnp.matmul(a, b, precision=_HI)
    if mode != "high":
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")

    def split(t):  # reduce_precision: a rounding XLA may not fold away
        hi = lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
        return hi, lax.reduce_precision(t - hi, exponent_bits=8, mantissa_bits=7)

    a_hi, a_lo = split(a)
    b_hi, b_lo = split(b)
    p = functools.partial(jnp.matmul, precision=_HI)
    return p(a_hi, b_lo) + p(a_lo, b_hi) + p(a_hi, b_hi)


def rbf(x, z, log_ls, log_var):
    """sigma^2 exp(-|x - z|^2_ell / 2) for x (..., n, 2), z (..., m, 2)."""
    inv = jnp.exp(-log_ls)[..., None, None, :]
    d = (x[..., :, None, :] - z[..., None, :, :]) * inv
    return jnp.exp(log_var)[..., None, None] * jnp.exp(-0.5 * jnp.sum(d * d, axis=-1))


def chol_s(s_tril):
    """chol S from its unconstrained form: strict lower part + exp(diag)."""
    m = s_tril.shape[-1]
    eye = jnp.eye(m, dtype=s_tril.dtype)
    return jnp.tril(s_tril, -1) + eye * jnp.exp(jnp.diagonal(s_tril, axis1=-2, axis2=-1))[..., None, :]


def kmm_chol(z, log_ls, log_var, jitter):
    m = z.shape[-2]
    kmm = rbf(z, z, log_ls, log_var)
    return jnp.linalg.cholesky(kmm + jitter * jnp.eye(m, dtype=kmm.dtype))


# -- the served blend --------------------------------------------------------


def _posterior(params, pid, xq, mode, jitter):
    """Mean and variance of partition ``pid``'s q(f) at one point each,
    pid (N,), xq (N, 2), with L = chol(Kmm + jitter I) and its inverse
    formed per point:
        v = L^-1 k,  a = L^-T v = Kmm^-1 k,  t = (chol S)^T a
        mean = a . m_star,  var = k** - |v|^2 + |t|^2."""
    p = {k: v[pid] for k, v in params.items()}
    m = p["z"].shape[-2]
    lmm = kmm_chol(p["z"], p["log_ls"], p["log_var"], jitter)  # (N, m, m)
    eye = jnp.broadcast_to(jnp.eye(m, dtype=lmm.dtype), lmm.shape)
    linv = solve_triangular(lmm, eye, lower=True)
    k = rbf(xq[:, None, :], p["z"], p["log_ls"], p["log_var"])[:, 0, :, None]  # (N, m, 1)
    v = mm(linv, k, mode)
    a = mm(jnp.swapaxes(linv, -1, -2), v, mode)
    t = mm(jnp.swapaxes(chol_s(p["s_tril"]), -1, -2), a, mode)
    mean = mm(jnp.swapaxes(a, -1, -2), p["m_star"][..., None], mode)[:, 0, 0]
    var = jnp.exp(p["log_var"]) - jnp.sum(v[..., 0] ** 2, -1) + jnp.sum(t[..., 0] ** 2, -1)
    return mean, jnp.maximum(var, 1e-12)


@functools.partial(jax.jit, static_argnames=("mode", "jitter"))
def _blend_block(params, ids, w, xq, mode, jitter):
    means, vars_ = [], []
    for c in range(4):
        mu, var = _posterior(params, ids[:, c], xq, mode, jitter)
        means.append(mu)
        vars_.append(var)
    mu = jnp.stack(means, 1)
    var = jnp.stack(vars_, 1)
    mean = jnp.sum(w * mu, 1)
    second = jnp.sum(w * (var + mu * mu), 1)
    return mean, jnp.maximum(second - mean * mean, 1e-12)


def blend(params, grid: Grid, pts: np.ndarray, *, mode: str = "highest",
          jitter: float = 1e-5, block: int = 2048) -> tuple[np.ndarray, np.ndarray]:
    """Blended posterior mean and variance at pts (N, 2), in blocks of
    rows padded to ``block`` (one compiled shape)."""
    pts = np.asarray(pts, np.float32)
    ids, w = corners(grid, pts)
    params = {k: jnp.asarray(v) for k, v in params.items()}
    out_m, out_v = [], []
    with jax.default_matmul_precision("highest"):
        for s in range(0, len(pts), block):
            n = min(block, len(pts) - s)
            pad = block - n
            take = lambda a: np.concatenate([a[s:s + n], np.repeat(a[s:s + 1], pad, 0)])
            mean, var = _blend_block(params, jnp.asarray(take(ids)), jnp.asarray(take(w)),
                                     jnp.asarray(take(pts)), mode, jitter)
            out_m.append(np.asarray(mean)[:n])
            out_v.append(np.asarray(var)[:n])
    return np.concatenate(out_m), np.concatenate(out_v)


# -- the SGD step (eqs. 3, 8, 9) ---------------------------------------------


class Partitions(NamedTuple):
    x: np.ndarray  # (P, n_max, 2) padded, each partition's points in data order
    y: np.ndarray  # (P, n_max)
    mask: np.ndarray  # (P, n_max) 1 on real rows
    counts: np.ndarray  # (P,)


def partitions(grid: Grid, x: np.ndarray, y: np.ndarray, pad_multiple: int = 8) -> Partitions:
    """Padded per-partition storage: rows in data order, n_max rounded up
    to a multiple of 8, padding rows repeating the partition's first point."""
    pid = cell_ids(grid, x)
    P = grid.gx * grid.gy
    counts = np.bincount(pid, minlength=P)
    n_max = -(-int(counts.max()) // pad_multiple) * pad_multiple
    order = np.argsort(pid, kind="stable")
    start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(len(x)) - start[pid[order]]
    xp = np.zeros((P, n_max, 2), np.float32)
    yp = np.zeros((P, n_max), np.float32)
    mp = np.zeros((P, n_max), np.float32)
    xp[pid[order], slot] = x[order]
    yp[pid[order], slot] = y[order]
    mp[pid[order], slot] = 1.0
    first = xp[:, :1, :]
    xp = np.where(mp[..., None] > 0, xp, np.where(counts[:, None, None] > 0, first, 0.0))
    return Partitions(xp.astype(np.float32), yp, mp, counts.astype(np.int64))


def slot_law(counts: np.ndarray, tbl: np.ndarray, delta: float) -> tuple[np.ndarray, np.ndarray]:
    """Eq. (9): P(k' = j) = n_j / n_eff_j, P(k' = k) = delta n_k / n_eff_j
    for each neighbour k, n_eff_j = n_j + delta sum_k n_k. -> (probs, n_eff)."""
    n = np.where(tbl >= 0, counts[np.maximum(tbl, 0)], 0).astype(np.float32)
    n[:, 1:] *= np.float32(delta)
    n_eff = n.sum(1)
    return n / np.maximum(n_eff[:, None], 1e-12), n_eff


def minibatch(key, step: int, parts: Partitions, tbl, probs, batch: int):
    """The step's mini-batch of every partition, from the seed: a source
    partition per eq. (8) by Gumbel-max over the five slots, then B rows of
    it uniformly without replacement (top-B of uniform scores over its
    real rows). The random stream is the paper implementation's:
    fold_in(key, step) split into a slot key and a row key, the row key
    folded with the partition index."""
    k_slot, k_rows = jax.random.split(jax.random.fold_in(key, step))
    P = tbl.shape[0]
    g = jax.random.gumbel(k_slot, (P, SLOTS))
    slot = np.asarray(jnp.argmax(jnp.log(jnp.maximum(jnp.asarray(probs), 1e-30)) + g, axis=1))
    src = tbl[np.arange(P), slot]
    src_mask = parts.mask[src]
    n_max = src_mask.shape[1]

    def rows(p, m):
        u = jax.random.uniform(jax.random.fold_in(k_rows, p), (n_max,))
        return lax.top_k(u + (m - 1.0) * 1e9, batch)[1]

    idx = np.asarray(jax.vmap(rows)(jnp.arange(P), jnp.asarray(src_mask)))
    r = np.arange(P)[:, None]
    return parts.x[src[:, None], idx], parts.y[src[:, None], idx], src_mask[r, idx]


def neg_elbo(p, bx, by, bm, n_eff, mode, jitter):
    """-(eq. 3) for one partition: (n_eff / B_eff) sum of the expected
    Gaussian log-likelihood over the real rows of the mini-batch, minus
    KL(q(u) || p(u)). Unwhitened q(u) = N(m_star, S)."""
    m = p["z"].shape[0]
    lmm = kmm_chol(p["z"], p["log_ls"], p["log_var"], jitter)
    knm = rbf(bx, p["z"], p["log_ls"], p["log_var"])  # (B, m)
    v = solve_triangular(lmm, knm.T, lower=True)  # (m, B)
    a = solve_triangular(lmm.T, v, lower=False)  # Kmm^-1 k_i
    sl = chol_s(p["s_tril"])
    fmean = mm(a.T, p["m_star"][:, None], mode)[:, 0]
    t = mm(sl.T, a, mode)
    fvar = jnp.maximum(jnp.exp(p["log_var"]) - jnp.sum(v * v, 0) + jnp.sum(t * t, 0), 1e-12)
    beta = jnp.exp(p["log_beta"])
    ll = 0.5 * p["log_beta"] - 0.5 * _LOG2PI - 0.5 * beta * (by - fmean) ** 2 - 0.5 * beta * fvar
    b_eff = jnp.maximum(jnp.sum(bm), 1.0)
    lik = n_eff / b_eff * jnp.sum(ll * bm)
    w = solve_triangular(lmm, sl, lower=True)
    u = solve_triangular(lmm, p["m_star"], lower=True)
    kl = 0.5 * (jnp.sum(w * w) + jnp.sum(u * u) - m
                + 2.0 * jnp.sum(jnp.log(jnp.diagonal(lmm))) - 2.0 * jnp.sum(jnp.diagonal(p["s_tril"])))
    return -(lik - kl)


@functools.partial(jax.jit, static_argnames=("mode", "jitter"))
def _grads(params, bx, by, bm, n_eff, mode, jitter):
    f = jax.value_and_grad(lambda p, *a: neg_elbo(p, *a, mode, jitter))
    return jax.vmap(f)(params, bx, by, bm, n_eff)


def adam(params, grads, mu, nu, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One Adam step (Kingma & Ba); t is the step's count, from 1."""
    mu = {k: b1 * mu[k] + (1 - b1) * grads[k] for k in params}
    nu = {k: b2 * nu[k] + (1 - b2) * grads[k] ** 2 for k in params}
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    new = {k: params[k] - lr * (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + eps) for k in params}
    return new, mu, nu


_adam = jax.jit(adam, static_argnames=("lr",))


def _batch_grads(params, parts, tbl, probs, n_eff, key, step, batch, mode, jitter):
    """Loss and gradients of every partition on the mini-batch of ``step``."""
    bx, by, bm = minibatch(key, step, parts, tbl, probs, batch)
    return _grads(params, jnp.asarray(bx), jnp.asarray(by), jnp.asarray(bm),
                  jnp.asarray(n_eff), mode, jitter)


def moments(params, x, y, grid: Grid, *, seed: int, first_step: int, window: int, delta: float,
            batch: int, b1: float = 0.9, b2: float = 0.999, mode: str = "highest",
            jitter: float = 1e-5):
    """Adam's moments as a fit at ``params`` holds them after ``first_step``
    steps, from their definition over the gradients of the ``window``
    mini-batches before it (steps first_step - window .. first_step - 1):
    mu ~ (1 - b1^t) E[g] and nu ~ (1 - b2^t) E[g^2], t = first_step."""
    tbl = neighbours(grid)
    key = jax.random.PRNGKey(seed)
    parts = partitions(grid, x, y)
    probs, n_eff = slot_law(parts.counts, tbl, delta)
    params = {k: jnp.asarray(v) for k, v in params.items()}
    g1 = {k: np.zeros(v.shape, np.float64) for k, v in params.items()}
    g2 = {k: np.zeros(v.shape, np.float64) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        for step in range(first_step - window, first_step):
            _, g = _batch_grads(params, parts, tbl, probs, n_eff, key, step, batch, mode, jitter)
            for k, v in g.items():
                v = np.asarray(v, np.float64)
                g1[k] += v / window
                g2[k] += v * v / window
    t = first_step
    mu = {k: ((1 - b1 ** t) * v).astype(np.float32) for k, v in g1.items()}
    nu = {k: ((1 - b2 ** t) * v).astype(np.float32) for k, v in g2.items()}
    return mu, nu


def sgd(params, x, y, grid: Grid, *, steps: int, seed: int, first_step: int, mu0: dict,
        nu0: dict, delta: float, batch: int, lr: float, mode: str = "highest",
        jitter: float = 1e-5):
    """``steps`` steps of the paper's SGD from ``params`` on one slice
    ``y`` at the locations ``x``. The optimizer state going in is a fit's
    after ``first_step`` steps: Adam's count at ``first_step``, its
    moments ``mu0`` and ``nu0``; the step counter that seeds the
    mini-batches starts there too. Returns (losses, first gradients,
    params after the last step, Adam's second moments after it), all on
    the host."""
    tbl = neighbours(grid)
    key = jax.random.PRNGKey(seed)
    parts = partitions(grid, x, y)
    probs, n_eff = slot_law(parts.counts, tbl, delta)
    params = {k: jnp.asarray(v) for k, v in params.items()}
    mu = {k: jnp.asarray(mu0[k], jnp.float32) for k in params}
    nu = {k: jnp.asarray(nu0[k], jnp.float32) for k in params}
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            loss, g = _batch_grads(params, parts, tbl, probs, n_eff, key, first_step + i, batch,
                                   mode, jitter)
            if first is None:
                first = {k: np.asarray(v) for k, v in g.items()}
            losses.append(loss)
            params, mu, nu = _adam(params, g, mu, nu, first_step + i + 1, lr)
    losses = [float(jnp.mean(v)) for v in losses]
    host = lambda d: {k: np.asarray(v) for k, v in d.items()}  # noqa: E731
    return losses, first, host(params), host(nu)

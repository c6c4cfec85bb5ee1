"""Post-hoc blended prediction — beyond-paper (the paper's §6 names
"post-hoc methods to increase boundary smoothness, for example based on
the patchwork kriging approach" as future work; this is the lightweight
variant of that idea).

Instead of asking ONE local model for the prediction at x, the stitched
surface blends the (up to) four models whose partition centers surround x,
with bilinear weights in cell-center coordinates. The blend is continuous
across partition boundaries BY CONSTRUCTION (weights of a model go to zero
exactly where its neighbor takes over), so the boundary-RMSD discontinuity
of ISVGP/PSVGP drops to zero at stitch time — at ZERO training cost and
with no extra communication (each model still predicts only near its own
territory; evaluating a neighbor's model at a point near the shared
boundary is local to that neighbor's rank in production).

Variances combine as the blend of second moments (a conservative mixture
bound): var = sum_i w_i (var_i + mean_i^2) - mean^2.

Serving path: evaluation runs against a ``repro.core.posterior``
PosteriorCache — the P local posteriors are factorized ONCE (O(P m^3),
amortized across every query batch; pass ``cache=`` to amortize across
calls too), and each corner is then one batched vmap of O(m^2) cached-
factor evaluations. The seed implementation re-ran a full Cholesky per
query point per corner; at the paper's P=400 / m=25 scale the cached path
is the difference between an analysis script and a serving endpoint (see
benchmarks/bench_predict.py, launch/serve.py --gp).
"""
from __future__ import annotations

import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import posterior
from repro.core.partition import PartitionGrid
from repro.core.psvgp import PSVGPState, PSVGPStatic, posterior_cache
from repro.obs import span


def corner_ids_weights(grid: PartitionGrid, pts: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The 4 surrounding partition models of each point + bilinear weights.

    This is the geometric core of both the blended predictor below and the
    distributed query router (``repro.core.routing``): a point x is blended
    from the (up to) four partitions whose CELL CENTERS surround it.

    Args:
      grid: the partition grid topology.
      pts: (N, 2) query coordinates (host numpy; routing is host-side).

    Returns:
      ids (N, 4) int64: flat partition ids of the corner models, ordered
        [lower-left, lower-right, upper-left, upper-right] in cell-center
        coordinates. At domain edges the out-of-grid corners are CLIPPED
        onto the boundary cells, so ids may repeat within a row — the
        bilinear weights of clipped duplicates are consistent (they sum to
        the same total mass; the blend degenerates to linear/nearest at
        edges by construction).
      w (N, 4) float32: bilinear weights, >= 0, summing to 1 per row.

    Every corner id is always within one grid step (including diagonals) of
    the cell that OWNS the point — the invariant that lets distributed
    serving resolve corners with a 1-hop halo exchange (see
    ``repro.core.routing.halo_ids``).
    """
    xe, ye = grid.x_edges, grid.y_edges
    cw = xe[1] - xe[0]
    ch = ye[1] - ye[0]
    # cell-center coordinates: center of cell (i) is at x0 + (i + .5) cw
    u = (pts[:, 0] - xe[0]) / cw - 0.5
    v = (pts[:, 1] - ye[0]) / ch - 0.5
    ix0 = np.clip(np.floor(u).astype(np.int64), 0, grid.gx - 1)
    iy0 = np.clip(np.floor(v).astype(np.int64), 0, grid.gy - 1)
    ix1 = np.clip(ix0 + 1, 0, grid.gx - 1)
    iy1 = np.clip(iy0 + 1, 0, grid.gy - 1)
    fx = np.clip(u - ix0, 0.0, 1.0)
    fy = np.clip(v - iy0, 0.0, 1.0)
    ids = np.stack(
        [
            iy0 * grid.gx + ix0,
            iy0 * grid.gx + ix1,
            iy1 * grid.gx + ix0,
            iy1 * grid.gx + ix1,
        ],
        axis=1,
    )  # (N, 4)
    w = np.stack(
        [(1 - fx) * (1 - fy), fx * (1 - fy), (1 - fx) * fy, fx * fy], axis=1
    ).astype(np.float32)
    return ids, w


@functools.partial(jax.jit, static_argnames=("cov_fn",))
def _blend_eval(
    cache: posterior.PosteriorCache,
    cov_fn: Callable,
    xq: jnp.ndarray,
    ids: jnp.ndarray,
    w: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """All N points against all 4 corners — cached factors only, no
    factorization anywhere inside."""

    def eval_corner(c):
        cache_c = posterior.take_cache(cache, ids[:, c])  # leaves (N, ...)

        def one(ca, xi):
            mean, var = posterior.predict_cached(ca, cov_fn, xi[None])
            return mean[0], var[0]

        return jax.vmap(one)(cache_c, xq)

    means, varis = zip(*(eval_corner(c) for c in range(4)), strict=True)
    means = jnp.stack(means, axis=1)  # (N, 4)
    varis = jnp.stack(varis, axis=1)
    mean = jnp.sum(w * means, axis=1)
    second = jnp.sum(w * (varis + means**2), axis=1)
    var = jnp.maximum(second - mean**2, 1e-12)
    return mean, var


def predict_blended(
    static: PSVGPStatic,
    state: PSVGPState,
    grid: PartitionGrid,
    points: jnp.ndarray,
    cache: posterior.PosteriorCache | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Continuous stitched prediction at arbitrary points.

    Args:
      static / state: the trained PSVGP bundle (``psvgp.build`` / ``fit``).
      grid: partition grid the state was trained on.
      points: (N, 2) query coordinates (any array-like; moved to host).
      cache: optional precomputed ``psvgp.posterior_cache``. Pass it when
        issuing repeated query batches against one trained state — the
        serving loop in ``repro.launch.serve --gp`` does exactly that.

    Returns:
      (mean (N,), var (N,)): the bilinear 4-corner blend of the local
      posteriors. var >= 1e-12 (clamped), WITHOUT observation noise.

    This is the replicated serving path: the full cache is resident on the
    calling host. The sharded multi-host equivalent (same math, cache
    factors partitioned over a device mesh) is
    ``repro.launch.serve_sharded``."""
    pts = np.asarray(points, np.float32)
    with span("blend.corners"):
        ids, w = corner_ids_weights(grid, pts)
    if cache is None:
        cache = posterior_cache(static, state)
    with span("blend.transfer"):
        args = jnp.asarray(pts), jnp.asarray(ids), jnp.asarray(w)
    with span("blend.launch"):
        return _blend_eval(cache, static.cov_fn, *args)

"""Device-sharded PSVGP — the production shard_map program (DESIGN.md §2).

Layout: ONE partition per device. The partition grid (gx x gy) is mapped
onto the physical mesh so that grid x-steps are shifts along the ``model``
mesh axis and grid y-steps are shifts along the (``pod`` x) ``data`` axes:

    partition (ix, iy)  <->  device (pod = iy // data, data = iy % data, model = ix)

East/west exchange is then a ``lax.ppermute`` along ``model``; north/south a
``lax.ppermute`` along the flattened (``pod``, ``data``) product axis — i.e.
every step costs exactly ONE collective-permute of one mini-batch per device
(the paper's "communicates with at most one of its neighbors per iteration"
mapped onto the ICI torus). The optimizer state and variational parameters
never move; only B-point mini-batches do (zero memory overhead, as the
paper claims).

Math is bit-identical to ``psvgp.train_step_ppermute`` (same fold_in key
streams) — tested in tests/test_psvgp_spmd.py.
"""
from __future__ import annotations

import functools
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import svgp
from repro.core.partition import PartitionGrid
from repro.core.psvgp import PSVGPConfig, PSVGPState, _loss_one
from repro.core.sampler import sample_row_indices
from repro.optim import adam_update


def _row_axes(axes: Sequence[str]) -> tuple[str, ...]:
    """Mesh axes carrying the grid's y coordinate (all but the last)."""
    return tuple(axes[:-1])


def grid_matches_mesh(grid: PartitionGrid, mesh: Mesh, axes: Sequence[str]) -> bool:
    gx = mesh.shape[axes[-1]]
    gy = int(np.prod([mesh.shape[a] for a in _row_axes(axes)]))
    return grid.gx == gx and grid.gy == gy


def shift_perm(n: int, up: bool) -> list[tuple[int, int]]:
    """(src, dst) ppermute pairs for 'receive from index+1' (up) or
    'index-1'; edge devices receive nothing (ppermute zero-fills them).

    Public API: the serving halo exchange (``repro.launch.serve_sharded``)
    builds its 3x3 neighborhood from the same permutation tables the
    training exchange uses, which is what keeps the two communication
    patterns provably identical."""
    if up:
        return [(i + 1, i) for i in range(n - 1)]
    return [(i - 1, i) for i in range(1, n)]



def make_spmd_step(
    mesh: Mesh,
    axes: Sequence[str],
    grid: PartitionGrid,
    cfg: PSVGPConfig,
    cov_fn: Callable,
    p_dir: jnp.ndarray,
):
    """Build the jitted, shard_map'd PSVGP train step.

    Arguments at call time (all sharded over the partition axis):
      state (PSVGPState with leading P axis), key, x (P,n,d), y (P,n),
      mask (P,n), probs (P,5), n_eff (P,).
    Returns (state, mean weighted loss).
    """
    if not grid_matches_mesh(grid, mesh, axes):
        raise ValueError(
            f"grid {grid.gx}x{grid.gy} must equal mesh axes {axes} "
            f"{[mesh.shape[a] for a in axes]} (one partition per device)"
        )
    if grid.wrap_x:
        raise NotImplementedError("wrapped grids need ring perms; default grids are unwrapped")
    gx, gy = grid.gx, grid.gy
    col_axis = axes[-1]
    row_axes = _row_axes(axes)
    B = cfg.batch_size

    def device_pid():
        """Flat partition id of this device: iy * gx + ix."""
        ix = jax.lax.axis_index(col_axis)
        iy = jax.lax.axis_index(row_axes) if len(row_axes) > 1 else jax.lax.axis_index(row_axes[0])
        return iy * gx + ix

    def exchange(payload, d):
        """Receive the neighbor-in-direction-d's payload (zeros at edges).

        Directions follow repro.core.neighbors slots:
          1=east (+x), 2=west (-x), 3=north (+y), 4=south (-y).
        """

        def self_(p):
            return p

        def east(p):
            return jax.tree.map(
                lambda a: jax.lax.ppermute(a, col_axis, shift_perm(gx, up=True)), p
            )

        def west(p):
            return jax.tree.map(
                lambda a: jax.lax.ppermute(a, col_axis, shift_perm(gx, up=False)), p
            )

        def north(p):
            ax = row_axes if len(row_axes) > 1 else row_axes[0]
            return jax.tree.map(lambda a: jax.lax.ppermute(a, ax, shift_perm(gy, up=True)), p)

        def south(p):
            ax = row_axes if len(row_axes) > 1 else row_axes[0]
            return jax.tree.map(lambda a: jax.lax.ppermute(a, ax, shift_perm(gy, up=False)), p)

        return jax.lax.switch(d, (self_, east, west, north, south), payload)

    def step_shard(state, key, x_l, y_l, m_l, probs_l, neff_l):
        # local block shapes: x_l (1, n_max, dim), probs_l (1, 5), params (1, ...)
        pid = device_pid()
        kd, kb = jax.random.split(jax.random.fold_in(key, state.step))
        d = jax.random.categorical(kd, jnp.log(jnp.maximum(p_dir, 1e-30)))  # global
        idx, bm = sample_row_indices(jax.random.fold_in(kb, pid), m_l[0], B)
        bx = jnp.take(x_l[0], idx, axis=0)  # (B, dim)
        by = jnp.take(y_l[0], idx, axis=0)
        # ONE collective: ship mini-batches one hop against direction d.
        bx, by, bm = exchange((bx, by, bm), d)
        w = probs_l[0, d] / jnp.maximum(p_dir[d], 1e-30)  # importance weight

        params_one = jax.tree.map(lambda a: a[0], state.params)
        loss_fn = functools.partial(_loss_one, cov_fn=cov_fn, scfg=cfg.svgp)
        loss, grads = jax.value_and_grad(loss_fn)(
            params_one, bx=bx, by=by, bm=bm, n_eff=neff_l[0], ll_weight=w
        )
        grads = jax.tree.map(lambda g: g[None], grads)
        new_params, new_opt = adam_update(state.params, grads, state.opt, lr=cfg.learning_rate)
        new_state = PSVGPState(new_params, new_opt, state.step + 1)
        mean_loss = jax.lax.pmean(loss, tuple(axes))
        return new_state, mean_loss

    from repro.gp.covariances import CovarianceParams
    from repro.optim import AdamState

    pspec = P(tuple(axes))  # leading partition axis over the whole mesh
    params_like = svgp.SVGPParams(
        m_star=pspec, s_tril=pspec, z=pspec,
        cov=CovarianceParams(log_lengthscale=pspec, log_variance=pspec),
        log_beta=pspec,
    )
    state_specs = PSVGPState(
        params=params_like,
        opt=AdamState(step=P(), mu=params_like, nu=params_like),
        step=P(),
    )

    step_fn = jax.shard_map(
        step_shard,
        mesh=mesh,
        in_specs=(state_specs, P(), pspec, pspec, pspec, pspec, pspec),
        out_specs=(state_specs, P()),
        check_vma=False,
    )
    return jax.jit(step_fn)

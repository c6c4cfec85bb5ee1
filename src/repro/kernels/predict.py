"""Pallas TPU kernel: fused cached-posterior prediction (serving hot path).

Per (block_q x m_pad) tile and in ONE VMEM residency of the query block:
    knm  = K(X*, Z)                      (VPU, explicit-diff RBF)
    mean = knm @ c                       (VPU reduction against resident c)
    lk   = knm @ W^T                     (MXU, W = Lmm^{-1} resident)
    su   = knm @ U^T                     (MXU, U = S-factor resident)
    var  = k_** - rowsum(lk^2) + rowsum(su^2)

The unfused path writes knm to HBM and reads it back TWICE (once per
projection); fusing removes both (Q x m_pad) round-trips and never
materializes lk/su in HBM at all — the kernel's only HBM traffic is the
query block in and two (Q,) vectors out. W, U and c stay resident in VMEM
across the whole grid (2 m_pad^2 + m_pad floats; m_pad <= 256 -> <= 513 KiB).

Same alignment contract as ``svgp_proj``: caller pads Q to the block, m to
the 128-lane width, and zero-pads W/U/c so padded inducing slots are inert
(zero COLUMNS of W/U kill the garbage knm columns; zero c entries kill them
in the mean). k_** for the stationary RBF is the process variance, exact
regardless of padding. Dispatch + padding live in ``kernels/ops.py``.

``posterior_predict_slots_pallas`` is the slot-stacked variant for the
SHARDED serving program: one launch whose grid spans (S halo slots x
q-blocks), evaluating the local model on all S stacked query blocks while
W, U and c stay resident in VMEM across the WHOLE (S x Qb) grid — the
factors are staged into VMEM once per request instead of once per slot,
and the (9*q_max, d) reshape round-trip of the unstacked call disappears.

Masking/row-mix contract (what lets TWO-LEVEL routing reuse this kernel
unchanged): both kernel bodies are strictly ROW-INDEPENDENT — output row
i is a function of input row i and the resident W/U/c only (the row-sum
reductions run along the m axis, never across queries). A block may
therefore freely mix owner rows, spilled-in neighbor rows and padded
placeholder rows; validity lives entirely in the caller's qmask /
corner-weight zeros, and the oracle for the masked semantics is
``ref.posterior_predict_slots_masked`` (held to the kernel in
tests/test_posterior.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _predict_kernel_body(
    x_ref, z_ref, invl_ref, var_ref, w_ref, u_ref, c_ref, mean_ref, fvar_ref
):
    x = x_ref[...]  # (bq, d)
    z = z_ref[...]  # (m, d)
    inv_l = invl_ref[...]  # (1, d)
    xs = x * inv_l
    zs = z * inv_l
    diff = xs[:, None, :] - zs[None, :, :]
    r2 = jnp.sum(diff * diff, axis=-1)  # (bq, m)
    var = var_ref[0, 0]
    knm = var * jnp.exp(-0.5 * r2)
    # VPU: mean = knm @ c with c resident as a (1, m) row.
    mean_ref[...] = jnp.sum(knm * c_ref[...], axis=-1, keepdims=True)
    # MXU: two (bq, m) @ (m, m) projections at full f32 precision — the
    # variance below cancels, so bf16 passes would swamp it.
    lk = jax.lax.dot_general(
        knm, w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),  # knm @ W^T
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(knm.dtype)
    su = jax.lax.dot_general(
        knm, u_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),  # knm @ U^T
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(knm.dtype)
    fvar_ref[...] = (
        var
        - jnp.sum(lk * lk, axis=-1, keepdims=True)
        + jnp.sum(su * su, axis=-1, keepdims=True)
    )


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def posterior_predict_pallas(
    x: jnp.ndarray,
    z: jnp.ndarray,
    log_lengthscale: jnp.ndarray,
    log_variance: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,
    c: jnp.ndarray,
    *,
    block_q: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x (Q, d), z (m, d), w/u (m, m), c (m,) -> (mean (Q,), fvar (Q,)).

    Caller contract: Q % block_q == 0, m % 128 == 0, and w/u/c are
    ZERO-PADDED outside the true m_true block (see module docstring).
    """
    Q, d = x.shape
    m, _ = z.shape
    grid = (Q // block_q,)
    inv_l = jnp.exp(-log_lengthscale).reshape(1, d).astype(x.dtype)
    var = jnp.exp(log_variance).reshape(1, 1).astype(x.dtype)
    c_row = c.reshape(1, m).astype(x.dtype)
    mean, fvar = pl.pallas_call(
        _predict_kernel_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((m, m), lambda i: (0, 0)),  # W resident across grid
            pl.BlockSpec((m, m), lambda i: (0, 0)),  # U resident across grid
            pl.BlockSpec((1, m), lambda i: (0, 0)),  # c resident across grid
        ],
        out_specs=[
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1), x.dtype),
            jax.ShapeDtypeStruct((Q, 1), x.dtype),
        ],
        interpret=interpret,
    )(x, z, inv_l, var, w, u, c_row)
    return mean[:, 0], fvar[:, 0]


def _predict_slots_kernel_body(
    x_ref, z_ref, invl_ref, var_ref, w_ref, u_ref, c_ref, mean_ref, fvar_ref
):
    x = x_ref[0]  # (bq, d): this (slot, q-block) grid cell's queries
    z = z_ref[...]  # (m, d)
    inv_l = invl_ref[...]  # (1, d)
    xs = x * inv_l
    zs = z * inv_l
    diff = xs[:, None, :] - zs[None, :, :]
    r2 = jnp.sum(diff * diff, axis=-1)  # (bq, m)
    var = var_ref[0, 0]
    knm = var * jnp.exp(-0.5 * r2)
    mean_ref[0] = jnp.sum(knm * c_ref[...], axis=-1, keepdims=True)
    lk = jax.lax.dot_general(
        knm, w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),  # knm @ W^T
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(knm.dtype)
    su = jax.lax.dot_general(
        knm, u_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),  # knm @ U^T
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(knm.dtype)
    fvar_ref[0] = (
        var
        - jnp.sum(lk * lk, axis=-1, keepdims=True)
        + jnp.sum(su * su, axis=-1, keepdims=True)
    )


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def posterior_predict_slots_pallas(
    hx: jnp.ndarray,
    z: jnp.ndarray,
    log_lengthscale: jnp.ndarray,
    log_variance: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,
    c: jnp.ndarray,
    *,
    block_q: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """hx (S, Q, d) slot-stacked queries -> (mean (S, Q), fvar (S, Q)).

    Grid = (S, Q // block_q): one launch covers every halo slot. The slot
    axis only moves the query BlockSpec — z/W/U/c index maps are constant,
    so the factors stay resident across the entire grid.

    Caller contract: Q % block_q == 0, m % 128 == 0, and w/u/c ZERO-PADDED
    outside the true m_true block (see module docstring).
    """
    S, Q, d = hx.shape
    m, _ = z.shape
    grid = (S, Q // block_q)
    inv_l = jnp.exp(-log_lengthscale).reshape(1, d).astype(hx.dtype)
    var = jnp.exp(log_variance).reshape(1, 1).astype(hx.dtype)
    c_row = c.reshape(1, m).astype(hx.dtype)
    mean, fvar = pl.pallas_call(
        _predict_slots_kernel_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda s, i: (s, i, 0)),
            pl.BlockSpec((m, d), lambda s, i: (0, 0)),
            pl.BlockSpec((1, d), lambda s, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda s, i: (0, 0)),
            pl.BlockSpec((m, m), lambda s, i: (0, 0)),  # W resident across grid
            pl.BlockSpec((m, m), lambda s, i: (0, 0)),  # U resident across grid
            pl.BlockSpec((1, m), lambda s, i: (0, 0)),  # c resident across grid
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, 1), lambda s, i: (s, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda s, i: (s, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, Q, 1), hx.dtype),
            jax.ShapeDtypeStruct((S, Q, 1), hx.dtype),
        ],
        interpret=interpret,
    )(hx, z, inv_l, var, w, u, c_row)
    return mean[..., 0], fvar[..., 0]

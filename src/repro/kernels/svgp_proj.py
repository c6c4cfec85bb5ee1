"""Pallas TPU kernel: fused SVGP projection — the ELBO's O(B m^2) hot path.

Fuses, per (block_b x m_pad) tile and in one VMEM residency of X:
    knm    = K(X, Z)                      (VPU, explicit-diff RBF)
    lk_t   = knm @ W^T                    (MXU, W = Lmm^{-1} resident)
    q_diag = row-sums of lk_t^2           (VPU reduction)

The unfused path writes knm to HBM and reads it back for the projection;
fusing removes a full (B x m_pad) HBM round-trip — that is the memory-term
optimization the roofline analysis attributes to this kernel. W stays
resident in VMEM across the whole grid (m_pad <= 256 -> <= 256 KiB).

The triangular solve producing W and the (m x m) Cholesky stay in XLA: one
128-lane tile of work, nothing for a custom kernel to win there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _proj_kernel_body(x_ref, z_ref, invl_ref, var_ref, w_ref, knm_ref, lkt_ref, qd_ref):
    x = x_ref[...]  # (bb, d)
    z = z_ref[...]  # (m, d)
    inv_l = invl_ref[...]  # (1, d)
    xs = x * inv_l
    zs = z * inv_l
    diff = xs[:, None, :] - zs[None, :, :]
    r2 = jnp.sum(diff * diff, axis=-1)  # (bb, m)
    knm = var_ref[0, 0] * jnp.exp(-0.5 * r2)
    knm_ref[...] = knm
    # MXU: (bb, m) @ (m, m) at full f32 precision: q_diag feeds the
    # cancelling k~ = k - q_diag of the ELBO.
    lkt = jax.lax.dot_general(
        knm,
        w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),  # knm @ W^T
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(knm.dtype)
    lkt_ref[...] = lkt
    qd_ref[...] = jnp.sum(lkt * lkt, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def svgp_projection_pallas(
    x: jnp.ndarray,
    z: jnp.ndarray,
    log_lengthscale: jnp.ndarray,
    log_variance: jnp.ndarray,
    w: jnp.ndarray,
    *,
    block_b: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x (B, d), z (m, d), w (m, m) -> (knm (B,m), lk_t (B,m), q_diag (B,)).

    Caller contract: B % block_b == 0, m % 128 == 0, and w is ZERO-PADDED
    outside the true (m_true, m_true) block — zero rows/cols make padded
    inducing slots exactly inert in lk_t and q_diag (knm's padded columns
    are garbage by design; callers must mask them, ops.py does).
    """
    B, d = x.shape
    m, _ = z.shape
    grid = (B // block_b,)
    inv_l = jnp.exp(-log_lengthscale).reshape(1, d).astype(x.dtype)
    var = jnp.exp(log_variance).reshape(1, 1).astype(x.dtype)
    knm, lkt, qd = pl.pallas_call(
        _proj_kernel_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((m, m), lambda i: (0, 0)),  # W resident across grid
        ],
        out_specs=[
            pl.BlockSpec((block_b, m), lambda i: (i, 0)),
            pl.BlockSpec((block_b, m), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, m), x.dtype),
            jax.ShapeDtypeStruct((B, m), x.dtype),
            jax.ShapeDtypeStruct((B, 1), x.dtype),
        ],
        interpret=interpret,
    )(x, z, inv_l, var, w)
    return knm, lkt, qd[:, 0]

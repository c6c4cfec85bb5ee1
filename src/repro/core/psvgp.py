"""PSVGP — the paper's contribution (§4): N_part local SVGPs trained with
delta-weighted neighbor sampling and decentralized communication.

Two communication modes (DESIGN.md §2):

* ``comm="gather"``  — paper-faithful: every partition independently samples
  its own source partition k' ~ eq. (9) and the mini-batch is materialized
  by a cross-partition gather. On one host this is exactly the paper's
  algorithm; under SPMD it lowers to a small all-gather.

* ``comm="ppermute"`` — TPU-native: one globally shared direction per step,
  mini-batches exchanged with a single ``lax.ppermute`` (ICI collective-
  permute = decentralized point-to-point), unbiasedness restored via
  importance weights pi_j(d)/p(d). Available both as a single-host
  simulation (bit-identical math) and as a true shard_map program
  (``repro.launch.dryrun`` lowers it on the production mesh).

The per-partition models are the ``repro.core.svgp`` SVGP; everything is
stacked on a leading partition axis and vmapped, so one XLA program trains
all 400 partitions at once — the SPMD analogue of the paper's MPI ranks.

Prediction is served through the ``repro.core.posterior`` PosteriorCache:
``posterior_cache`` factorizes all P local posteriors once (per trained
state), and ``predict_local`` / ``predict_at_partitions`` /
``blend.predict_blended`` evaluate O(m^2) against those cached factors —
the serving path for the paper's E3SM in-situ setting. Entry points:
``repro.launch.serve --gp`` (batched query loop with latency/throughput
report) and ``benchmarks.bench_predict`` (cached-vs-seed speedup gate).
"""
from __future__ import annotations

import functools
from collections.abc import Callable
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import posterior, svgp
from repro.core.neighbors import direction_permutations, neighbor_table
from repro.core.partition import PartitionedData
from repro.core.sampler import (
    SlotDistribution,
    gather_minibatch,
    sample_minibatch_indices,
    sample_slots,
    slot_distribution,
)
from repro.gp.covariances import make_covariance
from repro.optim import AdamState, adam_init, adam_update


class PSVGPConfig(NamedTuple):
    svgp: svgp.SVGPConfig
    delta: float = 0.0  # eq. (9): 0 = ISVGP, 1 = full PSVGP
    batch_size: int = 32
    learning_rate: float = 0.02
    comm: str = "gather"  # "gather" | "ppermute"
    seed: int = 0


class PSVGPState(NamedTuple):
    params: svgp.SVGPParams  # every leaf has leading (P, ...) axis
    opt: AdamState
    step: jnp.ndarray  # () int32


class PSVGPStatic(NamedTuple):
    """Static (host-side) companions to the jitted step functions."""

    cfg: PSVGPConfig
    cov_fn: Callable
    dist: SlotDistribution
    perms: jnp.ndarray  # (5, P) direction permutations (ppermute mode)
    p_dir: jnp.ndarray  # (5,) global direction probabilities (ppermute mode)


def build(cfg: PSVGPConfig, data: PartitionedData) -> PSVGPStatic:
    """Precompute topology-dependent tables from the partition grid."""
    tbl = jnp.asarray(neighbor_table(data.grid))
    dist = slot_distribution(data.counts, tbl, cfg.delta)
    perms = jnp.asarray(direction_permutations(data.grid))
    # Global direction distribution for the ppermute mode: the average of the
    # per-partition slot distributions (minimizes the spread of the
    # importance weights pi_j(d)/p(d) around 1).
    p_dir = jnp.mean(dist.probs, axis=0)
    p_dir = p_dir / jnp.sum(p_dir)
    return PSVGPStatic(cfg=cfg, cov_fn=make_covariance(cfg.svgp.covariance), dist=dist, perms=perms, p_dir=p_dir)


def init(key: jax.Array, cfg: PSVGPConfig, data: PartitionedData) -> PSVGPState:
    P = data.num_partitions
    keys = jax.random.split(key, P)
    init_one = functools.partial(svgp.init_svgp_params, cfg=cfg.svgp)
    # mask keeps inducing-point sampling on each partition's VALID rows —
    # padded rows replicate the first point and would collapse Kmm.
    params = jax.vmap(lambda k, x, mk: init_one(k, x_init=x, mask=mk))(
        keys, data.x, data.mask
    )
    return PSVGPState(params=params, opt=adam_init(params), step=jnp.zeros((), jnp.int32))


def _loss_one(params, cov_fn, bx, by, bm, n_eff, scfg: svgp.SVGPConfig, ll_weight=1.0):
    return -svgp.elbo(
        params,
        cov_fn,
        bx,
        by,
        mask=bm,
        n_total=n_eff,
        jitter=scfg.jitter,
        whitened=scfg.whitened,
        use_pallas=scfg.use_pallas,
        ll_weight=ll_weight,
        likelihood=scfg.likelihood,
    )


# --------------------------------------------------------------------------
# Paper-faithful mode: independent neighbor choice per partition (eq. 8/9).
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "cov_fn"))
def train_step_gather(
    state: PSVGPState,
    key: jax.Array,
    x: jnp.ndarray,
    y: jnp.ndarray,
    mask: jnp.ndarray,
    dist: SlotDistribution,
    cfg: PSVGPConfig,
    cov_fn: Callable,
) -> tuple[PSVGPState, jnp.ndarray]:
    """One SGD iteration of the paper's algorithm for all partitions at once.

    Communication pattern: partition j pulls a B-point mini-batch from its
    sampled source k'_j — at most ONE neighbor per iteration (the paper's
    key communication bound).
    """
    k_slot, k_batch = jax.random.split(jax.random.fold_in(key, state.step))
    kprime, _slot = sample_slots(k_slot, dist)  # (P,)
    src_mask = jnp.take(mask, kprime, axis=0)  # (P, n_max)
    idx, _ = sample_minibatch_indices(k_batch, src_mask, cfg.batch_size)
    bx, by, bm = gather_minibatch(x, y, mask, kprime, idx)

    loss_fn = functools.partial(_loss_one, cov_fn=cov_fn, scfg=cfg.svgp)
    losses, grads = jax.vmap(jax.value_and_grad(loss_fn))(
        state.params, bx=bx, by=by, bm=bm, n_eff=dist.n_eff
    )
    new_params, new_opt = adam_update(state.params, grads, state.opt, lr=cfg.learning_rate)
    return PSVGPState(new_params, new_opt, state.step + 1), jnp.mean(losses)


# --------------------------------------------------------------------------
# TPU-native mode: synchronized direction + permute, importance-weighted.
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "cov_fn"))
def train_step_ppermute(
    state: PSVGPState,
    key: jax.Array,
    x: jnp.ndarray,
    y: jnp.ndarray,
    mask: jnp.ndarray,
    dist: SlotDistribution,
    perms: jnp.ndarray,
    p_dir: jnp.ndarray,
    cfg: PSVGPConfig,
    cov_fn: Callable,
) -> tuple[PSVGPState, jnp.ndarray]:
    """Single-host simulation of the TPU-native step (identical math).

    One global direction d ~ p_dir; every partition ships its OWN mini-batch
    to the neighbor opposite d (a permutation = collective-permute on a real
    mesh); gradients are importance-weighted by pi_j(d)/p(d) so that
    E[update] matches eq. (8) exactly. See ``shard_map_step`` for the
    device-sharded version of the same program.
    """
    kd, kb = jax.random.split(jax.random.fold_in(key, state.step))
    d = jax.random.categorical(kd, jnp.log(jnp.maximum(p_dir, 1e-30)))  # ()
    # Every partition samples from its own data (no communication yet).
    idx, _ = sample_minibatch_indices(kb, mask, cfg.batch_size)
    bx = jnp.take_along_axis(x, idx[:, :, None], axis=1)  # (P, B, dim)
    by = jnp.take_along_axis(y, idx, axis=1)  # (P, B)
    bm = jnp.take_along_axis(mask, idx, axis=1)
    # Route mini-batches: receiver j gets the batch of perms[d][j].
    perm_row = jnp.take(perms, d, axis=0)  # (P,)
    bx = jnp.take(bx, perm_row, axis=0)
    by = jnp.take(by, perm_row, axis=0)
    bm = jnp.take(bm, perm_row, axis=0)
    # Importance weight: pi_j(d)/p(d); partitions with no neighbor in this
    # direction have pi_j(d)=0 -> weight 0 (their likelihood term is a
    # no-op this step). Applied to the likelihood term ONLY — the KL is
    # deterministic and keeps weight 1 (pure variance reduction; E[w]=1
    # makes both versions unbiased, see DESIGN.md §2).
    pi_jd = jnp.take_along_axis(dist.probs, jnp.full((dist.probs.shape[0], 1), d), axis=1)[:, 0]
    w = pi_jd / jnp.maximum(p_dir[d], 1e-30)  # (P,)

    loss_fn = functools.partial(_loss_one, cov_fn=cov_fn, scfg=cfg.svgp)
    losses, grads = jax.vmap(jax.value_and_grad(loss_fn))(
        state.params, bx=bx, by=by, bm=bm, n_eff=dist.n_eff, ll_weight=w
    )
    new_params, new_opt = adam_update(state.params, grads, state.opt, lr=cfg.learning_rate)
    return PSVGPState(new_params, new_opt, state.step + 1), jnp.mean(losses)


def _configured_step(state, key, x, y, mask, dist, perms, p_dir, cfg, cov_fn):
    """The step ``cfg.comm`` names, looked up as a module global at each
    call (inside ``fit_steps``, when its loop body is traced)."""
    if cfg.comm == "gather":
        return train_step_gather(state, key, x, y, mask, dist, cfg, cov_fn)
    if cfg.comm == "ppermute":
        return train_step_ppermute(state, key, x, y, mask, dist, perms, p_dir, cfg, cov_fn)
    raise ValueError(f"unknown comm mode {cfg.comm!r}")


def train_step(static: PSVGPStatic, state: PSVGPState, key: jax.Array, data: PartitionedData):
    """One step, dispatched from the host, of the configured communication
    mode (``fit`` runs its steps in one device program instead)."""
    return _configured_step(state, key, data.x, data.y, data.mask, static.dist, static.perms,
                            static.p_dir, static.cfg, static.cov_fn)


@functools.partial(jax.jit, static_argnames=("cfg", "cov_fn"))
def fit_steps(
    state: PSVGPState,
    num_iters: jnp.ndarray,
    key: jax.Array,
    x: jnp.ndarray,
    y: jnp.ndarray,
    mask: jnp.ndarray,
    dist: SlotDistribution,
    perms: jnp.ndarray,
    p_dir: jnp.ndarray,
    cfg: PSVGPConfig,
    cov_fn: Callable,
) -> tuple[PSVGPState, jnp.ndarray]:
    """``num_iters`` steps of the configured step in one device program.

    The trip count is traced, so every step budget over the same shapes
    shares one compiled program. The data and topology tables are loop
    invariants; only ``(state, last loss)`` is carried. Returns the loss
    of the last step run, NaN when none ran.
    """

    def body(_, carry):
        return _configured_step(carry[0], key, x, y, mask, dist, perms, p_dir, cfg, cov_fn)

    return jax.lax.fori_loop(0, num_iters, body, (state, jnp.full((), jnp.nan, jnp.float32)))


def fit(
    static: PSVGPStatic,
    state: PSVGPState,
    data: PartitionedData,
    num_iters: int,
    key: jax.Array | None = None,
    log_every: int = 0,
) -> PSVGPState:
    """Run ``num_iters`` SGD iterations (the paper runs 100-150 per E3SM
    time step; convergence experiments run a few thousand).

    The steps run on the device inside ``fit_steps``, one dispatch for the
    whole budget; with ``log_every`` the budget runs in chunks of that many
    steps through the same program, printing the mean loss after each.
    Step ``i`` draws from ``fold_in(key, state.step)``, so a state carried
    over from an earlier call continues the key sequence, and the chunking
    does not change the result. The input state is not donated: it is
    never modified.
    """
    key = jax.random.PRNGKey(static.cfg.seed) if key is None else key
    chunk = log_every or num_iters
    done = 0
    while done < num_iters:
        n = min(chunk, num_iters - done)
        state, loss = fit_steps(
            state, np.int32(n), key, data.x, data.y, data.mask, static.dist,
            static.perms, static.p_dir, static.cfg, static.cov_fn,
        )
        done += n
        if log_every:
            print(f"  iter {done:5d}  mean -ELBO/partition: {float(loss):.4f}")
    return state


# --------------------------------------------------------------------------
# Prediction / evaluation — all routed through the PosteriorCache subsystem
# (repro.core.posterior): factorize the P local posteriors ONCE per trained
# state, then every prediction is O(Q m^2) against the cached factors.
# --------------------------------------------------------------------------


def posterior_cache(static: PSVGPStatic, state: PSVGPState) -> posterior.PosteriorCache:
    """P-stacked prediction cache for the current state — one batched
    O(P m^3) factorization; reuse it across every prediction call below."""
    scfg = static.cfg.svgp
    return posterior.build_cache_stacked(
        state.params, static.cov_fn, jitter=scfg.jitter, whitened=scfg.whitened
    )


def predict_local(
    static: PSVGPStatic,
    state: PSVGPState,
    xstar: jnp.ndarray,
    cache: posterior.PosteriorCache | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Each partition's model predicts at its OWN rows of xstar (P, Q, d)."""
    if cache is None:
        cache = posterior_cache(static, state)
    return posterior.predict_cached_stacked(cache, static.cov_fn, xstar)


def predict_at_partitions(
    static: PSVGPStatic,
    state: PSVGPState,
    part_ids: jnp.ndarray,
    points: jnp.ndarray,
    cache: posterior.PosteriorCache | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Predict ``points`` (E, Q, d) with the models of ``part_ids`` (E,)."""
    if cache is None:
        cache = posterior_cache(static, state)
    cache_e = posterior.take_cache(cache, part_ids)
    return posterior.predict_cached_stacked(cache_e, static.cov_fn, points)

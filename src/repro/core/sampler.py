"""The paper's modified SGD sampler — eq. (8) with the delta interpolation
of eq. (9).

Per iteration and per partition j:
  1. choose a source slot k' over {self, 4 neighbors} with probabilities
        P(k'=j)            = n_j / n_eff_j
        P(k'=k), k in N_j  = delta * n_k / n_eff_j
        n_eff_j            = n_j + delta * sum_{k in N_j, k != j} n_k
     (we read eq. (9)'s "delta n_j 1(k in N_j)" as delta n_k — the weights
     "proportional to the number of observations in each partition" of
     eq. (8), consistent with the paper's own n_eff definition; taking it
     literally as n_j would make all neighbor weights equal regardless of
     their size, contradicting eq. (8).)
  2. draw B observations uniformly without replacement from partition k'.
  3. scale the mini-batch gradient by n_eff_j / B_eff.

delta = 0 reduces exactly to ISVGP (always slot 0); delta = 1 is full PSVGP.

Everything is computed for ALL partitions at once (leading axis P) so the
trainer can vmap; slot probabilities use the (P, 5) neighbor table from
``repro.core.neighbors``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.neighbors import NUM_SLOTS


class SlotDistribution(NamedTuple):
    probs: jnp.ndarray  # (P, 5) slot probabilities, rows sum to 1
    n_eff: jnp.ndarray  # (P,) effective data sizes n_eff_j (eq. 9)
    neighbor_tbl: jnp.ndarray  # (P, 5) int32, -1 where absent


def slot_distribution(
    counts: jnp.ndarray, neighbor_tbl: jnp.ndarray, delta: float | jnp.ndarray
) -> SlotDistribution:
    """Build eq. (9) slot probabilities for every partition.

    counts: (P,) true n_k. neighbor_tbl: (P, 5) with slot 0 = self.
    """
    valid = neighbor_tbl >= 0  # (P, 5)
    safe = jnp.maximum(neighbor_tbl, 0)
    n_k = jnp.take(counts, safe, axis=0).astype(jnp.float32) * valid  # (P, 5)
    delta = jnp.asarray(delta, jnp.float32)
    w = n_k.at[:, 1:].multiply(delta)  # self keeps n_j, neighbors get delta*n_k
    n_eff = jnp.sum(w, axis=1)  # (P,)
    probs = w / jnp.maximum(n_eff[:, None], 1e-12)
    return SlotDistribution(probs=probs, n_eff=n_eff, neighbor_tbl=neighbor_tbl)


def sample_slots(key: jax.Array, dist: SlotDistribution) -> jnp.ndarray:
    """k' sampling, vectorized over partitions -> (P,) partition indices."""
    P = dist.probs.shape[0]
    g = jax.random.gumbel(key, (P, NUM_SLOTS))
    logp = jnp.log(jnp.maximum(dist.probs, 1e-30))
    slot = jnp.argmax(logp + g, axis=1)  # (P,) Gumbel-max categorical
    return jnp.take_along_axis(dist.neighbor_tbl, slot[:, None], axis=1)[:, 0], slot


def sample_row_indices(key: jax.Array, mask_row: jnp.ndarray, batch: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-row version: (n_max,) mask -> (B,) indices + validity.

    This is the per-partition primitive; the SPMD step calls it directly with
    a per-device key, the vmap trainer maps it with per-partition folded keys
    — the two are therefore bit-identical (DESIGN.md §2 equivalence test).
    """
    n_max = mask_row.shape[0]
    scores = jax.random.uniform(key, (n_max,)) + (mask_row - 1.0) * 1e9
    idx = jax.lax.top_k(scores, batch)[1]
    return idx, jnp.take(mask_row, idx)


def sample_minibatch_indices(
    key: jax.Array, mask_rows: jnp.ndarray, batch: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Uniform WITHOUT-replacement indices from masked rows.

    mask_rows: (P, n_max) validity of each stored point in the SOURCE row.
    Returns (idx, bmask): (P, B) indices into n_max and their validity —
    if a source partition has fewer than B points, the surplus slots are
    masked out (bmask=0), i.e. the batch degrades to "all n_k points".
    Row p uses the independent stream fold_in(key, p).
    """
    P, _ = mask_rows.shape
    keys = jax.vmap(lambda p: jax.random.fold_in(key, p))(jnp.arange(P))
    return jax.vmap(lambda k, m: sample_row_indices(k, m, batch))(keys, mask_rows)


def gather_minibatch(
    x: jnp.ndarray,
    y: jnp.ndarray,
    mask: jnp.ndarray,
    kprime: jnp.ndarray,
    idx: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Materialize the (P, B, ...) mini-batches from source partitions kprime.

    This is the paper-faithful "gather" communication mode: partition j
    reads the B points ``idx[j]`` of partition ``kprime[j]``, and nothing
    else of it; under SPMD the cross-partition read lowers to a gather of
    B-point blocks.
    """
    rows = kprime[:, None]  # (P, 1) against idx (P, B)
    return x[rows, idx], y[rows, idx], mask[rows, idx]

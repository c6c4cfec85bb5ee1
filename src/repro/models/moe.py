"""Mixture-of-Experts FFN: shared + routed top-k experts (DeepSeekMoE /
Qwen3-MoE style) with capacity-based scatter dispatch.

Dispatch is the scatter/rank formulation (GShard capacity discipline
without the O(T*E*C) dense one-hot): per-token expert ranks come from a
stable argsort over the flattened (token, k) assignments, tokens beyond
each expert's capacity are dropped, and the (E, C, D) expert buffers are
built with a single scatter-add. Experts' weights carry a leading E axis —
the sharding rules put that axis on the ``model`` mesh axis, so the
token->expert buffer exchange lowers to the expected all-to-all pattern
under SPMD (visible in the roofline's collective bytes).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.common import Params, dense_init
from repro.models.config import ModelConfig, MoEConfig


def init_moe_params(key: jax.Array, cfg: ModelConfig) -> Params:
    m: MoEConfig = cfg.moe
    D, E, F = cfg.d_model, m.num_experts, m.d_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E)),
        "w_gate": dense_init(ks[1], (E, D, F), in_axis=1),
        "w_up": dense_init(ks[2], (E, D, F), in_axis=1),
        "w_down": dense_init(ks[3], (E, F, D), in_axis=1),
    }
    if m.num_shared > 0:
        sf = m.num_shared * F
        s1, s2, s3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(s1, (D, sf)),
            "w_up": dense_init(s2, (D, sf)),
            "w_down": dense_init(s3, (sf, D)),
        }
    return p


def moe_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, D) -> (out (B, S, D), aux load-balance loss scalar).

    Under a multi-device mesh with a "model" axis this routes through the
    manually-partitioned shard_map path (see _moe_forward_spmd) — XLA's
    auto-partitioner replicates the D-wide dispatch scatters otherwise
    (measured: ~5 GiB all-gathers per layer, EXPERIMENTS.md §Perf-2).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if (
        "model" in mesh.axis_names
        and mesh.shape["model"] > 1
        and cfg.moe.num_experts % mesh.shape["model"] == 0
    ):
        return _moe_forward_spmd(p, cfg, x, mesh)
    return _moe_forward_local(p, cfg, x)


def _moe_forward_local(p: Params, cfg: ModelConfig, x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    E, K = m.num_experts, m.top_k
    T = B * S
    dt = x.dtype
    xt = x.reshape(T, D)

    # --- routing (fp32) ---
    logits = (xt @ p["router"].astype(dt)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # --- load-balance auxiliary (Switch-style) ---
    dispatch_frac = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (T * K)
    prob_frac = probs.mean(0)
    aux = E * jnp.sum(dispatch_frac * prob_frac)

    # --- GROUP-LOCAL capacity ranks (GShard-style groups) ---
    # §Perf log (EXPERIMENTS.md): a GLOBAL argsort over the (T*K,)
    # assignments forces multi-pass sorted all-gathers when T is sharded
    # (measured: 48 s collective term for qwen3-moe train_4k); a global
    # cumsum lowers to an O(T^2) reduce-window (measured: 6x compute
    # blowup); an associative_scan unrolls 20 static passes over (T, E)
    # (compile blowup). The production answer is to make rank computation
    # LOCAL: tokens are split into G groups aligned with the data shards,
    # each group ranks and drops against its own capacity slice C/G
    # (exactly GShard's per-group capacity semantics). Ranks then never
    # cross shards; all communication concentrates in the (G <-> E) buffer
    # transpose below — a single all-to-all, as an MoE should.
    G = m.dispatch_groups
    while T % G:
        G //= 2
    Tg = T // G
    Cg = max(int(m.capacity_factor * Tg * K / E), 1)
    tok_l = jnp.repeat(jnp.arange(Tg), K)  # local owning token (same per group)

    def group_ranks(eid_flat):  # (Tg*K,) -> (Tg*K,) rank within expert
        order = jnp.argsort(eid_flat, stable=True)
        counts = jnp.zeros((E,), jnp.int32).at[eid_flat].add(1)
        seg_start = jnp.cumsum(counts) - counts
        rank_sorted = jnp.arange(Tg * K, dtype=jnp.int32) - seg_start[eid_flat[order]]
        return jnp.zeros((Tg * K,), jnp.int32).at[order].set(rank_sorted)

    eid_g = expert_ids.reshape(G, Tg * K)
    rank_g = jax.vmap(group_ranks)(eid_g)  # (G, Tg*K)
    keep_g = (rank_g < Cg).astype(dt)
    slot_g = eid_g * Cg + jnp.minimum(rank_g, Cg - 1)

    # --- dispatch: per-group scatter into (G, E*Cg, D) buffers ---
    x_g = xt.reshape(G, Tg, D)

    def group_scatter(slots, keeps, xg):
        return jnp.zeros((E * Cg, D), dt).at[slots].add(xg[tok_l] * keeps[:, None])

    buf = jax.vmap(group_scatter)(slot_g, keep_g, x_g)  # (G, E*Cg, D)
    # group-sharded -> expert-sharded: THE all-to-all of the MoE layer
    buf = buf.reshape(G, E, Cg, D).transpose(1, 0, 2, 3).reshape(E, G * Cg, D)

    # --- expert computation (grouped einsum over the E axis) ---
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    eout = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))

    # --- combine: transpose back, gather per group, weight by gates ---
    eout = eout.reshape(E, G, Cg, D).transpose(1, 0, 2, 3).reshape(G, E * Cg, D)
    gate_g = gate_vals.reshape(G, Tg * K).astype(dt)

    def group_combine(eo, slots, keeps, gates):
        per_assign = eo[slots] * (keeps * gates)[:, None]
        return jnp.zeros((Tg, D), dt).at[tok_l].add(per_assign)

    out = jax.vmap(group_combine)(eout, slot_g, keep_g, gate_g).reshape(T, D)

    # --- always-on shared experts (DeepSeekMoE) ---
    if m.num_shared > 0:
        sp = p["shared"]
        g = jax.nn.silu(xt @ sp["w_gate"].astype(dt))
        out = out + (g * (xt @ sp["w_up"].astype(dt))) @ sp["w_down"].astype(dt)

    return out.reshape(B, S, D), aux


def _moe_forward_spmd(p: Params, cfg: ModelConfig, x: jnp.ndarray, mesh) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Manually partitioned MoE (§Perf-2, beyond-paper).

    Layout: tokens sharded over the (pod, data) axes (replicated over
    "model"); expert weights sharded over "model" (E_local experts per
    device). Every model-row device routes ITS token shard redundantly
    (router is tiny), dispatches LOCALLY into buffers for its own E_local
    experts only, and the per-expert partial outputs are summed with ONE
    psum over "model" — the same collective shape as a tensor-parallel
    FFN. No scatter ever crosses devices.
    """
    from jax.sharding import PartitionSpec as P

    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    dt = x.dtype
    daxes = tuple(a for a in mesh.axis_names if a != "model")
    import numpy as np

    d_size = int(np.prod([mesh.shape[a] for a in daxes]))
    x_spec = P(daxes) if B % d_size == 0 else P()
    n_model = mesh.shape["model"]
    e_local = m.num_experts // n_model

    def body(xb, router, w_gate, w_up, w_down):
        # xb (B_l, S, D); router (D, E) replicated; w_* (E_l, D, F) local
        Bl = xb.shape[0]
        Tl = Bl * S
        E, K = m.num_experts, m.top_k
        xt = xb.reshape(Tl, D)
        logits = (xt @ router.astype(dt)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        disp = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (Tl * K)
        aux_l = E * jnp.sum(disp * probs.mean(0))
        aux_l = jax.lax.pmean(aux_l, daxes) if x_spec != P() else aux_l

        # local ranks over the LOCAL token shard (GShard per-group capacity)
        C = max(int(m.capacity_factor * Tl * K / E), 1)
        eid = expert_ids.reshape(-1)
        order = jnp.argsort(eid, stable=True)
        counts = jnp.zeros((E,), jnp.int32).at[eid].add(1)
        seg_start = jnp.cumsum(counts) - counts
        rank_sorted = jnp.arange(Tl * K, dtype=jnp.int32) - seg_start[eid[order]]
        rank = jnp.zeros((Tl * K,), jnp.int32).at[order].set(rank_sorted)
        keep = (rank < C).astype(dt)
        tok = jnp.repeat(jnp.arange(Tl), K)

        # keep only assignments belonging to THIS device's experts
        m_idx = jax.lax.axis_index("model")
        e_lo = m_idx * e_local
        mine = ((eid >= e_lo) & (eid < e_lo + e_local)).astype(dt)
        keep = keep * mine
        slot = (eid - e_lo).clip(0, e_local - 1) * C + jnp.minimum(rank, C - 1)

        buf = jnp.zeros((e_local * C, D), dt).at[slot].add(xt[tok] * keep[:, None])
        buf = buf.reshape(e_local, C, D)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(dt)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, w_up.astype(dt))
        eout = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt)).reshape(e_local * C, D)

        per_assign = eout[slot] * (keep * gate_vals.reshape(-1).astype(dt))[:, None]
        out_l = jnp.zeros((Tl, D), dt).at[tok].add(per_assign)
        # each model row holds partial sums for its experts only -> ONE psum
        out_l = jax.lax.psum(out_l, "model")
        return out_l.reshape(Bl, S, D), aux_l

    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, P(), P("model"), P("model"), P("model")),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if m.num_shared > 0:
        sp = p["shared"]
        xt = x.reshape(B * S, D)
        g = jax.nn.silu(xt @ sp["w_gate"].astype(dt))
        shared = (g * (xt @ sp["w_up"].astype(dt))) @ sp["w_down"].astype(dt)
        out = out + shared.reshape(B, S, D)
    return out, aux

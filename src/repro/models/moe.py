"""Mixture-of-Experts FFN with capacity-buffer dispatch (expert parallel).

Dispatch is scatter-based (no O(T*E*cap) one-hot einsum): token ranks within
each expert come from an exclusive cumsum over the [T, E] assignment matrix,
tokens are scattered into a static [E, cap, D] buffer, experts run as one
batched einsum, and results gather back weighted by the router gate.  The
buffer carries an 'expert' logical axis, so under the production mesh the
scatter/gather lower to all-to-alls across the EP ('model') axis.

Supports top-k routing (Moonlight 64e top-6) and an Arctic-style dense
residual MLP in parallel with the experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.sharding import logical_constraint
from repro.models.common import ModelConfig, MoEConfig, dense_init, mm

__all__ = ["init_moe", "apply_moe"]


def _act(name):
    return jax.nn.silu if name == "silu" else jax.nn.gelu


def init_moe(key, cfg: ModelConfig):
    mc: MoEConfig = cfg.moe
    D, E, F = cfg.d_model, mc.num_experts, mc.d_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32),
        "wi": dense_init(ks[1], (E, D, 2 * F if cfg.gated_mlp else F),
                         cfg.jdtype),
        "wo": dense_init(ks[2], (E, F, D), cfg.jdtype),
    }
    if mc.dense_residual:
        Fr = mc.dense_residual_ff or F
        p["res_wi"] = dense_init(
            ks[3], (D, 2 * Fr if cfg.gated_mlp else Fr), cfg.jdtype
        )
        p["res_wo"] = dense_init(ks[4], (Fr, D), cfg.jdtype)
    return p


def apply_moe(p, x, cfg: ModelConfig):
    """x: [B, S, D] -> [B, S, D].  Returns (out, aux_loss)."""
    mc: MoEConfig = cfg.moe
    B, S, D = x.shape
    E, k = mc.num_experts, mc.top_k
    T = B * S
    x2 = x.reshape(T, D)

    logits = (x2.astype(jnp.float32) @ p["router"])  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)            # [T, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=1), axis=0
    ) / k
    aux = E * jnp.sum(me * ce)

    cap = max(1, int(T * k / E * mc.capacity_factor))
    cap = -(-cap // 8) * 8  # round to 8 for TPU-friendly shapes

    # rank of each (token, slot) within its expert via exclusive cumsum
    assign = jnp.sum(jax.nn.one_hot(eidx, E, dtype=jnp.int32), axis=1)  # [T,E]
    ranks_base = jnp.cumsum(assign, axis=0) - assign                    # [T,E]
    flat_e = eidx.reshape(-1)                                            # [T*k]
    tok_of_slot = jnp.repeat(jnp.arange(T), k)
    # slot order within a token is distinct experts, so base rank suffices
    pos = ranks_base[tok_of_slot, flat_e]
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap - 1)

    # dispatch: scatter tokens into [E, cap, D] buffers
    buf = jnp.zeros((E, cap, D), x2.dtype)
    contrib = jnp.where(keep[:, None], x2[tok_of_slot], 0)
    buf = buf.at[flat_e, pos_c].add(contrib)
    buf = logical_constraint(buf, ("expert", None, None))

    # expert FFNs as one batched einsum (runs expert-parallel over 'model')
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    if cfg.gated_mlp:
        u, v = jnp.split(h, 2, axis=-1)
        h = _act(cfg.act)(u) * v
    else:
        h = _act(cfg.act)(h)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    out_buf = logical_constraint(out_buf, ("expert", None, None))

    # combine: route expert outputs back to tokens
    if mc.combine == "replicated":
        # one explicit all-gather of the expert outputs, then a LOCAL
        # gather+segment-sum — bounds the expert->token routing at
        # |out_buf| per layer instead of GSPMD's per-gather replication
        # (§Perf cell B iteration 4)
        out_buf = logical_constraint(out_buf, (None, None, None))
    if mc.combine == "scatter":
        # scatter-add from the expert-sharded buffer into token-sharded
        # output (the reverse of dispatch) — gives GSPMD a symmetric
        # expert->token routing instead of a cross-shard gather, which it
        # lowers to replication (§Perf cell B iteration 3)
        pos_drop = jnp.where(keep, pos, cap)  # out-of-bounds -> dropped
        slot_token = jnp.zeros((E, cap), jnp.int32).at[
            flat_e, pos_drop].set(tok_of_slot.astype(jnp.int32),
                                  mode="drop")
        slot_gate = jnp.zeros((E, cap), jnp.float32).at[
            flat_e, pos_drop].set((gates.reshape(-1) * keep).astype(
                jnp.float32), mode="drop")
        contrib_back = out_buf.astype(jnp.float32) * slot_gate[..., None]
        y = jnp.zeros((T, D), jnp.float32).at[
            slot_token.reshape(-1)].add(contrib_back.reshape(E * cap, D))
        y = logical_constraint(y, ("batch", None))
    else:
        slot_out = out_buf[flat_e, pos_c]                   # [T*k, D]
        slot_out = jnp.where(keep[:, None], slot_out, 0)
        w = (gates.reshape(-1) * keep).astype(jnp.float32)[:, None]
        y = jax.ops.segment_sum(slot_out.astype(jnp.float32) * w,
                                tok_of_slot, num_segments=T)

    if mc.dense_residual:
        hr = mm(x2, p["res_wi"])
        if cfg.gated_mlp:
            u, v = jnp.split(hr, 2, axis=-1)
            hr = _act(cfg.act)(u) * v
        else:
            hr = _act(cfg.act)(hr)
        y = y + (mm(hr, p["res_wo"])).astype(jnp.float32)

    return y.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Explicit shard_map expert parallelism (§Perf cell B end-state)
# ---------------------------------------------------------------------------


def apply_moe_shmap(p, x, cfg: ModelConfig):
    """Expert parallelism with *no* token movement (beyond-paper, §Perf B):

    batch is replicated across the EP ('model') axis under the production
    sharding, so every model-rank already holds every local token.  Each
    rank therefore (1) routes locally (identical decisions on all ranks),
    (2) dispatches only the slots destined to ITS E/ep experts into a local
    capacity buffer, (3) runs its experts, (4) combines locally and
    (5) psums partial outputs over 'model'.  Collective cost per layer =
    one [T_local, D] psum + the ZeRO weight all-gathers — vs GSPMD's
    replication of the [E, cap, D] buffers (the arctic baseline wall).
    Falls back to the pjit path when no mesh context is active.
    """
    import functools

    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import active_rules

    ctx = active_rules()
    mc: MoEConfig = cfg.moe
    if ctx is None or "model" not in ctx[0].axis_names:
        return apply_moe(p, x, cfg)
    mesh, rules = ctx
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    ep = mesh.shape["model"]
    E, k = mc.num_experts, mc.top_k
    if E % ep != 0:
        return apply_moe(p, x, cfg)
    E_loc = E // ep
    B, S, D = x.shape

    expert_p = {kk: v for kk, v in p.items()
                if kk in ("router", "wi", "wo")}

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(dp or None), {  # x over batch; weights: E over model,
            "router": P(),
            "wi": P("model", None, None),
            "wo": P("model", None, None),
        }),
        out_specs=(P(dp or None), P()),
        check_vma=False,
    )
    def body(x_loc, p_loc):
        Bl = x_loc.shape[0]
        T = Bl * S
        x2 = x_loc.reshape(T, D)
        logits = x2.astype(jnp.float32) @ p_loc["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(eidx, E, dtype=jnp.float32),
                              axis=1), axis=0) / k
        aux = E * jnp.sum(me * ce)
        if dp:
            aux = jax.lax.pmean(aux, dp)

        # local experts of this model-rank: [lo, lo + E_loc)
        lo = jax.lax.axis_index("model") * E_loc
        flat_e = eidx.reshape(-1)
        tok_of_slot = jnp.repeat(jnp.arange(T), k)
        mine = (flat_e >= lo) & (flat_e < lo + E_loc)
        le = jnp.where(mine, flat_e - lo, 0)

        cap = max(8, int(T * k / E * mc.capacity_factor))
        cap = -(-cap // 8) * 8
        assign = jnp.where(mine, 1, 0)
        # rank within local expert via segment-wise cumsum over slots
        onehot = jax.nn.one_hot(le, E_loc, dtype=jnp.int32) * assign[:, None]
        pos = (jnp.cumsum(onehot, axis=0) - onehot)[
            jnp.arange(T * k), le]
        keep = mine & (pos < cap)
        pos_c = jnp.where(keep, pos, cap)  # cap slot == dropped (mode drop)

        buf = jnp.zeros((E_loc, cap + 1, D), x2.dtype)
        buf = buf.at[le, pos_c].add(
            jnp.where(keep[:, None], x2[tok_of_slot], 0))
        buf = buf[:, :cap]

        h = jnp.einsum("ecd,edf->ecf", buf, p_loc["wi"])
        if cfg.gated_mlp:
            u, v = jnp.split(h, 2, axis=-1)
            h = _act(cfg.act)(u) * v
        else:
            h = _act(cfg.act)(h)
        out_buf = jnp.einsum("ecf,efd->ecd", h, p_loc["wo"])

        slot_out = out_buf[le, jnp.where(keep, pos_c, 0)]
        slot_out = jnp.where(keep[:, None], slot_out, 0)
        w = (gates.reshape(-1) * keep).astype(jnp.float32)[:, None]
        y = jax.ops.segment_sum(slot_out.astype(jnp.float32) * w,
                                tok_of_slot, num_segments=T)
        y = jax.lax.psum(y, "model")
        return y.reshape(Bl, S, D), aux  # f32: residual adds in full precision

    y, aux = body(x, expert_p)
    if mc.dense_residual:
        # the dense residual MLP stays in pjit-land: GSPMD handles a plain
        # TP-sharded FFN well, and keeping it inside shard_map would
        # replicate its compute across all EP ranks
        hr = mm(x.reshape(-1, D), p["res_wi"])
        if cfg.gated_mlp:
            u, v = jnp.split(hr, 2, axis=-1)
            hr = _act(cfg.act)(u) * v
        else:
            hr = _act(cfg.act)(hr)
        y = y + (mm(hr, p["res_wo"])).reshape(B, S, D).astype(jnp.float32)
    return y.astype(x.dtype), aux

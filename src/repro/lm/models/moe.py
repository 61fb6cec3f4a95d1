"""Top-k token-choice MoE with static capacity, shared experts, and EP.

Routing is sort/scatter based — no (T, E, C) dispatch tensor:

1. per-group top-k assignment (groups = data-parallel rows, so sorting
   stays shard-local under GSPMD),
2. rank-within-expert via sorted cumulative counts,
3. scatter into an (E, C, d) buffer with ``mode="drop"`` (capacity
   overflow drops, like classic capacity-factor routing),
4. grouped einsum over experts (experts sharded over the model axis ⇒
   the token->expert reshard lowers to an all-to-all = EP),
5. gather back + combine with router weights.

**Rhizome note (DESIGN.md §4):** token→expert routing is a skewed
bipartite graph; the (E, C) buffer is the expert's "replica slot" row and
the capacity clip plays the cutoff_chunk role — the same
split-hot-destinations idea the paper applies to hub vertices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.lm.models import layers as L
from repro.sharding.specs import ShardCtx, constrain


def init_moe(key, cfg, dtype):
    m = cfg.moe
    d = cfg.d_model
    f = m.d_expert_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], (d, m.num_experts), ("embed", None),
                               dtype, scale=0.1),
        "w_gate": L.dense_init(ks[1], (m.num_experts, d, f),
                               ("experts", "embed", "expert_mlp"), dtype,
                               fan_in=d),
        "w_up": L.dense_init(ks[2], (m.num_experts, d, f),
                             ("experts", "embed", "expert_mlp"), dtype,
                             fan_in=d),
        "w_down": L.dense_init(ks[3], (m.num_experts, f, d),
                               ("experts", "expert_mlp", "embed"), dtype,
                               fan_in=f),
    }
    if m.num_shared:
        shared_ff = m.d_shared_ff or m.d_expert_ff
        p["shared"] = L.init_mlp(ks[4], cfg, dtype,
                                 d_ff=shared_ff * m.num_shared)
    return p


def apply_moe(p, cfg, x, ctx: ShardCtx | None):
    """x: (B, S, d) -> (out, aux_losses dict)."""
    if "moe_shardmap" in cfg.opts and ctx is not None and ctx.mesh is not None:
        return apply_moe_shardmap(p, cfg, x, ctx)
    if ("moe_grouped" in cfg.opts or "moe_shardmap" in cfg.opts):
        return apply_moe_grouped(p, cfg, x, ctx)
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, K)              # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)            # renormalize top-k

    # ---- rank within expert (sorted cumulative counts) --------------------
    flat_e = eidx.reshape(-1)                              # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.bincount(se, length=E)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * K) - starts[se]

    C = max(int(T * K / E * m.capacity_factor), 1)
    keep = rank < C
    rank_c = jnp.where(keep, rank, 0)
    e_idx = jnp.where(keep, se, E)                         # E => dropped

    # ---- dispatch: (E, C, d) ----------------------------------------------
    buf = jnp.zeros((E, C, d), xt.dtype)
    buf = buf.at[e_idx, rank_c].set(
        jnp.where(keep[:, None], xt[st], 0.0), mode="drop")
    buf = constrain(buf, ("act_experts", None, None), ctx)

    # ---- expert compute (grouped einsums; experts sharded 'tp') -----------
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    act = jax.nn.silu(g) * h if cfg.mlp_act in ("swiglu", "geglu") else \
        jnp.square(jax.nn.relu(h))
    out_buf = jnp.einsum("ecf,efd->ecd", act, p["w_down"])
    out_buf = constrain(out_buf, ("act_experts", None, None), ctx)

    # ---- combine -----------------------------------------------------------
    gathered = out_buf[e_idx, rank_c]                      # (T*K, d), 0 if drop
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    out = jnp.zeros((T, d), xt.dtype).at[st].add(gathered * sg[:, None].astype(xt.dtype))

    if m.num_shared:
        out = out + L.apply_mlp(p["shared"], cfg, x, ctx).reshape(T, d)

    # ---- aux losses (Switch-style load balance + router z-loss) -----------
    me = probs.mean(axis=0)                                # (E,)
    ce = jnp.bincount(flat_e, length=E) / (T * K)
    aux = {
        "moe_load_balance": E * jnp.sum(me * ce) * m.router_aux_weight,
        "moe_router_z": (jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_weight),
        "moe_drop_fraction": 1.0 - keep.mean(),
    }
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# §Perf optimization: group-local routing
# ---------------------------------------------------------------------------

def _route_group(xt, logits, E, K, C, mlp_act):
    """Route one token group: returns (dispatch buffer (E,C,d), combine
    metadata). All ops are local to the group — under a (G[dp], ...)
    sharding, GSPMD keeps sort/bincount/scatter shard-local."""
    T = xt.shape[0]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    flat_e = eidx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.bincount(se, length=E)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * K) - starts[se]
    keep = rank < C
    rank_c = jnp.where(keep, rank, 0)
    e_idx = jnp.where(keep, se, E)
    buf = jnp.zeros((E, C, xt.shape[1]), xt.dtype)
    buf = buf.at[e_idx, rank_c].set(
        jnp.where(keep[:, None], xt[st], 0.0), mode="drop")
    return buf, (e_idx, rank_c, st, sg, keep, probs, flat_e)


def apply_moe_shardmap(p, cfg, x, ctx: ShardCtx):
    """§Perf iteration 2 (MoE cells): GSPMD lowers the combine gather (and
    the dispatch scatter's backward) into all-reduces of (Tg·K, d) f32
    buffers — 6×K more bytes than necessary. Hand-schedule EP with
    shard_map: each tp shard dispatches/computes ONLY its local experts,
    produces a partial (Tg, d) token-sum, and one bf16 psum over tp
    finishes the combine. Expert weights stay FSDP'd over dp (manual
    all-gather inside; AD gives the reduce-scatter wgrad)."""
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k
    dp_axes, tp_axes = ctx.dp, ctx.tp
    G = ctx.axis_size("dp")
    tp = ctx.axis_size("tp")
    T = B * S
    Tg = T // G
    Cg = max(int(Tg * K / E * m.capacity_factor), 1)
    assert E % tp == 0, (E, tp)
    E_loc = E // tp

    xg = x.reshape(G, Tg, d)
    # router + aux outside (tiny, replicated over tp is fine)
    logits = jnp.einsum("gtd,de->gte", xg, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, K)                # (G,Tg,K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    def ffn(xg_l, eidx_l, gate_l, wg_l, wu_l, wd_l):
        # shapes: xg_l (1,Tg,d) dp-local; eidx/gate (1,Tg,K);
        # w*_l (E_loc, d/|dp|, f) — gather FSDP shards of local experts
        xg_l, eidx_l, gate_l = xg_l[0], eidx_l[0], gate_l[0]
        wg = lax.all_gather(wg_l, dp_axes, axis=1, tiled=True)
        wu = lax.all_gather(wu_l, dp_axes, axis=1, tiled=True)
        wd = lax.all_gather(wd_l, dp_axes, axis=2, tiled=True)
        my = lax.axis_index(tp_axes)
        e0 = my * E_loc
        flat_e = eidx_l.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(Tg), K)
        # keep the whole dispatch/combine chain in activation dtype: a f32
        # gate here promotes the backward gather/scatter chain to f32 (2x
        # HBM traffic on (Tg*K, d) buffers — §Perf iteration 4)
        flat_g = gate_l.reshape(-1).astype(xg_l.dtype)
        order = jnp.argsort(flat_e)
        se, st, sg = flat_e[order], flat_t[order], flat_g[order]
        counts = jnp.bincount(se, length=E)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(Tg * K) - starts[se]
        local = (se >= e0) & (se < e0 + E_loc) & (rank < Cg)
        e_rel = jnp.where(local, se - e0, E_loc)
        rank_c = jnp.where(local, rank, 0)
        buf = jnp.zeros((E_loc, Cg, d), xg_l.dtype)
        buf = buf.at[e_rel, rank_c].set(
            jnp.where(local[:, None], xg_l[st], 0.0), mode="drop")
        h = jnp.einsum("ecd,edf->ecf", buf, wu)
        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        act = (jax.nn.silu(g) * h if cfg.mlp_act in ("swiglu", "geglu")
               else jnp.square(jax.nn.relu(h)))
        ob = jnp.einsum("ecf,efd->ecd", act, wd)
        gathered = ob[e_rel, rank_c]
        gathered = jnp.where(local[:, None], gathered, 0.0)
        part = jnp.zeros((Tg, d), ob.dtype).at[st].add(
            gathered * sg[:, None].astype(ob.dtype))
        out = lax.psum(part, tp_axes)               # one bf16 (Tg,d) reduce
        return out[None]

    fn = jax.shard_map(
        ffn, mesh=ctx.mesh,
        in_specs=(P(dp_axes, None, None), P(dp_axes, None, None),
                  P(dp_axes, None, None),
                  P(tp_axes, dp_axes, None), P(tp_axes, dp_axes, None),
                  P(tp_axes, None, dp_axes)),
        out_specs=P(dp_axes, None, None),
        check_vma=False,
    )
    out = fn(xg, eidx, gate_vals, p["w_gate"], p["w_up"], p["w_down"])
    out = out.reshape(B, S, d)
    if m.num_shared:
        out = out + L.apply_mlp(p["shared"], cfg, x, ctx)

    me = probs.mean(axis=(0, 1))
    ce = jax.vmap(lambda fe: jnp.bincount(fe.reshape(-1), length=E))(
        eidx).sum(0) / (T * K)
    aux = {
        "moe_load_balance": E * jnp.sum(me * ce) * m.router_aux_weight,
        "moe_router_z": (jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_weight),
        "moe_drop_fraction": jnp.zeros((), jnp.float32),  # tracked in tests
    }
    return out, aux


def apply_moe_grouped(p, cfg, x, ctx: ShardCtx | None):
    """Hypothesis (§Perf iteration 1, MoE cells): global-token routing puts
    argsort/bincount/scatter across the DP-sharded token dim, which GSPMD
    lowers to full-activation all-gathers per MoE layer. Routing *within
    per-DP-shard groups* keeps those ops local; the only cross-shard
    movement left is the dispatched (G, E, C_g, d) buffer reshard
    (token->expert all-to-all = textbook EP)."""
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k
    G = ctx.axis_size("dp") if ctx is not None and ctx.mesh is not None else 1
    T = B * S
    assert T % G == 0, (T, G)
    Tg = T // G
    Cg = max(int(Tg * K / E * m.capacity_factor), 1)

    xg = x.reshape(G, Tg, d)
    xg = constrain(xg, ("act_batch", None, None), ctx)
    logits = jnp.einsum("gtd,de->gte", xg, p["router"]).astype(jnp.float32)

    buf, meta = jax.vmap(
        lambda xt, lg: _route_group(xt, lg, E, K, Cg, cfg.mlp_act))(xg, logits)
    e_idx, rank_c, st, sg, keep, probs, flat_e = meta
    # (G, E, Cg, d): G over dp, E over tp => GSPMD emits the EP all-to-all
    buf = constrain(buf, ("act_batch", "act_experts", None, None), ctx)

    h = jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    g = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
    act = jax.nn.silu(g) * h if cfg.mlp_act in ("swiglu", "geglu") else \
        jnp.square(jax.nn.relu(h))
    out_buf = jnp.einsum("gecf,efd->gecd", act, p["w_down"])
    out_buf = constrain(out_buf, ("act_batch", "act_experts", None, None), ctx)

    def combine(out_b, e_i, r_c, s_t, s_g, kp):
        gathered = out_b[e_i, r_c]
        gathered = jnp.where(kp[:, None], gathered, 0.0)
        return jnp.zeros((Tg, d), out_b.dtype).at[s_t].add(
            gathered * s_g[:, None].astype(out_b.dtype))

    out = jax.vmap(combine)(out_buf, e_idx, rank_c, st, sg, keep)
    out = constrain(out, ("act_batch", None, None), ctx).reshape(B, S, d)

    if m.num_shared:
        out = out + L.apply_mlp(p["shared"], cfg, x, ctx)

    me = probs.mean(axis=(0, 1))
    ce = jax.vmap(lambda fe: jnp.bincount(fe, length=E))(flat_e).sum(0) / (T * K)
    aux = {
        "moe_load_balance": E * jnp.sum(me * ce) * m.router_aux_weight,
        "moe_router_z": (jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_weight),
        "moe_drop_fraction": 1.0 - keep.mean(),
    }
    return out, aux

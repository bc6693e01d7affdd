"""Mixture-of-Experts layer with capacity-based sorted dispatch.

GShard/Switch-style expert parallelism adapted for GSPMD:

1. router top-k per token (softmax over chosen experts);
2. tokens sorted by expert id, ranked within expert, dropped beyond the
   capacity ``C = ceil(top_k * tokens * capacity_factor / E)``;
3. scatter into per-expert buffers ``(E, C, D)`` — the (E,) dim is sharded
   over the `model` mesh axis, so GSPMD lowers the scatter/gather pair into
   the canonical all-to-all dispatch/combine schedule;
4. expert SwiGLU as batched einsums over (E, C, ...);
5. weighted combine back to token order.

The dispatch cost is linear in tokens (sort + scatter), unlike the one-hot
matmul dispatch which is quadratic; expert FLOPs are exactly
``3 * 2 * E * C * D * F ~= top_k * cf * tokens * 3 * 2 * D * F``, i.e. the
active-parameter FLOPs the roofline model expects.

``arctic``-style dense residual: a regular MLP runs in parallel with the
expert path and the outputs are summed.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.sharding import ShardingRules, shard
from .layers import init_mlp

__all__ = ["init_moe", "moe_apply", "MOE_SPECS"]


def init_moe(key, cfg, dtype) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    E = cfg.moe.num_experts
    kr, k1, k2, k3, kd = jax.random.split(key, 5)
    s_in = 1.0 / math.sqrt(D)
    s_out = 1.0 / math.sqrt(F)
    p = {
        "router": (jax.random.normal(kr, (D, E)) * s_in).astype(jnp.float32),
        "wi_gate": (jax.random.normal(k1, (E, D, F)) * s_in).astype(dtype),
        "wi_up": (jax.random.normal(k2, (E, D, F)) * s_in).astype(dtype),
        "wo": (jax.random.normal(k3, (E, F, D)) * s_out).astype(dtype),
    }
    if cfg.moe.dense_residual:
        p["dense"] = init_mlp(kd, D, F, dtype)
    return p


MOE_SPECS = {
    "router": ("d_model", None),
    "wi_gate": ("experts", "d_model", "expert_ff"),
    "wi_up": ("experts", "d_model", "expert_ff"),
    "wo": ("experts", "expert_ff", "d_model"),
    "dense": {
        "wi_gate": ("d_model", "ff"),
        "wi_up": ("d_model", "ff"),
        "wo": ("ff", "d_model"),
    },
}


def _capacity(tokens: int, top_k: int, num_experts: int, cf: float) -> int:
    cap = int(math.ceil(top_k * tokens * cf / num_experts))
    return max(4, ((cap + 3) // 4) * 4)  # pad to a multiple of 4


def _model_axis_size(rules: Optional[ShardingRules]) -> int:
    if rules is None or rules.mesh is None:
        return 1
    a = rules.assignment("experts")
    if a is None:
        return 1
    ax = a if isinstance(a, str) else a[0]
    return rules.mesh.shape[ax]


def moe_apply_shard_map(
    p: dict,
    x: jax.Array,  # (B, S, D)
    cfg,
    rules: ShardingRules,
    capacity_factor: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map (the performance path).

    Within a DP group the activations are replicated across the model axis,
    so every model rank already *has* the tokens its local experts need —
    dispatch costs zero communication.  Each rank masks the router output
    to its expert slice, sorts/ranks locally, runs its E/TP experts, and
    the only collective is one psum of the (T_local, D) combine over the
    model axis (plus the FSDP all-gather of the expert weights' d_model
    shards).  This avoids GSPMD's scatter-on-sharded-dim fallback, which
    all-gathers every token (measured: 64 GiB/layer on qwen3-moe).
    """
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    B, S, D = x.shape
    E, K = cfg.moe.num_experts, cfg.moe.top_k
    cf = capacity_factor or cfg.moe.capacity_factor
    T = B * S
    model_ax = rules.assignment("experts")
    model_ax = model_ax if isinstance(model_ax, str) else model_ax[0]
    M = mesh.shape[model_ax]
    E_l = E // M
    dp_axes = rules.assignment("act_batch") or ()
    dp_axes = dp_axes if isinstance(dp_axes, tuple) else (dp_axes,)
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    T_l = T // dp_size
    C = _capacity(T_l, K, E, cf)

    xt = x.reshape(T, D)
    logits = xt.astype(jnp.float32) @ p["router"]  # (T, E) — plain TP math
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)
    gate_vals = (
        gate_vals / jnp.maximum(gate_vals.sum(axis=-1, keepdims=True), 1e-9)
    ).astype(x.dtype)

    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    # two expert-weight regimes (the FSDP-vs-weight-stationary hillclimb):
    #  * d_model FSDP ("d_model"->data): gather the weights' D shards per
    #    layer call — wire = expert-weight bytes / data;
    #  * weight-stationary ("expert_ff"->data): weights never move; token
    #    buffers all-gather over data and partial outputs reduce-scatter
    #    back — wire = token-buffer bytes, ~10-100x smaller for big experts.
    data_ax = rules.assignment("d_model")
    ef_ax = rules.assignment("expert_ff")

    def local(xt_l, eid_l, g_l, wg_l, wu_l, wo_l):
        if data_ax is not None and ef_ax is None:
            wg_l = jax.lax.all_gather(wg_l, data_ax, axis=1, tiled=True)
            wu_l = jax.lax.all_gather(wu_l, data_ax, axis=1, tiled=True)
            wo_l = jax.lax.all_gather(wo_l, data_ax, axis=2, tiled=True)
        m = jax.lax.axis_index(model_ax)
        local_eid = eid_l.reshape(-1) - m * E_l  # (T_l*K,)
        sel = (local_eid >= 0) & (local_eid < E_l)
        flat_e = jnp.where(sel, local_eid, E_l)  # E_l = overflow bucket
        order = jnp.argsort(flat_e)
        se = flat_e[order]
        st = (jnp.arange(T_l * K) // K)[order]
        sg = g_l.reshape(-1)[order]
        sizes = jnp.zeros((E_l + 1,), jnp.int32).at[se].add(1)
        starts = jnp.cumsum(sizes) - sizes
        rank = jnp.arange(T_l * K) - starts[se]
        keep = (rank < C) & (se < E_l)
        slot = jnp.where(keep, se * C + rank, 0)
        vals = jnp.where(keep[:, None], xt_l[st], 0)
        buf = jnp.zeros((E_l * C, xt_l.shape[1]), xt_l.dtype).at[slot].add(vals)
        buf = buf.reshape(E_l, C, -1)
        if ef_ax is not None:
            # weight-stationary: gather every data rank's token buffer,
            # compute this rank's F-slice for all of them, reduce-scatter
            # the partial outputs back to their owners
            buf_all = jax.lax.all_gather(buf, ef_ax)  # (Gd, E_l, C, D)
            h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf_all, wg_l))
            h = h * jnp.einsum("gecd,edf->gecf", buf_all, wu_l)
            ob_part = jnp.einsum("gecf,efd->gecd", h, wo_l)
            ob = jax.lax.psum_scatter(
                ob_part, ef_ax, scatter_dimension=0, tiled=False
            ).reshape(E_l * C, -1)
        else:
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg_l))
            h = h * jnp.einsum("ecd,edf->ecf", buf, wu_l)
            ob = jnp.einsum("ecf,efd->ecd", h, wo_l).reshape(E_l * C, -1)
        contrib = jnp.where(keep[:, None], ob[slot], 0) * sg[:, None]
        y = jnp.zeros_like(xt_l).at[st].add(contrib)
        return jax.lax.psum(y, model_ax)

    dp = dp_axes if dp_axes else None
    if ef_ax is not None:  # weight-stationary expert layout
        w_specs = (
            P(model_ax, None, ef_ax),
            P(model_ax, None, ef_ax),
            P(model_ax, ef_ax, None),
        )
    else:  # FSDP layout: d_model dim sharded over data
        w_specs = (
            P(model_ax, data_ax, None),
            P(model_ax, data_ax, None),
            P(model_ax, None, data_ax),
        )
    y = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(dp, None),  # tokens: replicated over model within the group
            P(dp, None),
            P(dp, None),
        )
        + w_specs,
        out_specs=P(dp, None),
        check_vma=False,
    )(xt, expert_ids, gate_vals, p["wi_gate"], p["wi_up"], p["wo"])
    y = y.reshape(B, S, D)
    y = shard(y, rules, "act_batch", "seq", None)

    if "dense" in p:
        from .layers import swiglu_mlp

        y = y + swiglu_mlp(p["dense"], x, rules)
    return y, aux


def _dp_groups(rules: Optional[ShardingRules]) -> int:
    """Number of data-parallel shards (the dispatch locality granularity)."""
    if rules is None or rules.mesh is None:
        return 1
    a = rules.assignment("batch")
    if a is None:
        return 1
    axes = a if isinstance(a, tuple) else (a,)
    g = 1
    for ax in axes:
        g *= rules.mesh.shape[ax]
    return g


def moe_apply(
    p: dict,
    x: jax.Array,  # (B, S, D)
    cfg,
    rules: Optional[ShardingRules],
    capacity_factor: Optional[float] = None,
    impl: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux load-balance loss (scalar)).

    impl="auto" picks the shard_map expert-parallel path whenever a mesh
    with a divisible expert axis is available (see moe_apply_shard_map);
    the pure-GSPMD path below is the single-device / fallback
    implementation, with dispatch blocked per DP group so sort/rank stay
    local to each shard."""
    if impl in ("auto", "shard_map") and rules is not None and rules.mesh is not None:
        m = _model_axis_size(rules)
        dp = _dp_groups(rules)
        tokens = x.shape[0] * x.shape[1]
        if m > 1 and cfg.moe.num_experts % m == 0 and tokens % max(dp, 1) == 0:
            return moe_apply_shard_map(p, x, cfg, rules, capacity_factor)
    B, S, D = x.shape
    E, K = cfg.moe.num_experts, cfg.moe.top_k
    cf = capacity_factor or cfg.moe.capacity_factor
    T = B * S
    G = _dp_groups(rules)
    if T % G:
        G = 1
    Tg = T // G
    C = _capacity(Tg, K, E, cf)

    xt = x.reshape(G, Tg, D)
    xt = shard(xt, rules, "act_batch", None, None)
    logits = (xt.astype(jnp.float32)) @ p["router"]  # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)  # (G, Tg, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    # load-balance auxiliary loss (Switch-style), over all tokens
    me = probs.mean(axis=(0, 1))  # (E,)
    ce = (
        jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (T * K)
    )
    aux = E * jnp.sum(me * ce)

    # ---- per-group sorted dispatch ---------------------------------------- #
    TK = Tg * K
    flat_expert = expert_ids.reshape(G, TK)
    flat_token = jnp.broadcast_to(
        jnp.repeat(jnp.arange(Tg), K)[None], (G, TK)
    )
    flat_gate = gate_vals.reshape(G, TK)

    order = jnp.argsort(flat_expert, axis=1)
    se = jnp.take_along_axis(flat_expert, order, axis=1)
    st = jnp.take_along_axis(flat_token, order, axis=1)
    sg = jnp.take_along_axis(flat_gate, order, axis=1)
    group_sizes = jnp.zeros((G, E), jnp.int32).at[
        jnp.arange(G)[:, None], se
    ].add(1)
    starts = jnp.cumsum(group_sizes, axis=1) - group_sizes
    rank = jnp.arange(TK)[None, :] - jnp.take_along_axis(starts, se, axis=1)
    keep = rank < C
    # dropped entries are zeroed and added to slot 0 (capacity guarantees
    # no two kept entries collide, so `.add` of zeros is safe) — this keeps
    # the flat buffer exactly E*C wide, which the model axis divides, so
    # the scatter target can be expert-sharded instead of replicated.
    slot = jnp.where(keep, se * C + rank, 0)

    gi = jnp.arange(G)[:, None]
    gathered = jnp.take_along_axis(xt, st[..., None], axis=1).astype(x.dtype)
    gathered = jnp.where(keep[..., None], gathered, 0)
    buf = jnp.zeros((G, E * C, D), x.dtype)
    buf = shard(buf, rules, "act_batch", "experts", None)
    buf = buf.at[gi, slot].add(gathered)
    buf = shard(buf, rules, "act_batch", "experts", None)
    buf = buf.reshape(G, E, C, D)
    # G -> data, E -> model: the reshard here IS the dispatch all-to-all
    buf = shard(buf, rules, "act_batch", "experts", None, None)

    # ---- expert computation ------------------------------------------------ #
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["wi_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", buf, p["wi_up"])
    h = shard(h, rules, "act_batch", "experts", None, "expert_ff")
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["wo"])
    out_buf = shard(out_buf, rules, "batch", "experts", None, None)

    # ---- combine (all-to-all back) ----------------------------------------- #
    out_flat = out_buf.reshape(G, E * C, D)
    contrib = jnp.take_along_axis(out_flat, slot[..., None], axis=1)
    contrib = jnp.where(keep[..., None], contrib, 0.0) * sg[..., None].astype(
        x.dtype
    )
    y = jnp.zeros((G, Tg, D), x.dtype).at[gi, st].add(contrib)
    y = y.reshape(B, S, D)
    y = shard(y, rules, "act_batch", "seq", None)

    if "dense" in p:  # arctic: parallel dense residual
        from .layers import swiglu_mlp

        y = y + swiglu_mlp(p["dense"], x, rules)
    return y, aux

"""Mixture-of-Experts FFN: top-k router + grouped sort-based dispatch.

Dispatch follows the GShard/MaxText *grouped* discipline: tokens are
processed in G = batch groups (one per sequence), each with its own
capacity C = ceil(S*k/E * factor).  Every dispatch step (stable sort by
expert id, intra-expert ranking, capacity scatter) carries the leading G
dim, which is sharded over the DP axes — so the SPMD partitioner keeps the
whole dispatch LOCAL to each data shard and the only cross-shard traffic
is the expert einsum against model-sharded weights.  (A global sort/scatter
formulation compiles to a full-buffer all-reduce across the mesh —
~276 GB/device/layer for grok — which is why groups matter.)

Within a group the dispatch is the modern sort/gather (megablocks-style)
form rather than GShard's one-hot einsums: a [T, E, C] one-hot at 1M
tokens x 128 experts is ~10^12 elements, while the sort route is O(T*k*d).
Out-of-capacity slots scatter out of bounds and are dropped
(capacity-factor policy, as in Switch).

Decode (S == 1): each group is a single token whose k routed experts are
distinct, so C = k guarantees zero drops and decode stays bit-consistent
with teacher forcing.

A shared expert (``cfg.moe_shared_ff``) sees every token; its output is
added to the routed experts'.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import ParamSpec, shard
from repro.models import layers


def moe_specs(cfg: ModelConfig, stacked: tuple[int, ...] = ()) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe_experts
    lead = tuple("layers" for _ in stacked)
    specs = {
        "router": ParamSpec(stacked + (d, e), lead + ("ffn_in", "experts")),
        "w_gate": ParamSpec(
            stacked + (e, d, f), lead + ("experts", "expert_in", "expert_mlp")
        ),
        "w_up": ParamSpec(
            stacked + (e, d, f), lead + ("experts", "expert_in", "expert_mlp")
        ),
        "w_down": ParamSpec(
            stacked + (e, f, d), lead + ("experts", "expert_mlp", "expert_in")
        ),
    }
    if cfg.moe_shared_ff:
        specs["shared"] = layers.mlp_specs(d, cfg.moe_shared_ff, stacked)
    return specs


def group_capacity(group_tokens: int, cfg: ModelConfig) -> int:
    if group_tokens == 1:
        return cfg.moe_top_k  # decode: exact, zero drops
    cap = int(
        group_tokens * cfg.moe_top_k / cfg.moe_experts * cfg.moe_capacity_factor
    )
    return max(cap, cfg.moe_top_k)


def moe_ffn(p: dict, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y: [B, S, d], aux_loss scalar)."""
    dt = x.dtype
    b, s, d = x.shape
    e, k = cfg.moe_experts, cfg.moe_top_k
    g = b                       # one group per sequence (sharded over DP)
    tg = s * k                  # routed slots per group
    cap = group_capacity(s, cfg)

    # ---- router (f32 numerics) ----------------------------------------------
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)           # [B,S,E]
    gate_vals, gate_idx = jax.lax.top_k(probs, k)      # [B,S,K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # ---- load-balance auxiliary loss (Switch) ----------------------------------
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(me * ce)

    # ---- grouped sort-based dispatch (everything keeps the leading G dim) ----
    eids = gate_idx.reshape(g, tg).astype(jnp.int32)          # [G, Tg]
    gates = gate_vals.reshape(g, tg)
    tok = jnp.broadcast_to(jnp.arange(tg, dtype=jnp.int32) // k, (g, tg))
    order = jnp.argsort(eids, axis=1, stable=True)
    eids_s = jnp.take_along_axis(eids, order, axis=1)
    tok_s = jnp.take_along_axis(tok, order, axis=1)
    gates_s = jnp.take_along_axis(gates, order, axis=1)
    counts = jnp.sum(
        (eids[:, :, None] == jnp.arange(e)[None, None, :]), axis=1
    )                                                          # [G, E]
    seg_start = jnp.cumsum(counts, axis=1) - counts
    rank = jnp.arange(tg, dtype=jnp.int32)[None, :] - jnp.take_along_axis(
        seg_start, eids_s, axis=1
    ).astype(jnp.int32)
    in_cap = rank < cap
    # out-of-capacity -> out-of-bounds -> scatter mode="drop"
    slot = jnp.where(in_cap, eids_s * cap + rank, e * cap)

    xg = x.reshape(g, s, d)
    xs = jnp.take_along_axis(
        xg, tok_s[:, :, None].astype(jnp.int32), axis=1
    )                                                          # [G, Tg, d]
    gidx = jnp.arange(g, dtype=jnp.int32)[:, None]
    buf = jnp.zeros((g, e * cap, d), dt).at[gidx, slot].set(xs, mode="drop")
    xe = buf.reshape(g, e, cap, d)
    # under EP rules this constraint IS the token all-to-all: xe leaves the
    # moe_group sharding and lands expert-sharded
    xe = shard(xe, "moe_group", "experts", "capacity", "expert_in")

    # ---- expert SwiGLU --------------------------------------------------------------
    h = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"].astype(dt))
    u = jnp.einsum("gecd,edf->gecf", xe, p["w_up"].astype(dt))
    h = jax.nn.silu(h) * u
    h = shard(h, "moe_group", "experts", "capacity", "expert_mlp")
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(dt))
    ye = shard(ye, "moe_group", "experts", "capacity", "expert_in")

    # ---- combine (un-sort + gate-weighted sum over the k slots) ----------------
    ye_flat = ye.reshape(g, e * cap, d)
    y_s = jnp.take_along_axis(
        ye_flat, jnp.minimum(slot, e * cap - 1)[:, :, None], axis=1
    )
    y_s = y_s * (gates_s * in_cap)[:, :, None].astype(dt)
    y = jnp.zeros((g, s, d), dt).at[gidx, tok_s].add(y_s)
    y = y.reshape(b, s, d)
    if cfg.moe_shared_ff:
        y = y + layers.mlp(p["shared"], x, dt)
    return shard(y, "batch", "seq", "act_embed"), aux

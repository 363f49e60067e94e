"""GQA attention with RoPE (or none, ``cfg.rope=False``): training,
prefill (cache write), decode.  ``cfg.attention_multiplier``, when set,
scales the scores in place of 1/sqrt(head_dim).

KV caches have logical axes (batch, long_kv/kv_seq, kv_heads, head_dim);
the long-context rules map the cache length onto the 'data' mesh axis when
the batch cannot fill it (long_500k), letting XLA partition the softmax
reduction across shards (flash-decode in SPMD form).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import ParamSpec, shard
from repro.kernels import ops
from repro.models import layers


def attn_specs(cfg: ModelConfig, stacked: tuple[int, ...] = ()) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    lead = tuple("layers" for _ in stacked)
    out = {
        "wq": ParamSpec(stacked + (d, h, hd), lead + ("ffn_in", "heads", "head_dim")),
        "wk": ParamSpec(stacked + (d, kv, hd), lead + ("ffn_in", "kv_heads", "head_dim")),
        "wv": ParamSpec(stacked + (d, kv, hd), lead + ("ffn_in", "kv_heads", "head_dim")),
        "wo": ParamSpec(stacked + (h, hd, d), lead + ("heads", "head_dim", "ffn_in")),
    }
    if cfg.qkv_bias:
        out["bq"] = ParamSpec(stacked + (h, hd), lead + ("heads", "head_dim"), init="zeros")
        out["bk"] = ParamSpec(stacked + (kv, hd), lead + ("kv_heads", "head_dim"), init="zeros")
        out["bv"] = ParamSpec(stacked + (kv, hd), lead + ("kv_heads", "head_dim"), init="zeros")
    return out


def _qkv(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array, dt):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.rope:
        q = layers.rope(q, positions, cfg.rope_theta)
        k = layers.rope(k, positions, cfg.rope_theta)
    if cfg.attention_multiplier:
        # the kernels scale scores by 1/sqrt(head_dim); undo it in q
        q = q * jnp.asarray(cfg.attention_multiplier * q.shape[-1] ** 0.5, dt)
    # 'seq_attn' is None by default; rules map it to 'model' for archs
    # whose head count cannot take the TP axis (context-parallel attention)
    q = shard(q, "batch", "seq_attn", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def self_attention(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Full causal self-attention (training / scoring)."""
    dt = x.dtype
    positions = jnp.arange(x.shape[1])
    q, k, v = _qkv(p, x, cfg, positions, dt)
    out = ops.attention(q, k, v, causal=True, impl=cfg.attn_impl)
    out = shard(out, "batch", "seq_attn", "heads", "head_dim")
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def prefill_attention(
    p: dict, x: jax.Array, cfg: ModelConfig, cache: dict
) -> tuple[jax.Array, dict]:
    """Causal attention over the prompt; writes k/v into the cache at [0, S)."""
    dt = x.dtype
    s = x.shape[1]
    positions = jnp.arange(s)
    q, k, v = _qkv(p, x, cfg, positions, dt)
    out = ops.attention(q, k, v, causal=True, impl=cfg.attn_impl)
    new_cache = {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)
        ),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)
        ),
    }
    out = shard(out, "batch", "seq", "heads", "head_dim")
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt)), new_cache


def decode_attention(
    p: dict,
    x: jax.Array,          # [B, 1, d]
    cfg: ModelConfig,
    cache: dict,           # k/v: [B, S_max, KV, hd]
    cache_len: jax.Array,  # scalar int32: tokens already in cache
) -> tuple[jax.Array, dict]:
    """Single-token decode against the KV cache."""
    dt = x.dtype
    positions = cache_len[None] if cache_len.ndim == 0 else cache_len
    q, k, v = _qkv(p, x, cfg, positions.reshape(1), dt)
    bsz = x.shape[0]
    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, cache_len, 0, 0)
    )
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, cache_len, 0, 0)
    )
    kv_len = jnp.full((bsz,), cache_len + 1, jnp.int32)
    out = ops.attention(
        q,
        k_cache.astype(dt),
        v_cache.astype(dt),
        causal=False,
        kv_len=kv_len,
        impl="ref",  # single-query path: XLA partitions the length reduction
    )
    out = shard(out, "batch", "seq", "heads", "head_dim")
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return y, {"k": k_cache, "v": v_cache}


def cache_specs(cfg: ModelConfig, batch: int, max_len: int, long_ctx: bool,
                stacked: tuple[int, ...] = ()) -> dict:
    """ParamSpec tree for the attention KV cache (used by serve dry-run)."""
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    seq_axis = "long_kv" if long_ctx else "kv_seq"
    lead = tuple("layers" for _ in stacked)
    spec = ParamSpec(
        stacked + (batch, max_len, kv, hd),
        lead + ("batch", seq_axis, "kv_heads", "head_dim"),
        init="zeros",
        dtype=layers.dtype_of(cfg.compute_dtype),
    )
    return {"k": spec, "v": spec}

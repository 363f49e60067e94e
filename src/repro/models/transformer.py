"""Decoder stack assembly for all assigned families.

A *block* is the scan unit; each family defines a block layout — a list of
(mixer, ffn) sublayers:

  dense    : [(attn, mlp)]                                x n_layers
  moe e1   : [(attn, moe)]                                x n_layers   (grok)
  moe e2   : [(attn, mlp), (attn, moe)]                   x n_layers/2 (llama4)
  hybrid   : [(attn, mlp|moe), (mamba, ...) x 7]          x n_layers/8 (jamba,
             1 attention per 8 sublayers, MoE on odd global layer indices);
             attention at sublayer ``attn_offset`` of its period, MoE on
             the last sublayer of every ``moe_every`` (granite-4.0-h:
             attention at 5 of 10, MoE on all)
  ssm      : [(mamba, None)]                              x n_layers   (mamba2)

Within a block, params of each sublayer type are stacked on a 'sublayers'
dim and applied by a short unrolled loop; blocks themselves are stacked on
a 'layers' dim and driven by ``lax.scan`` (keeps HLO size and compile time
independent of depth).  ``cfg.remat`` wraps the scan body in
``jax.checkpoint`` with the selected policy.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import ParamSpec, shard
from repro.models import attention, layers, mamba, moe


# ---------------------------------------------------------------------------
# layouts
# ---------------------------------------------------------------------------
def block_layout(cfg: ModelConfig) -> list[tuple[str, str | None]]:
    if cfg.family == "dense":
        return [("attn", "mlp")]
    if cfg.family == "moe":
        if cfg.moe_every == 1:
            return [("attn", "moe")]
        out = []
        for i in range(cfg.moe_every):
            out.append(("attn", "moe" if i % 2 == 1 else "mlp"))
        return out
    if cfg.family == "hybrid":
        out = []
        for i in range(cfg.attn_every):
            mixer = "attn" if i == cfg.attn_offset else "mamba"
            moe_here = cfg.moe_experts and i % cfg.moe_every == cfg.moe_every - 1
            ffn = "moe" if moe_here else "mlp"
            out.append((mixer, ffn))
        return out
    if cfg.family == "ssm":
        return [("mamba", None)]
    raise ValueError(cfg.family)


def _counts(cfg: ModelConfig) -> dict[str, int]:
    layout = block_layout(cfg)
    return {
        "attn": sum(1 for m, _ in layout if m == "attn"),
        "mamba": sum(1 for m, _ in layout if m == "mamba"),
        "mlp": sum(1 for _, f in layout if f == "mlp"),
        "moe": sum(1 for _, f in layout if f == "moe"),
        "sub": len(layout),
        "ffn": sum(1 for _, f in layout if f),
    }


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------
def block_specs(cfg: ModelConfig) -> dict:
    c = _counts(cfg)
    nb = cfg.n_blocks
    d = cfg.d_model
    specs: dict[str, Any] = {
        "mixer_norm": ParamSpec(
            (nb, c["sub"], d), ("layers", "layers", "act_embed"), init="ones"
        ),
    }
    if c["ffn"]:
        specs["ffn_norm"] = ParamSpec(
            (nb, c["ffn"], d), ("layers", "layers", "act_embed"), init="ones"
        )
    if c["attn"]:
        specs["attn"] = attention.attn_specs(cfg, stacked=(nb, c["attn"]))
    if c["mamba"]:
        specs["mamba"] = mamba.mamba_specs(cfg, stacked=(nb, c["mamba"]))
    if c["mlp"]:
        specs["mlp"] = layers.mlp_specs(d, cfg.d_ff, stacked=(nb, c["mlp"]))
    if c["moe"]:
        specs["moe"] = moe.moe_specs(cfg, stacked=(nb, c["moe"]))
    return specs


def param_specs(cfg: ModelConfig) -> dict:
    specs = {
        "tok": layers.embed_specs(cfg.vocab, cfg.d_model, cfg.tie_embeddings),
        "blocks": block_specs(cfg),
        "final_norm": layers.rmsnorm_spec(cfg.d_model),
    }
    return specs


# ---------------------------------------------------------------------------
# cache specs (serving)
# ---------------------------------------------------------------------------
def cache_specs(cfg: ModelConfig, batch: int, max_len: int, long_ctx: bool) -> dict:
    c = _counts(cfg)
    nb = cfg.n_blocks
    out: dict[str, Any] = {}
    if c["attn"]:
        out["attn"] = attention.cache_specs(
            cfg, batch, max_len, long_ctx, stacked=(nb, c["attn"])
        )
    if c["mamba"]:
        out["mamba"] = mamba.state_specs(cfg, batch, stacked=(nb, c["mamba"]))
    return out


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------
def _tree_index(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


def _residual(y: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.residual_multiplier == 1.0:
        return y
    return y * jnp.asarray(cfg.residual_multiplier, y.dtype)


def apply_block(
    bp: dict,
    x: jax.Array,
    cfg: ModelConfig,
    cache: dict | None,
    cache_len: jax.Array | None,
    mode: str,  # full | prefill | decode
):
    """Returns (x, new_cache_or_None, aux_loss)."""
    layout = block_layout(cfg)
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict[str, Any] = {}
    idx = {"attn": 0, "mamba": 0, "mlp": 0, "moe": 0}
    attn_caches, mamba_caches = [], []

    for sub, (mixer, ffn) in enumerate(layout):
        h = layers.rmsnorm(x, bp["mixer_norm"][sub], eps)
        if mixer == "attn":
            ap = _tree_index(bp["attn"], idx["attn"])
            if mode == "full":
                y = attention.self_attention(ap, h, cfg)
            elif mode == "prefill":
                cslice = _tree_index(cache["attn"], idx["attn"])
                y, nc = attention.prefill_attention(ap, h, cfg, cslice)
                attn_caches.append(nc)
            else:
                cslice = _tree_index(cache["attn"], idx["attn"])
                y, nc = attention.decode_attention(ap, h, cfg, cslice, cache_len)
                attn_caches.append(nc)
        else:
            mp = _tree_index(bp["mamba"], idx["mamba"])
            st = _tree_index(cache["mamba"], idx["mamba"]) if mode == "decode" else None
            y, nst = mamba.mamba_forward(mp, h, cfg, st)
            if mode in ("prefill", "decode"):
                mamba_caches.append(nst)
        idx[mixer] += 1
        x = x + _residual(y, cfg)
        x = shard(x, "batch", "seq", "act_embed")

        if ffn:
            fi = idx["mlp"] + idx["moe"]
            h = layers.rmsnorm(x, bp["ffn_norm"][fi], eps)
            if ffn == "mlp":
                y = layers.mlp(
                    _tree_index(bp["mlp"], idx["mlp"]),
                    h,
                    layers.dtype_of(cfg.compute_dtype),
                )
            else:
                y, a = moe.moe_ffn(_tree_index(bp["moe"], idx["moe"]), h, cfg)
                aux = aux + a
            idx[ffn] += 1
            x = x + _residual(y, cfg)
            x = shard(x, "batch", "seq", "act_embed")

    if mode == "full":
        return x, None, aux
    if attn_caches:
        new_cache["attn"] = jax.tree.map(lambda *a: jnp.stack(a), *attn_caches)
    if mamba_caches:
        new_cache["mamba"] = jax.tree.map(lambda *a: jnp.stack(a), *mamba_caches)
    return x, new_cache, aux


def cache_max_len(cache) -> int:
    """Static max length from an (abstract or real) attn cache tree."""
    return cache["attn"]["k"].shape[-3]


# ---------------------------------------------------------------------------
# stack (scan over blocks)
# ---------------------------------------------------------------------------
def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    elif cfg.remat == "full":
        policy = jax.checkpoint_policies.nothing_saveable
    else:
        raise ValueError(cfg.remat)
    return jax.checkpoint(fn, policy=policy)


def run_stack(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    cache: dict | None = None,
    cache_len: jax.Array | None = None,
    mode: str = "full",
):
    """x: [B, S, d] hidden states -> (x, new_cache_or_None, aux)."""

    if mode == "full":

        def body(carry, bp):
            h, aux = carry
            h, _, a = apply_block(bp, h, cfg, None, None, "full")
            return (h, aux + a), None

        body = _remat_wrap(body, cfg)
        if cfg.scan_layers:
            (x, aux), _ = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), params["blocks"]
            )
        else:
            aux = jnp.zeros((), jnp.float32)
            for i in range(cfg.n_blocks):
                (x, aux), _ = body((x, aux), _tree_index(params["blocks"], i))
        return x, None, aux

    # prefill and decode both stream the cache through scan xs/ys
    def body(carry, xs):
        h, aux = carry
        bp, cslice = xs
        h, nc, a = apply_block(bp, h, cfg, cslice, cache_len, mode)
        return (h, aux + a), nc

    if cfg.scan_layers:
        (x, aux), new_cache = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (params["blocks"], cache)
        )
    else:
        aux = jnp.zeros((), jnp.float32)
        caches = []
        for i in range(cfg.n_blocks):
            (x, aux), nc = body(
                (x, aux), (_tree_index(params["blocks"], i), _tree_index(cache, i))
            )
            caches.append(nc)
        new_cache = jax.tree.map(lambda *a: jnp.stack(a), *caches)
    return x, new_cache, aux

"""Unified model API over the architecture zoo.

    specs   = param_specs(cfg)                    # ParamSpec tree
    params  = init_params(key, cfg)               # real weights (tests/training)
    ab      = abstract_params(cfg)                # ShapeDtypeStructs (dry-run)
    logits, aux = forward(params, cfg, tokens=...)      # teacher-forced
    loss, metrics = loss_fn(params, cfg, batch)
    logits, cache = prefill(params, cfg, tokens, cache)
    logits, cache = decode_step(params, cfg, token, cache, cache_len)

`[vlm]`/`[audio]` archs take precomputed frontend embeddings via
``embeds=`` (the assignment's stub frontend).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import sharding
from repro.dist.sharding import shard
from repro.models import layers, transformer


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def param_specs(cfg: ModelConfig) -> dict:
    return transformer.param_specs(cfg)


def init_params(key: jax.Array, cfg: ModelConfig):
    return sharding.materialize(
        key, param_specs(cfg), layers.dtype_of(cfg.param_dtype)
    )


def abstract_params(cfg: ModelConfig):
    return sharding.tree_abstract(param_specs(cfg), layers.dtype_of(cfg.param_dtype))


def cache_specs(cfg: ModelConfig, batch: int, max_len: int, long_ctx: bool = False):
    return transformer.cache_specs(cfg, batch, max_len, long_ctx)


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, long_ctx: bool = False):
    return sharding.tree_abstract(
        cache_specs(cfg, batch, max_len, long_ctx), layers.dtype_of(cfg.compute_dtype)
    )


def init_cache(cfg: ModelConfig, batch: int, max_len: int, long_ctx: bool = False):
    # all cache specs are zeros-init
    return sharding.materialize(
        jax.random.PRNGKey(0),
        cache_specs(cfg, batch, max_len, long_ctx),
        layers.dtype_of(cfg.compute_dtype),
    )


# ---------------------------------------------------------------------------
# forward paths
# ---------------------------------------------------------------------------
def _embed_in(params, cfg: ModelConfig, tokens, embeds):
    dt = layers.dtype_of(cfg.compute_dtype)
    if embeds is not None:
        x = embeds.astype(dt)
    else:
        x = layers.embed_lookup(params["tok"], tokens, dt)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, dt)
    return shard(x, "batch", "seq", "act_embed")


def _logits(params, cfg: ModelConfig, x):
    logits = layers.unembed(params["tok"], x, layers.dtype_of(cfg.compute_dtype))
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return logits


def forward(params, cfg: ModelConfig, tokens=None, embeds=None):
    """Teacher-forced full-sequence forward.  Returns (logits, aux)."""
    x = _embed_in(params, cfg, tokens, embeds)
    x, _, aux = transformer.run_stack(params, x, cfg, mode="full")
    x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _logits(params, cfg, x), aux


def loss_fn(params, cfg: ModelConfig, batch: dict):
    """batch: {'tokens' or 'embeds', 'labels', optional 'mask'}."""
    logits, aux = forward(
        params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds")
    )
    xent = layers.softmax_xent(logits, batch["labels"], valid_vocab=cfg.vocab)
    loss = xent + cfg.moe_aux_weight * aux
    return loss, {"xent": xent, "aux": aux}


def prefill(params, cfg: ModelConfig, tokens=None, cache=None, embeds=None):
    """Process the prompt, fill the cache.  Returns (last-position logits, cache)."""
    x = _embed_in(params, cfg, tokens, embeds)
    x, new_cache, _ = transformer.run_stack(params, x, cfg, cache=cache, mode="prefill")
    x = layers.rmsnorm(x[:, -1:, :], params["final_norm"], cfg.norm_eps)
    return _logits(params, cfg, x), new_cache


def decode_step(params, cfg: ModelConfig, token=None, cache=None, cache_len=None,
                embeds=None):
    """One decode step.  token: [B, 1] ids (or embeds [B, 1, d]);
    cache_len: scalar int32 tokens already in cache.  Returns (logits, cache)."""
    x = _embed_in(params, cfg, token, embeds)
    x, new_cache, _ = transformer.run_stack(
        params, x, cfg, cache=cache, cache_len=cache_len, mode="decode"
    )
    x = layers.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return _logits(params, cfg, x), new_cache


# ---------------------------------------------------------------------------
# analytic parameter counts (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------
def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    import numpy as np

    specs = jax.tree.leaves(
        param_specs(cfg), is_leaf=lambda s: isinstance(s, sharding.ParamSpec)
    )
    total = sum(int(np.prod(s.shape)) for s in specs)
    if not active_only or not cfg.moe_experts:
        return total
    # active = total - (inactive experts' weights)
    layout = transformer.block_layout(cfg)
    n_moe = sum(1 for _, f in layout if f == "moe") * cfg.n_blocks
    per_expert = 3 * cfg.d_model * cfg.d_ff
    inactive = n_moe * (cfg.moe_experts - cfg.moe_top_k) * per_expert
    return total - inactive

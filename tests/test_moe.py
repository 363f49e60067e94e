"""MoE dispatch invariants."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import moe


def _cfg(**kw):
    base = dict(
        arch_id="t", family="moe", n_layers=1, d_model=32, n_heads=4, n_kv_heads=4,
        d_ff=64, vocab=64, moe_experts=4, moe_top_k=2,
        param_dtype="float32", compute_dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base)


def _params(cfg, key):
    from repro.dist import sharding

    return sharding.materialize(key, moe.moe_specs(cfg), jnp.float32)


def test_moe_matches_dense_sum_when_no_drops():
    """With capacity >= tokens, MoE output == explicit per-token expert mix."""
    cfg = _cfg(moe_capacity_factor=16.0)
    key = jax.random.PRNGKey(0)
    p = _params(cfg, key)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    y, aux = moe.moe_ffn(p, x, cfg)

    # dense reference: route every token through its top-k experts
    logits = jnp.einsum("bsd,de->bse", x, p["router"])
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gv, gi = jax.lax.top_k(probs, cfg.moe_top_k)
    gv = gv / gv.sum(-1, keepdims=True)
    want = jnp.zeros_like(x)
    for b in range(2):
        for s in range(8):
            acc = jnp.zeros((cfg.d_model,))
            for k in range(cfg.moe_top_k):
                e = int(gi[b, s, k])
                h = jax.nn.silu(x[b, s] @ p["w_gate"][e]) * (x[b, s] @ p["w_up"][e])
                acc = acc + gv[b, s, k] * (h @ p["w_down"][e])
            want = want.at[b, s].set(acc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-4, atol=2e-4)
    assert float(aux) > 0


def test_capacity_drops_bounded():
    """With a tight capacity, output norm shrinks but stays finite, and no
    token receives weight from an expert it wasn't routed to."""
    cfg = _cfg(moe_capacity_factor=0.5)
    p = _params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, _ = moe.moe_ffn(p, x, cfg)
    assert bool(jnp.isfinite(y).all())


def test_group_capacity_decode_exact():
    assert moe.group_capacity(1, _cfg()) == 2  # == top_k, zero drops


def test_aux_loss_uniform_router_is_one():
    """Perfectly uniform routing gives aux ~= 1 (Switch normalization)."""
    cfg = _cfg(moe_experts=4, moe_top_k=1)
    p = _params(cfg, jax.random.PRNGKey(0))
    p = dict(p)
    p["router"] = jnp.zeros_like(p["router"])  # uniform probs
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))
    _, aux = moe.moe_ffn(p, x, cfg)
    # me = 1/E exactly; ce depends on top-1 tie-breaking; aux = E*sum(me*ce) = 1
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-5)


def test_the_shared_expert_adds_to_the_routed_experts():
    """A shared expert sees every token and adds its MLP's output to the
    routed experts'; the router and the routed experts are unchanged."""
    from repro.models import layers

    cfg = _cfg(moe_experts=8, moe_top_k=3, moe_shared_ff=48, moe_capacity_factor=16.0)
    p = _params(cfg, jax.random.PRNGKey(0))
    assert moe.moe_specs(cfg)["shared"]["w_up"].shape == (cfg.d_model, 48)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    y, aux = moe.moe_ffn(p, x, cfg)
    routed = dataclasses.replace(cfg, moe_shared_ff=0)
    assert set(moe.moe_specs(routed)) == set(p) - {"shared"}
    y0, aux0 = moe.moe_ffn({k: v for k, v in p.items() if k != "shared"}, x, routed)
    shared = layers.mlp(p["shared"], x, jnp.float32)
    assert float(jnp.abs(shared).max()) > 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0 + shared), rtol=1e-5, atol=1e-5)
    assert float(aux) == float(aux0)

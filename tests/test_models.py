"""Per-arch smoke tests (reduced configs): forward/train step shapes +
finiteness, prefill/decode consistency against teacher forcing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model
from repro.optim import optimizers
from repro.train import step as step_lib

ARCHS = configs.ARCH_IDS


def _inputs(cfg, key, B=2, S=16):
    if cfg.input_kind == "embeddings":
        return {"embeds": jax.random.normal(key, (B, S, cfg.d_model), jnp.float32) * 0.1}
    return {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = configs.get_smoke(arch)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key, cfg)
    B, S = 2, 16
    inp = _inputs(cfg, key, B, S)
    logits, aux = model.forward(params, cfg, **inp)
    assert logits.shape[:2] == (B, S)
    assert logits.shape[2] >= cfg.vocab
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"

    labels = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    batch = dict(inp, labels=labels)
    opt = optimizers.adamw(1e-3, max_grad_norm=1.0)
    opt_state = opt.init(params)
    train_step = jax.jit(step_lib.make_train_step(cfg, opt))
    params2, opt_state, metrics = train_step(params, opt_state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually moved
    delta = optimizers.global_norm(
        jax.tree.map(lambda a, b: a - b, params, params2)
    )
    assert float(delta) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_consistency(arch):
    # no-drop capacity so MoE decode == teacher forcing exactly
    cfg = dataclasses.replace(configs.get_smoke(arch), moe_capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key, cfg)
    B, S = 2, 16
    inp = _inputs(cfg, key, B, S)
    logits, _ = model.forward(params, cfg, **inp)

    cache = model.init_cache(cfg, B, S + 4)
    lg_pref, cache = model.prefill(params, cfg, cache=cache, **inp)
    np.testing.assert_allclose(
        np.asarray(lg_pref, np.float32),
        np.asarray(logits[:, -1:, :], np.float32),
        rtol=2e-3, atol=2e-3,
    )

    if cfg.input_kind == "embeddings":
        nxt = {"embeds": jax.random.normal(jax.random.PRNGKey(2), (B, 1, cfg.d_model)) * 0.1}
        ext = {"embeds": jnp.concatenate([inp["embeds"], nxt["embeds"]], axis=1)}
        dec = {"embeds": nxt["embeds"]}
    else:
        t = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab)
        ext = {"tokens": jnp.concatenate([inp["tokens"], t], axis=1)}
        dec = {"token": t}
    lg_dec, cache = model.decode_step(params, cfg, cache=cache, cache_len=jnp.int32(S), **dec)
    lg_ext, _ = model.forward(params, cfg, **ext)
    np.testing.assert_allclose(
        np.asarray(lg_dec, np.float32),
        np.asarray(lg_ext[:, -1:, :], np.float32),
        rtol=2e-3, atol=2e-3,
    )


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_abstract_params(arch):
    """Full-size configs build abstract trees (no allocation) with sane counts."""
    cfg = configs.get(arch)
    ab = model.abstract_params(cfg)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(ab))
    expected_ballpark = {
        "deepseek-coder-33b": 33e9, "minicpm-2b": 2.7e9, "starcoder2-15b": 15e9,
        "qwen1.5-4b": 4e9, "grok-1-314b": 314e9,
        "llama4-maverick-400b-a17b": 400e9, "jamba-1.5-large-398b": 398e9,
        "mamba2-1.3b": 1.3e9, "internvl2-76b": 70e9, "musicgen-medium": 1.5e9,
        "granite-4.0-h-small": 32e9,
    }[arch]
    assert 0.5 * expected_ballpark < n < 2.2 * expected_ballpark, (arch, n)


def test_scan_vs_unrolled_equivalence():
    cfg = configs.get_smoke("deepseek-coder-33b")
    key = jax.random.PRNGKey(0)
    params = model.init_params(key, cfg)
    toks = jax.random.randint(key, (2, 8), 0, cfg.vocab)
    a, _ = model.forward(params, cfg, tokens=toks)
    cfg2 = dataclasses.replace(cfg, scan_layers=False)
    b, _ = model.forward(params, cfg2, tokens=toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)

"""NTTD model unit tests (paper Alg. 2)."""
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import nttd
from repro.core.folding import make_folding_spec, spec_from_factors

GRANITE = Path(__file__).resolve().parents[1] / "bench" / "configs" / "granite4h_small-ep9.json"


def _setup(shape=(12, 10, 8), rank=4, hidden=8):
    spec = make_folding_spec(shape)
    cfg = nttd.NTTDConfig(rank=rank, hidden=hidden)
    params = nttd.init_params(jax.random.PRNGKey(0), spec, cfg)
    return spec, cfg, params


def test_output_shape_and_finite():
    spec, cfg, params = _setup()
    rng = np.random.default_rng(0)
    pos = np.stack([rng.integers(0, n, 64) for n in spec.shape], axis=1)
    out = nttd.apply_at_positions(params, jnp.asarray(pos, jnp.int32), spec, cfg)
    assert out.shape == (64,)
    assert bool(jnp.isfinite(out).all())


def test_gradients_reach_every_param():
    spec, cfg, params = _setup()
    rng = np.random.default_rng(1)
    pos = np.stack([rng.integers(0, n, 128) for n in spec.shape], axis=1)
    vals = jnp.asarray(rng.normal(size=128), jnp.float32)

    def loss(p):
        preds = nttd.apply_at_positions(p, jnp.asarray(pos, jnp.int32), spec, cfg)
        return jnp.sum((preds - vals) ** 2)

    grads = jax.grad(loss)(params)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert float(jnp.abs(g).sum()) > 0, f"dead gradient at {path}"


def test_chain_matches_manual_product():
    """The TT chain equals an explicit per-entry matrix product."""
    spec, cfg, params = _setup(rank=3, hidden=8)
    rng = np.random.default_rng(2)
    pos = np.stack([rng.integers(0, n, 8) for n in spec.shape], axis=1)
    fidx = spec.fold_indices(pos)
    out = nttd.apply(params, jnp.asarray(fidx, jnp.int32), spec, cfg)

    # manual recomputation
    embeds = [
        params[f"embed_{m}"][fidx[:, j]] for j, m in enumerate(spec.folded_shape)
    ]
    x = jnp.stack(embeds, axis=1)
    from repro.kernels import ref

    hs = ref.lstm_scan(x, params["lstm"]["wi"], params["lstm"]["wh"], params["lstm"]["b"])
    r = cfg.rank
    manual = []
    for b in range(8):
        t = (hs[b, 0] @ params["head_first"]["w"] + params["head_first"]["b"])[None, :]
        for k in range(1, spec.d_prime - 1):
            m = (hs[b, k] @ params["head_mid"]["w"] + params["head_mid"]["b"]).reshape(r, r)
            t = t @ m
        last = (hs[b, -1] @ params["head_last"]["w"] + params["head_last"]["b"])[:, None]
        manual.append((t @ last)[0, 0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(manual), rtol=2e-5, atol=2e-5)


def test_count_params_matches_theorem1_structure():
    spec, cfg, params = _setup(rank=4, hidden=8)
    h, r = 8, 4
    expected = (
        sum(m * h for m in set(spec.folded_shape))  # shared embedding tables
        + (h * 4 * h) * 2 + 4 * h                   # LSTM
        + h * r + r                                 # first head
        + h * r * r + r * r                         # shared mid head
        + h * r + r                                 # last head
    )
    assert nttd.count_params(params) == expected


def test_generate_tensor_matches_pointwise():
    spec, cfg, params = _setup(shape=(6, 5, 4))
    full = nttd.generate_tensor(params, spec, cfg, slab=64)
    rng = np.random.default_rng(3)
    pos = np.stack([rng.integers(0, n, 32) for n in spec.shape], axis=1)
    vals = nttd.apply_at_positions(params, jnp.asarray(pos, jnp.int32), spec, cfg)
    np.testing.assert_allclose(
        full[tuple(pos[:, j] for j in range(3))], np.asarray(vals), rtol=1e-5, atol=1e-5
    )


@functools.partial(jax.jit, static_argnames=("shape", "factors", "slab"))
def _per_entry_indices(start, inv_pi, *, shape, factors, slab):
    """The dense decode's folded indices as one entry at a time gives
    them: each flat index unravelled, every mode mapped through its
    ``inv_pi`` and folded.  [slab, d']"""
    rem = start + jnp.arange(slab, dtype=jnp.int32)
    pos = [None] * len(shape)
    for k in reversed(range(len(shape))):
        pos[k] = jnp.take(inv_pi[k], rem % shape[k])
        rem = rem // shape[k]
    return spec_from_factors(shape, factors).fold_modes(pos)


def _slab_cases():
    """(shape, d', slab, start, order) of every granite leaf at the
    restore's slab, at its first, an interior start not aligned to its
    tail, and its last (shifted) slab; and small shapes at each edge of
    the split."""
    leaves = [(tuple(x["shape"]), x["d_prime"], nttd.SLAB_ENTRIES, x["key"])
              for x in json.loads(GRANITE.read_text())["leaves"]]
    small = [((40, 30), None, 1000, "smaller-than-the-tail"),
             ((6, 5, 4), None, 7, "one-table"),
             ((5, 300, 300), None, 1000, "tail-not-dividing-the-slab"),
             ((1, 1, 300, 700), None, 5000, "leading-ones"),
             ((3, 70000), None, 1000, "trailing-mode-over-the-cap"),
             ((70000,), None, 1000, "one-mode"),
             ((5, 300, 300), None, 1000, "identity")]
    cases = []
    for shape, d_prime, slab, name in leaves + small:
        n = int(np.prod(shape))
        slab = min(slab, n)
        starts = {"first": 0}
        if n - slab > 2:  # odd, so not on a row of any of these (even) tails
            starts["interior"] = (n - slab) // 2 | 1
        if n > slab:
            starts["last"] = n - slab
        order = "identity" if name == "identity" else "random"
        cases += [pytest.param(shape, d_prime, slab, start, order, id=f"{name}-{where}")
                  for where, start in starts.items()]
    return cases


@pytest.mark.parametrize("shape,d_prime,slab,start,order", _slab_cases())
def test_slab_indices_equal_the_per_entry_build(shape, d_prime, slab, start, order):
    """The broadcast sum of head rows and the tail table gives every
    entry's folded index exactly as the per-entry unravel, gather and
    fold do, so the tile sees the same inputs."""
    spec = make_folding_spec(shape, d_prime)
    rng = np.random.default_rng(start + len(shape))
    inv_pi = [np.arange(m) if order == "identity" else rng.permutation(m) for m in shape]
    t = nttd.slab_split(shape, slab)[0]
    static = {"shape": shape, "factors": tuple(tuple(int(f) for f in row) for row in spec.factors),
              "slab": slab}
    want = _per_entry_indices(jnp.int32(start), tuple(jnp.asarray(p, jnp.int32) for p in inv_pi),
                              **static)
    got = jax.jit(nttd.slab_indices, static_argnames=tuple(static))(
        jnp.int32(start), tuple(jnp.asarray(p, jnp.int32) for p in inv_pi[:t]),
        jnp.asarray(nttd.tail_table(inv_pi, spec)), **static)
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype == np.int32
    assert np.array_equal(got.T, want)

"""Property sweeps for the TT-tensor folding index math (paper Eq. 4).

hypothesis is unavailable offline; properties are checked over seeded
randomized shape grids (same invariants, deterministic).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.folding import choose_factors, default_d_prime, make_folding_spec

RNG = np.random.default_rng(0)
SHAPES = [
    (8,), (5,), (7, 3), (16, 16), (12, 9, 30), (963, 144, 440)[:2],
    (40, 25, 30), (31, 17, 5), (8, 8, 8, 8), (13, 7, 11, 3), (183, 24, 57),
]


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_fold_unfold_bijective(shape):
    spec = make_folding_spec(shape)
    n = int(np.prod(shape))
    take = min(n, 5000)
    flat = RNG.choice(n, size=take, replace=False)
    dims = np.array(shape)
    radix = np.concatenate([np.cumprod(dims[::-1])[::-1][1:], [1]])
    idx = (flat[:, None] // radix) % dims
    folded = spec.fold_indices(idx)
    # folded indices are in range
    assert (folded >= 0).all()
    assert (folded < np.array(spec.folded_shape)).all()
    back = spec.unfold_indices(folded)
    np.testing.assert_array_equal(back, idx)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_fold_injective(shape):
    """Distinct original entries never collide in the folded tensor."""
    spec = make_folding_spec(shape)
    n = int(np.prod(shape))
    take = min(n, 4000)
    flat = RNG.choice(n, size=take, replace=False)
    dims = np.array(shape)
    radix = np.concatenate([np.cumprod(dims[::-1])[::-1][1:], [1]])
    idx = (flat[:, None] // radix) % dims
    folded = spec.fold_indices(idx)
    fdims = np.array(spec.folded_shape)
    fradix = np.concatenate([np.cumprod(fdims[::-1])[::-1][1:], [1]])
    keys = (folded * fradix).sum(axis=1)
    assert len(np.unique(keys)) == take


def test_choose_factors_properties():
    for dim in [1, 2, 3, 5, 17, 144, 963, 1140, 5600, 122753]:
        for dp in [default_d_prime((dim,)), default_d_prime((dim,)) + 2]:
            f = choose_factors(dim, dp)
            assert len(f) == dp
            assert all(1 <= x <= 5 for x in f)
            prod = int(np.prod(f))
            assert prod >= dim
            # minimality-ish: halving any 2 would undershoot
            assert prod // 2 < dim or all(x != 2 for x in f)


def test_padding_is_bounded():
    """Folded size stays within a small factor of the input size."""
    for shape in SHAPES:
        spec = make_folding_spec(shape)
        assert spec.padded_entries < 8 * spec.n_entries


def test_dprime_exceeds_order():
    for shape in SHAPES:
        spec = make_folding_spec(shape)
        assert spec.d_prime > len(shape)  # paper: d' > d


@pytest.mark.parametrize("shape", [(7, 1, 30), (100, 37), (1, 9, 64, 131)])
def test_fold_modes_matches_fold_indices(shape):
    spec = make_folding_spec(shape)
    rng = np.random.default_rng(0)
    idx = np.stack([rng.integers(0, n, 257) for n in shape], axis=1)
    got = spec.fold_modes([jnp.asarray(idx[:, k], jnp.int32) for k in range(len(shape))])
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), spec.fold_indices(idx))

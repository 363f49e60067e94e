"""TT-tensor folding (paper Eq. 4).

Folds a d-order tensor of shape (N_1, ..., N_d) into a d'-order tensor whose
l-th mode has length prod_k n_{k,l}, where the factor matrix ``n[k, l]``
satisfies ``prod_l n[k, l] >= N_k``.  Original mode-k indices are decomposed
into big-endian mixed-radix digits ``i_{k,l}``; folded mode-l indices are the
big-endian mixed-radix composition of the l-th digit of every original mode.

The folded tensor is never materialized: all consumers work through
``fold_indices`` / ``unfold_indices``.  Positions whose digit expansion maps
outside the original shape ("padding", paper: values disregarded) are simply
never addressed.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax.numpy as jnp
import numpy as np

MAX_FACTOR = 5  # paper: "modify some of them using integers at most 5"


def choose_factors(dim: int, d_prime: int) -> list[int]:
    """Pick d' factors in [1, MAX_FACTOR] with product >= dim, close to dim.

    Mirrors the paper's recipe: start from all-2, bump factors (<=5) while the
    product is short of ``dim``, then shrink 2 -> 1 from the right while the
    product stays >= dim.
    """
    if dim <= 0:
        raise ValueError(f"mode length must be positive, got {dim}")
    if MAX_FACTOR**d_prime < dim:
        raise ValueError(f"d'={d_prime} too small for mode length {dim}")
    factors = [2] * d_prime
    prod = 2**d_prime
    # Grow: bump the smallest factor (leftmost among ties) until prod >= dim.
    while prod < dim:
        j = min(range(d_prime), key=lambda t: (factors[t], t))
        if factors[j] >= MAX_FACTOR:
            raise AssertionError("unreachable: growth exhausted")
        prod = prod // factors[j] * (factors[j] + 1)
        factors[j] += 1
    # Shrink: drop 2 -> 1 from the right while we can stay >= dim.
    for j in reversed(range(d_prime)):
        if factors[j] == 2 and prod // 2 >= dim:
            factors[j] = 1
            prod //= 2
    assert prod >= dim
    return factors


def default_d_prime(shape: Sequence[int]) -> int:
    """Paper: d' > d and d' = O(log N_max)."""
    n_max = max(shape)
    return max(len(shape) + 1, math.ceil(math.log2(max(n_max, 2))))


@dataclasses.dataclass(frozen=True)
class FoldingSpec:
    """Precomputed index maps between the original and folded tensors."""

    shape: tuple[int, ...]            # original (N_1..N_d)
    factors: np.ndarray               # [d, d'] int64, n_{k,l}
    # strides[k, l] = prod_{l' > l} n[k, l']   (digit extraction, original)
    strides: np.ndarray               # [d, d'] int64
    # fstrides[k, l] = prod_{k' > k} n[k', l]  (digit composition, folded)
    fstrides: np.ndarray              # [d, d'] int64
    folded_shape: tuple[int, ...]     # (m_1..m_d'), m_l = prod_k n[k, l]

    @property
    def d(self) -> int:
        return len(self.shape)

    @property
    def d_prime(self) -> int:
        return len(self.folded_shape)

    @property
    def n_entries(self) -> int:
        return int(np.prod(self.shape))

    @property
    def padded_entries(self) -> int:
        return int(np.prod(self.folded_shape))

    def fold_indices(self, idx):
        """[..., d] original indices -> [..., d'] folded indices."""
        xp = jnp if isinstance(idx, jnp.ndarray) else np
        digits = (idx[..., :, None] // self.strides) % self.factors
        return xp.sum(digits * self.fstrides, axis=-2)

    def fold_modes(self, modes):
        """``fold_indices`` of d index vectors ([B] each, original mode
        order) -> [B, d'] int32, as digit arithmetic by the spec's own
        factors: a chain of scalar-constant ops, which XLA compiles far
        faster than the [B, d, d'] broadcast at large B."""
        folded = [0] * self.d_prime
        for k, idx in enumerate(modes):
            for j in reversed(range(self.d_prime)):
                f = int(self.factors[k, j])
                if f > 1:
                    folded[j] = folded[j] + (idx % f) * int(self.fstrides[k, j])
                    idx = idx // f
        b = jnp.shape(modes[0])
        return jnp.stack(
            [jnp.broadcast_to(jnp.asarray(v, jnp.int32), b) for v in folded], axis=-1
        )

    def unfold_indices(self, fidx):
        """[..., d'] folded indices -> [..., d] original indices.

        Inverse of ``fold_indices`` on the image of valid indices; for padded
        folded positions the result may exceed ``shape`` (callers mask).
        """
        xp = jnp if isinstance(fidx, jnp.ndarray) else np
        digits = (fidx[..., None, :] // self.fstrides) % self.factors
        return xp.sum(digits * self.strides, axis=-1)


def make_folding_spec(shape: Sequence[int], d_prime: int | None = None) -> FoldingSpec:
    shape = tuple(int(s) for s in shape)
    if d_prime is None:
        d_prime = default_d_prime(shape)
    factors = np.array([choose_factors(n, d_prime) for n in shape], dtype=np.int64)
    return spec_from_factors(shape, factors)


def spec_from_factors(shape: Sequence[int], factors) -> FoldingSpec:
    """The spec of an explicit [d, d'] factor matrix (a stored payload's,
    or ``make_folding_spec``'s own choice)."""
    shape = tuple(int(s) for s in shape)
    factors = np.asarray(factors, dtype=np.int64)
    d, d_prime = factors.shape
    strides = np.ones((d, d_prime), dtype=np.int64)
    for j in range(d_prime - 2, -1, -1):
        strides[:, j] = strides[:, j + 1] * factors[:, j + 1]
    fstrides = np.ones((d, d_prime), dtype=np.int64)
    for k in range(d - 2, -1, -1):
        fstrides[k, :] = fstrides[k + 1, :] * factors[k + 1, :]
    folded_shape = tuple(int(x) for x in factors.prod(axis=0))
    return FoldingSpec(
        shape=shape,
        factors=factors,
        strides=strides,
        fstrides=fstrides,
        folded_shape=folded_shape,
    )

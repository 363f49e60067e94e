"""Neural Tensor-Train Decomposition (paper §IV-B, Alg. 2).

TT cores are generated per entry by an auto-regressive network:

    mode indices --embedding--> e_1..e_d' --LSTM--> h_1..h_d'
    T_1 = W1 h_1 + b1 (1xR);  T_k = W h_k + b (RxR, shared k=2..d'-1);
    T_d' = Wd h_d' + bd (Rx1);  value = T_1 T_2 ... T_d'

Embedding tables are shared across folded modes of equal length (paper
footnote 2).  Params are a plain pytree; ``apply`` is pure and jit/pjit
friendly (folded indices in, scalar approximations out).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.folding import FoldingSpec, spec_from_factors
from repro.kernels import ops, ref

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class NTTDConfig:
    rank: int = 8            # R, TT rank
    hidden: int = 16         # h, LSTM hidden == embedding dim
    dtype: Any = jnp.float32


def _mode_table_names(folded_shape: tuple[int, ...]) -> list[str]:
    """One embedding table per distinct folded mode length."""
    return [f"embed_{m}" for m in folded_shape]


def init_params(key: jax.Array, spec: FoldingSpec, cfg: NTTDConfig) -> Params:
    h, r = cfg.hidden, cfg.rank
    keys = jax.random.split(key, 8)
    params: Params = {}
    # shared embedding tables (by folded-mode length)
    for m in sorted(set(spec.folded_shape)):
        k = jax.random.fold_in(keys[0], m)
        params[f"embed_{m}"] = (
            jax.random.normal(k, (m, h), cfg.dtype) * (1.0 / np.sqrt(h))
        )
    glorot = lambda k, shape: jax.random.normal(k, shape, cfg.dtype) * jnp.sqrt(  # noqa: E731
        2.0 / (shape[0] + shape[-1])
    )
    params["lstm"] = {
        "wi": glorot(keys[1], (h, 4 * h)),
        "wh": glorot(keys[2], (h, 4 * h)),
        "b": jnp.zeros((4 * h,), cfg.dtype),
    }
    # Bias init keeps the initial chain product at O(1) scale for any d':
    # mid cores start at the identity, first/last at 1/sqrt(R), so the
    # initial prediction is ~1 and gradients reach every head.
    inv_sqrt_r = (jnp.ones((r,), cfg.dtype) / np.sqrt(r)).astype(cfg.dtype)
    params["head_first"] = {"w": glorot(keys[3], (h, r)), "b": inv_sqrt_r}
    params["head_mid"] = {
        "w": glorot(keys[4], (h, r * r)),
        "b": jnp.eye(r).reshape(r * r).astype(cfg.dtype),
    }
    params["head_last"] = {"w": glorot(keys[5], (h, r)), "b": inv_sqrt_r}
    return params


def count_params(params: Params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def fused_decode_inputs(
    params: Params, spec: FoldingSpec, cfg: NTTDConfig
) -> tuple[jax.Array, ...]:
    """Stack params into the flat operand layout of the fused decode kernel.

    Embedding tables (shared per folded-mode length) are stacked per step
    and zero-padded to ``M = max(folded_shape)`` rows, giving one dense
    [T, M, H] operand that the kernel broadcasts once per core.  Returns
    ``(emb, wi, wh, b, w_first, b_first, w_mid, b_mid, w_last, b_last)``.
    """
    m_max = max(spec.folded_shape)
    steps = []
    for m in spec.folded_shape:
        tab = params[f"embed_{m}"]
        if m < m_max:
            tab = jnp.concatenate(
                [tab, jnp.zeros((m_max - m, tab.shape[1]), tab.dtype)], axis=0
            )
        steps.append(tab)
    emb = jnp.stack(steps, axis=0)  # [T, M, H]
    lstm = params["lstm"]
    return (
        emb,
        lstm["wi"],
        lstm["wh"],
        lstm["b"],
        params["head_first"]["w"],
        params["head_first"]["b"],
        params["head_mid"]["w"],
        params["head_mid"]["b"],
        params["head_last"]["w"],
        params["head_last"]["b"],
    )


def apply(
    params: Params,
    folded_idx: jax.Array,  # [B, d'] int32
    spec: FoldingSpec,
    cfg: NTTDConfig,
) -> jax.Array:
    """Approximate entries at the given folded indices.  Returns [B].

    The differentiable chain that training, evaluation and reordering
    run; a served decode runs the fused tile instead
    (``ops.nttd_decode_tile``), which agrees with it to float rounding."""
    d_prime = spec.d_prime
    r = cfg.rank
    # --- embedding lookup (shared tables by mode length) -------------------
    embeds = [
        params[f"embed_{m}"][folded_idx[:, j]] for j, m in enumerate(spec.folded_shape)
    ]
    x = jnp.stack(embeds, axis=1)  # [B, d', h]
    # --- LSTM encoder -------------------------------------------------------
    lstm = params["lstm"]
    hs = ref.lstm_scan(x, lstm["wi"], lstm["wh"], lstm["b"])
    # --- TT-core heads --------------------------------------------------------
    first = hs[:, 0] @ params["head_first"]["w"] + params["head_first"]["b"]  # [B, R]
    last = hs[:, -1] @ params["head_last"]["w"] + params["head_last"]["b"]    # [B, R]
    if d_prime > 2:
        mids = (
            hs[:, 1:-1] @ params["head_mid"]["w"] + params["head_mid"]["b"]
        ).reshape(-1, d_prime - 2, r, r)  # [B, d'-2, R, R]
    else:
        mids = jnp.zeros((folded_idx.shape[0], 0, r, r), cfg.dtype)
    # --- chain contraction ----------------------------------------------------
    return ref.tt_contract(first, mids, last)


def apply_at_positions(
    params: Params,
    positions: jax.Array,  # [B, d] indices in the *reordered* tensor
    spec: FoldingSpec,
    cfg: NTTDConfig,
) -> jax.Array:
    """Convenience: fold positions on device then apply."""
    folded = spec.fold_indices(positions)
    return apply(params, folded, spec, cfg)


def make_predict(spec: FoldingSpec, cfg: NTTDConfig):
    """Jitted (params, positions[B, d]) -> values[B].  Cache and reuse —
    every call site holding its own instance avoids recompilation."""

    @jax.jit
    def predict(params: Params, positions: jax.Array) -> jax.Array:
        return apply_at_positions(params, positions, spec, cfg)

    return predict


# canonical home is repro.codecs.indexing; re-exported here for the many
# historical call sites (and external users) that import it from nttd
from repro.codecs.indexing import flat_to_multi  # noqa: E402, F401

#: entries of one slab of a dense decode: fixed, so that each tensor shape
#: compiles one slab program
SLAB_ENTRIES = 1 << 22


#: most entries of a dense decode's tail table (``slab_split``)
TAIL_ENTRIES = 1 << 16


def slab_split(shape: tuple[int, ...], slab: int) -> tuple[int, int, int]:
    """``(t, w, rows)`` of a dense decode in slabs.  The tail is the modes
    from ``t`` on: the longest run of trailing modes whose product ``w``
    stays within ``TAIL_ENTRIES``, or the last mode alone when it is
    longer.  ``rows`` is the rows of ``w`` entries whose folded indices a
    slab builds to cover any ``slab`` consecutive entries, never more
    than the tensor has."""
    t, w = len(shape) - 1, shape[-1]
    while t and w * shape[t - 1] <= TAIL_ENTRIES:
        t -= 1
        w *= shape[t]
    return t, w, min(-(-(slab + w - 1) // w), math.prod(shape) // w)


def tail_table(inv_pi, spec: FoldingSpec) -> np.ndarray:
    """``[d', w]`` int32: the folded indices of the tail's ``w`` entries
    in original row-major order, each mode mapped to its position through
    ``inv_pi``, with the head modes at 0.  The fold's digits of different
    modes never carry into each other, so an entry's folded index is its
    head row's part plus its tail entry's part."""
    shape = tuple(spec.shape)
    t, w, _ = slab_split(shape, 1)
    out = np.zeros((spec.d_prime, w), np.int64)
    rem = np.arange(w)
    for k in reversed(range(t, len(shape))):
        pos = np.asarray(inv_pi[k])[rem % shape[k]][None, :]
        rem //= shape[k]
        digits = (pos // spec.strides[k][:, None]) % spec.factors[k][:, None]
        out += digits * spec.fstrides[k][:, None]
    return out.astype(np.int32)


def slab_indices(start, head_inv_pi, tail, *, shape, factors, slab):
    """Folded indices ``[d', slab]`` of entries ``[start, start + slab)``
    of the original row-major order: the head part of each of ``rows``
    rows of ``w`` entries (its head modes unravelled, mapped through
    ``head_inv_pi`` on the device, and folded) broadcast-added to the
    tail table (``tail_table``), and the slab sliced out of the block.
    The block starts at ``start``'s row, shifted back so that it ends
    within the tensor; no index is gathered per entry."""
    spec = spec_from_factors(shape, factors)
    t, w, rows = slab_split(shape, slab)
    r0 = jnp.minimum(start // w, math.prod(shape[:t]) - rows)
    if t:
        rem = r0 + jnp.arange(rows, dtype=jnp.int32)
        pos = [None] * t
        for k in reversed(range(t)):
            pos[k] = jnp.take(head_inv_pi[k], rem % shape[k])
            rem = rem // shape[k]
        head = spec.fold_modes(pos + [0] * (len(shape) - t)).T  # [d', rows]
    else:
        head = jnp.zeros((spec.d_prime, rows), jnp.int32)
    block = (head[:, :, None] + tail[:, None, :]).reshape(spec.d_prime, rows * w)
    return jax.lax.dynamic_slice(block, (0, start - r0 * w), (spec.d_prime, slab))


def _slab_values(start, operands, head_inv_pi, tail, mean, std, shape, factors, slab):
    """Entries ``[start, start + slab)`` of the original row-major order:
    folded indices (``slab_indices``), decoded through the fused tile and
    de-normalised.  [slab] float32."""
    folded = slab_indices(start, head_inv_pi, tail, shape=shape, factors=factors, slab=slab)
    vals = ops.nttd_decode_tile(folded.T, *operands)
    return vals.astype(jnp.float32) * std + mean


@functools.partial(
    jax.jit, static_argnames=("shape", "factors", "slab"), donate_argnums=(0,)
)
def restore_slab(buf, start, operands, head_inv_pi, tail, mean, std, *, shape, factors,
                 slab):
    """``buf`` (flat float32, donated) with one slab of the dense decode
    written at ``start``."""
    vals = _slab_values(start, operands, head_inv_pi, tail, mean, std, shape, factors, slab)
    return jax.lax.dynamic_update_slice(buf, vals.astype(buf.dtype), (start,))


@functools.partial(jax.jit, static_argnames=("shape", "factors", "slab"))
def slab_sq_err(x, first, start, operands, head_inv_pi, tail, mean, std, *, shape, factors,
                slab):
    """Summed squared error of one slab of the dense decode against ``x``
    (the same slab of the original tensor), over entries from ``first``."""
    vals = _slab_values(start, operands, head_inv_pi, tail, mean, std, shape, factors, slab)
    flat = start + jnp.arange(slab, dtype=jnp.int32)
    return jnp.sum(jnp.where(flat >= first, jnp.square(x - vals), 0.0))


class DenseSlabs:
    """A tensor's dense decode on the device, in fixed slabs of its
    original row-major order.  No index or value crosses the host per
    slab: the operands, the head modes' inverse orders, the tail table
    (``tail_table``; ``tail`` entries, ``rows`` of them a slab, from
    ``slab_split``) and the normalisation are uploaded once.  The decode
    is the fused tile's (on a TPU the tile is the decode path).

    Slab ``k`` holds entries ``[k * slab, (k + 1) * slab)``; the last one
    is shifted back to end at the tensor's end, so it overlaps the slab
    before it and every slab has one shape."""

    def __init__(self, params: Params, spec: FoldingSpec, cfg: NTTDConfig,
                 inv_pi=None, mean: float = 0.0, std: float = 1.0,
                 slab: int = SLAB_ENTRIES):
        n = spec.n_entries
        if n >= 2**31:
            raise ValueError(f"{n} entries overflow int32 flat indices")
        if spec.d_prime < 2:
            raise ValueError(f"the fused decode needs d' >= 2, got {spec.d_prime}")
        self.shape = tuple(spec.shape)
        self.d_prime = spec.d_prime
        self.n = n
        self.slab = min(int(slab), n)
        self.n_slabs = -(-n // self.slab)
        if inv_pi is None:
            inv_pi = [np.arange(m) for m in self.shape]
        t, self.tail, self.rows = slab_split(self.shape, self.slab)
        self._static = {
            "shape": self.shape,
            "factors": tuple(tuple(int(f) for f in row) for row in spec.factors),
            "slab": self.slab,
        }
        self._args = jax.device_put((
            fused_decode_inputs(params, spec, cfg),
            tuple(jnp.asarray(p, jnp.int32) for p in inv_pi[:t]),
            tail_table(inv_pi, spec),
            jnp.float32(mean),
            jnp.float32(std),
        ))

    def start(self, k: int) -> int:
        return min(k * self.slab, self.n - self.slab)

    def entries(self, k: int) -> int:
        """Entries that slab ``k`` adds (the last slab's overlap not
        counted)."""
        return min(self.slab, self.n - k * self.slab)

    def write(self, buf: jax.Array, k: int) -> jax.Array:
        """``buf`` (flat float32 of the tensor's size, donated) with slab
        ``k`` written; asynchronous."""
        return restore_slab(buf, jnp.int32(self.start(k)), *self._args, **self._static)

    def dense(self) -> jax.Array:
        """The whole decode, on the device, in the tensor's shape."""
        buf = jnp.zeros((self.n,), jnp.float32)
        for k in range(self.n_slabs):
            buf = self.write(buf, k)
        return buf.reshape(self.shape)

    def sq_err(self, x: np.ndarray) -> float:
        """Summed squared error of the decode against ``x`` (original
        order, any shape of the tensor's size), slab by slab on the device;
        each slab of ``x`` is uploaded once."""
        flat = np.asarray(x, np.float32).reshape(-1)
        total = jnp.zeros((), jnp.float32)
        for k in range(self.n_slabs):
            s = self.start(k)
            total = total + slab_sq_err(
                jnp.asarray(flat[s : s + self.slab]), jnp.int32(k * self.slab),
                jnp.int32(s), *self._args, **self._static,
            )
        return float(total)


def generate_tensor(
    params: Params, spec: FoldingSpec, cfg: NTTDConfig, slab: int = SLAB_ENTRIES
) -> np.ndarray:
    """Materialize the full approximated tensor (reordered coordinates),
    decoded on the device in slabs (``DenseSlabs``).  Used by the
    expressiveness experiment (Fig. 8)."""
    return np.asarray(DenseSlabs(params, spec, cfg, slab=slab).dense())

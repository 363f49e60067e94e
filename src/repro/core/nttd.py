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
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.folding import FoldingSpec
from repro.kernels import ops

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class NTTDConfig:
    rank: int = 8            # R, TT rank
    hidden: int = 16         # h, LSTM hidden == embedding dim
    dtype: Any = jnp.float32
    kernel_impl: str = "ref"  # see kernels.ops


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


def uses_fused_decode(spec: FoldingSpec, cfg: NTTDConfig) -> bool:
    """Whether ``apply`` decodes through the fused kernel, the one branch
    that takes ``fused_decode_inputs``."""
    return cfg.kernel_impl == "fused" and spec.d_prime >= 2


def apply(
    params: Params,
    folded_idx: jax.Array,  # [B, d'] int32
    spec: FoldingSpec,
    cfg: NTTDConfig,
    operands: tuple[jax.Array, ...] | None = None,
) -> jax.Array:
    """Approximate entries at the given folded indices.  Returns [B].

    ``operands``, on the fused branch only, is ``fused_decode_inputs`` of
    ``params`` built beforehand; ``None`` stacks them here (inside a
    caller's ``jit``, as part of its program)."""
    d_prime = spec.d_prime
    r = cfg.rank
    if uses_fused_decode(spec, cfg):
        # single-program decode: whole chain in one kernel / one XLA program
        # (Pallas on TPU, jitted oracle on CPU — see kernels.ops)
        if operands is None:
            with obs.span("nttd.operands", built=1):
                operands = fused_decode_inputs(params, spec, cfg)
        return ops.nttd_decode_tile(
            folded_idx.astype(jnp.int32), *operands, impl="fused"
        )
    # --- embedding lookup (shared tables by mode length) -------------------
    embeds = [
        params[f"embed_{m}"][folded_idx[:, j]] for j, m in enumerate(spec.folded_shape)
    ]
    x = jnp.stack(embeds, axis=1)  # [B, d', h]
    # --- LSTM encoder -------------------------------------------------------
    lstm = params["lstm"]
    hs = ops.lstm_scan(x, lstm["wi"], lstm["wh"], lstm["b"], impl=cfg.kernel_impl)
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
    return ops.tt_contract(first, mids, last, impl=cfg.kernel_impl)


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


def generate_tensor(
    params: Params,
    spec: FoldingSpec,
    cfg: NTTDConfig,
    batch: int = 65536,
    predict_fn=None,
) -> np.ndarray:
    """Materialize the full approximated tensor (reordered coordinates).

    Used for fitness evaluation on small/medium tensors and for the
    expressiveness experiment (Fig. 8).
    """
    n = spec.n_entries
    out = np.empty((n,), dtype=np.float32)
    fn = predict_fn or make_predict(spec, cfg)
    # fixed batch (pad the tail) so the jitted fn compiles exactly once
    for start in range(0, n, batch):
        stop = min(start + batch, n)
        flat = np.arange(start, stop, dtype=np.int64)
        if stop - start < batch:
            flat = np.pad(flat, (0, batch - (stop - start)))
        pos = flat_to_multi(flat, spec.shape)
        got = np.asarray(fn(params, jnp.asarray(pos, jnp.int32)))
        out[start:stop] = got[: stop - start]
    return out.reshape(spec.shape)

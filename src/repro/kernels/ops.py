"""Backend-dispatching wrappers around the Pallas kernels.

``impl`` selects the execution path:
  * "ref"               — pure-jnp oracle (XLA).  Default on CPU.
  * "pallas"            — compiled Pallas kernel.  Default on TPU.
  * "pallas_interpret"  — Pallas kernel body interpreted in Python
                          (correctness validation on CPU).
  * "fused"             — one-program decode (``nttd_decode_tile`` only):
                          the Pallas kernel on TPU, the jitted oracle on
                          CPU.  Either way the whole decode chain runs as
                          a single compiled program instead of a chain of
                          separately dispatched ops.
  * "auto"              — "pallas" on TPU else "ref" ("fused" for
                          ``nttd_decode_tile``, where the jitted oracle is
                          the fast CPU path).

Wrappers also handle batch padding so callers never worry about tile
divisibility.  Silent fallback to the oracle on shapes a kernel cannot
take is reserved for ``impl="auto"``; an explicitly requested backend is
honored by padding+masking instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import attention as _attention
from repro.kernels import decode_tile as _dt
from repro.kernels import lstm as _lstm
from repro.kernels import ref as _ref
from repro.kernels import tt_contract as _tt


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def _pad_batch(x: jax.Array, mult: int) -> tuple[jax.Array, int]:
    bsz = x.shape[0]
    pad = (-bsz) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    return x, bsz


def tt_contract(
    first: jax.Array,
    mid: jax.Array,
    last: jax.Array,
    *,
    impl: str = "auto",
    tile_b: int | None = None,
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.tt_contract(first, mid, last)
    if impl == "ref_unrolled":
        return _ref.tt_contract_unrolled(first, mid, last)
    if mid.shape[1] == 0:
        # degenerate 2-core chain: no mid tensor to tile (zero-size blocks
        # break pallas); the contraction is a plain row dot
        return jnp.sum(first * last, axis=-1)
    tile = tile_b or min(_tt.DEFAULT_TILE_B, max(8, first.shape[0]))
    f, bsz = _pad_batch(first, tile)
    m, _ = _pad_batch(mid, tile)
    lp, _ = _pad_batch(last, tile)
    out = _tt.tt_contract(f, m, lp, tile_b=tile, interpret=impl == "pallas_interpret")
    return out[:bsz]


def lstm_scan(
    x: jax.Array,
    wi: jax.Array,
    wh: jax.Array,
    b: jax.Array,
    *,
    impl: str = "auto",
    tile_b: int | None = None,
) -> jax.Array:
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.lstm_scan(x, wi, wh, b)
    if impl == "ref_unrolled":
        # XLA-path fusion lever: unrolling the d' ~ 8..12 steps lets XLA
        # fuse gate math across steps instead of round-tripping the carry
        # through the while-loop boundary (the same motivation as the
        # Pallas kernel, achievable without Pallas)
        return _ref.lstm_unrolled(x, wi, wh, b)
    tile = tile_b or min(_lstm.DEFAULT_TILE_B, max(8, x.shape[0]))
    xp, bsz = _pad_batch(x, tile)
    out = _lstm.lstm_scan(xp, wi, wh, b, tile_b=tile, interpret=impl == "pallas_interpret")
    return out[:bsz]


CHUNKED_THRESHOLD = 2048  # switch the XLA path to q-chunked attention


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    kv_len: jax.Array | None = None,
    impl: str = "auto",
) -> jax.Array:
    requested = impl
    impl = _resolve(impl)
    misaligned = q.shape[1] % _attention.DEFAULT_TILE_Q or (
        k.shape[1] % _attention.DEFAULT_TILE_KV
    )
    if (
        impl in ("ref", "chunked")
        or kv_len is not None
        or (requested == "auto" and misaligned)
    ):
        # variable-length cases use the oracle path; silent fallback on
        # non-tile-aligned shapes is reserved for impl="auto" — an explicit
        # "pallas"/"pallas_interpret" request is honored via pad+mask below
        if kv_len is None and (
            impl == "chunked" or q.shape[1] >= CHUNKED_THRESHOLD
        ):
            return _ref.mha_attention_chunked(q, k, v, causal=causal, q_offset=q_offset)
        return _ref.mha_attention(q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len)
    sq, skv = q.shape[1], k.shape[1]
    pad_q = (-sq) % _attention.DEFAULT_TILE_Q
    pad_kv = (-skv) % _attention.DEFAULT_TILE_KV
    kv_valid = None
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        kv_valid = skv  # static: mask the padded kv columns in-kernel
    out = _attention.flash_attention(
        q, k, v, causal=causal, q_offset=q_offset,
        interpret=impl == "pallas_interpret",
        kv_valid=kv_valid,
    )
    return out[:, :sq] if pad_q else out


# Fused NTTD decode: jitted oracle = the single-program CPU path (the whole
# chain compiles to one XLA executable instead of per-op dispatches).
_fused_oracle = jax.jit(_ref.nttd_decode_tile)


def nttd_decode_tile(
    idx: jax.Array,
    emb: jax.Array,
    wi: jax.Array,
    wh: jax.Array,
    b: jax.Array,
    w_first: jax.Array,
    b_first: jax.Array,
    w_mid: jax.Array,
    b_mid: jax.Array,
    w_last: jax.Array,
    b_last: jax.Array,
    *,
    impl: str = "auto",
    tile_b: int | None = None,
) -> jax.Array:
    """Fused NTTD decode of a [B, T] tile of folded indices -> [B] values.

    See ``decode_tile.decode_tile`` for the operands; the kernel itself
    runs batch-on-lanes (``[T, B]`` indices in, a lane-dense ``[1, B]``
    out), and its program pads and transposes.  B == 0 short-circuits (a
    zero-size grid is invalid in Pallas).

    The ``kernel_decode`` span times the enqueue of an asynchronous call,
    not the kernel: the kernel's device time is ``jit_decode_tile`` in a
    profiler trace.  ``b`` and ``padded`` count the rows asked for and
    the rows the kernel runs, ``tile`` the lanes of one grid step
    (``decode_tile.tile_width``; the whole batch for the oracles).
    """
    bsz = idx.shape[0]
    if bsz == 0:
        return jnp.zeros((0,), emb.dtype)
    if impl in ("auto", "fused"):
        impl = "pallas" if jax.default_backend() == "tpu" else "fused"
    heads = (w_first, b_first, w_mid, b_mid, w_last, b_last)
    if impl in ("ref", "fused"):
        tile = padded = bsz
    else:
        tile = _dt.tile_width(bsz, emb.shape[2], b_first.shape[0], tile_b)
        padded = bsz + (-bsz) % tile
    with obs.span("kernel_decode", impl=impl, b=bsz, padded=padded, tile=tile):
        if impl == "ref":
            return _ref.nttd_decode_tile(idx, emb, wi, wh, b, *heads)
        if impl == "fused":
            return _fused_oracle(idx, emb, wi, wh, b, *heads)
        return _dt.decode_tile(
            idx, emb, wi, wh, b, *heads,
            tile_b=tile, interpret=impl == "pallas_interpret",
        )

"""Backend-dispatching wrappers around the Pallas kernels.

``nttd_decode_tile``, the served NTTD decode, picks its backend from JAX's
own: the Pallas kernel on a TPU (or under the interpreter when asked,
for tests), the jitted jnp oracle elsewhere.  Either way the whole decode
chain runs as one compiled program.

``attention`` takes ``impl``:
  * "ref"               — pure-jnp oracle (XLA).
  * "chunked"           — q-chunked jnp oracle (bounded score memory).
  * "pallas"            — compiled Pallas kernel.
  * "pallas_interpret"  — Pallas kernel body interpreted in Python
                          (correctness validation on CPU).
  * "auto"              — "pallas" on TPU else "ref".

Silent fallback to the oracle on shapes the kernel cannot take is
reserved for ``impl="auto"``; an explicitly requested backend is honored
by padding+masking instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import attention as _attention
from repro.kernels import decode_tile as _dt
from repro.kernels import ref as _ref


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


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


# the decode tile off a TPU: the whole chain as one XLA executable
_oracle = jax.jit(_ref.nttd_decode_tile)


def nttd_decode_tile(
    idx: jax.Array,
    *operands: jax.Array,
    tile_b: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused NTTD decode of a [B, T] tile of folded indices -> [B] values.

    ``operands`` are ``nttd.fused_decode_inputs``: ``(emb, wi, wh, b,
    w_first, b_first, w_mid, b_mid, w_last, b_last)`` (see
    ``decode_tile.decode_tile``).  The Pallas kernel runs on a TPU, or
    under the interpreter with ``interpret=True``; elsewhere the jitted
    oracle ``ref.nttd_decode_tile`` does, bit-identical to the
    interpreted kernel.  The kernel itself runs batch-on-lanes (``[T, B]``
    indices in, a lane-dense ``[1, B]`` out), and its program pads and
    transposes.  B == 0 short-circuits (a zero-size grid is invalid in
    Pallas).

    The ``kernel_decode`` span times the enqueue of an asynchronous call,
    not the kernel: the kernel's device time is ``jit_decode_tile`` in a
    profiler trace.  Its ``impl`` names the path taken (``pallas``,
    ``pallas_interpret`` or ``oracle``); ``b`` and ``padded`` count the
    rows asked for and the rows the kernel runs, ``tile`` the lanes of
    one grid step (``decode_tile.tile_width``; the whole batch for the
    oracle).
    """
    emb, b_first = operands[0], operands[5]
    bsz = idx.shape[0]
    if bsz == 0:
        return jnp.zeros((0,), emb.dtype)
    if interpret or jax.default_backend() == "tpu":
        impl = "pallas_interpret" if interpret else "pallas"
        tile = _dt.tile_width(bsz, emb.shape[2], b_first.shape[0], tile_b)
        padded = bsz + (-bsz) % tile
    else:
        impl, tile, padded = "oracle", bsz, bsz
    with obs.span("kernel_decode", impl=impl, b=bsz, padded=padded, tile=tile):
        if impl == "oracle":
            return _oracle(idx, *operands)
        return _dt.decode_tile(idx, *operands, tile_b=tile, interpret=interpret)

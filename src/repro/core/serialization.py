"""Real on-disk serialization of the compressed payload D = (theta, pi).

Layout (little-endian):
  magic 'TCDC' | u16 version | u8 d | u8 d' | u8 dtype | u8 flags
  u32 rank | u32 hidden | f64 mean | f64 std
  d  x u64   original shape
  d*d' x u8  folding factors
  theta: arrays in sorted-key traversal order, raw bytes at `dtype`
  pi:    per mode, N_k indices bit-packed at ceil(log2 N_k) bits each

The pi encoding matches the paper's size accounting exactly
(N_k * ceil(log2 N_k) bits, §V-A); round-trip is bit-exact.

This v2 layout is now the NTTD *body* inside the multi-codec container
(``repro.codecs.container``, v3), which prefixes a codec-id header so any
registered codec round-trips through one format.  ``load_bytes`` there
still accepts bare v2 blobs; use ``repro.codecs.save_bytes/load_bytes``
for new code.
"""
from __future__ import annotations

import io
import struct

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec as codec_mod
from repro.core import nttd
from repro.core.folding import spec_from_factors

MAGIC = b"TCDC"
VERSION = 2
_DTYPES = {0: np.float16, 1: np.float32, 2: np.float64}
_DTYPE_CODES = {np.dtype(v): k for k, v in _DTYPES.items()}


# ---------------------------------------------------------------------------
# bit packing
# ---------------------------------------------------------------------------
def pack_permutation(perm: np.ndarray) -> bytes:
    """Pack N integers in [0, N) at ceil(log2 N) bits each."""
    n = perm.shape[0]
    if n <= 1:
        return b""
    bits = max(int(np.ceil(np.log2(n))), 1)
    total = n * bits
    buf = np.zeros((total + 7) // 8, dtype=np.uint8)
    bitpos = np.arange(n, dtype=np.int64) * bits
    for b in range(bits):
        p = bitpos + b
        bit = (perm >> (bits - 1 - b)) & 1
        np.bitwise_or.at(buf, p // 8, (bit << (7 - (p % 8))).astype(np.uint8))
    return buf.tobytes()


def unpack_permutation(data: bytes, n: int) -> np.ndarray:
    if n <= 1:
        return np.arange(n, dtype=np.int64)
    bits = max(int(np.ceil(np.log2(n))), 1)
    buf = np.frombuffer(data, dtype=np.uint8)
    out = np.zeros(n, dtype=np.int64)
    bitpos = np.arange(n, dtype=np.int64) * bits
    for b in range(bits):
        p = bitpos + b
        bit = (buf[p // 8] >> (7 - (p % 8))) & 1
        out |= bit.astype(np.int64) << (bits - 1 - b)
    return out


# ---------------------------------------------------------------------------
# theta traversal (stable order)
# ---------------------------------------------------------------------------
def _theta_items(params: nttd.Params):
    def walk(prefix: str, node):
        if isinstance(node, dict):
            for k in sorted(node):
                yield from walk(f"{prefix}/{k}", node[k])
        else:
            yield prefix, node

    yield from walk("", params)


def save_bytes(ct: codec_mod.CompressedTensor, dtype=np.float32) -> bytes:
    spec = ct.spec
    out = io.BytesIO()
    code = _DTYPE_CODES[np.dtype(dtype)]
    out.write(MAGIC)
    out.write(
        struct.pack(
            "<HBBBBII dd",
            VERSION,
            spec.d,
            spec.d_prime,
            code,
            0,
            ct.cfg.rank,
            ct.cfg.hidden,
            ct.norm_mean,
            ct.norm_std,
        )
    )
    out.write(np.asarray(spec.shape, dtype=np.uint64).tobytes())
    out.write(spec.factors.astype(np.uint8).tobytes())
    for _, arr in _theta_items(ct.params):
        out.write(np.asarray(arr, dtype=dtype).tobytes())
    for k in range(spec.d):
        out.write(pack_permutation(ct.pi[k]))
    return out.getvalue()


def load_bytes(data: bytes) -> codec_mod.CompressedTensor:
    """Rebuild a CompressedTensor from its v2 body."""
    buf = io.BytesIO(data)
    if buf.read(4) != MAGIC:
        raise ValueError("not a TensorCodec payload")
    version, d, d_prime, code, _flags, rank, hidden, mean, std = struct.unpack(
        "<HBBBBII dd", buf.read(struct.calcsize("<HBBBBII dd"))
    )
    if version != VERSION:
        raise ValueError(f"unsupported version {version}")
    shape = tuple(np.frombuffer(buf.read(8 * d), dtype=np.uint64).astype(int))
    factors = np.frombuffer(buf.read(d * d_prime), dtype=np.uint8).reshape(d, d_prime)
    # the stored factors, not today's chooser, define the payload's folding
    spec = spec_from_factors(shape, factors)
    cfg = nttd.NTTDConfig(rank=rank, hidden=hidden)
    dtype = _DTYPES[code]
    # rebuild an abstract params tree to know the shapes, then fill
    template = jax.eval_shape(
        lambda key: nttd.init_params(key, spec, cfg), jax.random.PRNGKey(0)
    )
    params = _fill(template, buf, dtype)
    pi = []
    for k in range(d):
        n = shape[k]
        bits = max(int(np.ceil(np.log2(n))), 1) if n > 1 else 0
        nbytes = (n * bits + 7) // 8
        pi.append(unpack_permutation(buf.read(nbytes), n))
    return codec_mod.CompressedTensor(params, pi, spec, cfg, mean, std)


def _fill(template, buf: io.BytesIO, dtype):
    if isinstance(template, dict):
        return {k: _fill(template[k], buf, dtype) for k in sorted(template)}
    n = int(np.prod(template.shape))
    raw = np.frombuffer(buf.read(n * np.dtype(dtype).itemsize), dtype=dtype)
    return jnp.asarray(raw.reshape(template.shape), template.dtype)


def save_file(path: str, ct: codec_mod.CompressedTensor, dtype=np.float32) -> int:
    data = save_bytes(ct, dtype)
    with open(path, "wb") as f:
        f.write(data)
    return len(data)


def load_file(path: str) -> codec_mod.CompressedTensor:
    with open(path, "rb") as f:
        return load_bytes(f.read())

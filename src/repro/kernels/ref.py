"""Pure-jnp functions of the kernels package.

``lstm_scan`` and ``tt_contract`` are the differentiable NTTD chain that
training, evaluation and reordering run (``nttd.apply``).  The others
are the oracles of the Pallas kernels, their semantic ground truth: each
kernel is validated against the function of the same name here
(interpret=True on CPU, compiled on TPU), and the oracle is the
execution path on non-TPU backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_tile import LANES, lane_operands


# ----------------------------------------------------------------------------
# TT-core chain contraction (NTTD, Alg. 2 line 8)
# ----------------------------------------------------------------------------
def tt_contract(first: jax.Array, mid: jax.Array, last: jax.Array) -> jax.Array:
    """Chain product  T1 @ T2 @ ... @ Td  per batch element.

    first: [B, R]        (T1, shape 1xR squeezed)
    mid:   [B, K, R, R]  (T2..T_{d-1}); K may be 0
    last:  [B, R]        (Td, shape Rx1 squeezed)
    returns [B]
    """
    def step(v, m):
        # v: [B, R], m: [B, R, R] -> [B, R]
        return jnp.einsum("br,brs->bs", v, m), None

    if mid.shape[1] == 0:
        v = first
    else:
        v, _ = jax.lax.scan(step, first, jnp.moveaxis(mid, 1, 0))
    return jnp.sum(v * last, axis=-1)


# ----------------------------------------------------------------------------
# Fused LSTM scan (NTTD, Alg. 2 line 3)
# ----------------------------------------------------------------------------
def lstm_scan(
    x: jax.Array, wi: jax.Array, wh: jax.Array, b: jax.Array
) -> jax.Array:
    """Single-layer LSTM over a short sequence.

    x:  [B, T, H]  input embeddings
    wi: [H, 4H]    input->gates
    wh: [H, 4H]    hidden->gates
    b:  [4H]       gate bias
    returns hidden states [B, T, H] in ``x.dtype``

    Gate layout along the 4H axis: (i, f, g, o).  Carries and gate math
    run in f32 regardless of ``x.dtype`` and cast back, as the decode
    tile does, so bf16 comparisons with the tile compare like with like.
    """
    bsz, _, hid = x.shape
    xf = x.astype(jnp.float32)
    wif = wi.astype(jnp.float32)
    whf = wh.astype(jnp.float32)
    bf = b.astype(jnp.float32)

    def step(carry, xt):
        h, c = carry
        gates = xt @ wif + h @ whf + bf
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    init = (
        jnp.zeros((bsz, hid), dtype=jnp.float32),
        jnp.zeros((bsz, hid), dtype=jnp.float32),
    )
    _, hs = jax.lax.scan(step, init, jnp.moveaxis(xf, 1, 0))
    return jnp.moveaxis(hs, 0, 1).astype(x.dtype)


# ----------------------------------------------------------------------------
# Fused NTTD decode tile (paper Alg. 2, the whole per-entry chain)
# ----------------------------------------------------------------------------
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
) -> jax.Array:
    """Oracle for ``decode_tile.decode_tile``: embedding gather -> T-step
    LSTM -> first/mid/last head projections -> R-wide chain contraction,
    all in one expression.

    idx: [B, T] int32 folded indices; emb: [T, M, H] stacked per-step
    embedding tables (padded to M rows); heads as in decode_tile.
    Returns [B] in ``emb.dtype``.

    All math is f32 internally (matching the kernel), in the kernel's
    batch-on-lanes layout and grouping (``decode_tile.lane_operands``:
    the batch on the minor axis and padded to whole 128-lane columns,
    hidden units and core rows zero-padded to a multiple of 8, the input
    gates of every table row gathered, ``h``'s dots stacked) with the
    chain contracted step-interleaved in the exact order the kernel uses,
    so interpret-mode parity is bitwise, not merely close: XLA:CPU sums a
    dot in one order for every width of whole columns.
    """
    bsz, t_steps = idx.shape
    if t_steps < 2:
        raise ValueError(f"nttd_decode_tile needs T >= 2 steps, got {t_steps}")
    rank = b_first.shape[0]
    gx, b, wf, bf, wm, bm, wl, bl = lane_operands(
        emb, wi, wh, b, w_first, b_first, w_mid, b_mid, w_last, b_last)
    g4 = b.shape[0]
    hp, rp = g4 // 4, wl.shape[0]
    idx = jnp.pad(idx, ((0, (-bsz) % LANES), (0, 0)))
    c = jnp.zeros((hp, idx.shape[0]), jnp.float32)
    rec = v = out = None
    for t in range(t_steps):
        parts = jnp.take(gx[t], idx[:, t], axis=1).astype(jnp.float32)
        gates = parts[:g4] + parts[g4 : 2 * g4] + parts[2 * g4 :]  # [4Hp, B]
        if rec is not None:
            gates = gates + rec
        gates = gates + b
        i = jax.nn.sigmoid(gates[:hp])
        f = jax.nn.sigmoid(gates[hp : 2 * hp])
        g = jnp.tanh(gates[2 * hp : 3 * hp])
        o = jax.nn.sigmoid(gates[3 * hp :])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        if t == t_steps - 1:
            out = jnp.sum(v * (wl @ h + bl), axis=0)
        elif t == 0:
            z = wf @ h
            rec, v = z[:g4], z[g4:] + bf
        else:
            z = wm @ h
            rec, mid = z[:g4], z[g4:] + bm
            # v[s] = sum_r v[r] * mid[r*Rp + s], summed over r in order
            acc = v[0:1] * mid[0:rp]
            for r in range(1, rank):
                acc = acc + v[r : r + 1] * mid[r * rp : (r + 1) * rp]
            v = acc
    return out[:bsz].astype(emb.dtype)


# ----------------------------------------------------------------------------
# Causal GQA attention (LM serving/training path)
# ----------------------------------------------------------------------------
def mha_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    kv_len: jax.Array | None = None,
) -> jax.Array:
    """Grouped-query attention oracle.

    q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D] with Hq % Hkv == 0.
    ``q_offset``: absolute position of q[0] (decode: cache length so far).
    ``kv_len``: optional [B] valid kv lengths (entries beyond are masked).
    Softmax in f32; output in q.dtype.
    """
    bq, sq, hq, dim = q.shape
    _, skv, hkv, _ = k.shape
    group = hq // hkv
    qf = q.astype(jnp.float32) / jnp.sqrt(dim).astype(jnp.float32)
    qg = qf.reshape(bq, sq, hkv, group, dim)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    mask = None
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(skv)
        mask = qpos[:, None] >= kpos[None, :]  # [Sq, Skv]
        mask = mask[None, None, None]
    if kv_len is not None:
        valid = jnp.arange(skv)[None, :] < kv_len[:, None]  # [B, Skv]
        valid = valid[:, None, None, None, :]
        mask = valid if mask is None else jnp.logical_and(mask, valid)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(bq, sq, hq, dim).astype(q.dtype)


def mha_attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    chunk: int = 512,
) -> jax.Array:
    """Memory-bounded exact attention: scan over q chunks, rematerialized.

    The [B, H, chunk, Skv] score block is the peak transient instead of the
    full [B, H, Sq, Skv] — this is the XLA-path equivalent of the flash
    kernel's working-set bound and the configuration the dry-run lowers for
    long sequences.  Ragged sequences (sq % chunk != 0) scan the aligned
    prefix and attend the tail chunk separately, so the memory bound holds
    for every length, not just multiples of ``chunk``.
    """
    bq, sq, hq, dim = q.shape
    if sq <= chunk:
        return mha_attention(q, k, v, causal=causal, q_offset=q_offset)

    nq, tail = divmod(sq, chunk)
    aligned = nq * chunk

    def body(carry, qc_and_off):
        qc, off = qc_and_off
        out = mha_attention(qc, k, v, causal=causal, q_offset=off)
        return carry, out

    body = jax.checkpoint(body)
    qs = jnp.moveaxis(
        q[:, :aligned].reshape(bq, nq, chunk, hq, dim), 1, 0
    )  # [nq,B,chunk,H,D]
    offs = q_offset + jnp.arange(nq) * chunk
    _, outs = jax.lax.scan(body, (), (qs, offs))
    out = jnp.moveaxis(outs, 0, 1).reshape(bq, aligned, hq, dim)
    if tail:
        tail_out = mha_attention(
            q[:, aligned:], k, v, causal=causal, q_offset=q_offset + aligned
        )
        out = jnp.concatenate([out, tail_out], axis=1)
    return out

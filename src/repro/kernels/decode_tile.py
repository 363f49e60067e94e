"""Pallas TPU kernel: the whole NTTD decode for one tile, fused, with the
batch on lanes.

The serving hot path (paper Alg. 2) reconstructs a batch of entries as

    folded indices --embedding--> e_1..e_T --LSTM--> h_1..h_T
    T_1 = h_1 W_f + b_f (1xR); T_t = h_t W_m + b_m (RxR); T_T = h_T W_l + b_l
    value = T_1 T_2 ... T_T

and this kernel runs the entire chain in ONE ``pl.pallas_call``.  The
batch sits on the 128-wide lane axis and every per-entry vector on
sublanes: ``h`` and ``c`` are ``[H, TB]``, the gates ``[4H, TB]``, a
middle core ``[R*R, TB]``, the running TT row vector ``[R, TB]``.  So a
``[H, TB]`` value fills ``H/8`` vregs at full lane width, a gate is a
sublane row block, a chain step is R sublane-row broadcasts times R-row
blocks, and the dots are ``W^T [rows, H] @ h [H, TB]``.  The state stays
resident across all T steps, and every weight tensor is broadcast once
per core via constant index maps.

Layout (``lane_operands``, built inside the jitted wrapper): the hidden
units are zero-padded to a multiple of 8 in every stacked operand, and
each R-row block of a middle core to a multiple of 8 rows, so every
slice is sublane-aligned.  A padded unit's gates read 0, so its ``c``
and ``h`` stay exactly 0 and the zero head rows add nothing: the result
is the unpadded one.  The folded indices come in as ``[T, B]`` (padded
and transposed inside ``decode_tile``'s own program) and the values go
out lane-dense, ``[1, B]``.

Two regroupings keep the MXU's work down; each output element is still
one f32 sum at ``Precision.HIGHEST``:

- the embedding lookup and the input-gate product are one gather of
  ``emb_t @ wi`` (an f32 table per step): a one-hot matmul over its
  three bfloat16 parts, which is exact in one MXU pass (one 1.0
  coefficient, the rest 0.0) where an f32 operand takes six;
- ``h`` meets ``wh`` (the next step's recurrent gates) and the step's
  head in one stacked dot, so the MXU loads it once a step.

The T-step loop is unrolled at trace time (T = d' is ~4..17 for NTTD).
All internal math is f32 regardless of the parameter dtype, and the
oracle ``ref.nttd_decode_tile`` computes in this same layout and order;
the output is cast back to the embedding dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
# f32 contraction on the MXU: Mosaic's default rounds f32 operands to bf16
_F32 = jax.lax.Precision.HIGHEST


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


# vregs the largest value of one grid step may fill, summed over its lane
# columns, and the most columns: on a v5e, at d' 9-17, 8 columns (1024
# lanes) run 10-25% faster than 4, 16 no faster, and 32 up to 15% slower
_STEP_VREGS = 288
_MAX_COLS = 8


def tile_width(bsz: int, hid: int, rank: int, requested: int | None = None) -> int:
    """Lanes of one grid step: ``requested`` rounded up to whole 128-lane
    columns, or else chosen from the widths the kernel sees.

    Independent lane columns keep the MXUs fed, so a step takes as many
    as fit: the largest value a step holds (the gathered input gates,
    ``12Hp`` rows, or the stacked dot of ``h``, ``4Hp + R*Rp`` rows)
    times the columns stays within ``_STEP_VREGS`` vregs, in a power of
    two of at most ``_MAX_COLS`` columns, and no wider than the batch
    rounded up to whole columns."""
    if requested:
        return _round_up(requested, LANES)
    hp, rp = _round_up(hid, SUBLANES), _round_up(rank, SUBLANES)
    vregs = max(12 * hp, 4 * hp + rank * rp) // SUBLANES
    cols = min(max(1, _STEP_VREGS // vregs), _MAX_COLS)
    cols = 1 << (cols.bit_length() - 1)
    return min(LANES * cols, _round_up(max(bsz, 1), LANES))


def lane_operands(
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
) -> tuple[jax.Array, ...]:
    """The fused decode's operands (``nttd.fused_decode_inputs``' layout)
    in the kernel's batch-on-lanes layout, with Hp, Rp and Mp the hidden
    size, rank and table rows rounded up to a multiple of 8 and gate k's
    unit j in row ``k*Hp + j`` of a gate block:

    gx ``[T, 3*4Hp, Mp]`` bfloat16: ``emb_t @ wi``, the input gates of
    every table row, as three bfloat16 parts stacked on rows whose f32
    sum is exactly the f32 product (so a one-hot dot gathers it in one
    MXU pass, exactly); b ``[4Hp, 1]``; w_first ``[4Hp + Rp, Hp]`` and
    w_mid ``[4Hp + R*Rp, Hp]``: ``wh^T`` over the head's rows, so one
    dot of ``h`` gives the next step's recurrent gates and this step's
    core (``mid[r, s]`` in head row ``r*Rp + s``); w_last ``[Rp, Hp]``;
    the heads' biases ``[Rp | R*Rp, 1]``.  All but gx are float32, and
    padding is zeros throughout."""
    _, m, hid = emb.shape
    rank = b_first.shape[0]
    hp, rp, mp = (_round_up(n, SUBLANES) for n in (hid, rank, m))
    dh, dr = hp - hid, rp - rank
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def gates(w):  # [H, 4H] -> [H, 4Hp]
        return jnp.pad(f32(w).reshape(hid, 4, hid), ((0, 0), (0, 0), (0, dh))).reshape(hid, 4 * hp)

    def head(w, bias):  # [H, R], [R] -> [Rp, Hp], [Rp, 1]
        return (jnp.pad(f32(w).T, ((0, dr), (0, dh))),
                jnp.pad(f32(bias), (0, dr))[:, None])

    gx = jnp.einsum("tmh,hg->tgm", f32(emb), gates(wi), precision=_F32)
    gx = jnp.pad(gx, ((0, 0), (0, 0), (0, mp - m)))
    # round to bfloat16 in f32 (XLA drops a plain bf16 round trip)
    hi = jax.lax.reduce_precision(gx, exponent_bits=8, mantissa_bits=7)
    mid = jax.lax.reduce_precision(gx - hi, exponent_bits=8, mantissa_bits=7)
    gx = jnp.concatenate([hi, mid, gx - hi - mid], axis=1).astype(jnp.bfloat16)
    rec = jnp.pad(gates(wh), ((0, dh), (0, 0))).T  # [4Hp, Hp]
    wf, bf = head(w_first, b_first)
    wm = jnp.pad(f32(w_mid).reshape(hid, rank, rank), ((0, dh), (0, 0), (0, dr)))
    bm = jnp.pad(f32(b_mid).reshape(rank, rank), ((0, 0), (0, dr)))
    return (
        gx,
        jnp.pad(f32(b).reshape(4, hid), ((0, 0), (0, dh))).reshape(4 * hp, 1),
        jnp.concatenate([rec, wf]),
        bf,
        jnp.concatenate([rec, wm.reshape(hp, rank * rp).T]),
        bm.reshape(rank * rp, 1),
        *head(w_last, b_last),
    )


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a, b, precision=_F32, preferred_element_type=jnp.float32)


def _kernel(
    idx_ref,
    gx_ref,
    b_ref,
    wf_ref,
    bf_ref,
    wm_ref,
    bm_ref,
    wl_ref,
    bl_ref,
    out_ref,
    *,
    rank: int,
):
    t_steps, tb = idx_ref.shape
    g4 = b_ref.shape[0]
    hp, rp = g4 // 4, wl_ref.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (gx_ref.shape[2], tb), 0)
    c = jnp.zeros((hp, tb), jnp.float32)
    rec = None  # the recurrent gates h_{t-1} @ wh, [4Hp, TB]
    v = None  # running TT row vector [Rp, TB]
    for t in range(t_steps):
        onehot = (idx_ref[t : t + 1, :] == rows).astype(jnp.bfloat16)
        parts = jnp.dot(gx_ref[t], onehot, preferred_element_type=jnp.float32)
        gates = parts[:g4] + parts[g4 : 2 * g4] + parts[2 * g4 :]  # [4Hp, TB]
        if rec is not None:
            gates = gates + rec
        gates = gates + b_ref[...]
        i = jax.nn.sigmoid(gates[:hp])
        f = jax.nn.sigmoid(gates[hp : 2 * hp])
        g = jnp.tanh(gates[2 * hp : 3 * hp])
        o = jax.nn.sigmoid(gates[3 * hp :])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        if t == t_steps - 1:
            last = _dot(wl_ref[...], h) + bl_ref[...]
            out_ref[...] = jnp.sum(v * last, axis=0, keepdims=True)  # [1, TB]
        elif t == 0:
            z = _dot(wf_ref[...], h)
            rec, v = z[:g4], z[g4:] + bf_ref[...]
        else:
            z = _dot(wm_ref[...], h)
            rec, mid = z[:g4], z[g4:] + bm_ref[...]  # mid: [R*Rp, TB]
            # v[s] = sum_r v[r] * mid[r, s], summed over r in order
            acc = v[0:1] * mid[0:rp]
            for r in range(1, rank):
                acc = acc + v[r : r + 1] * mid[r * rp : (r + 1) * rp]
            v = acc


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def decode_tile(
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
    tile_b: int,
    interpret: bool = False,
) -> jax.Array:
    """Fused NTTD decode of a batch of folded indices.

    idx:      [B, T] int32 folded indices (T = d'), any B
    emb:      [T, M, H] per-step embedding tables, padded to M rows
    wi, wh:   [H, 4H] LSTM gate weights; b: [4H]
    w_first:  [H, R],   b_first: [R]
    w_mid:    [H, R*R], b_mid:   [R*R]   (unused when T == 2)
    w_last:   [H, R],   b_last:  [R]
    returns   [B] in ``emb.dtype``

    ``tile_b`` is the lanes of a grid step, a multiple of 128
    (``tile_width``).  The batch pad, the transpose to ``[T, B]``, the
    operands' layout (``lane_operands``, a few small ops on the weights)
    and the slice of the lane-dense ``[1, B]`` output are all part of
    this one program, so XLA fuses them into a caller's ``jit``.
    """
    bsz, t_steps = idx.shape
    if t_steps < 2:
        raise ValueError(f"decode_tile needs T >= 2 steps, got {t_steps}")
    if tile_b % LANES:
        raise ValueError(f"tile_b {tile_b} is not a multiple of {LANES} lanes")
    rank = b_first.shape[0]
    padded = _round_up(bsz, tile_b)
    idx_t = jnp.pad(idx, ((0, padded - bsz), (0, 0))).T  # [T, B]
    operands = lane_operands(emb, wi, wh, b, w_first, b_first, w_mid, b_mid,
                             w_last, b_last)

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)

    out = pl.pallas_call(
        functools.partial(_kernel, rank=rank),
        grid=(padded // tile_b,),
        in_specs=[pl.BlockSpec((t_steps, tile_b), lambda i: (0, i))]
        + [whole(a) for a in operands],
        out_specs=pl.BlockSpec((1, tile_b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded), jnp.float32),
        interpret=interpret,
        name="decode_tile",  # a stable kernel name in device traces
    )(idx_t, *operands)
    return out[0, :bsz].astype(emb.dtype)

"""Pallas TPU kernel: the whole NTTD decode for one tile, fused.

The serving hot path (paper Alg. 2) reconstructs a batch of entries as

    folded indices --embedding--> e_1..e_T --LSTM--> h_1..h_T
    T_1 = h_1 W_f + b_f (1xR); T_t = h_t W_m + b_m (RxR); T_T = h_T W_l + b_l
    value = T_1 T_2 ... T_T

which previously crossed four separately dispatched ops per decode tile
(gather, ``lstm.py``, three head matmuls, ``tt_contract.py``).  This kernel
runs the entire chain in ONE ``pl.pallas_call``: the batch is tiled on the
sublane axis, ``(h, c)`` and the running TT row vector stay resident in
VMEM/registers across all T steps, and every weight tensor (the stacked
embedding tables included) is broadcast once per core via constant index
maps — each HBM operand is read exactly once per core regardless of how
many batch tiles stream through.

The embedding gather is a one-hot matmul (``[TB, M] @ [M, H]``), the
standard TPU formulation of a row gather: it hits the MXU, needs no
dynamic indexing, and is exact in f32 (one 1.0 coefficient, the rest
0.0).  The T-step loop is unrolled at trace time (T = d' is ~4..12 for
NTTD), so the mid-core head projection and the R-wide chain contraction
of step t fuse directly with step t's gate math.

All internal math is f32 regardless of the parameter dtype (matching
``lstm.py``/``tt_contract.py`` and the promoted oracles in ``ref.py``);
the output is cast back to the embedding dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE_B = 256
# f32 contraction on the MXU: Mosaic's default rounds f32 operands to bf16
_F32 = jax.lax.Precision.HIGHEST


def _kernel(
    idx_ref,
    emb_ref,
    wi_ref,
    wh_ref,
    b_ref,
    wf_ref,
    bf_ref,
    wm_ref,
    bm_ref,
    wl_ref,
    bl_ref,
    out_ref,
    *,
    t_steps: int,
    hid: int,
    rank: int,
    m: int,
):
    tb = idx_ref.shape[0]
    wi = wi_ref[...].astype(jnp.float32)
    wh = wh_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)

    h = jnp.zeros((tb, hid), jnp.float32)
    c = jnp.zeros((tb, hid), jnp.float32)
    v = None  # running TT row vector [TB, R]
    out = None
    lanes = jax.lax.broadcasted_iota(jnp.int32, (tb, m), 1)
    for t in range(t_steps):
        onehot = (idx_ref[:, t][:, None] == lanes).astype(jnp.float32)
        xt = jnp.dot(
            onehot, emb_ref[t, :, :].astype(jnp.float32),
            precision=_F32, preferred_element_type=jnp.float32,
        )  # [TB, H]
        gates = (
            jnp.dot(xt, wi, precision=_F32, preferred_element_type=jnp.float32)
            + jnp.dot(h, wh, precision=_F32, preferred_element_type=jnp.float32)
            + b
        )
        i = jax.nn.sigmoid(gates[:, :hid])
        f = jax.nn.sigmoid(gates[:, hid : 2 * hid])
        g = jnp.tanh(gates[:, 2 * hid : 3 * hid])
        o = jax.nn.sigmoid(gates[:, 3 * hid :])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        if t == 0:
            v = (
                jnp.dot(h, wf_ref[...].astype(jnp.float32),
                        precision=_F32, preferred_element_type=jnp.float32)
                + bf_ref[...].astype(jnp.float32)
            )
        elif t == t_steps - 1:
            last = (
                jnp.dot(h, wl_ref[...].astype(jnp.float32),
                        precision=_F32, preferred_element_type=jnp.float32)
                + bl_ref[...].astype(jnp.float32)
            )
            out = jnp.sum(v * last, axis=-1, keepdims=True)  # [TB, 1]
        else:
            mid = (
                jnp.dot(h, wm_ref[...].astype(jnp.float32),
                        precision=_F32, preferred_element_type=jnp.float32)
                + bm_ref[...].astype(jnp.float32)
            )  # [TB, R*R], row-major R x R per entry
            v = chain_step(v, mid, rank)
    out_ref[...] = out.astype(out_ref.dtype)


def chain_step(v: jax.Array, mid: jax.Array, rank: int) -> jax.Array:
    """``v[b, s] = sum_r v[b, r] * mid[b, r*R + s]``: one link of the TT
    chain as R lane slices multiply-accumulated on the VPU (R is tiny).
    Mosaic refuses the [TB, R*R] -> [TB, R, R] reshape, so the R x R core
    stays flat; ``ref.nttd_decode_tile`` sums in this same order, which
    keeps interpret mode bit-identical to the oracle."""
    acc = v[:, 0:1] * mid[:, 0:rank]
    for r in range(1, rank):
        acc = acc + v[:, r : r + 1] * mid[:, r * rank : (r + 1) * rank]
    return acc


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
    tile_b: int = DEFAULT_TILE_B,
    interpret: bool = False,
) -> jax.Array:
    """Fused NTTD decode of one tile of folded indices.

    idx:      [B, T] int32 folded indices (T = d')
    emb:      [T, M, H] per-step embedding tables, padded to M rows
    wi, wh:   [H, 4H] LSTM gate weights; b: [4H]
    w_first:  [H, R],   b_first: [R]
    w_mid:    [H, R*R], b_mid:   [R*R]   (unused when T == 2)
    w_last:   [H, R],   b_last:  [R]
    returns   [B] in ``emb.dtype``

    B must be a multiple of ``tile_b``; ``ops.nttd_decode_tile`` pads.
    """
    bsz, t_steps = idx.shape
    _, m, hid = emb.shape
    rank = b_first.shape[0]
    if t_steps < 2:
        raise ValueError(f"decode_tile needs T >= 2 steps, got {t_steps}")
    if bsz % tile_b:
        raise ValueError(f"batch {bsz} not a multiple of tile_b {tile_b}")
    grid = (bsz // tile_b,)
    return pl.pallas_call(
        functools.partial(_kernel, t_steps=t_steps, hid=hid, rank=rank, m=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, t_steps), lambda i: (i, 0)),
            pl.BlockSpec((t_steps, m, hid), lambda i: (0, 0, 0)),
            pl.BlockSpec((hid, 4 * hid), lambda i: (0, 0)),
            pl.BlockSpec((hid, 4 * hid), lambda i: (0, 0)),
            pl.BlockSpec((4 * hid,), lambda i: (0,)),
            pl.BlockSpec((hid, rank), lambda i: (0, 0)),
            pl.BlockSpec((rank,), lambda i: (0,)),
            pl.BlockSpec((hid, rank * rank), lambda i: (0, 0)),
            pl.BlockSpec((rank * rank,), lambda i: (0,)),
            pl.BlockSpec((hid, rank), lambda i: (0, 0)),
            pl.BlockSpec((rank,), lambda i: (0,)),
        ],
        # a [B, 1] column: a 1-D output block's layout disagrees with XLA's
        out_specs=pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, 1), emb.dtype),
        interpret=interpret,
        name="decode_tile",  # a stable kernel name in device traces
    )(idx, emb, wi, wh, b, w_first, b_first, w_mid, b_mid, w_last, b_last)[:, 0]

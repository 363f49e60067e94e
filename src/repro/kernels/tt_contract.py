"""Pallas TPU kernel: batched TT-core chain contraction.

The NTTD reconstruction hot spot (paper Alg. 2 line 8) multiplies, per
sampled entry, a 1xR row vector through K RxR matrices and a final Rx1
column.  R is small (4..32), so a 128x128 MXU pass would be >94% idle —
this is restructured as a *lane-parallel batched matvec*: the batch is
tiled into VMEM blocks of TILE_B rows (sublane axis), and the per-step
contraction v[b,s] = sum_r v[b,r] * M[b,k,r,s] is an unrolled VPU
multiply-accumulate over the tiny R axis (``chain_step``).

HBM traffic: each core tensor is read exactly once; the running vector
stays in registers/VMEM across all K steps (the fusion the XLA path
cannot guarantee across scan iterations).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE_B = 256


def chain_step(v: jax.Array, mid: jax.Array, rank: int) -> jax.Array:
    """``v[b, s] = sum_r v[b, r] * mid[b, r*R + s]``: one link of the TT
    chain as R lane slices multiply-accumulated on the VPU (R is tiny).
    Mosaic refuses the [TB, R*R] -> [TB, R, R] reshape, so the R x R core
    stays flat."""
    acc = v[:, 0:1] * mid[:, 0:rank]
    for r in range(1, rank):
        acc = acc + v[:, r : r + 1] * mid[:, r * rank : (r + 1) * rank]
    return acc


def _kernel(first_ref, mid_ref, last_ref, out_ref, *, k_steps: int, rank: int):
    v = first_ref[...].astype(jnp.float32)  # [TB, R]
    rr = rank * rank
    for k in range(k_steps):  # K = d' - 2 is tiny: unrolled at trace time
        v = chain_step(v, mid_ref[:, k * rr : (k + 1) * rr].astype(jnp.float32), rank)
    out_ref[...] = jnp.sum(
        v * last_ref[...].astype(jnp.float32), axis=1, keepdims=True
    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def tt_contract(
    first: jax.Array,
    mid: jax.Array,
    last: jax.Array,
    *,
    tile_b: int = DEFAULT_TILE_B,
    interpret: bool = False,
) -> jax.Array:
    """first: [B, R], mid: [B, K, R, R], last: [B, R] -> [B].

    B must be a multiple of ``tile_b`` (callers pad; ``ops.tt_contract``
    handles padding automatically).
    """
    bsz, r = first.shape
    _, k_steps, _, _ = mid.shape
    if bsz % tile_b:
        raise ValueError(f"batch {bsz} not a multiple of tile_b {tile_b}")
    grid = (bsz // tile_b,)
    # cores flat on lanes: an [R, R] minor block would pad to (8, 128)
    # tiles, about 20x the VMEM at R = 10
    mid = mid.reshape(bsz, k_steps * r * r)
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, rank=r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, r), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, k_steps * r * r), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, r), lambda i: (i, 0)),
        ],
        # a [B, 1] column: a 1-D output block's layout disagrees with XLA's
        out_specs=pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, 1), first.dtype),
        interpret=interpret,
    )(first, mid, last)[:, 0]

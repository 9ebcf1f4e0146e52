"""DPX kernels: tropical (max,+) matmul and banded Smith-Waterman.

The TPU analogs of the paper's DPX section (§III-D-1): Hopper fuses
min/max(+add,+relu) into one instruction; on TPU the same fusion is a
single VPU loop inside a Pallas kernel.  Two kernels:

  * tropical_matmul — C[i,j] = max_k(A[i,k]+B[k,j]), the Floyd-
    Warshall / Viterbi inner step, tiled like the MXU matmul but run
    entirely on the VPU (the dissection point: DP work lands on the
    vector unit, there is no MXU path for (max,+)).
  * smith_waterman — anti-diagonal wavefront local alignment whose
    inner recurrence is exactly __viaddmax_s32_relu; one grid step per
    anti-diagonal, two previous diagonals live in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


INT_MIN = jnp.iinfo(jnp.int32).min // 2


def _tropical_kernel(a_ref, b_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, INT_MIN)

    a = a_ref[...]                                  # [bm, bk]
    b = b_ref[...]                                  # [bk, bn]
    # viaddmax over the contraction: max_k(a+b), fused on the VPU
    cand = jnp.max(a[:, :, None] + b[None, :, :], axis=1)
    acc_ref[...] = jnp.maximum(acc_ref[...], cand)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def tropical_matmul(a: jax.Array, b: jax.Array, *, bm: int = 32,
                    bn: int = 32, bk: int = 32,
                    interpret: bool = True) -> jax.Array:
    """(max,+) matrix product, int32."""
    m, k = a.shape
    _, n = b.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    return pl.pallas_call(
        _tropical_kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)


# ----------------------------------------------------------------------
# Smith-Waterman wavefront
# ----------------------------------------------------------------------

def _sw_kernel(sub_ref, o_ref, h1_ref, h2_ref, best_ref, *, gap: int,
               width: int):
    """One anti-diagonal per grid step; diagonals in scratch."""
    d = pl.program_id(1)

    @pl.when(d == 0)
    def _init():
        h1_ref[...] = jnp.zeros_like(h1_ref)     # diagonal d-1
        h2_ref[...] = jnp.zeros_like(h2_ref)     # diagonal d-2
        best_ref[...] = jnp.zeros_like(best_ref)

    s = sub_ref[0, 0, 0]                          # [width] packed subs
    valid = sub_ref[0, 0, 1] > 0                  # [width] validity lane
    h1 = h1_ref[0]                                # H on diag d-1, by j
    h2 = h2_ref[0]                                # H on diag d-2, by j
    diag = jnp.roll(h2, 1)                        # H[i-1, j-1] slot
    up = h1                                       # H[i-1, j]
    left = jnp.roll(h1, 1)                        # H[i, j-1]
    # __viaddmax_s32_relu chain: max(diag+s, up+gap, left+gap, 0)
    h = jnp.maximum(jnp.maximum(diag + s, jnp.maximum(up + gap, left + gap)),
                    0)
    h = jnp.where(valid, h, 0)
    h2_ref[0] = h1
    h1_ref[0] = h
    best_ref[...] = jnp.maximum(best_ref[...], jnp.max(h))

    @pl.when(d == pl.num_programs(1) - 1)
    def _done():
        o_ref[0, 0] = best_ref[0, 0]


def _pack_diagonals(seq_a: jax.Array, seq_b: jax.Array, match: int,
                    mismatch: int, width: int) -> jax.Array:
    """[B, D, 2, width]: lane 0 = substitution score of cell (i,j) on
    diagonal d at column j; lane 1 = cell-validity mask."""
    B, la = seq_a.shape
    lb = seq_b.shape[1]
    D = la + lb
    d_idx = jnp.arange(1, D + 1)[:, None]               # diag number
    j_idx = jnp.arange(width)[None, :]                  # column
    i_idx = d_idx - j_idx
    valid = (i_idx >= 1) & (i_idx <= la) & (j_idx >= 1) & (j_idx < lb + 1)
    ai = jnp.clip(i_idx - 1, 0, la - 1)
    bj = jnp.clip(j_idx - 1, 0, lb - 1)
    # gather per batch: a[b, i-1], b[b, j-1]
    a_g = jnp.take_along_axis(
        seq_a[:, None, :].repeat(D, 1),
        jnp.broadcast_to(ai[None], (B, D, width)), axis=2)
    b_g = jnp.take_along_axis(
        seq_b[:, None, :].repeat(D, 1),
        jnp.broadcast_to(bj[None], (B, D, width)), axis=2)
    sub = jnp.where(a_g == b_g, match, mismatch).astype(jnp.int32)
    sub = jnp.where(valid[None], sub, INT_MIN)
    lanes = jnp.stack([sub, jnp.broadcast_to(
        valid[None], sub.shape).astype(jnp.int32)], axis=2)
    return lanes                                        # [B, D, 2, width]


def smith_waterman(seq_a: jax.Array, seq_b: jax.Array, *, match: int = 2,
                   mismatch: int = -1, gap: int = -1,
                   interpret: bool = True) -> jax.Array:
    """Best local-alignment score per pair. seq_*: [B, L] int32."""
    B, la = seq_a.shape
    lb = seq_b.shape[1]
    width = lb + 1
    pad = (-width) % 128
    width_p = width + pad
    D = la + lb
    lanes = _pack_diagonals(seq_a, seq_b, match, mismatch, width)
    if pad:
        fill = jnp.full((B, D, 2, pad), INT_MIN, jnp.int32)
        fill = fill.at[:, :, 1, :].set(0)
        lanes = jnp.concatenate([lanes, fill], axis=-1)

    return pl.pallas_call(
        functools.partial(_sw_kernel, gap=gap, width=width_p),
        grid=(B, D),
        in_specs=[pl.BlockSpec((1, 1, 2, width_p),
                               lambda b, d: (b, d, 0, 0))],
        out_specs=pl.BlockSpec((1, 1), lambda b, d: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((1, width_p), jnp.int32),
            pltpu.VMEM((1, width_p), jnp.int32),
            pltpu.VMEM((1, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lanes)[:, 0]

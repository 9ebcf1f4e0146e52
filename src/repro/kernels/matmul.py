"""Tiled MXU matmul kernel — the TPU analog of mma/wgmma (paper §III-B).

One (bm, bn, bk) tile is the unit the paper's Tables VII-X sweep: the
K-innermost grid streams A/B tiles HBM->VMEM through the Pallas
pipeline (the asynchronous "warp-group" execution wgmma introduced),
accumulating into a VMEM fp32/int32 scratch.  benchmarks/tensorcore.py
sweeps (bm, bn, bk) x dtype over this kernel and checks the measured
shape sensitivity against core/mxu_model.py predictions.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _acc_dtype(dtype) -> jnp.dtype:
    return jnp.int32 if jnp.issubdtype(jnp.dtype(dtype), jnp.integer) \
        else jnp.float32


def matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    """Grid (m/bm, n/bn, k/bk), K innermost; acc lives across K steps."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=acc_ref.dtype)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
           bk: int = 128, out_dtype=None, interpret: bool = True
           ) -> jax.Array:
    """C = A @ B with explicit VMEM tiling. Shapes must tile evenly."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"{(m, n, k)} not tiled by {(bm, bn, bk)}"
    if out_dtype is None:
        # integer inputs accumulate (and return) int32, like mma IMMA
        out_dtype = _acc_dtype(a.dtype) if jnp.issubdtype(
            jnp.dtype(a.dtype), jnp.integer) else a.dtype
    acc = _acc_dtype(out_dtype)
    return pl.pallas_call(
        matmul_kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)


def single_tile_matmul(a: jax.Array, b: jax.Array, *,
                       interpret: bool = True) -> jax.Array:
    """One-tile kernel — the synchronous `mma` analog (whole operand is
    one VMEM-resident tile, no pipeline).  Used for the latency table."""
    m, k = a.shape
    _, n = b.shape

    def kernel(a_ref, b_ref, o_ref):
        o_ref[...] = jnp.dot(a_ref[...], b_ref[...],
                             preferred_element_type=o_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), _acc_dtype(a.dtype)),
        interpret=interpret,
    )(a, b)

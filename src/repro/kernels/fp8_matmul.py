"""FP8 matmul kernel — the QGMMA analog (paper Table VI/VIII).

Operands live in HBM as e4m3/e5m2 (1 byte/elem: half the bf16 traffic),
are upcast to bf16 *inside the tile* after the VMEM load, accumulate in
fp32 scratch, and the per-tensor TE scales (sx*sw) are applied once in
the epilogue.  v5e has no FP8 MXU mode — this kernel is exactly how FP8
pays on TPU: memory-bound layers see the 2x byte reduction while the
MXU runs at its bf16 rate (DESIGN.md hardware-adaptation note).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def fp8_matmul_kernel(sx_ref, sw_ref, a_ref, b_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.bfloat16)        # in-tile upcast (free on VPU)
    b = b_ref[...].astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        scale = sx_ref[0] * sw_ref[0]          # TE epilogue
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


def fp8_matmul(aq: jax.Array, bq: jax.Array, sx: jax.Array, sw: jax.Array,
               *, bm: int = 128, bn: int = 128, bk: int = 128,
               out_dtype=jnp.bfloat16, interpret: bool = True) -> jax.Array:
    """C = (A_q @ B_q) * sx*sw with fp8 operands."""
    m, k = aq.shape
    _, n = bq.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    return pl.pallas_call(
        fp8_matmul_kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # sx
            pl.BlockSpec(memory_space=pltpu.SMEM),   # sw
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(sx.reshape(1), sw.reshape(1), aq, bq)

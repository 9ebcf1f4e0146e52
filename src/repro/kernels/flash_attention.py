"""Fused flash-attention kernel (GQA), online softmax in VMEM scratch.

Grid is (batch*q_heads, q_blocks, kv_blocks) with KV innermost; the
(m, l, acc) running-softmax state lives in VMEM scratch across the KV
sweep — the same streaming structure the tiled-matmul kernel uses, with
softmax state instead of a plain accumulator.  GQA is handled in the
index maps: q head h reads kv head h // (H // KH), so no KV replication
is materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, bq: int, bk: int):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                    # [bq, hd]
    k = k_ref[0]                                    # [bk, hd]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bk]

    if causal:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    interpret: bool = True) -> jax.Array:
    """q: [B,S,H,hd]; k,v: [B,S,KH,hd] -> [B,S,H,hd].

    S must tile by (bq, bk).  Softmax scale = hd**-0.5.
    """
    B, S, H, hd = q.shape
    KH = k.shape[2]
    G = H // KH
    scale = hd ** -0.5
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0

    # [B*H, S, hd] query-major layout; kv stays per-kv-head
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KH, S, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KH, S, hd)

    def kv_index(bh, qi, ki):
        return ((bh // H) * KH + (bh % H) // G, ki, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(B * H, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # m
            pltpu.VMEM((bq, 1), jnp.float32),     # l
            pltpu.VMEM((bq, hd), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)

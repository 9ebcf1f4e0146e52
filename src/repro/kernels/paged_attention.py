"""Paged decode/chunk attention: block-table walk inside the kernel.

The gather read path (`attention.gather_paged_cache`) materializes a
[B, max_blocks*block_size, KH, hd] view per layer per step, an
O(max_len) copy whatever the slot's live context.  These kernels never
build that view: each (slot, kv-head) grid cell walks the slot's block
table, DMAs only the `ceil(kv_len/bs)` *valid* blocks of its head from
the pool (HBM, memory space ANY) into a VMEM scratch, and attends its
query rows there.  Table entries past the valid prefix are never
touched — the loop bound comes from the slot's position, not the table
width — so poisoned pool blocks cannot leak (the gather path instead
relies on masking; see attention.gather_paged_cache).

Pool layout
-----------
Head-major blocks, ``[NB, KH, bs, hd]`` per layer: one (block, head)
tile is a contiguous ``[bs, hd]`` slab, so the per-head DMA slices only
leading dims.  A ``[NB, bs, KH, hd]`` pool would slice its KH axis down
to 1, and the TPU compiler refuses DMA slices that are not aligned to
the memory tiling.

Decode is the one-row case of the chunk kernel: a decode at `kv_len` is
a chunk of one query position at `pos = kv_len - 1`.

Numerics
--------
Scores and the PV contraction are `dot_general`s with float32
accumulation (the MXU on a TPU).  Scratch rows past the valid frontier
are zero-filled, and masked scores carry exactly-zero softmax weight.
The result is not bitwise equal to the gather path, whose XLA dots
sum in another order over the whole gathered view:
tests/test_paged_kernel.py compares both against a float32 reference
within tolerances stated there.

FP8 pools
---------
With `k_scale`/`v_scale` (``[NB, KH, bs]`` f32, one scale per token row
per kv-head, rows on the lane axis) the pools hold e4m3 codes.  The
kernel converts the codes to q.dtype (exact: every e4m3 value is a
bf16 value) and applies the scales after the contractions — scores
per key column, softmax weights per value row — so no scale ever has
to move from the lane axis to the sublane axis.  The wrapper gathers
the slot's scale rows through the block table into ``[B, KH, 1, T]``
(4 bytes per token row, next to the hd bytes of codes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode: explicit, or chosen by backend — compiled
    on a TPU, interpreted on the CPU (the test suite), and an error on
    any other backend, where these TPU kernels cannot run."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels need a TPU (or interpret mode on the CPU); "
        f"the default backend is {backend!r}")


def _attend(q, k, v, pos, group, k_scale=None, v_scale=None):
    """Causal attention of query rows q [R, hd] against k/v [T, hd].

    Row r is query position ``pos + r // group`` and sees keys
    t <= pos + r // group, i.e. ``group * (t - pos) <= r`` (integer
    rows; no vector division).  Optional [1, T] fp8 scales multiply
    the scores per key and the softmax weights per value row.  f32
    result [R, hd]."""
    hd = q.shape[-1]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    if k_scale is not None:
        s = s * k_scale
    s = s * hd ** -0.5
    r = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    t = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(group * (t - pos) <= r, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    return lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _fetch_blocks(bt_ref, b, kh, nvb, kpool_ref, vpool_ref, k_s, v_s,
                  stage, sem, *, bs):
    """Copy head `kh` of physical blocks bt[b, 0:nvb] of both pools into
    scratch rows [i*bs, (i+1)*bs).  -1 entries only occur at i >= nvb
    (the allocator assigns blocks up to the frontier), so the
    max(.., 0) clamp is pure defense.  With a staging buffer (fp8
    pools) each block lands there first and is converted into the
    scratch dtype."""

    def body(i, carry):
        blk = jnp.maximum(bt_ref[b, i], 0)
        rows = pl.ds(pl.multiple_of(i * bs, bs), bs)
        for pool, dst in ((kpool_ref, k_s), (vpool_ref, v_s)):
            cp = pltpu.make_async_copy(
                pool.at[blk, kh], dst.at[rows] if stage is None else stage,
                sem)
            cp.start()
            cp.wait()
            if stage is not None:
                dst[rows, :] = stage[...].astype(dst.dtype)
        return carry

    lax.fori_loop(0, nvb, body, 0)


def _kernel(bt_ref, pos_ref, q_ref, kpool_ref, vpool_ref, *refs, bs,
            group, fp8):
    if fp8:
        ks_ref, vs_ref, o_ref, k_s, v_s, stage, sem = refs
    else:
        o_ref, k_s, v_s, sem = refs
        stage = None
    b = pl.program_id(0)
    kh = pl.program_id(1)
    k_s[...] = jnp.zeros_like(k_s)
    v_s[...] = jnp.zeros_like(v_s)
    pos = pos_ref[b]
    n_pos = q_ref.shape[2] // group
    nvb = jnp.minimum((pos + n_pos + bs - 1) // bs, bt_ref.shape[1])
    _fetch_blocks(bt_ref, b, kh, nvb, kpool_ref, vpool_ref, k_s, v_s,
                  stage, sem, bs=bs)
    scales = (ks_ref[0, 0], vs_ref[0, 0]) if fp8 else (None, None)
    o_ref[0, 0] = _attend(q_ref[0, 0], k_s[...], v_s[...], pos, group,
                          *scales)


def _row_scales(scale: jax.Array, block_table: jax.Array) -> jax.Array:
    """Per-slot scale rows through the table: [NB, KH, bs] ->
    [B, KH, 1, MB*bs] (the unit axis keeps the kernel's block equal to
    the array's trailing dims)."""
    s = scale[jnp.maximum(block_table, 0)]                # [B,MB,KH,bs]
    B, MB, KH, bs = s.shape
    return s.transpose(0, 2, 1, 3).reshape(B, KH, 1, MB * bs)


def _paged_attention(q, ck, cv, block_table, pos, k_scale, v_scale,
                     interpret):
    B, C, H, hd = q.shape
    NB, KH, bs, _ = ck.shape
    MB = block_table.shape[1]
    G, T = H // KH, MB * bs
    R = C * G
    # rows ordered (position, group): row r is position pos + r // G
    qr = q.reshape(B, C, KH, G, hd).transpose(0, 2, 1, 3, 4
                                              ).reshape(B, KH, R, hd)
    pos = jnp.broadcast_to(jnp.asarray(pos), (B,)).astype(jnp.int32)
    fp8 = k_scale is not None
    scratch_dtype = q.dtype if fp8 else ck.dtype
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    row_spec = pl.BlockSpec((1, 1, R, hd), lambda b, kh, *_: (b, kh, 0, 0))
    in_specs = [row_spec, pool_spec, pool_spec]
    operands = [block_table, pos, qr, ck, cv]
    scratch = [pltpu.VMEM((T, hd), scratch_dtype),
               pltpu.VMEM((T, hd), scratch_dtype)]
    if fp8:
        scale_spec = pl.BlockSpec((1, 1, 1, T),
                                  lambda b, kh, *_: (b, kh, 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [_row_scales(k_scale, block_table),
                     _row_scales(v_scale, block_table)]
        scratch += [pltpu.VMEM((bs, hd), ck.dtype)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KH),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, group=G, fp8=fp8),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, R, hd), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(*operands)
    out = out.reshape(B, KH, C, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, C, H, hd).astype(q.dtype)


def paged_decode(q: jax.Array, ck: jax.Array, cv: jax.Array,
                 block_table: jax.Array, kv_len: jax.Array, *,
                 k_scale: Optional[jax.Array] = None,
                 v_scale: Optional[jax.Array] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """One-step paged decode.  q [B,1,H,hd]; pools [NB,KH,bs,hd];
    block_table [B,MB] int32 (-1 = unallocated); kv_len scalar or [B]
    (the query sits at position kv_len - 1).  With `k_scale`/`v_scale`
    ([NB,KH,bs] f32) the pools are e4m3.  Returns [B,1,H,hd]."""
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len), (q.shape[0],))
    return _paged_attention(q, ck, cv, block_table, kv_len - 1, k_scale,
                            v_scale, interpret)


def paged_chunk(q: jax.Array, ck: jax.Array, cv: jax.Array,
                block_table: jax.Array, pos: jax.Array, *,
                k_scale: Optional[jax.Array] = None,
                v_scale: Optional[jax.Array] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """Paged chunk attention.  q [B,C,H,hd]; `pos` [B] is each slot's
    cache length BEFORE the chunk (row i sits at position pos+i and the
    chunk's k/v must already be scattered).  Rows past a slot's valid
    token count attend in-pool garbage and produce garbage rows the
    caller discards — same contract as attention.chunk_attention."""
    return _paged_attention(q, ck, cv, block_table, pos, k_scale,
                            v_scale, interpret)

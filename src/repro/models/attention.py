"""GQA attention: flash-style blockwise training path + cached decode.

The training/prefill path is a pure-jnp online-softmax (flash) attention
so 32k-token prefill never materializes an S x S score matrix — the
live working set is one (q_chunk x kv_chunk) block per head group.
kernels/flash_attention.py provides the Pallas TPU version of the same
algorithm; this module is also its oracle (kernels/ref.py imports it).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import ParamSpec, apply_rope, fixed_tree_sum
from repro.sharding.axes import constrain

NEG_INF = -1e30


# ----------------------------------------------------------------------
# params
# ----------------------------------------------------------------------

def attn_specs(cfg, d_model: Optional[int] = None) -> Dict[str, ParamSpec]:
    d = d_model or cfg.d_model
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # explicit fan-in scales: the default (shape[-2]) would read H, KH
    # or hd here, and the over-scaled q.k saturates every softmax row of
    # a random model (a 1-ulp input change then flips its argmax)
    specs = {
        "wq": ParamSpec((d, H, hd), ("embed", "heads", "head_dim"),
                        scale=d ** -0.5),
        "wk": ParamSpec((d, KH, hd), ("embed", "kv_heads", "head_dim"),
                        scale=d ** -0.5),
        "wv": ParamSpec((d, KH, hd), ("embed", "kv_heads", "head_dim"),
                        scale=d ** -0.5),
        "wo": ParamSpec((H, hd, d), ("heads", "head_dim", "embed"),
                        scale=(H * hd) ** -0.5),
    }
    if cfg.use_bias:
        specs["bq"] = ParamSpec((H, hd), ("heads", "head_dim"), init="zeros")
        specs["bk"] = ParamSpec((KH, hd), ("kv_heads", "head_dim"), init="zeros")
        specs["bv"] = ParamSpec((KH, hd), ("kv_heads", "head_dim"), init="zeros")
    return specs


def qkv_project(cfg, p, x: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B,S,d] -> q [B,S,H,hd], k/v [B,S,KH,hd]."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.use_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    return q, k, v


def out_project(p, o: jax.Array, *, groups: int = 0) -> jax.Array:
    """o [B,S,H,hd] -> [B,S,d] through the row-parallel wo.

    With ``groups > 1`` (serving: transformer.serving_det_groups) the
    head contraction is restructured as `groups` partial einsums in
    fp32 reduced by ``common.fixed_tree_sum`` — an addition order fixed
    by the group count alone, so a tensor-parallel mesh sharding the
    head axis over any tp dividing `groups` yields bitwise-identical
    outputs to tp=1 (a plain einsum would psum per-device partials in
    a layout-dependent order).  ``groups=0`` keeps the single-einsum
    training path.
    """
    wo = p["wo"].astype(o.dtype)
    if groups > 1:
        B, S, H, hd = o.shape
        og = o.reshape(B, S, groups, H // groups, hd)
        wg = wo.reshape(groups, H // groups, hd, wo.shape[-1])
        parts = jnp.einsum("bsghk,ghkd->gbsd", og, wg,
                           preferred_element_type=jnp.float32)
        y = fixed_tree_sum(parts, tag="xshard_attn_out").astype(o.dtype)
    else:
        y = jnp.einsum("bshk,hkd->bsd", o, wo)
    return constrain(y, ("batch", "seq", "embed"))


def qkv_project_fp8(cfg, p, q8, x: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """fp8 serving variant of `qkv_project`: x through weights
    pre-quantized to e4m3 by te/linear.quantize_serving_params, with a
    fresh per-call activation scale (te/linear.fp8_serving_dot).
    Biases, if any, stay in the bf16 params `p`.  tp=1 serving only —
    there is no grouped/deterministic-reduction structure here."""
    from repro.te import linear as te_linear
    q = te_linear.fp8_serving_dot(x, q8["wq"])
    k = te_linear.fp8_serving_dot(x, q8["wk"])
    v = te_linear.fp8_serving_dot(x, q8["wv"])
    if cfg.use_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    return q, k, v


def out_project_fp8(p, q8, o: jax.Array) -> jax.Array:
    """fp8 serving variant of `out_project` (tp=1 only)."""
    from repro.te import linear as te_linear
    y = te_linear.fp8_serving_dot(o, q8["wo"], x_contract_ndim=2,
                                  w_contract_ndim=2)
    return constrain(y, ("batch", "seq", "embed"))


# ----------------------------------------------------------------------
# flash attention (pure jnp, the oracle + XLA path)
# ----------------------------------------------------------------------

def _chunk_arrays(q, k, v, qc, kc):
    """Pad + reshape to chunked layouts; returns geometry too."""
    B, Sq, H, hd = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    pad_q = (-Sq) % qc
    pad_k = (-Sk) % kc
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = (Sq + pad_q) // qc, (Sk + pad_k) // kc
    qs = q.reshape(B, nq, qc, KH, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, kc, KH, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kc, KH, hd).transpose(1, 0, 2, 3, 4)
    return qs, ks, vs, (B, Sq, Sk, H, KH, G, hd, nq, nk)


def _block_mask(qp, kp, kval, causal, window):
    mask = kval[None, :]
    if causal:
        mask = mask & (kp[None, :] <= qp[:, None])
    if window:
        mask = mask & (kp[None, :] > qp[:, None] - window)
    return mask                                   # [qc, kc]


def _causal_pairs(nq, nk, qc, kc, q_offset):
    """Static lower-triangle (i, j) block-pair list: block j is needed by
    block i iff its first key position can be attended by i's last query.
    Ordered i-major so the online-softmax state streams per q-block."""
    pairs = []
    for i in range(nq):
        q_max = q_offset + (i + 1) * qc - 1
        for j in range(nk):
            if j * kc <= q_max:
                pairs.append((i, j))
    return pairs


def _attn_fwd_pairs(qs, ks, vs, geom, scale, q_pos, k_pos, k_valid,
                    causal, window, q_offset, qc, kc):
    """Causal-skip forward: scan over the lower-triangle block pairs
    only (~half the FLOPs of the full grid at Sq == Sk)."""
    B, Sq, Sk, H, KH, G, hd, nq, nk = geom
    pairs = _causal_pairs(nq, nk, qc, kc, q_offset)
    pi = jnp.asarray([p[0] for p in pairs], jnp.int32)
    pj = jnp.asarray([p[1] for p in pairs], jnp.int32)
    # `new_i` marks the first pair of each q-block (state reset)
    new_i = jnp.asarray([1] + [int(pairs[t][0] != pairs[t - 1][0])
                               for t in range(1, len(pairs))], jnp.int32)
    # `last_j` marks the final pair of each q-block (state flush)
    last_j = jnp.asarray([int(t + 1 == len(pairs)
                              or pairs[t + 1][0] != pairs[t][0])
                          for t in range(len(pairs))], jnp.int32)

    def step(carry, inp):
        m, l, acc, out_buf, lse_buf = carry
        i, j, fresh, flush = inp
        reset = fresh.astype(jnp.float32)
        m = jnp.where(fresh > 0, jnp.full_like(m, NEG_INF), m)
        l = l * (1.0 - reset)
        acc = acc * (1.0 - reset)
        qb = qs[i]
        kb, vb = ks[j], vs[j]
        qp, kp, kval = q_pos[i], k_pos[j], k_valid[j]
        s = jnp.einsum("bqkgd,btkd->bkgqt", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        mask = _block_mask(qp, kp, kval, causal, window)
        s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv

        def do_flush(bufs):
            out_buf, lse_buf = bufs
            lse = m_new + jnp.log(jnp.maximum(l, 1e-30))
            norm = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
            out_i = (acc / norm)[None]
            return (lax.dynamic_update_slice(
                        out_buf, out_i, (i, 0, 0, 0, 0, 0)),
                    lax.dynamic_update_slice(
                        lse_buf, lse[None], (i, 0, 0, 0, 0)))

        out_buf, lse_buf = lax.cond(flush > 0, do_flush,
                                    lambda b: b, (out_buf, lse_buf))
        return (m_new, l, acc, out_buf, lse_buf), None

    m0 = jnp.full((B, KH, G, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, G, qc), jnp.float32)
    a0 = jnp.zeros((B, qc, KH, G, hd), jnp.float32)
    out0 = jnp.zeros((nq, B, qc, KH, G, hd), jnp.float32)
    lse0 = jnp.full((nq, B, KH, G, qc), NEG_INF, jnp.float32)
    (_, _, _, out, lse), _ = lax.scan(
        step, (m0, l0, a0, out0, lse0), (pi, pj, new_i, last_j))
    return out, lse


def _attn_fwd(q, k, v, causal, window, qc, kc, q_offset):
    """Blockwise online-softmax forward. Also returns the LSE rows
    (needed by the hand-written backward)."""
    qs, ks, vs, (B, Sq, Sk, H, KH, G, hd, nq, nk) = \
        _chunk_arrays(q, k, v, qc, kc)
    scale = hd ** -0.5
    q_pos = q_offset + jnp.arange(nq * qc).reshape(nq, qc)
    k_pos = jnp.arange(nk * kc).reshape(nk, kc)
    k_valid = (jnp.arange(nk * kc) < Sk).reshape(nk, kc)

    if causal and not window and nq > 1:
        # causal block skipping: only lower-triangle pairs executed
        out, lse = _attn_fwd_pairs(
            qs, ks, vs, (B, Sq, Sk, H, KH, G, hd, nq, nk), scale,
            q_pos, k_pos, k_valid, causal, window, q_offset, qc, kc)
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qc, H, hd)
        return out[:, :Sq].astype(q.dtype), lse

    def q_block(args):
        qb, qp = args                       # [B,qc,KH,G,hd], [qc]

        def kv_step(carry, inp):
            m, l, acc = carry
            kb, vb, kp, kval = inp
            s = jnp.einsum("bqkgd,btkd->bkgqt", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _block_mask(qp, kp, kval, causal, window)
            s = jnp.where(mask[None, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KH, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KH, G, qc), jnp.float32)
        a0 = jnp.zeros((B, qc, KH, G, hd), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0),
                                  (ks, vs, k_pos, k_valid))
        lse = m + jnp.log(jnp.maximum(l, 1e-30))      # [B,KH,G,qc]
        l = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
        return acc / l, lse

    out, lse = lax.map(q_block, (qs, q_pos))  # [nq,B,qc,KH,G,hd], [nq,B,KH,G,qc]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qc, H, hd)
    return out[:, :Sq].astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, qc, kc, q_offset):
    return _attn_fwd(q, k, v, causal, window, qc, kc, q_offset)[0]


def _flash_fwd_rule(q, k, v, causal, window, qc, kc, q_offset):
    out, lse = _attn_fwd(q, k, v, causal, window, qc, kc, q_offset)
    return out, (q, k, v, out, lse)


def _bwd_pairs_scan(qs, gs, lses, Ds, ks, vs, geom, scale, q_pos, k_pos,
                    k_valid, causal, window, q_offset, qc, kc):
    """Causal-skip backward: j-major lower-triangle pair scan."""
    B, Sq, Sk, H, KH, G, hd, nq, nk = geom
    pairs = [(i, j) for j in range(nk) for i in range(nq)
             if j * kc <= q_offset + (i + 1) * qc - 1]
    pi = jnp.asarray([p[0] for p in pairs], jnp.int32)
    pj = jnp.asarray([p[1] for p in pairs], jnp.int32)
    new_j = jnp.asarray([1] + [int(pairs[t][1] != pairs[t - 1][1])
                               for t in range(1, len(pairs))], jnp.int32)
    last_i = jnp.asarray([int(t + 1 == len(pairs)
                              or pairs[t + 1][1] != pairs[t][1])
                          for t in range(len(pairs))], jnp.int32)

    def step(carry, inp):
        dk_j, dv_j, dq_buf, dk_buf, dv_buf = carry
        i, j, fresh, flush = inp
        keep = 1.0 - fresh.astype(jnp.float32)
        dk_j = dk_j * keep
        dv_j = dv_j * keep
        qb, gb, lseb, Db = qs[i], gs[i], lses[i], Ds[i]
        kb, vb = ks[j], vs[j]
        s = jnp.einsum("bqkgd,btkd->bkgqt", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        mask = _block_mask(q_pos[i], k_pos[j], k_valid[j], causal, window)
        p = jnp.where(mask[None, None, None, :, :],
                      jnp.exp(s - lseb[..., None]), 0.0)
        dv_j = dv_j + jnp.einsum("bkgqt,bqkgd->btkd", p, gb)
        dp = jnp.einsum("bqkgd,btkd->bkgqt", gb, vb.astype(jnp.float32))
        ds = p * (dp - Db[..., None]) * scale
        dk_j = dk_j + jnp.einsum("bkgqt,bqkgd->btkd", ds,
                                 qb.astype(jnp.float32))
        dq_i = jnp.einsum("bkgqt,btkd->bqkgd", ds, kb.astype(jnp.float32))
        old = lax.dynamic_slice(
            dq_buf, (i, 0, 0, 0, 0, 0), (1,) + dq_buf.shape[1:])
        dq_buf = lax.dynamic_update_slice(dq_buf, old + dq_i[None],
                                          (i, 0, 0, 0, 0, 0))

        def do_flush(bufs):
            dk_buf, dv_buf = bufs
            return (lax.dynamic_update_slice(dk_buf, dk_j[None],
                                             (j, 0, 0, 0, 0)),
                    lax.dynamic_update_slice(dv_buf, dv_j[None],
                                             (j, 0, 0, 0, 0)))

        dk_buf, dv_buf = lax.cond(flush > 0, do_flush, lambda b: b,
                                  (dk_buf, dv_buf))
        return (dk_j, dv_j, dq_buf, dk_buf, dv_buf), None

    zeros_kv = jnp.zeros((B, kc, KH, hd), jnp.float32)
    dq0 = jnp.zeros((nq, B, qc, KH, G, hd), jnp.float32)
    dkv0 = jnp.zeros((nk, B, kc, KH, hd), jnp.float32)
    (_, _, dq, dks, dvs), _ = lax.scan(
        step, (zeros_kv, zeros_kv, dq0, dkv0, dkv0),
        (pi, pj, new_j, last_i))
    return dq, dks, dvs


def _flash_bwd_rule(causal, window, qc, kc, q_offset, res, g):
    """Hand-written blockwise backward (FlashAttention bwd): recomputes
    each (q-block, kv-block) probability tile from (q, k, lse) and
    accumulates dq/dk/dv — O(S*d) live memory, never O(S^2).  Causal
    cells iterate only the lower-triangle block pairs."""
    q, k, v, out, lse = res
    lses = lse                               # [nq, B, KH, G, qc]
    in_dtype = q.dtype
    qs, ks, vs, (B, Sq, Sk, H, KH, G, hd, nq, nk) = \
        _chunk_arrays(q, k, v, qc, kc)
    gs = _chunk_arrays(g.astype(jnp.float32), k, v, qc, kc)[0]
    scale = hd ** -0.5
    # D = rowsum(dout * out), per query row
    D = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    D = jnp.pad(D, ((0, 0), (0, nq * qc - Sq), (0, 0)))
    Ds = D.reshape(B, nq, qc, KH, G).transpose(1, 0, 3, 4, 2)  # [nq,B,KH,G,qc]
    q_pos = q_offset + jnp.arange(nq * qc).reshape(nq, qc)
    k_pos = jnp.arange(nk * kc).reshape(nk, kc)
    k_valid = (jnp.arange(nk * kc) < Sk).reshape(nk, kc)

    if causal and not window and nq > 1:
        dq, dks, dvs = _bwd_pairs_scan(
            qs, gs, lses, Ds, ks, vs,
            (B, Sq, Sk, H, KH, G, hd, nq, nk), hd ** -0.5,
            q_pos, k_pos, k_valid, causal, window, q_offset, qc, kc)
        dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(
            B, nq * qc, H, hd)[:, :Sq]
        dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, nk * kc, KH,
                                                  hd)[:, :Sk]
        dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, nk * kc, KH,
                                                  hd)[:, :Sk]
        return (dq.astype(in_dtype), dk.astype(in_dtype),
                dv.astype(in_dtype))

    def kv_block(dq_acc, inp):
        kb, vb, kp, kval = inp

        def q_step(carry, qinp):
            dk_j, dv_j = carry
            qb, gb, lseb, Db, qp = qinp
            s = jnp.einsum("bqkgd,btkd->bkgqt", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _block_mask(qp, kp, kval, causal, window)
            p = jnp.where(mask[None, None, None, :, :],
                          jnp.exp(s - lseb[..., None]), 0.0)
            dv_j = dv_j + jnp.einsum("bkgqt,bqkgd->btkd", p, gb)
            dp = jnp.einsum("bqkgd,btkd->bkgqt", gb,
                            vb.astype(jnp.float32))
            ds = p * (dp - Db[..., None]) * scale
            dk_j = dk_j + jnp.einsum("bkgqt,bqkgd->btkd", ds,
                                     qb.astype(jnp.float32))
            dq_i = jnp.einsum("bkgqt,btkd->bqkgd", ds,
                              kb.astype(jnp.float32))
            return (dk_j, dv_j), dq_i

        zeros_kv = jnp.zeros((B, kc, KH, hd), jnp.float32)
        (dk_j, dv_j), dq_contrib = lax.scan(
            q_step, (zeros_kv, zeros_kv), (qs, gs, lses, Ds, q_pos))
        return dq_acc + dq_contrib, (dk_j, dv_j)

    dq0 = jnp.zeros((nq, B, qc, KH, G, hd), jnp.float32)
    dq, (dks, dvs) = lax.scan(kv_block, dq0,
                              (ks, vs, k_pos, k_valid))
    dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qc, H, hd)[:, :Sq]
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, nk * kc, KH, hd)[:, :Sk]
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, nk * kc, KH, hd)[:, :Sk]
    return (dq.astype(in_dtype), dk.astype(in_dtype), dv.astype(in_dtype))


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    q_chunk: int = 512, kv_chunk: int = 512,
                    q_offset: int = 0) -> jax.Array:
    """Blockwise online-softmax attention with GQA + flash backward.

    q: [B,Sq,H,hd]; k,v: [B,Sk,KH,hd]; H % KH == 0.  `q_offset` is the
    absolute position of q[0] (prefill: 0; decode chunk: cache length).
    Returns [B,Sq,H,hd] in q.dtype; softmax in fp32.
    """
    qc = min(q_chunk, q.shape[1])
    kc = min(kv_chunk, k.shape[1])
    return _flash(q, k, v, causal, window, qc, kc, q_offset)


def attention_reference(q, k, v, *, causal=True, window=0, q_offset=0):
    """Naive O(S^2)-memory oracle for tests (small shapes only)."""
    B, Sq, H, hd = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    qg = q.reshape(B, Sq, KH, G, hd)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    qp = q_offset + jnp.arange(Sq)
    kp = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kp[None, :] <= qp[:, None]
    if window:
        mask &= kp[None, :] > qp[:, None] - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v.dtype), v)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# ----------------------------------------------------------------------
# decode with KV cache
# ----------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, max_len: int, *, layers: int,
                  dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    KH, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (layers, batch, max_len, KH, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "length": jnp.zeros((), jnp.int32)}


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len: jax.Array) -> jax.Array:
    """One-step decode: q [B,1,H,hd] vs cache [B,T,KH,hd], attending
    positions t < kv_len (a scalar, or one length per slot [B]).

    Memory is linear in T, so no chunking is needed even at T=512k; with
    the cache sequence-sharded ("kv_seq" -> a mesh axis) XLA emits the
    split-K/flash-decode pattern (partial max/sum + small all-reduces).

    The C=1 case of `chunk_attention`, its one row at position
    kv_len - 1; for G=1 XLA lowers its dots to the matrix-vector
    products they are.
    """
    B = q.shape[0]
    last = jnp.broadcast_to(jnp.asarray(kv_len)[..., None] - 1, (B, 1))
    return chunk_attention(q, k_cache, v_cache, last)


def chunk_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    q_positions: jax.Array) -> jax.Array:
    """Chunked-prefill attention: a C-token query chunk vs the KV cache.

    q: [B,C,H,hd]; k_cache/v_cache: [B,T,KH,hd]; q_positions: [B,C]
    absolute positions of the chunk rows (per-slot `pos + arange(C)`).
    Cache-aware causal mask: row i attends cache position t iff
    t <= q_positions[b, i] — the chunk's own k/v must already be written
    at those positions (update_cache with the chunk, then attend).

    One C-row block of the blockwise flash sweep: live memory is
    O(C * T) scores (C is the chunk size, 16-64), never O(S^2).
    Rows past a slot's valid token count attend garbage but only
    produce garbage in their own output rows, which callers discard.

    Both contractions are `dot_general`s on the operands' own dtypes
    (bf16 on the serving paths) with float32 accumulation, over the
    [B,T,KH,hd] view as it comes (dimension numbers, no transpose), so
    on a TPU they run on the MXU; `Precision.HIGHEST` keeps f32
    operands from being cut to bf16 passes.  The mask, the NEG_INF
    fill, the max/exp/sum and the normalisation are float32; `p` is
    cast to the value dtype before PV.  Masked weights are exact
    zeros, so what sits at a masked position (a contiguous cache's
    stale rows, a paged view's garbage block) never reaches the
    output.  The order of the f32 sums is the compiler's: programs of
    different shapes (a chunk step, a decode step) agree to rounding,
    not bit for bit.
    """
    B, C, H, hd = q.shape
    T, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    qg = q.reshape(B, C, KH, G, hd)
    # [B,C,KH,G,hd] x [B,T,KH,hd] -> [B,KH,C,G,T]
    s = lax.dot_general(qg, k_cache, (((4,), (3,)), ((0, 2), (0, 2))),
                        precision=lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    mask = jnp.arange(T)[None, None, :] <= q_positions[:, :, None]  # [B,C,T]
    s = jnp.where(mask[:, None, :, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    pv = p.astype(v_cache.dtype)
    # [B,KH,C,G,T] x [B,T,KH,hd] -> [B,KH,C,G,hd]
    o = lax.dot_general(pv, v_cache, (((4,), (1,)), ((0, 1), (0, 2))),
                        precision=lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
    return o.transpose(0, 2, 1, 3, 4).reshape(B, C, H, hd).astype(q.dtype)


def update_cache(cache_k: jax.Array, cache_v: jax.Array, k1: jax.Array,
                 v1: jax.Array, pos: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """Write a step's k/v ([B,C,KH,hd], C=1 for decode or a whole
    prefill chunk) at `pos` into [B,T,KH,hd].

    `pos` may be a scalar (lockstep decode) or a per-slot [B] vector
    (continuous batching / chunked prefill, runtime/server.py).  Callers
    must keep `pos + C <= T` — dynamic_update_slice clamps the start
    index, so an out-of-range chunk write would silently shift onto the
    tail of the cache (servers allocate T = max_len + chunk headroom).
    """
    pos = jnp.asarray(pos)
    if pos.ndim == 1:
        def upd(ck, cv, k_, v_, p):
            ck = lax.dynamic_update_slice(ck, k_.astype(ck.dtype), (p, 0, 0))
            cv = lax.dynamic_update_slice(cv, v_.astype(cv.dtype), (p, 0, 0))
            return ck, cv
        return jax.vmap(upd)(cache_k, cache_v, k1, v1, pos)
    cache_k = lax.dynamic_update_slice(
        cache_k, k1.astype(cache_k.dtype), (0, pos, 0, 0))
    cache_v = lax.dynamic_update_slice(
        cache_v, v1.astype(cache_v.dtype), (0, pos, 0, 0))
    return cache_k, cache_v


# ----------------------------------------------------------------------
# paged KV cache: block pool + per-slot block tables
# ----------------------------------------------------------------------
#
# Instead of one contiguous [B, T, KH, hd] region per serving slot, the
# paged layout keeps a shared pool per layer plus a per-slot table
# block_table [B, max_blocks] int32 mapping logical block i (virtual
# positions [i*bs, (i+1)*bs)) to a physical pool block; -1 marks an
# unallocated entry.  The table is a fixed-shape jit operand, so the
# serving programs stay O(1) compiles while slots only pin the blocks
# their live prefix actually covers.  Blocks are head-major,
# [num_blocks, KH, block_size, hd]: one (block, head) tile is the
# contiguous slab the fused kernels DMA (kernels/paged_attention.py).

def init_paged_kv_cache(num_blocks: int, block_size: int, kv_heads: int,
                        head_dim: int, *, layers: int, dtype=jnp.bfloat16,
                        fp8: bool = False) -> Dict[str, jax.Array]:
    """Stacked block pool [L, NB, KH, bs, hd].  With ``fp8=True`` the
    k/v pools hold e4m3 codes and two extra f32 leaves
    "k_scale"/"v_scale" of shape [L, NB, KH, bs] hold one scale per
    token row per kv-head (te/fp8.quantize_rowwise), rows on the lane
    axis.  Every leaf has NB on axis 1 and KH on axis 2, so one block
    copy and one cache sharding (sharding/plans.py) fit them all."""
    shape = (layers, num_blocks, kv_heads, block_size, head_dim)
    if not fp8:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    from repro.te import fp8 as te_fp8
    sshape = shape[:-1]
    return {"k": jnp.zeros(shape, te_fp8.E4M3),
            "v": jnp.zeros(shape, te_fp8.E4M3),
            "k_scale": jnp.ones(sshape, jnp.float32),
            "v_scale": jnp.ones(sshape, jnp.float32)}


def _slot_view(pool: jax.Array, bt: jax.Array) -> jax.Array:
    """[NB, KH, bs, hd] pool through a clamped table [B, MB] ->
    [B, MB*bs, KH, hd] per-slot virtual view."""
    B, MB = bt.shape
    _, KH, bs, hd = pool.shape
    return pool[bt].transpose(0, 1, 3, 2, 4).reshape(B, MB * bs, KH, hd)


def gather_paged_cache(ck: jax.Array, cv: jax.Array,
                       block_table: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Materialize each slot's virtual cache view through its table.

    ck/cv: [num_blocks, KH, bs, hd]; block_table: [B, max_blocks].
    Returns [B, max_blocks*bs, KH, hd].

    Unallocated-entry contract: the allocator (runtime/server.py)
    assigns a slot's table entries densely from index 0 up to its
    frontier block and leaves -1 past it, so INVARIANT: every -1 entry
    maps only to virtual positions at or beyond the slot's kv frontier.
    The index is clamped (`maximum(bt, 0)`), so -1 entries read
    physical block 0 — arbitrary garbage owned by someone else — but
    the position masks of `chunk_attention` / `decode_attention`
    exclude exactly those positions (masked scores sit at NEG_INF, so
    their softmax weight underflows to an exact 0.0 and, since
    0.0 * x == 0.0 for finite x, the outputs stay bit-identical to a
    contiguous cache of the same length: the same program contracts
    the same shapes, and the garbage adds exact zeros).  A poisoned
    pool block therefore cannot leak into any slot's output through
    either this gather path or the in-kernel block-table walk of
    kernels/paged_attention, which never touches -1 entries at all
    (its loop bound is ceil(kv_len/bs));
    tests/test_paged_kernel.py pins the no-leak behaviour on both.
    """
    bt = jnp.maximum(block_table, 0)
    return _slot_view(ck, bt), _slot_view(cv, bt)


def update_paged_cache(ck: jax.Array, cv: jax.Array, k1: jax.Array,
                       v1: jax.Array, pos: jax.Array,
                       block_table: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Scatter a step's k/v ([B, C, KH, hd]) through the block table.

    Row i of slot b lands at virtual position p = pos[b] + i, i.e. in
    physical block block_table[b, p // bs] at row p % bs of every
    head.  Writes whose virtual block is unallocated (or past the
    table) are dropped: they are exactly the beyond-frontier padding
    rows the contiguous path writes into its `+ chunk` headroom and
    overwrites before they become visible — here they simply never
    land, so a slot can only ever touch its own blocks.

    Speculative decoding leans on the same contract for rollback
    (transformer.verify_step / runtime/spec_decode.py): a verify
    window writes K+1 rows at [pos, pos+K], the server then truncates
    the slot's block-table frontier back to the accepted position, and
    the rejected rows' KV is either beyond the (rolled-back) frontier
    inside a still-owned block — masked out of every read and
    overwritten by the next window before the frontier passes it — or
    was dropped right here because its block was never allocated.
    """
    NB, _, bs = ck.shape[:3]
    C = k1.shape[1]
    blk, row = _paged_rows(pos, block_table, C, NB, bs)
    return (_paged_scatter(ck, blk, row, k1.astype(ck.dtype)),
            _paged_scatter(cv, blk, row, v1.astype(cv.dtype)))


def _paged_rows(pos: jax.Array, block_table: jax.Array, C: int,
                num_blocks: int, block_size: int
                ) -> Tuple[jax.Array, jax.Array]:
    """(physical block, row in block), each [B*C], for a C-row write
    at `pos` through the table; invalid rows (unallocated block / past
    the table) get the out-of-range block NB so `.at[].set(mode=drop)`
    discards them.  Shared by the bf16 and fp8 scatter paths so both
    obey the same drop contract."""
    B, MB = block_table.shape
    pos = jnp.asarray(pos)
    if pos.ndim == 0:                     # lockstep decode: same frontier
        pos = jnp.full((B,), pos, jnp.int32)
    vpos = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]   # [B,C]
    blk = vpos // block_size
    phys = jnp.take_along_axis(block_table, jnp.clip(blk, 0, MB - 1),
                               axis=1)
    valid = (blk < MB) & (phys >= 0)
    return (jnp.where(valid, phys, num_blocks).reshape(-1),
            (vpos % block_size).reshape(-1))


def _paged_scatter(pool: jax.Array, blk: jax.Array, row: jax.Array,
                   rows: jax.Array) -> jax.Array:
    """Scatter rows [B, C, KH, ...] into pool [NB, KH, bs, ...] at
    (blk, row) pairs [B*C] (dropping out-of-range blocks).  The pool is
    addressed as flat (block, head, row) rows, so every update is one
    contiguous trailing slab and the pool keeps its own layout."""
    NB, KH, bs = pool.shape[:3]
    tail = pool.shape[3:]
    idx = ((blk[:, None] * KH + jnp.arange(KH)[None, :]) * bs
           + row[:, None]).reshape(-1)                   # [B*C*KH]
    flat = pool.reshape((NB * KH * bs,) + tail)
    flat = flat.at[idx].set(rows.reshape((-1,) + tail), mode="drop")
    return flat.reshape(pool.shape)


def update_paged_cache_fp8(cache_layer: Dict[str, jax.Array],
                           k1: jax.Array, v1: jax.Array, pos: jax.Array,
                           block_table: jax.Array
                           ) -> Dict[str, jax.Array]:
    """fp8 variant of `update_paged_cache` on a single-layer cache dict
    {"k", "v", "k_scale", "v_scale"}: quantize the step's k/v rows to
    e4m3 with one f32 scale per token-row per kv-head
    (te/fp8.quantize_rowwise) and scatter codes + scales through the
    same block/row drop contract."""
    from repro.te import fp8 as te_fp8
    ck = cache_layer["k"]
    NB, _, bs = ck.shape[:3]
    C = k1.shape[1]
    kq, k_sc = te_fp8.quantize_rowwise(k1, ck.dtype)
    vq, v_sc = te_fp8.quantize_rowwise(v1, ck.dtype)
    blk, row = _paged_rows(pos, block_table, C, NB, bs)
    return {"k": _paged_scatter(ck, blk, row, kq),
            "v": _paged_scatter(cache_layer["v"], blk, row, vq),
            "k_scale": _paged_scatter(cache_layer["k_scale"], blk, row,
                                      k_sc[..., 0]),
            "v_scale": _paged_scatter(cache_layer["v_scale"], blk, row,
                                      v_sc[..., 0])}


def gather_paged_cache_fp8(cache_layer: Dict[str, jax.Array],
                           block_table: jax.Array,
                           out_dtype=jnp.bfloat16
                           ) -> Tuple[jax.Array, jax.Array]:
    """Materialize + dequantize each slot's virtual view from an fp8
    single-layer cache dict: `(codes.astype(f32) * scale)
    .astype(out_dtype)` per element.  Same -1 clamp/mask contract as
    `gather_paged_cache`."""
    bt = jnp.maximum(block_table, 0)

    def dq(pool, scale):
        x = pool.astype(jnp.float32) * scale[..., None]
        return _slot_view(x.astype(out_dtype), bt)

    return (dq(cache_layer["k"], cache_layer["k_scale"]),
            dq(cache_layer["v"], cache_layer["v_scale"]))


def copy_paged_block(ck: jax.Array, cv: jax.Array, src: jax.Array,
                     dst: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Copy-on-write helper: duplicate physical block `src` into `dst`
    across every layer of the stacked pool ([L, NB, KH, bs, hd]).

    The radix prefix cache (runtime/prefix_cache.py) shares full blocks
    read-only; when a request's write frontier lands inside a shared,
    partially-matching block, the server copies it to a private block
    first so the cached entry is never mutated.  `src`/`dst` are scalar
    operands, so the jitted copy compiles once.
    """
    return (ck.at[:, dst].set(ck[:, src]),
            cv.at[:, dst].set(cv[:, src]))


# ----------------------------------------------------------------------
# fused paged kernels (kernels/paged_attention.py) + tp dispatch
# ----------------------------------------------------------------------

def _paged_kernel_call(fn, q, ck, cv, block_table, lens, k_scale,
                       v_scale, mesh, mesh_axis):
    """Run a paged kernel directly, or under shard_map over the kv-head
    axis when a mesh is given.  Heads shard over `mesh_axis` exactly
    when KH divides by the axis size (mirroring plans.ServingPlan);
    otherwise every operand is replicated and the kernel runs whole on
    each device.  Each device runs the same per-(slot, kv-head) grid
    cells the single-device kernel runs, so outputs are bitwise equal
    across tp degrees."""
    if mesh is None:
        return fn(q, ck, cv, block_table, lens, k_scale, v_scale)
    from jax.sharding import PartitionSpec as P
    tp = mesh.shape[mesh_axis]
    ax = mesh_axis if ck.shape[1] % tp == 0 else None
    qspec = P(None, None, ax, None)                 # [B, C, H, hd]
    pspec = P(None, ax)                             # [NB, KH, ...]
    in_specs = [qspec, pspec, pspec, P(None, None), P(None)]
    args = [q, ck, cv, block_table, lens]
    if k_scale is not None:
        in_specs += [pspec, pspec]
        args += [k_scale, v_scale]

    def inner(*a):
        return fn(a[0], a[1], a[2], a[3], a[4],
                  a[5] if len(a) > 5 else None,
                  a[6] if len(a) > 6 else None)

    return jax.shard_map(inner, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=qspec, check_vma=False)(*args)


def paged_decode_attention(q: jax.Array, ck: jax.Array, cv: jax.Array,
                           block_table: jax.Array, kv_len: jax.Array, *,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           mesh=None, mesh_axis: Optional[str] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Fused paged decode: block-table walk inside the Pallas kernel;
    same result as gather_paged_cache(+_fp8) -> decode_attention up to
    the tolerances of tests/test_paged_kernel.py."""
    from repro.kernels import paged_attention as pk

    def fn(q_, ck_, cv_, bt_, lens_, ks_, vs_):
        return pk.paged_decode(q_, ck_, cv_, bt_, lens_, k_scale=ks_,
                               v_scale=vs_, interpret=interpret)

    lens = jnp.broadcast_to(jnp.asarray(kv_len), (q.shape[0],)
                            ).astype(jnp.int32)
    return _paged_kernel_call(fn, q, ck, cv, block_table, lens,
                              k_scale, v_scale, mesh, mesh_axis)


def paged_chunk_attention(q: jax.Array, ck: jax.Array, cv: jax.Array,
                          block_table: jax.Array, pos: jax.Array, *,
                          k_scale: Optional[jax.Array] = None,
                          v_scale: Optional[jax.Array] = None,
                          mesh=None, mesh_axis: Optional[str] = None,
                          interpret: Optional[bool] = None) -> jax.Array:
    """Fused paged chunk attention; `pos` [B] is each slot's cache
    length before the chunk (same contract as chunk_attention with
    q_positions = pos[:, None] + arange(C))."""
    from repro.kernels import paged_attention as pk

    def fn(q_, ck_, cv_, bt_, pos_, ks_, vs_):
        return pk.paged_chunk(q_, ck_, cv_, bt_, pos_, k_scale=ks_,
                              v_scale=vs_, interpret=interpret)

    pos = jnp.broadcast_to(jnp.asarray(pos), (q.shape[0],)
                           ).astype(jnp.int32)
    return _paged_kernel_call(fn, q, ck, cv, block_table, pos,
                              k_scale, v_scale, mesh, mesh_axis)


def attention_flops(B: int, Sq: int, Sk: int, H: int, hd: int,
                    causal: bool) -> float:
    """Useful FLOPs of the score+value matmuls (for MODEL_FLOPS)."""
    pairs = Sq * Sk if not causal else Sq * Sk - Sq * (Sq - 1) / 2 \
        if Sq == Sk else Sq * Sk
    return 2 * 2 * B * H * pairs * hd

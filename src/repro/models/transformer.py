"""Decoder-only transformer LM (dense + MoE) with scan-over-layers.

Layer parameters are stacked on a leading "layers" axis and the forward
pass scans over them (MaxText-style), so compile time and HLO size are
O(1) in depth — essential for dry-running 40-62-layer models on a
512-device mesh.  Remat policy per config: none | dots | full.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.layout import Layout, with_layout_constraint

from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import sampling
from repro.models.common import (ParamSpec, apply_norm, apply_rope,
                                 chunked_softmax_xent, cross_entropy,
                                 norm_spec)
from repro.sharding.axes import constrain

Params = Dict[str, Any]


# ----------------------------------------------------------------------
# specs
# ----------------------------------------------------------------------

def layer_specs(cfg) -> Params:
    specs: Params = {
        "ln1": norm_spec(cfg, cfg.d_model),
        "ln2": norm_spec(cfg, cfg.d_model),
        "attn": attn.attn_specs(cfg),
    }
    if cfg.family == "moe":
        specs["moe"] = moe_mod.moe_specs(cfg)
    else:
        specs["mlp"] = mlp_mod.mlp_specs(cfg)
    return specs


def stack_specs(specs: Params, n: int, axis_name: str = "layers") -> Params:
    def add_dim(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, (axis_name,) + s.axes,
                         dtype=s.dtype, init=s.init, scale=s.scale)
    return jax.tree_util.tree_map(add_dim, specs,
                                  is_leaf=lambda x: isinstance(x, ParamSpec))


def transformer_specs(cfg) -> Params:
    specs: Params = {
        "embed": ParamSpec((cfg.padded_vocab_size, cfg.d_model),
                           ("vocab", "embed"), scale=0.02),
        "layers": stack_specs(layer_specs(cfg), cfg.num_layers),
        "final_norm": norm_spec(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((cfg.d_model, cfg.padded_vocab_size),
                                     ("embed", "vocab"))
    return specs


# ----------------------------------------------------------------------
# blocks
# ----------------------------------------------------------------------

def attn_block(cfg, p, x: jax.Array, positions: jax.Array) -> jax.Array:
    q, k, v = attn.qkv_project(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = attn.flash_attention(q, k, v, causal=True,
                             window=cfg.sliding_window)
    # tagged so remat="full_save_attn" keeps it instead of recomputing
    # the whole attention sweep in the backward pass
    o = checkpoint_name(o, "attn_out")
    return attn.out_project(p, o)


def layer_fwd(cfg, p, x: jax.Array, positions: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """Pre-norm block. Returns (x, aux_loss)."""
    h = apply_norm(cfg, x, p["ln1"])
    x = x + attn_block(cfg, p["attn"], h, positions)
    h = apply_norm(cfg, x, p["ln2"])
    if cfg.family == "moe":
        y, aux = moe_mod.moe_mlp(cfg, p["moe"], h)
    else:
        y, aux = mlp_mod.mlp(cfg, p["mlp"], h), jnp.zeros((), jnp.float32)
    return x + y, aux


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.remat == "full_save_attn":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out"))
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


# ----------------------------------------------------------------------
# forward / loss
# ----------------------------------------------------------------------

def embed_tokens(cfg, params, tokens: jax.Array,
                 prefix_embeds: Optional[jax.Array] = None) -> jax.Array:
    x = params["embed"].astype(jnp.bfloat16)[tokens]
    if prefix_embeds is not None:
        npfx = prefix_embeds.shape[1]
        x = jnp.concatenate(
            [prefix_embeds.astype(x.dtype), x[:, npfx:]], axis=1)
    return constrain(x, ("batch", "seq", "embed"))


def forward(cfg, params, tokens: jax.Array, *,
            prefix_embeds: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B,S] -> final hidden [B,S,d] (pre-unembed)."""
    B, S = tokens.shape
    x = embed_tokens(cfg, params, tokens, prefix_embeds)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def body(carry, lp):
        x, aux = carry
        x, a = _remat(cfg, functools.partial(layer_fwd, cfg))(lp, x, positions)
        return (x, aux + a), None

    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                           params["layers"])
    x = apply_norm(cfg, x, params["final_norm"])
    return x, aux


def unembed_matrix(cfg, params) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["unembed"]


def logits_fn(cfg, params, h: jax.Array) -> jax.Array:
    w = unembed_matrix(cfg, params).astype(h.dtype)
    out = constrain(h @ w, ("batch", "seq", "vocab"))
    # drop the TP-padding columns (never valid tokens)
    if cfg.padded_vocab_size != cfg.vocab_size:
        out = out[..., : cfg.vocab_size]
    return out


def loss_fn(cfg, params, batch: Dict[str, jax.Array]) -> jax.Array:
    """Causal-LM loss; big vocabs go through the chunked-CE scan."""
    h, aux = forward(cfg, params, batch["tokens"],
                     prefix_embeds=batch.get("prefix_embeds"))
    B, S, d = h.shape
    labels = batch["labels"]
    w = unembed_matrix(cfg, params).astype(h.dtype)
    if cfg.vocab_size * S * B > 2 ** 28:       # big-vocab: chunk token dim
        ce = chunked_softmax_xent(h.reshape(B * S, d), w,
                                  labels.reshape(B * S))
    else:
        logits = constrain(h @ w, ("batch", "seq", "vocab"))
        ce = cross_entropy(logits, labels)
    return ce + cfg.aux_loss_weight * aux


# ----------------------------------------------------------------------
# serving: prefill + single-token decode over a KV cache
# ----------------------------------------------------------------------

def serving_det_groups(cfg) -> Tuple[int, int]:
    """(attention, mlp) group counts for the order-deterministic
    grouped reductions of the serving forward (out_project / mlp with
    ``groups=``): the largest power of two ≤ 16 dividing the head count
    / hidden width.  Any tensor-parallel degree dividing these groups
    produces bitwise-identical serving outputs to tp=1, because the
    only cross-shard float reductions run through
    ``common.fixed_tree_sum`` whose addition order is fixed by the
    group count alone."""
    def pow2_div(n: int, cap: int = 16) -> int:
        g = 1
        while g < cap and n % (g * 2) == 0:
            g *= 2
        return g
    return pow2_div(cfg.num_heads), pow2_div(cfg.d_ff)


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16, *,
               paged: bool = False, block_size: int = 16,
               num_blocks: Optional[int] = None,
               sharding=None, fp8_kv: bool = False) -> Params:
    """Contiguous cache [L, B, T, KH, hd] or, with ``paged=True``, a
    shared block pool [L, num_blocks, KH, block_size, hd] addressed
    through a per-slot block table (see attention.gather_paged_cache).
    The paged default pool matches the contiguous capacity
    (batch * ceil(max_len / block_size) blocks); servers pass a smaller
    pool to actually share memory across slots.  ``sharding`` (a
    NamedSharding; sharding/plans.ServingPlan.cache_sharding) lays the
    cache leaves out over a serving mesh at init instead of on the
    default device.  ``fp8_kv`` (paged only) stores
    e4m3 codes + per-row f32 scales (attention.init_paged_kv_cache)."""
    L = cfg.num_layers
    KH, hd = cfg.num_kv_heads, cfg.head_dim
    if paged:
        if num_blocks is None:
            num_blocks = batch * -(-max_len // block_size)
        cache = attn.init_paged_kv_cache(num_blocks, block_size, KH, hd,
                                         layers=L, dtype=dtype,
                                         fp8=fp8_kv)
    else:
        if fp8_kv:
            raise NotImplementedError(
                "fp8_kv requires the paged cache layout")
        cache = {
            "k": jnp.zeros((L, batch, max_len, KH, hd), dtype),
            "v": jnp.zeros((L, batch, max_len, KH, hd), dtype),
        }
    if sharding is not None:
        cache = jax.device_put(cache, sharding)
    return cache


def _cache_attend(q, k1, v1, cl, pos, positions, block_table, *,
                  decode: bool, kernel: bool, mesh, mesh_axis):
    """Write a window's k/v into one layer's cache slice `cl` (dict of
    leaves: contiguous {"k","v"}, paged bf16 {"k","v"}, or paged fp8
    {"k","v","k_scale","v_scale"} — detected by key) and attend over
    the result.  ``kernel=True`` routes the paged read through the
    fused Pallas block-table kernels (attention.paged_*_attention,
    within tolerance of the gather path); ``mesh`` runs them under
    shard_map over the kv-head axis.  Returns (o, new_cl)."""
    if block_table is None:
        ck, cv = attn.update_cache(cl["k"], cl["v"], k1, v1, pos)
        o = (attn.decode_attention(q, ck, cv, jnp.asarray(pos) + 1)
             if decode else attn.chunk_attention(q, ck, cv, positions))
        return o, {"k": ck, "v": cv}
    if "k_scale" in cl:
        cl = attn.update_paged_cache_fp8(cl, k1, v1, pos, block_table)
        scales = (cl["k_scale"], cl["v_scale"])
    else:
        ck, cv = attn.update_paged_cache(cl["k"], cl["v"], k1, v1, pos,
                                         block_table)
        cl = {"k": ck, "v": cv}
        scales = (None, None)
    if kernel:
        if decode:
            o = attn.paged_decode_attention(
                q, cl["k"], cl["v"], block_table, jnp.asarray(pos) + 1,
                k_scale=scales[0], v_scale=scales[1], mesh=mesh,
                mesh_axis=mesh_axis)
        else:
            o = attn.paged_chunk_attention(
                q, cl["k"], cl["v"], block_table, pos,
                k_scale=scales[0], v_scale=scales[1], mesh=mesh,
                mesh_axis=mesh_axis)
    else:
        if scales[0] is None:
            kg, vg = attn.gather_paged_cache(cl["k"], cl["v"],
                                             block_table)
        else:
            kg, vg = attn.gather_paged_cache_fp8(cl, block_table,
                                                 out_dtype=q.dtype)
        o = (attn.decode_attention(q, kg, vg, jnp.asarray(pos) + 1)
             if decode else attn.chunk_attention(q, kg, vg, positions))
    return o, cl


def _stored_layout(tree):
    """Pin each layer's weight slices to the row-major layout the
    stacked weights are stored in.  Where a program runs the layer scan
    more than once (the decode span), the TPU compiler is otherwise free
    to pick another layout for the loop-invariant stack — the one its
    small-M decode dots prefer — and copies every stacked weight once
    up front: 6.4 GB more for Yi-6B, past one v5e's HBM.  Pinned, it
    relayouts one layer's slice at a time, as a single decode step
    does."""
    return jax.tree_util.tree_map(
        lambda x: with_layout_constraint(x, Layout(tuple(range(x.ndim)))),
        tree)


def _serving_scan(cfg, params, cache, x, pos, positions, block_table, *,
                  decode: bool, kernel: bool, quant, mesh, mesh_axis):
    """Scan layers (+ their cache slices, + optionally their
    pre-quantized fp8 weight slices) for the serving steps.  The cache
    travels as a pytree dict through scan's xs, so the same scan serves
    the contiguous, paged-bf16 and paged-fp8 layouts."""
    ga, gm = serving_det_groups(cfg)

    def body(x, inp):
        if quant is None:
            lp, cl = inp
            qlp = None
        else:
            lp, qlp, cl = inp
            qlp = _stored_layout(qlp)
        lp = _stored_layout(lp)
        h = apply_norm(cfg, x, lp["ln1"])
        if qlp is None:
            q, k1, v1 = attn.qkv_project(cfg, lp["attn"], h)
        else:
            q, k1, v1 = attn.qkv_project_fp8(cfg, lp["attn"],
                                             qlp["attn"], h)
        q = apply_rope(q, positions, cfg.rope_theta)
        k1 = apply_rope(k1, positions, cfg.rope_theta)
        o, cl = _cache_attend(q, k1, v1, cl, pos, positions, block_table,
                              decode=decode, kernel=kernel, mesh=mesh,
                              mesh_axis=mesh_axis)
        if qlp is None:
            x = x + attn.out_project(lp["attn"], o, groups=ga)
        else:
            x = x + attn.out_project_fp8(lp["attn"], qlp["attn"], o)
        h = apply_norm(cfg, x, lp["ln2"])
        if cfg.family == "moe":
            y, _ = moe_mod.moe_mlp(cfg, lp["moe"], h)
        elif qlp is None:
            y = mlp_mod.mlp(cfg, lp["mlp"], h, groups=gm)
        else:
            y = mlp_mod.mlp_fp8(cfg, lp["mlp"], qlp["mlp"], h)
        return x + y, cl

    xs = ((params["layers"], cache) if quant is None
          else (params["layers"], quant["layers"], cache))
    x, new_cache = lax.scan(body, x, xs)
    x = apply_norm(cfg, x, params["final_norm"])
    # trace hook: every serving program's jaxpr must carry this tag —
    # the static analyzer (repro.analysis, JX006) uses it to prove a
    # traced work unit actually went through the serving forward
    x = checkpoint_name(x, "serving_hot_path")
    return x, new_cache


def decode_step(cfg, params, cache: Params, token: jax.Array,
                pos: jax.Array, block_table: Optional[jax.Array] = None,
                *, kernel: bool = False, quant: Optional[Params] = None,
                mesh=None, mesh_axis: Optional[str] = None
                ) -> Tuple[jax.Array, Params]:
    """One decode step. token [B], pos scalar int32 (current length).

    Scans layers together with their cache slices; each layer attends to
    cache[:pos+1] after inserting its new k/v at `pos`.  With
    ``block_table`` the cache is a paged block pool and the read/write
    paths go through the table (attention.update_paged_cache /
    gather_paged_cache); outputs are bit-identical to a contiguous
    cache of the same length (the same attention program, and masked
    positions add exact zeros).  Attention contracts on the MXU with
    float32 accumulation (attention.decode_attention), so a program of
    another shape (a chunk step) agrees with this one to the order of
    its float32 sums, not bit for bit.  ``kernel=True`` reads through
    the fused Pallas block-table kernel instead of materializing the
    gathered view (within tolerance of it); ``quant``
    (te/linear.quantize_serving_params output) routes the linears
    through pre-quantized fp8 weights.
    """
    B = token.shape[0]
    x = params["embed"].astype(jnp.bfloat16)[token][:, None, :]  # [B,1,d]
    x = constrain(x, ("batch", None, "embed"))
    pos = jnp.asarray(pos)
    positions = (pos[:, None] if pos.ndim == 1
                 else jnp.full((B, 1), pos, jnp.int32))
    x, new_cache = _serving_scan(cfg, params, cache, x, pos, positions,
                                 block_table, decode=True, kernel=kernel,
                                 quant=quant, mesh=mesh,
                                 mesh_axis=mesh_axis)
    logits = logits_fn(cfg, params, x)[:, 0]
    return logits, new_cache


def _chunk_fwd(cfg, params, cache: Params, tokens: jax.Array,
               pos: jax.Array, block_table: Optional[jax.Array], *,
               kernel: bool = False, quant: Optional[Params] = None,
               mesh=None, mesh_axis: Optional[str] = None
               ) -> Tuple[jax.Array, Params]:
    """Shared serving forward over a [B, C] token window written into
    the KV cache at [pos, pos+C): the body of both `chunk_step` (which
    reads out the last valid row) and `verify_step` (which reads out
    every row).  Returns (final hidden [B, C, d], cache)."""
    B, C = tokens.shape
    x = params["embed"].astype(jnp.bfloat16)[tokens]          # [B,C,d]
    x = constrain(x, ("batch", None, "embed"))
    positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    return _serving_scan(cfg, params, cache, x, pos, positions,
                         block_table, decode=False, kernel=kernel,
                         quant=quant, mesh=mesh, mesh_axis=mesh_axis)


def chunk_step(cfg, params, cache: Params, tokens: jax.Array,
               pos: jax.Array, n_tokens: jax.Array,
               block_table: Optional[jax.Array] = None, *,
               kernel: bool = False, quant: Optional[Params] = None,
               mesh=None, mesh_axis: Optional[str] = None
               ) -> Tuple[jax.Array, Params]:
    """One chunked-prefill/decode step for a batch of server slots.

    tokens [B,C] int32 — per slot, the next `n_tokens[b]` tokens of its
    request (a C-token prefill chunk, a single decode token at row 0, or
    nothing for an idle slot; rows past n_tokens[b] are padding).
    pos [B] int32 — each slot's current cache length; the chunk's k/v is
    written at cache positions [pos, pos+C) (padding rows included —
    they sit beyond the valid frontier, are never attended by valid
    queries, and the next step's write starts at the new frontier so
    they are overwritten before becoming visible).
    n_tokens [B] int32 in [0, C].
    block_table [B, max_blocks] int32 (optional) — cache is a paged
    block pool; reads/writes gather/scatter through the table (padding
    rows whose virtual block is unallocated are dropped instead of
    overwritten later).  The table has a fixed shape, so the paged
    program compiles once too.

    Returns (logits [B, vocab] at each slot's last valid row, cache).
    Shapes are fixed by (B, C) only, so a server compiles this once no
    matter how prompt lengths are distributed.
    """
    B, C = tokens.shape
    x, cache = _chunk_fwd(cfg, params, cache, tokens, pos, block_table,
                          kernel=kernel, quant=quant, mesh=mesh,
                          mesh_axis=mesh_axis)
    last = jnp.clip(n_tokens - 1, 0, C - 1)                   # [B]
    h_last = jnp.take_along_axis(x, last[:, None, None], axis=1)  # [B,1,d]
    logits = logits_fn(cfg, params, h_last)[:, 0]
    return logits, cache


def verify_step(cfg, params, cache: Params, tokens: jax.Array,
                pos: jax.Array, block_table: Optional[jax.Array] = None,
                *, kernel: bool = False, quant: Optional[Params] = None,
                mesh=None, mesh_axis: Optional[str] = None,
                sample=None) -> Tuple[jax.Array, Params]:
    """Speculative-decode verify: score a [B, C] window (row 0 = each
    slot's current token, rows 1..C-1 = draft tokens) in ONE fixed-shape
    call and return the greedy argmax at EVERY row, not just the last.

    Exactly the `chunk_step` program shape — same KV write path
    (`update_cache` / `update_paged_cache` at [pos, pos+C)), same
    cache-aware causal read (`chunk_attention` over the gathered paged
    view) — so a server running it compiles exactly one extra program
    and row j's prediction is what a one-token-at-a-time decode of the
    same prefix would produce, up to the order of the attention's
    float32 sums (the two programs contract different shapes).  The
    caller accepts
    the longest draft prefix matching the argmax chain and rolls its
    frontier back over the rejected suffix: the rejected rows' KV
    writes land beyond the rolled-back frontier, where the position
    masks never read and the next window's writes overwrite (or, past
    the paged block table's allocated entries, were dropped at scatter
    time — see attention.update_paged_cache).

    ``sample=(temp, top_k, top_p, seed)`` (each ``[B]``) swaps the
    per-row argmax for the stochastic sample head
    (models/sampling.sample_tokens): row j's token is drawn from the
    fp32 softmax of its logits with the key folded from
    ``(seed[b], pos[b] + 1 + j)`` — the same position key the span
    loop would use emitting that token one at a time, which is what
    keeps spec-decode sampling exact-match-given-seed.  Greedy rows
    (``temp<=0`` or ``top_k==1``) stay bit-identical to the argmax
    chain.  ``sample=None`` keeps the historical greedy head.

    Returns (preds [B, C] int32 next-token ids, cache).
    """
    x, cache = _chunk_fwd(cfg, params, cache, tokens, pos, block_table,
                          kernel=kernel, quant=quant, mesh=mesh,
                          mesh_axis=mesh_axis)
    logits = logits_fn(cfg, params, x)                        # [B,C,V]
    if sample is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache
    temp, top_k, top_p, seed = sample
    C = tokens.shape[1]
    index = pos[:, None] + 1 + jnp.arange(C, dtype=jnp.int32)  # [B,C]
    preds = sampling.sample_tokens(logits, temp, top_k, top_p, seed,
                                   index)
    return preds, cache


def prefill(cfg, params, tokens: jax.Array, cache: Params,
            *, prefix_embeds: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Params]:
    """Run the full prompt, filling the cache. Returns (last logits, cache)."""
    B, S = tokens.shape
    x = embed_tokens(cfg, params, tokens, prefix_embeds)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def body(x, inp):
        lp, ck, cv = inp
        h = apply_norm(cfg, x, lp["ln1"])
        q, k, v = attn.qkv_project(cfg, lp["attn"], h)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, 0, 0, 0))
        cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, 0, 0, 0))
        o = attn.flash_attention(q, k, v, causal=True,
                                 window=cfg.sliding_window)
        x = x + attn.out_project(lp["attn"], o)
        h = apply_norm(cfg, x, lp["ln2"])
        if cfg.family == "moe":
            y, _ = moe_mod.moe_mlp(cfg, lp["moe"], h)
        else:
            y = mlp_mod.mlp(cfg, lp["mlp"], h)
        return x + y, (ck, cv)

    x, (new_k, new_v) = lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    x = apply_norm(cfg, x, params["final_norm"])
    logits = logits_fn(cfg, params, x[:, -1:])[:, 0]
    return logits, {"k": new_k, "v": new_v}

"""Unified model API: every arch family behind one dispatch surface.

Used by launch/{dryrun,train,serve}.py, tests and benchmarks:

    param_shapes / init / abstract / pspecs     parameters
    loss_fn                                     training objective
    init_cache / prefill / decode_step          serving
    chunk_step                                  chunked-prefill serving
    verify_step                                 speculative-decode verify
    SamplingParams / sample_tokens              stochastic sample head
    compile_count                               jit program-cache probe
    input_specs / make_batch                    shape cells (dry-run / smoke)
    model_flops                                 6ND-style accounting
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import common, encdec, hybrid, ssm_lm, transformer
from repro.models.common import ParamSpec
from repro.models.sampling import (GREEDY, SamplingParams,  # noqa: F401
                                   ks_two_sample, sample_tokens)

Params = Dict[str, Any]

_TRANSFORMER_FAMILIES = ("dense", "moe", "vlm")


def param_shapes(cfg: ModelConfig) -> Params:
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer.transformer_specs(cfg)
    if cfg.family == "ssm":
        return ssm_lm.ssm_lm_specs(cfg)
    if cfg.family == "hybrid":
        return hybrid.hybrid_specs(cfg)
    if cfg.family == "encdec":
        return encdec.encdec_specs(cfg)
    raise ValueError(f"unknown family {cfg.family}")


def init(cfg: ModelConfig, rng: jax.Array, *, dtype=None,
         shardings=None) -> Params:
    """Random weights from `rng` (common.init_params): float32 master
    weights by default (training); serving passes ``dtype=cfg.dtype``
    and, under a mesh, its ServingPlan.param_shardings."""
    return common.init_params(param_shapes(cfg), rng, dtype=dtype,
                              shardings=shardings)


def abstract(cfg: ModelConfig) -> Params:
    return common.abstract_params(param_shapes(cfg))


def pspecs(cfg: ModelConfig, rules: Dict[str, Optional[str]],
           mesh_sizes: Optional[Dict[str, int]] = None):
    return common.partition_specs(param_shapes(cfg), rules, mesh_sizes)


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array]
            ) -> jax.Array:
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer.loss_fn(cfg, params, batch)
    if cfg.family == "ssm":
        return ssm_lm.loss_fn(cfg, params, batch)
    if cfg.family == "hybrid":
        return hybrid.loss_fn(cfg, params, batch)
    if cfg.family == "encdec":
        return encdec.loss_fn(cfg, params, batch)
    raise ValueError(cfg.family)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, *, paged: bool = False,
               block_size: int = 16,
               num_blocks: Optional[int] = None,
               sharding=None, fp8_kv: bool = False) -> Params:
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer.init_cache(cfg, batch, max_len, dtype,
                                      paged=paged, block_size=block_size,
                                      num_blocks=num_blocks,
                                      sharding=sharding, fp8_kv=fp8_kv)
    if paged or sharding is not None or fp8_kv:
        raise NotImplementedError(
            f"paged/sharded KV cache is transformer-only for now "
            f"(family {cfg.family})")
    if cfg.family == "ssm":
        return ssm_lm.init_cache(cfg, batch, max_len, dtype)
    if cfg.family == "hybrid":
        return hybrid.init_cache(cfg, batch, max_len, dtype)
    if cfg.family == "encdec":
        return encdec.init_cache(cfg, batch, max_len, dtype)
    raise ValueError(cfg.family)


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jax.Array, pos: jax.Array,
                block_table: Optional[jax.Array] = None, **fwd_kw
                ) -> Tuple[jax.Array, Params]:
    """``fwd_kw`` (transformer families only): kernel= routes paged
    reads through the fused Pallas block-table kernels, quant= supplies
    pre-quantized fp8 serving weights, mesh=/mesh_axis= run the kernel
    under shard_map (see transformer.decode_step)."""
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer.decode_step(cfg, params, cache, token, pos,
                                       block_table, **fwd_kw)
    if fwd_kw:
        raise NotImplementedError(
            f"kernel/fp8 serving options are transformer-only (family "
            f"{cfg.family})")
    if block_table is not None:
        raise NotImplementedError(
            f"paged KV cache is transformer-only for now (family "
            f"{cfg.family})")
    if cfg.family == "ssm":
        return ssm_lm.decode_step(cfg, params, cache, token, pos)
    if cfg.family == "hybrid":
        return hybrid.decode_step(cfg, params, cache, token, pos)
    if cfg.family == "encdec":
        return encdec.decode_step(cfg, params, cache, token, pos)
    raise ValueError(cfg.family)


def chunk_step(cfg: ModelConfig, params: Params, cache: Params,
               tokens: jax.Array, pos: jax.Array, n_tokens: jax.Array,
               block_table: Optional[jax.Array] = None, **fwd_kw
               ) -> Tuple[jax.Array, Params]:
    """Chunk-write serving step: per slot, write `n_tokens[b]` of the
    C-wide `tokens[b]` into the KV cache at `pos[b]` and return logits
    at each slot's last valid row.  Fixed (B, C) shape -> one compile
    regardless of the prompt-length distribution (runtime/server.py).
    With `block_table` the cache is the paged block pool of
    `init_cache(..., paged=True)`."""
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer.chunk_step(cfg, params, cache, tokens, pos,
                                      n_tokens, block_table, **fwd_kw)
    raise NotImplementedError(
        f"chunked prefill is transformer-only for now (family "
        f"{cfg.family}); use prefill/decode_step")


def verify_step(cfg: ModelConfig, params: Params, cache: Params,
                tokens: jax.Array, pos: jax.Array,
                block_table: Optional[jax.Array] = None, **fwd_kw
                ) -> Tuple[jax.Array, Params]:
    """Speculative-decode verify: score a [B, C] window of (current
    token + C-1 drafts) per slot and return the next-token id at every
    row (`chunk_step` returns only the last valid row's logits).  One
    fixed-shape program — the serving runtime's spec-decode path
    (runtime/spec_decode.py) compiles it exactly once.  Pass
    ``sample=(temp, top_k, top_p, seed)`` through ``fwd_kw`` to swap
    the greedy argmax chain for the stochastic sample head (see
    transformer.verify_step)."""
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer.verify_step(cfg, params, cache, tokens, pos,
                                       block_table, **fwd_kw)
    raise NotImplementedError(
        f"speculative decoding is transformer-only for now (family "
        f"{cfg.family}); use prefill/decode_step")


def cow_copy_block(cfg: ModelConfig, cache: Params, src, dst) -> Params:
    """Copy physical pool block `src` to `dst` in a paged KV cache
    (all layers; scalar operands, one compile).  Used by the serving
    runtime's copy-on-write path when a request extends into a block
    shared through the radix prefix cache."""
    if cfg.family not in _TRANSFORMER_FAMILIES:
        raise NotImplementedError(
            f"paged KV cache is transformer-only for now (family "
            f"{cfg.family})")
    # tree_map so the fp8 layout's scale leaves ride along with k/v
    return jax.tree_util.tree_map(
        lambda leaf: leaf.at[:, dst].set(leaf[:, src]), cache)


def compile_count(fn) -> int:
    """Number of programs a jitted callable has compiled (-1 unknown).

    Probes the jit program cache (`_cache_size` of a jax.jit
    wrapper); servers expose this so tests/benchmarks can assert O(1)
    compilation.
    """
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return -1
    try:
        return int(probe())
    except Exception:  # noqa: BLE001 - diagnostics only, never raise
        return -1


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            cache: Params) -> Tuple[jax.Array, Params]:
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer.prefill(cfg, params, batch["tokens"], cache,
                                   prefix_embeds=batch.get("prefix_embeds"))
    if cfg.family == "ssm":
        return ssm_lm.prefill(cfg, params, batch["tokens"], cache)
    if cfg.family == "hybrid":
        # hybrid prefill = forward pass; state rebuilt from decode loop in
        # serving; for benchmarking we reuse the training forward.
        h, _ = hybrid.forward(cfg, params, batch["tokens"])
        logits = hybrid.logits_fn(cfg, params, h[:, -1:])[:, 0]
        return logits, cache
    if cfg.family == "encdec":
        return encdec.prefill(cfg, params, batch["frames"], cache)
    raise ValueError(cfg.family)


# ----------------------------------------------------------------------
# shape cells: abstract input specs (dry-run) and concrete batches (smoke)
# ----------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        if cfg.family == "encdec":
            # seq_len = source frames; decoder runs at its max target len
            T = cfg.max_target_len
            return {
                "frames": jax.ShapeDtypeStruct(
                    (B, min(S, cfg.max_source_len), cfg.d_model),
                    jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((B, T), i32),
                "labels": jax.ShapeDtypeStruct((B, T), i32),
            }
        out = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
        if cfg.family == "vlm" and cfg.num_prefix_tokens:
            out["prefix_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.num_prefix_tokens, cfg.d_model), jnp.bfloat16)
        return out
    if shape.kind == "prefill":
        if cfg.family == "encdec":
            return {"frames": jax.ShapeDtypeStruct(
                (B, min(S, cfg.max_source_len), cfg.d_model), jnp.bfloat16)}
        out = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.family == "vlm" and cfg.num_prefix_tokens:
            out["prefix_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.num_prefix_tokens, cfg.d_model), jnp.bfloat16)
        return out
    # decode: one new token against a seq_len-deep cache
    return {
        "token": jax.ShapeDtypeStruct((B,), i32),
        "pos": jax.ShapeDtypeStruct((), i32),
    }


def cache_specs(cfg: ModelConfig, shape: ShapeConfig) -> Params:
    cache = jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
    return cache


def make_batch(cfg: ModelConfig, shape: ShapeConfig, rng: jax.Array
               ) -> Dict[str, jax.Array]:
    """Concrete random batch matching input_specs (smoke tests/examples)."""
    specs = input_specs(cfg, shape)
    out = {}
    for k, s in specs.items():
        rng, sub = jax.random.split(rng)
        if s.dtype == jnp.int32:
            hi = cfg.vocab_size if k in ("tokens", "labels", "token") else max(
                shape.seq_len - 1, 1)
            out[k] = jax.random.randint(sub, s.shape, 0, hi, jnp.int32) \
                if s.shape else jnp.asarray(min(shape.seq_len - 1, 1), jnp.int32)
        else:
            out[k] = jax.random.normal(sub, s.shape, jnp.float32
                                       ).astype(s.dtype)
    return out


# ----------------------------------------------------------------------
# FLOP accounting
# ----------------------------------------------------------------------

def active_param_count(cfg: ModelConfig) -> float:
    """N (dense) or N_active (MoE: experts counted at top_k/E)."""
    total = float(common.count_params(param_shapes(cfg)))
    if cfg.family != "moe":
        return total
    expert = common.count_params(
        {k: v for k, v in transformer.layer_specs(cfg)["moe"].items()
         if k != "router"})
    expert_total = float(expert * cfg.num_layers)
    frac = cfg.top_k / cfg.num_experts
    return total - expert_total * (1.0 - frac)


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS per the assignment: 6*N*D train, 2*N*D inference."""
    n_active = active_param_count(cfg)
    if shape.kind == "train":
        if cfg.family == "encdec":
            tokens = shape.global_batch * (
                min(shape.seq_len, cfg.max_source_len) + cfg.max_target_len)
        else:
            tokens = shape.tokens
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * (
            min(shape.seq_len, cfg.max_source_len)
            if cfg.family == "encdec" else shape.seq_len)
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch

"""Fused TE modules: LayerNormMLP and TransformerLayer (paper §III-C-2).

te_layernorm_mlp fuses norm + MLP so the norm->linear boundary stays in
FP8 (the paper's point about eliminating conversion overhead between
fused operators).  te_transformer_layer assembles a full block the way
TE does: attention stays in bf16 flash attention (the paper notes TE's
DotProductAttention is *not* FP8), while every linear runs through
te_linear.  FP8 state for all constituent linears is one pytree.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.common import ParamSpec, apply_norm, apply_rope, norm_spec
from repro.te import fp8
from repro.te.fp8 import DelayedScalingRecipe
from repro.te.linear import init_state, te_linear, te_linear_specs

Params = Dict[str, Any]


# ----------------------------------------------------------------------
# LayerNormMLP
# ----------------------------------------------------------------------

def layernorm_mlp_specs(cfg) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    specs = {
        "ln": norm_spec(cfg, d),
        "up": te_linear_specs(d, f, bias=cfg.mlp_bias),
        "down": te_linear_specs(f, d, bias=cfg.mlp_bias,
                                axes=("mlp", "embed")),
    }
    if cfg.activation == "swiglu":
        specs["gate"] = te_linear_specs(d, f)
    return specs


def layernorm_mlp_state(cfg, recipe: DelayedScalingRecipe) -> Params:
    st = {"up": init_state(recipe), "down": init_state(recipe)}
    if cfg.activation == "swiglu":
        st["gate"] = init_state(recipe)
    return st


def te_layernorm_mlp(cfg, params: Params, state: Params, x: jax.Array,
                     recipe: DelayedScalingRecipe = DelayedScalingRecipe(),
                     ) -> Tuple[jax.Array, Params]:
    h = apply_norm(cfg, x, params["ln"])
    up, st_up = te_linear(params["up"], state["up"], h, recipe)
    new_state = {"up": st_up}
    if cfg.activation == "swiglu":
        gate, st_g = te_linear(params["gate"], state["gate"], h, recipe)
        new_state["gate"] = st_g
        act = jax.nn.silu(gate) * up
    else:
        act = jax.nn.gelu(up)
    y, st_down = te_linear(params["down"], state["down"], act, recipe)
    new_state["down"] = st_down
    return y, new_state


# ----------------------------------------------------------------------
# TransformerLayer
# ----------------------------------------------------------------------

def transformer_layer_specs(cfg) -> Params:
    d, H, KH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "ln1": norm_spec(cfg, d),
        "wq": te_linear_specs(d, H * hd, axes=("embed", "heads")),
        "wk": te_linear_specs(d, KH * hd, axes=("embed", "kv_heads")),
        "wv": te_linear_specs(d, KH * hd, axes=("embed", "kv_heads")),
        "wo": te_linear_specs(H * hd, d, axes=("heads", "embed")),
        "mlp": layernorm_mlp_specs(cfg),
    }


def transformer_layer_state(cfg, recipe: DelayedScalingRecipe) -> Params:
    return {
        "wq": init_state(recipe), "wk": init_state(recipe),
        "wv": init_state(recipe), "wo": init_state(recipe),
        "mlp": layernorm_mlp_state(cfg, recipe),
    }


def te_transformer_layer(cfg, params: Params, state: Params, x: jax.Array,
                         recipe: DelayedScalingRecipe = DelayedScalingRecipe(),
                         ) -> Tuple[jax.Array, Params]:
    """One encoder block, FP8 linears + bf16 flash attention."""
    B, S, d = x.shape
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h = apply_norm(cfg, x, params["ln1"])
    q, st_q = te_linear(params["wq"], state["wq"], h, recipe)
    k, st_k = te_linear(params["wk"], state["wk"], h, recipe)
    v, st_v = te_linear(params["wv"], state["wv"], h, recipe)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KH, hd)
    v = v.reshape(B, S, KH, hd)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = attn.flash_attention(q, k, v, causal=True)      # bf16, like TE
    o, st_o = te_linear(params["wo"], state["wo"], o.reshape(B, S, H * hd),
                        recipe)
    x = x + o
    y, st_mlp = te_layernorm_mlp(cfg, params["mlp"], state["mlp"], x, recipe)
    return x + y, {"wq": st_q, "wk": st_k, "wv": st_v, "wo": st_o,
                   "mlp": st_mlp}

"""Dense MLP (SwiGLU / GELU), optionally routed through the TE fp8 path."""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec, activation, fixed_tree_sum
from repro.sharding.axes import constrain


def mlp_specs(cfg, d_model: Optional[int] = None,
              d_ff: Optional[int] = None) -> Dict[str, ParamSpec]:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    specs = {
        "w_up": ParamSpec((d, f), ("embed", "mlp")),
        "w_down": ParamSpec((f, d), ("mlp", "embed")),
    }
    if cfg.activation == "swiglu":
        specs["w_gate"] = ParamSpec((d, f), ("embed", "mlp"))
    if cfg.mlp_bias:
        specs["b_up"] = ParamSpec((f,), ("mlp",), init="zeros")
        specs["b_down"] = ParamSpec((d,), ("embed",), init="zeros")
    return specs


def mlp(cfg, p, x: jax.Array, *, groups: int = 0) -> jax.Array:
    """x: [..., d] -> [..., d].

    ``groups > 1`` (serving, [B,S,d] inputs only) restructures the
    row-parallel w_down contraction as per-group fp32 partials reduced
    by a fixed halving tree — the same order-deterministic reduction as
    attention.out_project, so tensor-parallel sharding of the hidden
    dim over any tp dividing `groups` is bitwise-identical to tp=1.
    """
    dt = x.dtype
    up = x @ p["w_up"].astype(dt)
    if cfg.mlp_bias:
        up = up + p["b_up"].astype(dt)
    gate = x @ p["w_gate"].astype(dt) if "w_gate" in p else None
    h = activation(cfg, up, gate)
    h = constrain(h, ("batch", None, "mlp"))
    if groups > 1 and h.ndim == 3:
        B, S, f = h.shape
        hg = h.reshape(B, S, groups, f // groups)
        wg = p["w_down"].astype(dt).reshape(groups, f // groups, -1)
        parts = jnp.einsum("bsgf,gfd->gbsd", hg, wg,
                           preferred_element_type=jnp.float32)
        y = fixed_tree_sum(parts, tag="xshard_mlp_down").astype(dt)
    else:
        y = h @ p["w_down"].astype(dt)
    if cfg.mlp_bias:
        y = y + p["b_down"].astype(dt)
    return constrain(y, ("batch", "seq", "embed"))


def mlp_fp8(cfg, p, q8, x: jax.Array) -> jax.Array:
    """fp8 serving variant of `mlp`: matmuls through weights
    pre-quantized by te/linear.quantize_serving_params with per-call
    activation scales.  Biases stay bf16 in `p`.  tp=1 serving only,
    so there is no grouped-reduction path here."""
    from repro.te import linear as te_linear
    dt = x.dtype
    up = te_linear.fp8_serving_dot(x, q8["w_up"])
    if cfg.mlp_bias:
        up = up + p["b_up"].astype(dt)
    gate = te_linear.fp8_serving_dot(x, q8["w_gate"]) \
        if "w_gate" in q8 else None
    h = activation(cfg, up, gate)
    h = constrain(h, ("batch", None, "mlp"))
    y = te_linear.fp8_serving_dot(h, q8["w_down"])
    if cfg.mlp_bias:
        y = y + p["b_down"].astype(dt)
    return constrain(y, ("batch", "seq", "embed"))


def mlp_flops(d: int, f: int, gated: bool) -> float:
    """Matmul FLOPs per token, fwd only."""
    n_mats = 3 if gated else 2
    return 2.0 * n_mats * d * f

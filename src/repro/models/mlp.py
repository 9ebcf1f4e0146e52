"""Dense MLP (SwiGLU / GELU), optionally routed through the TE fp8 path."""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.models.common import ParamSpec, activation
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


def mlp(cfg, p, x: jax.Array, *, fp32_reduce: bool = False) -> jax.Array:
    """x: [..., d] -> [..., d].

    ``fp32_reduce`` (the serving forward, transformer._serving_scan):
    the row-parallel w_down contraction is one dot whose output stays
    fp32 until one cast to the activation dtype (tagged for the
    analyzer's JX004/JX006), so under a tensor-parallel mesh the psum of
    the hidden-dim shards runs in fp32.  Training keeps the dot's bf16
    output: remat="dots" saves it as a residual, and a tp plan
    all-reduces it, at half the bytes of fp32.
    """
    dt = x.dtype
    up = x @ p["w_up"].astype(dt)
    if cfg.mlp_bias:
        up = up + p["b_up"].astype(dt)
    gate = x @ p["w_gate"].astype(dt) if "w_gate" in p else None
    h = activation(cfg, up, gate)
    h = constrain(h, ("batch", None, "mlp"))
    if fp32_reduce:
        y = jnp.einsum("...f,fd->...d", h, p["w_down"].astype(dt),
                       preferred_element_type=jnp.float32)
        y = checkpoint_name(y, "xshard_mlp_down").astype(dt)
    else:
        y = h @ p["w_down"].astype(dt)
    if cfg.mlp_bias:
        y = y + p["b_down"].astype(dt)
    return constrain(y, ("batch", "seq", "embed"))


def mlp_fp8(cfg, p, q8, x: jax.Array) -> jax.Array:
    """fp8 serving variant of `mlp`: matmuls through weights
    pre-quantized by te/linear.quantize_serving_params with per-call
    activation scales.  Biases stay bf16 in `p`.  tp=1 serving only."""
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

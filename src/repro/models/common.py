"""Shared model substrate: param specs, norms, RoPE, embeddings, loss.

Single source of truth per model: ``param_shapes(cfg)`` returns a pytree
of :class:`ParamSpec`; ``init`` / ``abstract`` / ``partition_specs`` are
all derived from it, so the dry-run (ShapeDtypeStruct, no allocation)
and the smoke tests (real arrays) can never diverge.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

# ----------------------------------------------------------------------
# Parameter specification
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]       # logical axis name per dim
    dtype: Any = jnp.float32
    init: str = "normal"                  # normal | zeros | ones
    scale: Optional[float] = None         # None -> 1/sqrt(fan_in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "sharding"))
def _init_leaf(key: jax.Array, spec: ParamSpec, dtype, sharding
               ) -> jax.Array:
    """One leaf in one program: the float32 draw, scale and cast fuse,
    so only the `dtype` result is written, laid out by `sharding`."""
    if spec.init == "zeros":
        out = jnp.zeros(spec.shape, dtype)
    elif spec.init == "ones":
        out = jnp.ones(spec.shape, dtype)
    else:
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        scale = spec.scale if spec.scale is not None else fan_in ** -0.5
        out = (jax.random.normal(key, spec.shape, jnp.float32) * scale
               ).astype(dtype)
    if sharding is not None:
        out = jax.lax.with_sharding_constraint(out, sharding)
    return out


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(specs, rng: jax.Array, *, dtype=None, shardings=None):
    """Random parameters, built leaf by leaf on the device.

    ``dtype`` overrides every spec's dtype: training keeps the specs'
    float32 master weights, serving builds ``cfg.dtype`` (bf16)
    directly, so a model whose float32 weights would not fit one chip
    never exists in float32.  ``shardings`` (a NamedSharding per leaf,
    e.g. sharding/plans.ServingPlan.param_shardings) builds each leaf
    already sharded instead of whole on one device.  Values do not
    depend on either: a leaf is the float32 draw of its key, cast."""
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(rng, len(leaves))
    shs = (treedef.flatten_up_to(shardings) if shardings is not None
           else [None] * len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        _init_leaf(k, spec=s,
                   dtype=jnp.dtype(s.dtype if dtype is None else dtype),
                   sharding=sh)
        for s, k, sh in zip(leaves, keys, shs)])


def abstract_params(specs):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs,
        is_leaf=is_spec)


def partition_specs(specs, rules: Dict[str, Optional[str]],
                    mesh_sizes: Optional[Dict[str, int]] = None):
    """Resolve logical axes -> PartitionSpec under `rules`.

    A mesh axis is used at most once per param (first logical axis that
    maps to it wins); an axis whose dim doesn't divide the mesh size is
    left replicated (GSPMD jit-argument shardings must divide evenly —
    e.g. yi-6b's 4 KV heads can't split 16 ways).
    """
    def resolve(spec: ParamSpec) -> P:
        used = set()
        out = []
        for dim, ax in zip(spec.shape, spec.axes):
            mesh_ax = rules.get(ax) if ax is not None else None
            if mesh_ax is not None and mesh_sizes is not None:
                sizes = ((mesh_sizes.get(a, 1) for a in mesh_ax)
                         if isinstance(mesh_ax, tuple)
                         else [mesh_sizes.get(mesh_ax, 1)])
                total = 1
                for s in sizes:
                    total *= s
                if dim % total:
                    mesh_ax = None
            if mesh_ax is None or mesh_ax in used:
                out.append(None)
            else:
                used.add(mesh_ax)
                out.append(mesh_ax)
        return P(*out)

    return jax.tree_util.tree_map(resolve, specs, is_leaf=is_spec)


def count_params(specs) -> int:
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=is_spec)
    total = 0
    for s in leaves:
        n = 1
        for d in s.shape:
            n *= d
        total += n
    return total


# ----------------------------------------------------------------------
# Layers (functional)
# ----------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: Optional[jax.Array],
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(cfg, x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p.get("bias"))
    return rmsnorm(x, p["scale"])


def norm_spec(cfg, d: int) -> Dict[str, ParamSpec]:
    out = {"scale": ParamSpec((d,), ("embed",), init="ones")}
    if cfg.norm == "layernorm":
        out["bias"] = ParamSpec((d,), ("embed",), init="zeros")
    return out


def activation(cfg, x: jax.Array, gate: Optional[jax.Array]) -> jax.Array:
    if cfg.activation == "swiglu":
        assert gate is not None
        return jax.nn.silu(gate) * x
    if gate is not None:            # geglu
        return jax.nn.gelu(gate) * x
    return jax.nn.gelu(x)


# --- rotary embeddings -------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)                    # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..,s,hd/2]
    angles = angles[..., None, :]                                 # broadcast heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --- losses ------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Token-mean CE; logits [..., vocab] fp32-stable."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def chunked_softmax_xent(x: jax.Array, w_out: jax.Array, labels: jax.Array,
                         *, chunk: int = 8192,
                         mask: Optional[jax.Array] = None) -> jax.Array:
    """Cross-entropy without materializing [tokens, vocab] at once.

    Big-vocab archs (command-r 256k, moonshot 164k) would need an
    O(tokens x vocab) logits buffer; chunking the token dim through a
    scan bounds the live buffer at [chunk, vocab].  x: [tokens, d];
    w_out: [d, vocab]; labels: [tokens].
    """
    tokens = x.shape[0]
    pad = (-tokens) % chunk
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        mask = jnp.pad(mask if mask is not None
                       else jnp.ones((tokens,), jnp.float32), (0, pad))
    elif mask is None:
        mask = jnp.ones((tokens,), jnp.float32)
    n_chunks = x.shape[0] // chunk
    xs = x.reshape(n_chunks, chunk, -1)
    ls = labels.reshape(n_chunks, chunk)
    ms = mask.reshape(n_chunks, chunk)

    @jax.checkpoint   # recompute chunk logits in bwd: never store them
    def step(acc, inp):
        xc, lc, mc = inp
        logits = (xc @ w_out).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll_sum, m_sum = acc
        return (nll_sum + jnp.sum((lse - gold) * mc), m_sum + jnp.sum(mc)), None

    (nll, m), _ = lax.scan(step, (jnp.zeros((), jnp.float32),
                                  jnp.zeros((), jnp.float32)), (xs, ls, ms))
    return nll / jnp.maximum(m, 1.0)

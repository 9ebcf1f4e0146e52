"""Random weights from the seed, made on the device.

Each value is a fixed function of (seed, leaf name, flat index): an
integer hash mapped to a uniform draw with the variance of the leaf's
fan-in scale, rounded once to bfloat16.  The serving weights are made
a whole stacked leaf at a time (one jitted program per distinct leaf
shape, its key and scale as operands), and the reference makes one
layer's slice of the same leaf on its own with the same function, so
both read bit-identical values while the reference never touches what
the program holds.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# q/k/v bias draw (Qwen1.5 has them; the value only has to exercise the
# bias path, not to match a trained model)
BIAS_STD = 0.1


def dims(conf: Dict) -> Dict[str, int]:
    """The widths of a configuration file under short names."""
    return {"L": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "H": conf["num_attention_heads"],
            "KH": conf["num_key_value_heads"], "hd": conf["head_dim"],
            "f": conf["intermediate_size"], "V": conf["vocab_size"],
            "bias": bool(conf["attention_bias"])}


def rule(name: str, dm: Dict[str, int]) -> Tuple[str, float]:
    """(kind, std) of one leaf by its dotted name: kind is "uniform",
    "ones" or "zeros".  An unknown leaf is an error."""
    d, f = dm["d"], dm["f"]
    last = name.rsplit(".", 1)[-1]
    table = {
        "embed": ("uniform", 1.0),
        "unembed": ("uniform", d ** -0.5),
        "wq": ("uniform", d ** -0.5), "wk": ("uniform", d ** -0.5),
        "wv": ("uniform", d ** -0.5),
        "wo": ("uniform", (dm["H"] * dm["hd"]) ** -0.5),
        "w_up": ("uniform", d ** -0.5), "w_gate": ("uniform", d ** -0.5),
        "w_down": ("uniform", f ** -0.5),
        "bq": ("uniform", BIAS_STD), "bk": ("uniform", BIAS_STD),
        "bv": ("uniform", BIAS_STD),
        "b_up": ("zeros", 0.0), "b_down": ("zeros", 0.0),
        "scale": ("ones", 0.0),
    }
    if last not in table:
        raise KeyError(f"no weight rule for leaf {name!r}")
    return table[last]


def _fmix(h):
    """murmur3's 32-bit finalizer (a bijection with full avalanche)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _fmix_host(h: int) -> int:
    h &= 0xFFFFFFFF
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    return h ^ (h >> 16)


def leaf_key(seed: int, name: str) -> int:
    """32-bit key of one leaf under one seed (any size of seed)."""
    s = int(seed)
    k = _fmix_host(zlib.crc32(name.encode()))
    k = _fmix_host(k ^ (s & 0xFFFFFFFF))
    return _fmix_host(k ^ ((s >> 32) & 0xFFFFFFFF) ^ 0x9E3779B9)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, offset, mult, *, shape, dtype):
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for ax in reversed(range(len(shape))):
        idx = idx + (jax.lax.broadcasted_iota(jnp.uint32, shape, ax)
                     * jnp.uint32(stride))
        stride *= shape[ax]
    h = _fmix((idx + offset) * jnp.uint32(0x9E3779B1) ^ key)
    # top 24 bits -> an integer in [-2^23, 2^23), exact in float32; one
    # rounding (the multiply), so every fusion gives the same bits
    u = (h >> 8).astype(jnp.int32).astype(jnp.float32) - 8388608.0
    return (u * mult).astype(dtype)


def draw(seed: int, name: str, shape, std: float, *, offset: int = 0,
         dtype=jnp.bfloat16) -> jax.Array:
    """Uniform values of standard deviation ``std`` for elements
    [offset, offset + prod(shape)) of leaf ``name``."""
    mult = np.float32(math.sqrt(3.0) * std / 8388608.0)
    return _draw(np.uint32(leaf_key(seed, name)), np.uint32(offset), mult,
                 shape=tuple(int(s) for s in shape), dtype=jnp.dtype(dtype))


def make_leaf(seed: int, name: str, shape, dm: Dict[str, int], *,
              offset: int = 0, dtype=jnp.bfloat16) -> jax.Array:
    kind, std = rule(name, dm)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    return draw(seed, name, shape, std, offset=offset, dtype=dtype)


def layer_slice(seed: int, name: str, layer_shape, layer: int,
                dm: Dict[str, int], dtype=jnp.bfloat16) -> jax.Array:
    """Layer ``layer`` of stacked leaf ``name`` ([L] + layer_shape),
    bit-identical to that slice of the whole leaf."""
    n = int(np.prod(layer_shape))
    return make_leaf(seed, name, tuple(layer_shape), dm, offset=layer * n,
                     dtype=dtype)

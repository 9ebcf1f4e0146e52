"""jit'd public wrappers for the kernel suite.

Tile shapes default to the dissection-driven autotuner
(core/mxu_model.pick_tile) — the paper's measure->model->optimize loop
— or, where a kernel has fixed defaults, to an automatic
``min(default, operand)`` fit, so decode-sized operands (S, m or n of
1-16 on the serving hot path) never inherit a 128-wide training tile.
Tile policy: ``0`` means "auto" everywhere; an explicitly passed tile
may be *smaller* than the operand (it is still divisor-fitted to tile
evenly) but a tile strictly larger than its operand dimension raises
``ValueError`` instead of being silently clamped — a silent clamp hides
a mis-sized launch, which is exactly the class of bug the decode-tile
audit was after.

`interpret` defaults to True on the CPU backend so the whole suite
validates there; on a TPU backend the kernels compile to Mosaic, and
any other backend is an error (paged_attention.resolve_interpret).
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import mxu_model
from repro.kernels import async_pipeline as _async
from repro.kernels import dpx_kernel as _dpx
from repro.kernels import flash_attention as _flash
from repro.kernels import fp8_matmul as _fp8
from repro.kernels import matmul as _mm
from repro.kernels import paged_attention as _paged


_interp = _paged.resolve_interpret


class TileAlignmentWarning(UserWarning):
    """An explicitly-requested tile the hardware would pad: its
    lane-facing extent is not a multiple of 128 (or sublane-facing not
    a multiple of 8) and it does not span the full operand dimension.
    The launch is correct but wastes MXU/VPU lanes — the same
    diagnostic the static analyzer reports as KL003/KL004."""


def _check_tiles(fn_name: str, lane=(), sublane=(),
                 **tile_vs_dim) -> None:
    """Reject explicitly-requested tiles strictly larger than their
    operand dimension (0 = auto is always fine), and warn when an
    explicit tile is misaligned: ``lane``/``sublane`` name the tile
    parameters that land on the minor / second-minor axis of some
    block (a tile spanning the whole operand dimension is exempt —
    there is nothing left to align)."""
    for name, (tile, dim) in tile_vs_dim.items():
        if tile and tile > dim:
            raise ValueError(
                f"{fn_name}: requested tile {name}={tile} exceeds the "
                f"operand dimension {dim}; pass {name}=0 (auto) or a "
                f"tile <= {dim}")
        if not tile or tile == dim:
            continue
        if name in lane and tile % 128:
            warnings.warn(
                f"{fn_name}: tile {name}={tile} is lane-misaligned "
                f"(not a multiple of 128 and not the full dimension "
                f"{dim}); the hardware pads the minor axis to 128",
                TileAlignmentWarning, stacklevel=3)
        if name in sublane and tile % 8:
            warnings.warn(
                f"{fn_name}: tile {name}={tile} is sublane-misaligned "
                f"(not a multiple of 8 and not the full dimension "
                f"{dim}); the hardware pads the second-minor axis to 8",
                TileAlignmentWarning, stacklevel=3)


def _fit_tiles(m, n, k, bm, bn, bk):
    """Clamp autotuned tiles to divisors of the problem (even tiling)."""
    def clamp(dim, t):
        t = min(t, dim)
        while dim % t:
            t //= 2
        return max(t, 1)
    return clamp(m, bm), clamp(n, bn), clamp(k, bk)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(a, b, *, bm: int = 0, bn: int = 0, bk: int = 0,
           interpret: Optional[bool] = None):
    m, k = a.shape
    n = b.shape[1]
    _check_tiles("matmul", lane=("bn", "bk"), sublane=("bm", "bk"),
                 bm=(bm, m), bn=(bn, n), bk=(bk, k))
    if not (bm and bn and bk):
        t = mxu_model.pick_tile(m, n, k, str(a.dtype))
        bm, bn, bk = t.bm, t.bn, t.bk
    bm, bn, bk = _fit_tiles(m, n, k, bm, bn, bk)
    return _mm.matmul(a, b, bm=bm, bn=bn, bk=bk,
                      interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def fp8_matmul(aq, bq, sx, sw, *, bm: int = 0, bn: int = 0, bk: int = 0,
               interpret: Optional[bool] = None):
    m, k = aq.shape
    n = bq.shape[1]
    _check_tiles("fp8_matmul", lane=("bn", "bk"), sublane=("bm", "bk"),
                 bm=(bm, m), bn=(bn, n), bk=(bk, k))
    if not (bm and bn and bk):
        t = mxu_model.pick_tile(m, n, k, str(aq.dtype))
        bm, bn, bk = t.bm, t.bn, t.bk
    bm, bn, bk = _fit_tiles(m, n, k, bm, bn, bk)
    return _fp8.fp8_matmul(aq, bq, sx, sw, bm=bm, bn=bn, bk=bk,
                           interpret=_interp(interpret))


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 0,
                    bk: int = 0, interpret: Optional[bool] = None):
    """bq/bk default 0 = auto ``min(128, S)`` — decode-length inputs
    (S < 128) get an S-sized tile instead of relying on a silent clamp
    of the old 128 default."""
    Sq, Sk = q.shape[1], k.shape[1]
    _check_tiles("flash_attention", sublane=("bq", "bk"),
                 bq=(bq, Sq), bk=(bk, Sk))
    bq = bq or min(128, Sq)
    bk = bk or min(128, Sk)
    return _flash.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                                  interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def tropical_matmul(a, b, *, bm: int = 0, bn: int = 0, bk: int = 0,
                    interpret: Optional[bool] = None):
    m, n, k = a.shape[0], b.shape[1], a.shape[1]
    _check_tiles("tropical_matmul", lane=("bn", "bk"),
                 sublane=("bm", "bk"),
                 bm=(bm, m), bn=(bn, n), bk=(bk, k))
    bm, bn, bk = _fit_tiles(m, n, k, bm or 32, bn or 32, bk or 32)
    return _dpx.tropical_matmul(a, b, bm=bm, bn=bn, bk=bk,
                                interpret=_interp(interpret))


@functools.partial(jax.jit,
                   static_argnames=("match", "mismatch", "gap", "interpret"))
def smith_waterman(seq_a, seq_b, *, match: int = 2, mismatch: int = -1,
                   gap: int = -1, interpret: Optional[bool] = None):
    return _dpx.smith_waterman(seq_a, seq_b, match=match, mismatch=mismatch,
                               gap=gap, interpret=_interp(interpret))


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "stages", "interpret"))
def pipelined_matmul(a, b, *, bm: int = 0, bn: int = 0, bk: int = 0,
                     stages: int = 2, interpret: Optional[bool] = None):
    m, n, k = a.shape[0], b.shape[1], a.shape[1]
    _check_tiles("pipelined_matmul", lane=("bn", "bk"),
                 sublane=("bm", "bk"),
                 bm=(bm, m), bn=(bn, n), bk=(bk, k))
    bm, bn, bk = _fit_tiles(m, n, k, bm or 32, bn or 32, bk or 32)
    return _async.pipelined_matmul(a, b, bm=bm, bn=bn, bk=bk, stages=stages,
                                   interpret=_interp(interpret))


def paged_decode_attention(q, ck, cv, block_table, kv_len, *,
                           k_scale=None, v_scale=None,
                           interpret: Optional[bool] = None):
    """Fused paged flash-decode (kernels/paged_attention.paged_decode):
    the block-table walk runs inside the kernel, touching only the
    valid blocks.  Not jitted here — serving callers jit the whole
    step; the tile is the slot's whole virtual extent so there is no
    tile parameter to audit."""
    return _paged.paged_decode(q, ck, cv, block_table, kv_len,
                               k_scale=k_scale, v_scale=v_scale,
                               interpret=_interp(interpret))


def paged_chunk_attention(q, ck, cv, block_table, pos, *,
                          k_scale=None, v_scale=None,
                          interpret: Optional[bool] = None):
    """Fused paged chunk attention (kernels/paged_attention.paged_chunk)."""
    return _paged.paged_chunk(q, ck, cv, block_table, pos,
                              k_scale=k_scale, v_scale=v_scale,
                              interpret=_interp(interpret))

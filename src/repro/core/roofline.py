"""Three-term roofline analysis from compiled XLA artifacts.

This is the TPU counterpart of the paper's measurement layer: where the
paper times instructions on silicon, this repo (CPU host, TPU target)
derives per-device seconds for the three hardware resources that the
dissection quantifies:

    compute    = HLO_FLOPs_per_device   / peak_FLOP/s            (MXU)
    memory     = HLO_bytes_per_device   / HBM_GB/s               (HBM)
    collective = wire_bytes_per_device  / ICI_GB/s_per_chip      (ICI)

FLOPs/bytes come from ``compiled.cost_analysis()`` of the *partitioned*
(per-device) module.  Collective bytes are NOT in cost_analysis: we parse
the post-optimization HLO text and sum operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from repro.core import hw

# ----------------------------------------------------------------------
# HLO text parsing
# ----------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

# e.g. "f32[256,1024]{1,0}" or "bf16[8,128]" or "f32[]"
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\](?:\{[^}]*\})?")

_COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)
# `= f32[..] all-reduce(...)` | `= (f32[..], f32[..]) all-reduce(...)`
_COLL_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVE_KINDS) + r")(-start|-done)?\("
)


def shape_bytes(text: str) -> int:
    """Sum the bytes of every typed shape literal appearing in `text`."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-kind operand bytes of collectives in (per-device) HLO text.

    We count the *result* shape bytes of each collective op: for a
    ring-scheduled collective this is, to within the (N-1)/N factor, the
    data each device must move over ICI.  `-done` ops are skipped so
    async pairs (`-start`/`-done`) are not double counted.
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if m.group(3) == "-done":
            continue
        result_sig, kind = m.group(1), m.group(2)
        out[kind] += shape_bytes(result_sig)
    return out


def count_ops(hlo_text: str, opname: str) -> int:
    return len(re.findall(r"=\s+\S+\s+" + re.escape(opname) + r"[.(]",
                          hlo_text))


# ----------------------------------------------------------------------
# cost / memory analysis extraction
# ----------------------------------------------------------------------

def cost_analysis(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def memory_analysis(compiled) -> Dict[str, int]:
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        return {}
    if ma is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}


# ----------------------------------------------------------------------
# Roofline report
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Roofline:
    name: str
    mesh_desc: str
    num_chips: int
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: Dict[str, int]
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops_global: float            # 6*N*D (or 6*N_active*D for MoE)
    hbm_bytes_per_dev: Dict[str, int]    # from memory_analysis
    chip: hw.ChipSpec = hw.TPU_V5E

    @property
    def total_coll_bytes(self) -> int:
        return sum(self.coll_bytes_per_dev.values())

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Lower-bound step time: resources overlap, the max dominates."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (global): remat/redundancy waste metric."""
        hlo_global = self.flops_per_dev * self.num_chips
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs utilization at the roofline-bound step time."""
        if self.step_s <= 0:
            return 0.0
        peak = self.num_chips * self.chip.peak_for("bf16")
        return self.model_flops_global / (self.step_s * peak)

    @property
    def roofline_fraction(self) -> float:
        """compute_term / step time — how close the binding resource lets
        the MXUs run to their own roofline."""
        return self.compute_s / self.step_s if self.step_s else 0.0

    def row(self) -> str:
        c = self.coll_bytes_per_dev
        return (
            f"{self.name},{self.mesh_desc},{self.num_chips},"
            f"{self.flops_per_dev:.4g},{self.bytes_per_dev:.4g},"
            f"{self.total_coll_bytes:.4g},"
            f"{self.compute_s:.4g},{self.memory_s:.4g},{self.collective_s:.4g},"
            f"{self.dominant},{self.useful_ratio:.3f},{self.mfu:.3f}"
        )

    @staticmethod
    def header() -> str:
        return ("name,mesh,chips,flops/dev,bytes/dev,coll_bytes/dev,"
                "compute_s,memory_s,collective_s,dominant,useful_ratio,mfu")


def analyze(
    compiled,
    *,
    name: str,
    mesh_spec: hw.MeshSpec,
    model_flops_global: float,
    hlo_text: Optional[str] = None,
    collective_axis_gbps: Optional[float] = None,
) -> Roofline:
    """Build the 3-term roofline for one compiled (per-device) module."""
    chip = mesh_spec.chip
    ca = cost_analysis(compiled)
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    if collective_axis_gbps is None:
        # conservative: one ICI link per chip serves the collective stream
        collective_axis_gbps = chip.ici_gbps_per_link
    return Roofline(
        name=name,
        mesh_desc="x".join(str(s) for s in mesh_spec.shape),
        num_chips=mesh_spec.num_chips,
        flops_per_dev=flops,
        bytes_per_dev=byts,
        coll_bytes_per_dev=coll,
        compute_s=flops / chip.peak_for("bf16"),
        memory_s=byts / (chip.hbm_gbps * 1e9),
        collective_s=sum(coll.values()) / (collective_axis_gbps * 1e9),
        model_flops_global=model_flops_global,
        hbm_bytes_per_dev=memory_analysis(compiled),
        chip=chip,
    )


# ----------------------------------------------------------------------
# paged-decode KV traffic model (serving hot path)
# ----------------------------------------------------------------------

def paged_decode_kv_bytes(kv_len: int, *, block_size: int,
                          max_blocks: int, kv_heads: int, head_dim: int,
                          kv_dtype_bytes: int = 2, scale_bytes: int = 4,
                          mode: str = "gather") -> int:
    """Modeled HBM bytes moved by the K+V read path of ONE decode step,
    per layer per slot, at a current context of `kv_len` tokens.

    mode="gather" (models/attention.gather_paged_cache + attention):
    the gather reads the pool rows for all `max_blocks` table entries
    (clamped -1s included), writes the [max_blocks*block_size, KH, hd]
    virtual view, and the attention reads that view again — three
    passes over the slot's FULL virtual extent regardless of how short
    its live prefix is.

    mode="kernel" (kernels/paged_attention): the in-kernel block-table
    walk DMAs only the ceil(kv_len/block_size) valid blocks, once,
    straight into VMEM scratch — one pass over the live prefix, zero
    traffic for unallocated tail blocks.

    mode="fp8_kernel": same walk on an e4m3 pool — 1 byte per element
    of the valid blocks — plus the f32 scales (`scale_bytes` per
    token row per kv-head), which the kernel wrapper gathers for the
    slot's whole table extent: three passes like the gather path
    (read the scale pool, write the per-slot rows, the kernel reads
    them).

    The factor-of-3 gather overhead and the valid-block-only kernel
    traffic are what `paged_decode_speedup` compares;
    tests/test_roofline.py pins the ratios.
    """
    row = kv_heads * head_dim
    if mode == "gather":
        return 3 * max_blocks * block_size * row * kv_dtype_bytes * 2
    valid_tokens = -(-kv_len // block_size) * block_size
    if mode == "kernel":
        return valid_tokens * row * kv_dtype_bytes * 2
    if mode == "fp8_kernel":
        scales = 3 * max_blocks * block_size * kv_heads * scale_bytes
        return (valid_tokens * row + scales) * 2
    raise ValueError(f"unknown mode {mode!r}")


def paged_decode_speedup(kv_len: int, *, block_size: int,
                         max_blocks: int, kv_heads: int, head_dim: int
                         ) -> Dict[str, float]:
    """Byte-traffic ratios of the three paged decode read paths at one
    context length (HBM-bound decode: bytes ~ time)."""
    kw = dict(block_size=block_size, max_blocks=max_blocks,
              kv_heads=kv_heads, head_dim=head_dim)
    gather = paged_decode_kv_bytes(kv_len, mode="gather", **kw)
    kern = paged_decode_kv_bytes(kv_len, mode="kernel", **kw)
    fp8 = paged_decode_kv_bytes(kv_len, mode="fp8_kernel", **kw)
    return {"gather_bytes": float(gather), "kernel_bytes": float(kern),
            "fp8_kernel_bytes": float(fp8),
            "kernel_speedup": gather / kern,
            "fp8_speedup": gather / fp8,
            "fp8_vs_kernel_bytes": fp8 / kern}

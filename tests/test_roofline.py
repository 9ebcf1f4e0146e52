"""Roofline machinery: HLO parsing, cost_analysis semantics, analytic
model validation against unrolled HLO."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import analytic, hw, roofline


def test_shape_bytes_parser():
    assert roofline.shape_bytes("f32[16,16]") == 1024
    assert roofline.shape_bytes("bf16[8]{0}") == 16
    assert roofline.shape_bytes("(f32[4], s8[4])") == 20
    assert roofline.shape_bytes("f8e4m3fn[128]") == 128
    assert roofline.shape_bytes("f32[]") == 4


def test_collective_parser_counts_result_bytes():
    hlo = """
  %ar = f32[256,4]{1,0} all-reduce(f32[256,4]{1,0} %x), replica_groups={}
  %ag.1 = bf16[64]{0} all-gather(bf16[4]{0} %y), dimensions={0}
  %d = f32[8]{0} all-reduce-done(f32[8]{0} %s)
  %s2 = f32[8]{0} all-reduce-start(f32[8]{0} %z)
"""
    got = roofline.collective_bytes(hlo)
    assert got["all-reduce"] == 256 * 4 * 4 + 8 * 4   # ar + start, not done
    assert got["all-gather"] == 64 * 2


def test_cost_analysis_counts_scan_body_once():
    """Documents the XLA behavior the analytic model exists to fix."""
    def one(x, w):
        return x @ w

    def scanned(x, ws):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w1 = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w8 = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
    f1 = roofline.cost_analysis(jax.jit(one).lower(x, w1).compile())
    f8 = roofline.cost_analysis(jax.jit(scanned).lower(x, w8).compile())
    assert f8["flops"] < 1.5 * f1["flops"]  # body counted once!


def _tiny_cfg() -> ModelConfig:
    return dataclasses.replace(
        reduced_config("yi-6b"), d_model=128, d_ff=256, num_heads=4,
        num_kv_heads=2, head_dim=32, vocab_size=512, num_layers=2,
        remat="none")


def test_analytic_flops_vs_unrolled_hlo():
    """The analytic fwd FLOPs must match XLA's count on an *unrolled*
    tiny model (where cost_analysis sees every op) within 2x."""
    from repro.models import transformer
    from repro.models.common import abstract_params

    cfg = _tiny_cfg()
    B, S = 2, 64
    specs = transformer.transformer_specs(cfg)
    params_sds = abstract_params(specs)

    def fwd_unrolled(params, tokens):
        x = transformer.embed_tokens(cfg, params, tokens)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        for l in range(cfg.num_layers):
            lp = jax.tree_util.tree_map(lambda p: p[l], params["layers"])
            x, _ = transformer.layer_fwd(cfg, lp, x, pos)
        from repro.models.common import apply_norm
        x = apply_norm(cfg, x, params["final_norm"])
        return transformer.logits_fn(cfg, params, x)

    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    comp = jax.jit(fwd_unrolled).lower(params_sds, toks).compile()
    hlo_flops = roofline.cost_analysis(comp)["flops"]

    mesh1 = hw.MeshSpec(shape=(1,), axis_names=("data",))
    shape = ShapeConfig("tiny", S, B, "prefill")
    cell = analytic.analyze_cell(cfg, shape, mesh1, "dp")
    ratio = cell.impl_flops_dev / hlo_flops
    assert 0.5 < ratio < 2.0, (cell.impl_flops_dev, hlo_flops, ratio)


def test_analytic_cell_full_config_sane():
    """Full-config cells: MODEL_FLOPS matches the 6ND convention and the
    dominant term is physically plausible."""
    from repro.configs.base import SHAPES
    cfg = get_config("yi-6b")
    cell = analytic.analyze_cell(cfg, SHAPES["train_4k"], hw.SINGLE_POD)
    n = cfg.param_count()
    six_nd = 6.0 * n * SHAPES["train_4k"].tokens
    assert 0.8 < cell.model_flops / six_nd < 1.5
    rf = cell.roofline(hw.SINGLE_POD)
    assert rf.compute_s > 0 and rf.memory_s > 0
    assert 0 < rf.mfu <= 1.0
    assert 0 < rf.useful_ratio <= 1.2


def test_decode_cells_memory_bound():
    """Paper's Table XII insight transfers: short-output decode is
    memory-bound -> the roofline must agree for every decoder arch."""
    from repro.configs import ASSIGNED
    from repro.configs.base import SHAPES
    for arch in ("yi-6b", "command-r-35b", "falcon-mamba-7b"):
        cfg = get_config(arch)
        cell = analytic.analyze_cell(cfg, SHAPES["decode_32k"],
                                     hw.SINGLE_POD)
        rf = cell.roofline(hw.SINGLE_POD)
        assert rf.dominant == "memory", (arch, rf.dominant)


def test_roofline_row_format():
    cfg = get_config("yi-6b")
    from repro.configs.base import SHAPES
    cell = analytic.analyze_cell(cfg, SHAPES["train_4k"], hw.SINGLE_POD)
    rf = cell.roofline(hw.SINGLE_POD)
    row = rf.row()
    assert rf.name in row and rf.dominant in row
    assert len(roofline.Roofline.header().split(",")) == \
        len(row.split(","))


def test_paged_decode_kv_bytes_ratios():
    """Pin the modeled byte ratios of the three paged decode read
    paths (core/roofline.paged_decode_kv_bytes): the kernel moves
    fewer bytes than the gather, and the fp8 kernel fewer still."""
    from repro.core import roofline
    kw = dict(block_size=16, max_blocks=8, kv_heads=4, head_dim=64)
    full = 16 * 8
    r = roofline.paged_decode_speedup(full, **kw)
    # at full context the gather's 3 passes over the full extent vs
    # the kernel's single pass over the (all-valid) blocks = exactly 3x
    assert r["kernel_speedup"] == 3.0
    # fp8 kernel bytes per token-row per head at full context: hd codes
    # + 3 passes of 4-byte scales over the table extent, vs 2*hd
    assert r["fp8_vs_kernel_bytes"] == (64 + 3 * 4) / (2 * 64) == 0.59375
    # at quarter context the kernel touches 1/4 of the blocks: 12x
    r4 = roofline.paged_decode_speedup(full // 4, **kw)
    assert r4["kernel_speedup"] == 12.0
    # gather traffic is context-independent (that's the indictment)
    assert r4["gather_bytes"] == r["gather_bytes"]
    # partial last block rounds UP to a whole block on the kernel path
    ra = roofline.paged_decode_kv_bytes(17, mode="kernel", **kw)
    rb = roofline.paged_decode_kv_bytes(32, mode="kernel", **kw)
    assert ra == rb
    with pytest.raises(ValueError):
        roofline.paged_decode_kv_bytes(8, mode="nope", **kw)

"""Sharding plans, logical-axis resolution, divisibility fallbacks."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.models import api
from repro.models.common import ParamSpec, partition_specs
from repro.sharding import plans
from repro.sharding.axes import resolve


MESH16 = {"data": 16, "model": 16}


def test_partition_specs_divisibility_fallback():
    specs = {"w": ParamSpec((4096, 4, 128),
                            ("embed", "kv_heads", "head_dim"))}
    rules = {"embed": "data", "kv_heads": "model"}
    ps = partition_specs(specs, rules, MESH16)
    # kv_heads=4 can't split 16 ways -> replicated
    assert ps["w"] == P("data", None, None)


def test_partition_specs_no_axis_reuse():
    specs = {"w": ParamSpec((256, 256), ("embed", "vocab"))}
    rules = {"embed": "model", "vocab": "model"}
    ps = partition_specs(specs, rules, MESH16)
    assert ps["w"] == P("model", None)


def test_plan_with_pod():
    plan = plans.get_plan("fsdp_tp", multi_pod=True)
    assert plan.batch_axes == ("pod", "data")


def test_batch_pspec_uneven_fallback():
    plan = plans.get_plan("fsdp_tp", multi_pod=True)
    mesh_shape = {"pod": 2, "data": 16, "model": 16}
    # batch=1: replicate
    assert plans.batch_pspec(plan, 1, mesh_shape) == P(None)
    # batch=16: only 'data'? 2*16=32 doesn't divide 16 -> prefix ('pod',)
    spec = plans.batch_pspec(plan, 16, mesh_shape)
    assert spec[0] in ("pod", ("pod",), ("pod", "data"))


def test_default_plan_sp_for_tiny_batch_decode():
    cfg = get_config("yi-6b")
    plan = plans.default_plan(cfg, SHAPES["long_500k"])
    assert plan.kv_seq_axis is not None
    plan2 = plans.default_plan(cfg, SHAPES["train_4k"])
    assert plan2.kv_seq_axis is None


@pytest.mark.parametrize("arch", ["yi-6b", "codeqwen1.5-7b",
                                  "falcon-mamba-7b", "zamba2-2.7b",
                                  "whisper-small"])
def test_cache_pspecs_shapes_match(arch):
    """Every cache leaf gets a spec of matching rank; KV heads shard
    only when divisible (CodeQwen1.5-7B's 4 KV heads, like Yi-6B's, do
    not split 16 ways: its cache shards along the sequence instead)."""
    cfg = get_config(arch)
    shape = SHAPES["decode_32k"]
    plan = plans.default_plan(cfg, shape)

    class FakeMesh:
        axis_names = ("data", "model")
        import numpy as np
        devices = np.empty((16, 16), dtype=object)

    specs = plans.cache_pspecs(cfg, shape, plan, FakeMesh())
    cache = api.cache_specs(cfg, shape)
    flat_c = jax.tree_util.tree_leaves(cache)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_c) == len(flat_s)
    for sds, spec in zip(flat_c, flat_s):
        assert len(spec) <= len(sds.shape)
        for dim, ax in zip(sds.shape, tuple(spec) + (None,) * 8):
            if ax is None:
                continue
            size = 16 if isinstance(ax, str) else 16 ** len(ax)
            assert dim % size == 0, (arch, sds.shape, spec)
    if isinstance(cache, dict) and "k" in cache:
        # [layers, batch, seq, kv_heads, head_dim]
        k = tuple(specs["k"]) + (None,) * 5
        assert cache["k"].shape[3] == cfg.num_kv_heads
        assert (k[3] == "model") == (cfg.num_kv_heads % 16 == 0), \
            (arch, specs["k"])


def test_resolve_with_dims():
    spec = resolve(("batch", "heads"), {"batch": "data", "heads": "model"},
                   dims=(32, 4), mesh_sizes=MESH16)
    assert spec == P("data", None)     # 4 heads can't split 16 ways

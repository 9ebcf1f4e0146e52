"""Compile for the real chip, without the chip, plus the CPU rehearsal
of chip_smoke.py.

The TPU compiler is installed wherever libtpu is, and it compiles for
a described ``v5e:2x2`` topology that is not attached: what it refuses
here (a DMA slice not aligned to the tiling, a kernel over the scoped
VMEM limit, a step that does not fit HBM) it would refuse on the chip.
The topology is described inside a module fixture, never at import:
only one process may load libtpu, and pytest-xdist workers import
every test file.  Nothing here runs on a TPU and nothing reports a time.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config, reduced_config
from repro.core import hw
from repro.kernels import paged_attention as pk
from repro.launch import compile_cache
from repro.models import api

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

# serving geometry of chip_smoke.py: 8 slots of max_len 2048 in 16-token
# blocks, 16-token prefill chunks
SLOTS, MAX_LEN, BS, CHUNK = 8, 2048, 16, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU program written to a persistent cache cannot be read back
    # without the chip; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("pool", ["bfloat16", "float8_e4m3fn"])
def test_paged_kernels_compile_for_v5e(one_chip, kind, pool):
    """Both paged kernels, bf16 and fp8 pools, at Yi-6B widths (KH=4,
    G=8, hd=128) over 128 blocks per slot, compiled by Mosaic."""
    cfg = get_config("yi-6b")
    KH, H, hd = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    MB = MAX_LEN // BS
    NB = SLOTS * MB
    C = 1 if kind == "decode" else CHUNK
    fp8 = pool != "bfloat16"
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    q = sds((SLOTS, C, H, hd), jnp.bfloat16)
    kv = sds((NB, KH, BS, hd), jnp.dtype(pool))
    scale = sds((NB, KH, BS), jnp.float32) if fp8 else None
    bt, lens = sds((SLOTS, MB), jnp.int32), sds((SLOTS,), jnp.int32)
    fn = pk.paged_decode if kind == "decode" else pk.paged_chunk

    def f(q, k, v, bt, lens, ks, vs):
        return fn(q, k, v, bt, lens, k_scale=ks, v_scale=vs,
                  interpret=False)

    compiled = jax.jit(f).lower(q, kv, kv, bt, lens, scale,
                                scale).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def serving_compiles(one_chip):
    """arch -> {program name: compiled} for the server's chunk and span
    work units at full width with bf16 weights, at the slots and max_len
    of the arch's benchmark configuration (bench/configs/<arch>.json,
    read through bench/harness.py); each arch compiles once for every
    test that asks.  The server is built over shapes only: weights and
    the KV pool are never allocated."""
    from repro.analysis.jaxpr_check import serving_programs
    from repro.runtime.server import ChunkedServer
    done = {}

    def compiled(arch):
        if arch not in done:
            cell = next(w["name"] for w in harness.load_benchmark()
                        ["workloads"] if w["config"] == arch)
            conf = harness.load_cell(cell)["conf"]
            cfg = harness.program_config(conf)
            params = jax.eval_shape(lambda: api.init(
                cfg, jax.random.PRNGKey(0), dtype=cfg.dtype))
            real_init_cache = api.init_cache
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(api, "init_cache",
                           lambda *a, **k: jax.eval_shape(
                               lambda: real_init_cache(*a, **k)))
                srv = ChunkedServer(cfg, params,
                                    batch_slots=conf["batch_slots"],
                                    max_len=conf["max_len"])
            done[arch] = {
                name: jitted.lower(*_on(one_chip, ops)).compile()
                for name, _, jitted, ops in serving_programs(srv)}
        return done[arch]

    return compiled


@pytest.mark.parametrize("arch", ["yi-6b", "codeqwen1.5-7b"])
def test_serving_steps_fit_one_v5e(serving_compiles, arch):
    """The server's chunk and span work units at full width with bf16
    weights, at each benchmark cell's slots x max_len (CodeQwen1.5-7B's
    weights take 13.5 GiB): arguments + temporaries - donated aliases
    fit one chip's HBM."""
    for name, compiled in serving_compiles(arch).items():
        mem = compiled.memory_analysis()
        need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes)
        assert need < hw.TPU_V5E.hbm_bytes, (name, need)


def _layer_weight_producers(hlo, cfg):
    """Instructions outside fused computations whose result is one
    layer's weight matrix (bf16 [d,f], [f,d], [1,d,f], [1,f,d], or wo
    as [1,H,hd,d], [H,hd,d] or [H*hd,d]): a slice copied out of the
    stacked weights, or its relayout.  A slice read in place inside a
    dot's fusion is not one."""
    d, f = cfg.d_model, cfg.d_ff
    H, hd = cfg.num_heads, cfg.head_dim
    shapes = {(d, f), (f, d), (1, d, f), (1, f, d),
              (1, H, hd, d), (H, hd, d), (H * hd, d)}
    fused = {c for line in hlo.splitlines() if " fusion(" in line
             for c in re.findall(r"calls=%?([\w.\-]+)", line)}
    found, comp = [], None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        out = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = bf16\[([\d,]*)\]", line)
        if comp not in fused and out and tuple(
                int(n) for n in out.group(1).split(",") if n) in shapes:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("arch,span_temp_gib", [("yi-6b", 1.55),
                                                ("codeqwen1.5-7b", 0.80)])
def test_serving_steps_read_layer_weights_in_place(serving_compiles, arch,
                                                   span_temp_gib):
    """Neither the chunk step nor the decode span copies or relayouts a
    layer's weight matrix out of the stacked weights: every projection
    is one dot that reads its layer's slice in place.  A grouped
    (per-head-group or per-hidden-group) contraction made the compiler
    copy w_down and wo out of the stack and transpose w_up/w_gate on
    every span step, and raised the span's temporaries above these
    bounds."""
    cfg = get_config(arch)
    for name, compiled in serving_compiles(arch).items():
        found = _layer_weight_producers(compiled.as_text(), cfg)
        assert not found, (name, found)
    span = serving_compiles(arch)["decode_span"].memory_analysis()
    assert span.temp_size_in_bytes <= span_temp_gib * 2 ** 30, \
        span.temp_size_in_bytes


def test_sample_head_compiles_without_vocab_gather_or_scatter(one_chip):
    """The sample head at Yi-6B's 16 slots x 64000 in bf16: a batch-level
    conditional, at most two vocabulary sorts (top-k, nucleus), and no
    vocabulary-sized gather or scatter (the nucleus is found without
    the permutation)."""
    import re
    from repro.models import sampling
    B, V = 16, get_config("yi-6b").vocab_size
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    f32, i32 = (sds((B,), d) for d in (jnp.float32, jnp.int32))
    hlo = jax.jit(sampling.sample_tokens).lower(
        sds((B, V), jnp.bfloat16), f32, i32, f32, i32, i32
    ).compile().as_text()

    def ops(name):
        """Output shapes of every ``name`` instruction."""
        return [m.group(1) for m in re.finditer(
            r"= \(?([^=]*?)\)? %s\(" % name, hlo)]

    def elements(shape):
        return max((int(np.prod([int(d) for d in dims.split(",") if d]))
                    for dims in re.findall(r"\[([\d,]*)\]", shape)),
                   default=1)

    assert ops("conditional")
    assert not ops("scatter")
    assert all(elements(s) < V for s in ops("gather")), ops("gather")
    assert 1 <= sum(elements(s) >= V for s in ops("sort")) <= 2


def test_chip_smoke_phases_on_cpu():
    """chip_smoke.py's serving phases end to end at reduced size on the
    CPU (its platform check lives in main(), which this skips)."""
    sys.path.insert(0, ROOT)
    import chip_smoke
    errs = chip_smoke.serving_phase(
        reduced_config("yi-6b"), seed=0, slots=4, max_len=96,
        n_requests=6, max_input=48, max_output=16, log=lambda _: None)
    assert errs["kernel vs gather"] < chip_smoke.BF16_TOL


def test_compile_cache_location(monkeypatch):
    """Unset JAX_COMPILATION_CACHE_DIR: the cache goes to
    <checkout>/.jax_cache.  Set: the repo sets no other path."""
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.enable()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        jax.config.update("jax_compilation_cache_dir", prev)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

"""Serving-contract analyzer (repro.analysis): the seeded-violation
corpus proves every rule fires (exactly the expected number of times),
and the clean-run gates prove zero false positives on the repo across
the serving flag matrix — the same invocation the CI `analysis` job
runs with ``--strict``."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from repro.analysis import ast_lint, contracts, jaxpr_check, kernel_lint
from repro.analysis.report import RULES, Finding, Report
from repro.kernels import ops
from repro.models.common import fixed_tree_sum

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(TESTS_DIR)
CORPUS = os.path.join(TESTS_DIR, "analysis_corpus")


def _corpus(name):
    return os.path.join(CORPUS, name)


def _serving_like(x):
    """Minimal function carrying both trace hooks, so corpus fixtures
    trip exactly their target rule and nothing else."""
    parts = checkpoint_name(
        jnp.stack([x, x]).astype(jnp.float32), "xshard_ok")
    y = parts[0] + parts[1]
    return checkpoint_name(y, "serving_hot_path")


# ----------------------------------------------------------------------
# layer 1 corpus: one fixture per jaxpr rule
# ----------------------------------------------------------------------

def test_jx001_host_callback_fires():
    def bad(x):
        y = _serving_like(x)
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(y.shape, y.dtype), y)

    closed = jax.make_jaxpr(bad)(jnp.zeros((4, 4)))
    rep = Report()
    jaxpr_check._check_jaxpr("corpus", "chunk_step", closed.jaxpr, {},
                             rep)
    assert rep.count("JX001") == 1
    assert len(rep.findings) == 1


def test_jx002_symbolic_shape_fires():
    from jax import export
    b, = export.symbolic_shape("b")
    sds = jax.ShapeDtypeStruct((b, 4), jnp.float32)
    closed = jax.make_jaxpr(lambda x: x * 2)(sds)
    rep = Report(suppress=["JX006"])    # untagged on purpose
    jaxpr_check._check_jaxpr("corpus", "chunk_step", closed.jaxpr, {},
                             rep)
    assert rep.count("JX002") == 1
    assert len(rep.findings) == 1
    assert len(rep.suppressed) == 2     # serving + xshard hook misses


def test_jx003_undonated_cache_fires():
    cache = {"k": jnp.zeros((2, 4)), "v": jnp.zeros((2, 4))}

    def step(params, cache):
        return jax.tree_util.tree_map(lambda a: a + params, cache)

    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype),
        (jnp.zeros(()), cache))
    rep = Report()
    jaxpr_check._check_donation("corpus", "chunk_step", jax.jit(step),
                                abstract, cache, rep)
    assert rep.count("JX003") == 1

    # positive control: donating the cache operand clears the finding
    rep2 = Report()
    jaxpr_check._check_donation(
        "corpus", "chunk_step", jax.jit(step, donate_argnums=(1,)),
        abstract, cache, rep2)
    assert rep2.findings == []


def test_jx004_bf16_tree_reduction_fires():
    def bad(x):
        parts = x.astype(jnp.bfloat16)
        y = fixed_tree_sum(parts, tag="xshard_bad")
        return checkpoint_name(y.astype(jnp.float32),
                               "serving_hot_path")

    closed = jax.make_jaxpr(bad)(jnp.zeros((4, 8)))
    rep = Report()
    jaxpr_check._check_jaxpr("corpus", "decode_span", closed.jaxpr, {},
                             rep)
    assert rep.count("JX004") == 1
    assert len(rep.findings) == 1


def test_jx005_signature_drift_fires():
    rep = Report()
    registry = {}
    jaxpr_check.register_signature(
        registry, "chunk_step", "fp8_kv=0", "combo-a",
        (jax.ShapeDtypeStruct((2, 8), jnp.int32),), rep)
    jaxpr_check.register_signature(
        registry, "chunk_step", "fp8_kv=0", "combo-b",
        (jax.ShapeDtypeStruct((2, 16), jnp.int32),), rep)
    assert rep.count("JX005") == 1
    assert len(rep.findings) == 1


def test_jx006_missing_trace_hook_fires():
    def untagged(x):
        parts = checkpoint_name(x.astype(jnp.float32), "xshard_ok")
        return parts.sum()      # no serving_hot_path tag

    closed = jax.make_jaxpr(untagged)(jnp.zeros((4,)))
    rep = Report()
    jaxpr_check._check_jaxpr("corpus", "chunk_step", closed.jaxpr, {},
                             rep)
    assert rep.count("JX006") == 1
    assert len(rep.findings) == 1


# ----------------------------------------------------------------------
# layer 2 corpus: one synthetic launch per Pallas rule
# ----------------------------------------------------------------------

class _Spec:
    def __init__(self, block_shape, index_map=None):
        self.block_shape = block_shape
        self.index_map = index_map


def _launch(**kw):
    base = dict(kernel="corpus_kernel", module="corpus",
                workload="corpus", grid=None, in_specs=[], out_specs=[],
                out_shapes=[], scratch_shapes=[], num_scalar_prefetch=0,
                operands=[])
    base.update(kw)
    return kernel_lint.Launch(**base)


def _check_one(launch):
    rep = Report()
    kernel_lint.check_launches([launch], rep)
    return rep


def test_kl001_oversize_tile_fires():
    rep = _check_one(_launch(
        in_specs=[_Spec((64, 128))],
        operands=[((32, 128), jnp.float32)]))
    assert rep.count("KL001") == 1
    assert len(rep.findings) == 1


def test_kl002_grid_undercoverage_fires():
    rep = _check_one(_launch(
        grid=(2,),
        out_specs=[_Spec((1, 128), lambda i: (i, 0))],
        out_shapes=[jax.ShapeDtypeStruct((4, 128), jnp.float32)]))
    assert rep.count("KL002") == 1
    assert len(rep.findings) == 1


def test_kl003_lane_misaligned_fires():
    rep = _check_one(_launch(
        in_specs=[_Spec((8, 64))],
        operands=[((64, 256), jnp.float32)]))
    assert rep.count("KL003") == 1
    assert len(rep.findings) == 1


def test_kl004_sublane_misaligned_fires():
    rep = _check_one(_launch(
        in_specs=[_Spec((12, 128))],
        operands=[((64, 256), jnp.float32)]))
    assert rep.count("KL004") == 1
    assert len(rep.findings) == 1


def test_kl005_vmem_overbudget_fires():
    rep = _check_one(_launch(
        in_specs=[_Spec((4096, 4096))],
        operands=[((4096, 4096), jnp.float32)]))
    assert rep.count("KL005") == 1
    assert len(rep.findings) == 1


# ----------------------------------------------------------------------
# layer 3 corpus: one fixture file per AST rule
# ----------------------------------------------------------------------

def test_ast001_item_in_hot_path_fires():
    rep = Report()
    ast_lint.run(rep, paths=[_corpus("ast_host_transfer.py")],
                 repo_root=REPO_ROOT,
                 roots=[("ast_host_transfer", "hot_impl")],
                 parity_bodies={})
    assert rep.count("AST001") == 1
    assert len(rep.findings) == 1


def test_ast002_dot_in_parity_body_fires():
    rep = Report()
    ast_lint.run(
        rep, paths=[_corpus("ast_dot_parity.py")],
        repo_root=REPO_ROOT, roots=[],
        parity_bodies={"analysis_corpus/ast_dot_parity.py":
                       {"decode_attention"}})
    assert rep.count("AST002") == 1
    assert len(rep.findings) == 1


def test_ast003_mutable_state_capture_fires():
    rep = Report()
    ast_lint.run(rep, paths=[_corpus("ast_jit_capture.py")],
                 repo_root=REPO_ROOT, roots=[], parity_bodies={})
    assert rep.count("AST003") == 1
    assert len(rep.findings) == 1
    f = rep.findings[0]
    assert f.detail["attr"] == "pos"


# ----------------------------------------------------------------------
# telemetry-in-jit corpus: instrumentation INSIDE a jitted body is the
# failure mode the repro.obs host-side-only convention forbids; both
# existing layers catch it without any new rule
# ----------------------------------------------------------------------

def test_obs_callback_in_jitted_body_fires_jx001():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "obs_in_jit_corpus", _corpus("obs_in_jit.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    closed = jax.make_jaxpr(mod.instrumented_step)(jnp.zeros((4, 4)))
    rep = Report()
    jaxpr_check._check_jaxpr("corpus", "chunk_step", closed.jaxpr, {},
                             rep)
    assert rep.count("JX001") == 1
    assert len(rep.findings) == 1


def test_obs_transfer_in_hot_path_fires_ast001():
    rep = Report()
    ast_lint.run(rep, paths=[_corpus("obs_in_jit.py")],
                 repo_root=REPO_ROOT,
                 roots=[("obs_in_jit", "hot_impl")],
                 parity_bodies={})
    assert rep.count("AST001") == 1
    assert len(rep.findings) == 1


def test_clock_read_in_jitted_body_fires_jx001():
    # open-loop serving's failure mode: a wall-clock stamp smuggled
    # into the jitted step via pure_callback (a bare perf_counter()
    # would bake trace-time, so the callback is the only encoding)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "clock_in_jit_corpus", _corpus("clock_in_jit.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    closed = jax.make_jaxpr(mod.timed_step)(jnp.zeros((4, 4)))
    rep = Report()
    jaxpr_check._check_jaxpr("corpus", "chunk_step", closed.jaxpr, {},
                             rep)
    assert rep.count("JX001") == 1
    assert len(rep.findings) == 1


def test_latency_stamp_transfer_fires_ast001():
    # same mistake one layer down: the latency helper pairs a host
    # timestamp with np.asarray(device_value) on the hot path
    rep = Report()
    ast_lint.run(rep, paths=[_corpus("clock_in_jit.py")],
                 repo_root=REPO_ROOT,
                 roots=[("clock_in_jit", "hot_impl")],
                 parity_bodies={})
    assert rep.count("AST001") == 1
    assert len(rep.findings) == 1


def test_host_rng_in_span_fires_ast001():
    # sampling-era twin of the host-transfer rule: np.random / stdlib
    # random reachable from a hot-path root (one hit each)
    rep = Report()
    ast_lint.run(rep, paths=[_corpus("host_rng_in_span.py")],
                 repo_root=REPO_ROOT,
                 roots=[("host_rng_in_span", "hot_impl")],
                 parity_bodies={})
    assert rep.count("AST001") == 2
    assert len(rep.findings) == 2
    calls = sorted(f.detail["call"] for f in rep.findings)
    assert calls == ["np.random.gumbel() [host RNG]",
                     "random.random() [host RNG]"]


def test_host_rng_callback_in_jitted_body_fires_jx001():
    # the only encoding that "works" per-step — a pure_callback around
    # np.random inside the traced body — is exactly what JX001 flags
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "host_rng_corpus", _corpus("host_rng_in_span.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    closed = jax.make_jaxpr(mod.sampled_step)(jnp.zeros((4, 4)))
    rep = Report()
    jaxpr_check._check_jaxpr("corpus", "decode_span", closed.jaxpr, {},
                             rep)
    assert rep.count("JX001") == 1
    assert len(rep.findings) == 1


def test_device_rng_sample_head_is_clean():
    # positive control: the real sample head (threefry keyed by
    # (seed, position), models/sampling) carries no callback primitive
    # and no host RNG — greedy<->sampled stays inside the contract
    from repro.models import sampling as sampling_mod

    def head(logits, temp, top_k, top_p, seed, pos):
        z = _serving_like(logits)
        toks = sampling_mod.sample_tokens(logits, temp, top_k, top_p,
                                          seed, pos + 1)
        return toks + z.astype(jnp.int32)[:, 0]

    closed = jax.make_jaxpr(head)(
        jnp.zeros((2, 64)), jnp.zeros((2,), jnp.float32),
        jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.float32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32))
    rep = Report()
    jaxpr_check._check_jaxpr("corpus", "decode_span", closed.jaxpr, {},
                             rep)
    assert rep.findings == [], [str(f) for f in rep.findings]


def test_ast_scan_covers_online_serving_modules():
    """The online-serving observatory modules must fall inside
    AST_SCAN_PACKAGES so the transfer gate scans them by default."""
    scanned = {os.path.relpath(p, REPO_ROOT)
               for p in ast_lint.collect_paths(REPO_ROOT)}
    for rel in ("src/repro/runtime/arrivals.py",
                "src/repro/runtime/server.py",
                "src/repro/obs/windows.py",
                "src/repro/obs/slo.py",
                "src/repro/obs/tracer.py"):
        assert rel in scanned, f"{rel} escapes the AST transfer gate"


# ----------------------------------------------------------------------
# clean runs: zero false positives on the repo
# ----------------------------------------------------------------------

def test_ast_layer_clean_on_repo():
    rep = Report()
    ast_lint.run(rep, repo_root=REPO_ROOT)
    assert rep.findings == [], [str(f) for f in rep.findings]


def test_kernel_layer_clean_on_workload_sweep():
    rep = Report()
    kernel_lint.run(rep)
    assert rep.findings == [], [str(f) for f in rep.findings]
    # the sweep must actually capture launches, or the gate is vacuous
    assert len(rep.extras["kernel_launches"]) >= 10


def test_jaxpr_layer_clean_across_serving_combos():
    """The CI gate: every serving flag combo traces clean, and the
    signature registry proves flag switches within a cache layout
    never recompile."""
    rep = Report()
    jaxpr_check.run(rep)
    assert rep.findings == [], [str(f) for f in rep.findings]
    assert len(rep.extras["combos"]) >= 14
    regs = rep.extras["signatures"]
    assert set(regs) == {"chunk_step", "decode_span", "verify_step"}
    # 8 single-device combos share the default bf16 pool layout —
    # kernel/fp8_linear/spec/eos switches all hash identical
    assert len(regs["chunk_step"]["fp8_kv=0"]["combos"]) >= 8


# ----------------------------------------------------------------------
# report plumbing + CLI + ops tile warnings
# ----------------------------------------------------------------------

def test_unknown_suppress_rule_rejected():
    with pytest.raises(ValueError):
        Report(suppress=["NOPE"])


def test_warning_severity_gates_only_strict():
    rep = Report()
    rep.add(Finding("KL003", "corpus"))
    assert rep.exit_code(strict=False) == 0
    assert rep.exit_code(strict=True) == 1
    rep.add(Finding("KL001", "corpus"))
    assert rep.exit_code(strict=False) == 1


def test_cli_list_rules_and_ast_layer():
    from repro.analysis.__main__ import main
    assert main(["--list-rules"]) == 0
    assert main(["--layer", "ast", "--repo-root", REPO_ROOT]) == 0


def test_ops_tile_alignment_warning():
    a = np.ones((64, 64), np.float32)
    with pytest.warns(ops.TileAlignmentWarning):
        ops.matmul(a, a, bm=16, bn=16, bk=16)
    # auto tiles and full-dim tiles stay silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", ops.TileAlignmentWarning)
        ops.matmul(a, a)
        ops.matmul(a, a, bm=64, bn=64, bk=64)

"""Fused paged decode/chunk kernels vs the gather path.

Three tiers:

1. Unit accuracy: the jitted kernel and the jitted gather path, decode
   and chunk, f32/bf16, small/large block sizes, G=1 (matvec) and G>1,
   ragged mid-block frontiers, -1 table tails, COW-fresh blocks — each
   compared with a float64 numpy reference of the same attention.  The
   kernel contracts on the MXU with f32 accumulation and the gather
   path multiplies and sums in XLA, so the two round differently; both
   must sit inside the tolerance of their dtype (below).

2. fp8 tier: the fp8 kernel (scales applied after the contractions)
   and the fp8 gather (dequantize to bf16, then attend) against the
   reference computed from the same dequantized cache; fp8-vs-bf16 is
   a quantization tier with the e4m3 bound documented below.

3. E2E: ChunkedServer(kernel=True) greedy outputs match kernel=False
   on the ShareGPT / sysprompt / repetitive mixes with paged + prefix
   cache + spec decode all on (COW-fresh blocks and spec
   rollback-then-redecode included), with O(1) compile counts; fp8_kv
   shrinks the per-device pool by exactly (hd+4)/(2*hd); tp=2 kernel
   runs on a forced 8-device mesh in a subprocess.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.kernels import ops
from repro.kernels import paged_attention as pk
from repro.models import api, attention
from repro.te import fp8 as te_fp8

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# e4m3 has a 3-bit mantissa (max relative step 2^-3 halfway = 6.25%)
# and quantize_rowwise applies the TE margin of 2.0, so for unit-normal
# KV entries (|x| <~ 4) the dequantized cache is within ~0.12 absolute
# of the bf16 cache; attention outputs are convex combinations of V
# rows so they inherit the same bound.
FP8_ATOL = 0.15

# Absolute error against the float64 reference, for unit-normal q/k/v
# (outputs are convex combinations of V rows, |o| < 4).
#   float32: f32 accumulation over hd <= 64 products and T <= 48 softmax
#     terms stays within ~1e-6; 1e-5 leaves margin, and a path that
#     computed in bf16 anywhere would miss it by three orders.
#   bfloat16: both paths round the softmax weights to bf16 before PV
#     (2^-9 relative each) and the output to bf16 (half an ulp: 2^-9
#     relative, 7.8e-3 at |o| < 4); 2e-2 covers both with margin, and
#     fp8 or lower-precision KV would miss it (see FP8_ATOL).
REF_ATOL = {"float32": 1e-5, "bfloat16": 2e-2}


# ----------------------------------------------------------------------
# jitted endpoints and the float64 reference
# ----------------------------------------------------------------------

@jax.jit
def _oracle_decode(q, ck, cv, bt, kv_len):
    kg, vg = attention.gather_paged_cache(ck, cv, bt)
    return attention.decode_attention(q, kg, vg, kv_len)


@jax.jit
def _kernel_decode(q, ck, cv, bt, kv_len):
    return pk.paged_decode(q, ck, cv, bt, kv_len)


@jax.jit
def _oracle_chunk(q, ck, cv, bt, pos):
    kg, vg = attention.gather_paged_cache(ck, cv, bt)
    positions = pos[:, None] + jnp.arange(q.shape[1])[None, :]
    return attention.chunk_attention(q, kg, vg, positions)


@jax.jit
def _kernel_chunk(q, ck, cv, bt, pos):
    return pk.paged_chunk(q, ck, cv, bt, pos)


def _bitwise(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


def _ref(q, ck, cv, bt, pos):
    """float64 attention of q [B,C,H,hd] at positions pos[b] + i over
    each slot's blocks of the head-major pools [NB,KH,bs,hd]."""
    q = np.asarray(q, np.float64)
    ck, cv = np.asarray(ck, np.float64), np.asarray(cv, np.float64)
    bt = np.maximum(np.asarray(bt), 0)
    B, C, H, hd = q.shape
    NB, KH, bs, _ = ck.shape
    G, T = H // KH, bt.shape[1] * bs
    out = np.zeros(q.shape)
    for b in range(B):
        k = ck[bt[b]].transpose(1, 0, 2, 3).reshape(KH, T, hd)
        v = cv[bt[b]].transpose(1, 0, 2, 3).reshape(KH, T, hd)
        for i in range(C):
            seen = np.arange(T) <= int(pos[b]) + i
            for h in range(H):
                s = k[h // G] @ q[b, i, h] * hd ** -0.5
                w = np.exp(s - s[seen].max()) * seen
                out[b, i, h] = w @ v[h // G] / w.sum()
    return out


def _close(got, ref, dtype):
    err = np.abs(np.asarray(got, np.float64) - ref).max()
    assert err < REF_ATOL[dtype], (dtype, err)


def _mk(dtype, B, H, KH, hd, NB, bs, MB, seed, kv_lens=None):
    """Pool + per-slot table with -1 tails + ragged kv_len."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), dtype)
    ck = jnp.asarray(rng.standard_normal((NB, KH, bs, hd)), dtype)
    cv = jnp.asarray(rng.standard_normal((NB, KH, bs, hd)), dtype)
    T = MB * bs
    if kv_lens is None:
        kv_lens = [1, bs + bs // 2 + 1, T, T // 2 + 1, bs, 2 * bs - 1]
    kv_len = np.minimum(np.asarray(kv_lens[:B]), T).astype(np.int32)
    bt = rng.permutation(NB)[:B * MB].reshape(B, MB).astype(np.int32)
    for b in range(B):
        bt[b, -(-int(kv_len[b]) // bs):] = -1      # unallocated tail
    return q, ck, cv, jnp.asarray(bt), jnp.asarray(kv_len)


CASES = [
    # dtype      B  H  KH  hd  NB  bs  MB seed
    ("float32",  3, 4, 2, 32, 24,  8,  6, 0),    # G=2
    ("bfloat16", 3, 4, 2, 32, 24,  8,  6, 1),
    ("float32",  2, 6, 6, 16, 17,  4,  8, 3),    # G=1 matvec, small bs
    ("bfloat16", 4, 8, 2, 64, 32, 16,  4, 5),    # G=4, large bs
    ("float32",  6, 8, 4, 64, 40, 16,  5, 7),
]


@pytest.mark.parametrize("dtype,B,H,KH,hd,NB,bs,MB,seed", CASES)
def test_decode_bit_parity(dtype, B, H, KH, hd, NB, bs, MB, seed):
    q, ck, cv, bt, kv_len = _mk(dtype, B, H, KH, hd, NB, bs, MB, seed)
    ref = _ref(q, ck, cv, bt, np.asarray(kv_len) - 1)
    _close(_kernel_decode(q, ck, cv, bt, kv_len), ref, dtype)
    _close(_oracle_decode(q, ck, cv, bt, kv_len), ref, dtype)


@pytest.mark.parametrize("dtype,B,H,KH,hd,NB,bs,MB,seed", CASES)
def test_chunk_bit_parity(dtype, B, H, KH, hd, NB, bs, MB, seed):
    _, ck, cv, bt, kv_len = _mk(dtype, B, H, KH, hd, NB, bs, MB, seed)
    C = 4
    rng = np.random.default_rng(seed + 100)
    q = jnp.asarray(rng.standard_normal((B, C, H, hd)), dtype)
    pos = jnp.maximum(kv_len - C, 0)
    ref = _ref(q, ck, cv, bt, np.asarray(pos))
    # rows past a slot's table reach unallocated blocks; the caller
    # discards them (paged_chunk's contract), so compare valid rows
    valid = (np.asarray(pos)[:, None] + np.arange(C)[None, :]
             < np.asarray(kv_len)[:, None])
    for got in (_kernel_chunk(q, ck, cv, bt, pos),
                _oracle_chunk(q, ck, cv, bt, pos)):
        _close(np.asarray(got, np.float64)[valid], ref[valid], dtype)


@pytest.mark.parametrize("kv", [1, 7, 8, 9, 47, 48])
def test_decode_ragged_frontiers(kv):
    """Every flavor of partial/full last block, incl. kv_len=1 and the
    exactly-full table."""
    q, ck, cv, bt, kv_len = _mk("float32", 2, 4, 2, 32, 24, 8, 6, 11,
                                kv_lens=[kv, 48])
    ref = _ref(q, ck, cv, bt, np.asarray(kv_len) - 1)
    _close(_kernel_decode(q, ck, cv, bt, kv_len), ref, "float32")
    _close(_oracle_decode(q, ck, cv, bt, kv_len), ref, "float32")


def test_cow_fresh_block_parity():
    """COW in the serving allocator copies a shared block to a fresh
    physical index and repoints one slot's table entry.  Simulate the
    post-COW state: identical contents at a different index must give
    bitwise identical kernel outputs, still within tolerance of the
    reference."""
    q, ck, cv, bt, kv_len = _mk("bfloat16", 2, 4, 2, 32, 24, 8, 6, 13,
                                kv_lens=[20, 20])
    before = _kernel_decode(q, ck, cv, bt, kv_len)
    # copy slot 1's block 1 to an unused physical block, repoint
    used = set(np.asarray(bt).ravel().tolist())
    fresh = next(i for i in range(ck.shape[0]) if i not in used)
    src = int(bt[1, 1])
    ck = ck.at[fresh].set(ck[src])
    cv = cv.at[fresh].set(cv[src])
    bt = bt.at[1, 1].set(fresh)
    after = _kernel_decode(q, ck, cv, bt, kv_len)
    assert _bitwise(before, after)
    _close(after, _ref(q, ck, cv, bt, np.asarray(kv_len) - 1),
           "bfloat16")


# ----------------------------------------------------------------------
# unallocated-entry contract (satellite: poisoned pool blocks)
# ----------------------------------------------------------------------

def test_poisoned_block_finite_garbage_never_leaks():
    """Fill the clamp target (physical block 0) with huge finite
    garbage while no slot's valid prefix references it.  The gather
    path clamps -1 -> 0 and masks (0.0 softmax weight x finite = 0.0);
    the kernel path never touches it (the walk stops at the frontier).
    Both outputs must be bitwise identical to their clean-pool
    results."""
    q, ck, cv, bt, kv_len = _mk("float32", 3, 4, 2, 32, 24, 8, 6, 17,
                                kv_lens=[5, 20, 33])
    bt = np.array(bt)
    # move any use of physical block 0 elsewhere, then poison it
    free = [i for i in range(ck.shape[0]) if i not in set(bt.ravel())]
    bt[bt == 0] = free.pop()
    bt = jnp.asarray(bt)
    ckp = ck.at[0].set(1e30)
    cvp = cv.at[0].set(-1e30)
    assert _bitwise(_oracle_decode(q, ckp, cvp, bt, kv_len),
                    _oracle_decode(q, ck, cv, bt, kv_len))
    assert _bitwise(_kernel_decode(q, ckp, cvp, bt, kv_len),
                    _kernel_decode(q, ck, cv, bt, kv_len))


def test_poisoned_block_nan_kernel_never_reads_it():
    """NaN poison is the stronger probe: 0.0 * NaN != 0.0, so only a
    path that genuinely never READS unallocated blocks stays clean.
    The kernel's loop bound comes from kv_len, not the table width, so
    its output is bitwise the clean-pool result even with NaNs in the
    clamp target."""
    q, ck, cv, bt, kv_len = _mk("float32", 3, 4, 2, 32, 24, 8, 6, 19,
                                kv_lens=[5, 20, 33])
    bt = np.array(bt)
    free = [i for i in range(ck.shape[0]) if i not in set(bt.ravel())]
    bt[bt == 0] = free.pop()
    bt = jnp.asarray(bt)
    clean = _kernel_decode(q, ck, cv, bt, kv_len)
    ckp = ck.at[0].set(jnp.nan)
    cvp = cv.at[0].set(jnp.nan)
    got = _kernel_decode(q, ckp, cvp, bt, kv_len)
    assert np.isfinite(np.asarray(got)).all()
    assert _bitwise(got, clean)


# ----------------------------------------------------------------------
# fp8 tier
# ----------------------------------------------------------------------

@jax.jit
def _oracle_decode_fp8(q, cl, bt, kv_len):
    kg, vg = attention.gather_paged_cache_fp8(cl, bt, out_dtype=q.dtype)
    return attention.decode_attention(q, kg, vg, kv_len)


@jax.jit
def _kernel_decode_fp8(q, cl, bt, kv_len):
    return pk.paged_decode(q, cl["k"], cl["v"], bt, kv_len,
                           k_scale=cl["k_scale"], v_scale=cl["v_scale"])


def _mk_fp8(B, H, KH, hd, NB, bs, MB, seed):
    q, kf, vf, bt, kv_len = _mk("bfloat16", B, H, KH, hd, NB, bs, MB,
                                seed)
    ck, ks = te_fp8.quantize_rowwise(kf, te_fp8.E4M3)
    cv, vs = te_fp8.quantize_rowwise(vf, te_fp8.E4M3)
    cl = {"k": ck, "v": cv, "k_scale": ks[..., 0], "v_scale": vs[..., 0]}
    return q, kf, vf, cl, bt, kv_len


def _dequant(cl, name):
    """float64 dequantized pool: codes * per-row scale."""
    return (np.asarray(cl[name], np.float64)
            * np.asarray(cl[name + "_scale"], np.float64)[..., None])


def test_fp8_kernel_vs_fp8_gather_bitwise():
    """The fp8 kernel (scales after the contractions) and the fp8
    gather (dequantize to bf16, then attend) both match the reference
    over the same dequantized cache within the bf16 tolerance."""
    q, _, _, cl, bt, kv_len = _mk_fp8(3, 4, 2, 32, 24, 8, 6, 23)
    ref = _ref(q, _dequant(cl, "k"), _dequant(cl, "v"), bt,
               np.asarray(kv_len) - 1)
    _close(_kernel_decode_fp8(q, cl, bt, kv_len), ref, "bfloat16")
    _close(_oracle_decode_fp8(q, cl, bt, kv_len), ref, "bfloat16")


def test_fp8_chunk_kernel_vs_gather_bitwise():
    q, _, _, cl, bt, kv_len = _mk_fp8(3, 4, 2, 32, 24, 8, 6, 29)
    C = 4
    rng = np.random.default_rng(123)
    qc = jnp.asarray(rng.standard_normal((3, C, 4, 32)), jnp.bfloat16)
    pos = jnp.maximum(kv_len - C, 0)

    @jax.jit
    def kern(qc, cl, bt, pos):
        return pk.paged_chunk(qc, cl["k"], cl["v"], bt, pos,
                              k_scale=cl["k_scale"],
                              v_scale=cl["v_scale"])

    @jax.jit
    def oracle(qc, cl, bt, pos):
        kg, vg = attention.gather_paged_cache_fp8(cl, bt,
                                                  out_dtype=qc.dtype)
        positions = pos[:, None] + jnp.arange(C)[None, :]
        return attention.chunk_attention(qc, kg, vg, positions)

    ref = _ref(qc, _dequant(cl, "k"), _dequant(cl, "v"), bt,
               np.asarray(pos))
    valid = (np.asarray(pos)[:, None] + np.arange(C)[None, :]
             < np.asarray(kv_len)[:, None])
    for got in (kern(qc, cl, bt, pos), oracle(qc, cl, bt, pos)):
        _close(np.asarray(got, np.float64)[valid], ref[valid],
               "bfloat16")


def test_fp8_vs_bf16_tolerance():
    """fp8 KV vs the bf16 cache it was quantized from: bounded by the
    e4m3 quantization error (FP8_ATOL), NOT bit-exact."""
    q, kf, vf, cl, bt, kv_len = _mk_fp8(3, 4, 2, 32, 24, 8, 6, 31)
    a = np.asarray(_kernel_decode_fp8(q, cl, bt, kv_len), np.float32)
    b = np.asarray(_oracle_decode(q, kf, vf, bt, kv_len), np.float32)
    err = np.abs(a - b).max()
    assert 0 < err < FP8_ATOL, err   # quantized => different, but close


def test_fp8_scatter_gather_roundtrip():
    """update_paged_cache_fp8 writes codes+scales the dequantizing
    gather recovers to within the e4m3 bound."""
    B, KH, hd, NB, bs, MB, C = 3, 2, 32, 24, 8, 6, 2
    rng = np.random.default_rng(37)
    cl = attention.init_paged_kv_cache(NB, bs, KH, hd, layers=1,
                                       fp8=True)
    cl = jax.tree_util.tree_map(lambda x: x[0], cl)
    assert cl["k"].dtype == te_fp8.E4M3
    assert cl["k_scale"].shape == (NB, KH, bs)
    k1 = jnp.asarray(rng.standard_normal((B, C, KH, hd)), jnp.bfloat16)
    v1 = jnp.asarray(rng.standard_normal((B, C, KH, hd)), jnp.bfloat16)
    pos = jnp.asarray([0, 5, 9], jnp.int32)
    bt = jnp.asarray(rng.permutation(NB)[:B * MB].reshape(B, MB),
                     jnp.int32)
    cl = attention.update_paged_cache_fp8(cl, k1, v1, pos, bt)
    kg, vg = attention.gather_paged_cache_fp8(cl, bt,
                                              out_dtype=jnp.bfloat16)
    for b in range(B):
        p = int(pos[b])
        for got, ref in ((kg, k1), (vg, v1)):
            err = np.abs(np.asarray(got[b, p:p + C], np.float32)
                         - np.asarray(ref[b], np.float32)).max()
            assert err < FP8_ATOL, err


def test_ops_wrappers_delegate():
    """kernels/ops exposes the un-jitted serving wrappers."""
    q, ck, cv, bt, kv_len = _mk("float32", 2, 4, 2, 32, 24, 8, 6, 41)
    got = ops.paged_decode_attention(q, ck, cv, bt, kv_len)
    want = pk.paged_decode(q, ck, cv, bt, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ----------------------------------------------------------------------
# E2E: the serving flag
# ----------------------------------------------------------------------

from repro.runtime.server import (ChunkedServer, clone_requests,  # noqa: E402
                                  repetitive_requests,
                                  sharegpt_like_requests,
                                  sysprompt_sharegpt_requests)

KW = dict(batch_slots=3, max_len=64, chunk=8, span=4,
          block_size=8, prefix_cache=True, spec_decode=2)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("yi-6b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    mixes = {
        "sharegpt": sharegpt_like_requests(
            6, cfg.vocab_size, max_input=16, max_output=8, seed=3),
        # shared templates -> radix hits -> COW-fresh blocks mid-serve
        "sysprompt": sysprompt_sharegpt_requests(
            6, cfg.vocab_size, num_templates=2, template_len=12,
            max_input=20, max_output=6, seed=4),
        # high n-gram acceptance -> rollback-then-redecode waves
        "repetitive": repetitive_requests(
            4, cfg.vocab_size, motif_len=4, reps=3, max_output=10,
            seed=5),
    }
    return cfg, params, mixes


def _serve(cfg, params, reqs, **extra):
    rs = clone_requests(reqs)
    srv = ChunkedServer(cfg, params, **KW, **extra)
    stats = srv.serve(rs)
    assert all(r.done for r in rs)
    return [r.output for r in rs], srv, stats


@pytest.mark.parametrize("mix", ["sharegpt", "sysprompt", "repetitive"])
def test_e2e_kernel_flag_token_identical(setup, mix):
    """kernel=True emits the same greedy tokens as the gather path
    end-to-end with paged + prefix cache + spec decode all on.  The two
    read paths round differently (the unit tier above), so this pins
    that no logit gap on these mixes is small enough to flip an argmax
    — and that COW, rollback and radix sharing feed the kernel the
    same KV the gather path reads."""
    cfg, params, mixes = setup
    base, _, _ = _serve(cfg, params, mixes[mix])
    kern, srv, _ = _serve(cfg, params, mixes[mix], kernel=True)
    assert base == kern
    counts = srv.compile_counts()
    assert counts["chunk_step"] == 1, counts
    assert counts["verify_step"] == 1, counts
    assert counts["decode_span"] in (0, 1), counts


def test_e2e_fp8_kv_pool_shrink(setup):
    """fp8_kv completes the mix and shrinks the per-device pool by
    exactly (hd + 4)/(2*hd): e4m3 codes + one f32 scale per token-row
    per kv-head vs bf16."""
    cfg, params, mixes = setup
    outs, _, st = _serve(cfg, params, mixes["sharegpt"], kernel=True)
    outs8, _, st8 = _serve(cfg, params, mixes["sharegpt"], kernel=True,
                           fp8_kv=True)
    hd = cfg.head_dim
    assert (st8["kv_bytes_per_device"] / st["kv_bytes_per_device"]
            == (hd + 4) / (2 * hd))
    # same request set, same lengths served (content may differ within
    # the quantization tolerance tier)
    assert [len(o) for o in outs8] == [len(o) for o in outs]


def test_e2e_fp8_kv_gather_path_matches_kernel(setup):
    """On fp8 pools both read paths serve the whole mix, and their
    prompt logits (ChunkedServer.prompt_logits) sit within the fp8 tier
    of the bf16 gather path's and of each other's.

    Tier: max |dlogit| < 0.6 x RMS(logits).  Each layer requantizes its
    k/v to e4m3 (3-bit mantissa, 6% steps), so an upstream rounding
    difference can flip codes and grow layer by layer: on this mix both
    fp8 paths measure 0.35-0.40 x RMS from bf16 and 0.22 x RMS from each
    other.  A read path that dropped or misapplied the scales misses by
    more than 1 x RMS."""
    cfg, params, mixes = setup
    a, srv_a, _ = _serve(cfg, params, mixes["sharegpt"], fp8_kv=True)
    b, srv_b, _ = _serve(cfg, params, mixes["sharegpt"], fp8_kv=True,
                         kernel=True)
    assert [len(o) for o in a] == [len(o) for o in b]
    prompts = [r.prompt for r in mixes["sharegpt"]]
    ref = ChunkedServer(cfg, params, **KW).prompt_logits(prompts)
    tol = 0.6 * np.sqrt(np.mean(ref ** 2))
    la = srv_a.prompt_logits(prompts)
    lb = srv_b.prompt_logits(prompts)
    for got in (la, lb):
        assert np.abs(got - ref).max() < tol, np.abs(got - ref).max()
    assert np.abs(la - lb).max() < tol, np.abs(la - lb).max()


def test_e2e_fp8_linear_serves(setup):
    """fp8 weights+activations on every serving linear: completes the
    mix with the right output lengths (a quality tier, not a parity
    tier — fp8 matmuls round differently by design)."""
    cfg, params, mixes = setup
    outs, _, _ = _serve(cfg, params, mixes["sharegpt"], kernel=True,
                        fp8_kv=True, fp8_linear=True)
    base, _, _ = _serve(cfg, params, mixes["sharegpt"])
    assert [len(o) for o in outs] == [len(o) for o in base]


def test_e2e_fp8_kernel_spec_compiles_once(setup):
    """The fp8 pool read through the kernel with speculation on keeps
    O(1) programs: every mix in turn through one server (radix hits,
    COW copies and rollbacks included) compiles each program at most
    once, chunk_step and verify_step exactly once, and serving the
    first mix again compiles nothing new."""
    cfg, params, mixes = setup
    srv = ChunkedServer(cfg, params, **{**KW, "spec_decode": 3},
                        kernel=True, fp8_kv=True)
    for name in list(mixes) + ["sharegpt"]:
        rs = clone_requests(mixes[name])
        srv.serve(rs)
        assert all(r.done for r in rs), name
        counts = srv.compile_counts()
        assert all(v <= 1 for v in counts.values()), (name, counts)
    assert counts["chunk_step"] == 1, counts
    assert counts["verify_step"] == 1, counts
    assert counts["decode_span"] in (0, 1), counts


# ----------------------------------------------------------------------
# tp=2 kernel parity on a forced 8-device mesh (subprocess)
# ----------------------------------------------------------------------

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys, json
sys.path.insert(0, os.path.join(%(root)r, "src"))
import jax
assert jax.device_count() >= 8
from repro.configs import reduced_config
from repro.models import api
from repro.runtime.server import (ChunkedServer, clone_requests,
                                  sharegpt_like_requests)

cfg = reduced_config("yi-6b")
params = api.init(cfg, jax.random.PRNGKey(0))
reqs = sharegpt_like_requests(6, cfg.vocab_size, max_input=16,
                              max_output=8, seed=3)
KW = dict(batch_slots=3, max_len=64, chunk=8, span=4,
          block_size=8, prefix_cache=True, spec_decode=2)

outs = {}
for name, extra in (("ref", {}), ("tp1_kern", {"kernel": True}),
                    ("tp2_kern", {"kernel": True, "tp": 2})):
    rs = clone_requests(reqs)
    srv = ChunkedServer(cfg, params, **KW, **extra)
    srv.serve(rs)
    assert all(r.done for r in rs)
    outs[name] = [r.output for r in rs]
print(json.dumps({
    "tp2_kernel_vs_gather": outs["tp2_kern"] == outs["ref"],
    "tp2_vs_tp1_kernel": outs["tp2_kern"] == outs["tp1_kern"],
}))
"""


def test_tp2_kernel_token_parity():
    """The sharded kernel (shard_map over the KV-head axis) keeps
    bitwise greedy parity with both the tp=1 kernel and the tp=1
    gather reference."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD % {"root": ROOT}],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.splitlines()[-1])
    assert res["tp2_kernel_vs_gather"]
    assert res["tp2_vs_tp1_kernel"]

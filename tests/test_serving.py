"""Chunked-prefill serving runtime: chunk math, greedy token parity
with a scheduler-free reference (``conftest.greedy_reference``), O(1)
compilation, and scheduler behavior."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import greedy_reference

from repro.analysis.jaxpr_check import iter_eqns
from repro.configs import reduced_config
from repro.models import api, transformer
from repro.runtime.server import (ChunkedServer, Server, Request,
                                  clone_requests, repetitive_requests,
                                  sharegpt_like_requests,
                                  sysprompt_sharegpt_requests)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("yi-6b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


MIXES = {
    "sharegpt": lambda V: sharegpt_like_requests(
        6, V, max_input=16, max_output=8, seed=3),
    "sysprompt": lambda V: sysprompt_sharegpt_requests(
        8, V, num_templates=2, template_len=24, max_input=40,
        max_output=8, seed=3),
    "repetitive": lambda V: repetitive_requests(
        4, V, motif_len=8, reps=3, max_output=24, seed=0),
}
VARIANTS = {
    "defaults": {},
    "no_prefix_cache": {"prefix_cache": False},
    "spec_decode": {"spec_decode": 4},
}
PARITY_MAX_LEN = 64


@pytest.fixture(scope="module")
def parity(setup):
    """One server per variant and one reference per mix, built once
    for the module; every mix is served through each variant in turn,
    so later mixes also meet the radix tree and n-gram table the
    earlier ones left behind."""
    cfg, params = setup
    servers = {name: ChunkedServer(cfg, params, batch_slots=3,
                                   max_len=PARITY_MAX_LEN, chunk=8,
                                   span=4, **kw)
               for name, kw in VARIANTS.items()}
    refs = {}

    def reference(mix):
        if mix not in refs:
            reqs = MIXES[mix](cfg.vocab_size)
            refs[mix] = (reqs, greedy_reference(cfg, params, reqs,
                                                max_len=PARITY_MAX_LEN))
        return refs[mix]
    return servers, reference


def test_default_server_is_chunked():
    assert Server is ChunkedServer


def test_chunk_step_matches_decode_path(setup):
    """Chunked prefill matches the token-at-a-time decode path bit for
    bit here on the CPU (same bf16 activations, same cache contents;
    the attention dots' f32 sums round alike at these shapes)."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    B, L, C = 2, 13, 4
    prompts = rng.integers(0, cfg.vocab_size, (B, L)).astype(np.int32)

    ref_cache = api.init_cache(cfg, B, 32)
    pos = jnp.zeros((B,), jnp.int32)
    for t in range(L):
        ref_logits, ref_cache = transformer.decode_step(
            cfg, params, ref_cache, jnp.asarray(prompts[:, t]), pos)
        pos = pos + 1

    cache = api.init_cache(cfg, B, 32 + C)
    pos = jnp.zeros((B,), jnp.int32)
    off = 0
    while off < L:
        n = min(C, L - off)
        chunk = np.zeros((B, C), np.int32)
        chunk[:, :n] = prompts[:, off:off + n]
        logits, cache = api.chunk_step(
            cfg, params, cache, jnp.asarray(chunk), pos,
            jnp.full((B,), n, jnp.int32))
        pos = pos + n
        off += n

    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(ref_logits))
    T = ref_cache["k"].shape[2]
    np.testing.assert_array_equal(
        np.asarray(cache["k"][:, :, :L], jnp.float32),
        np.asarray(ref_cache["k"][:, :, :L], jnp.float32))


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("mix", list(MIXES))
def test_chunked_matches_greedy_reference(parity, mix, variant):
    """Greedy tokens of every mix, through the default server, the one
    without a prefix cache and the speculative one, equal the
    scheduler-free reference token for token; each program compiles at
    most once and the pool's peak stays inside the pool."""
    servers, reference = parity
    reqs, (ref, ref_trunc) = reference(mix)
    srv = servers[variant]
    got = clone_requests(reqs)
    stats = srv.serve(got)
    assert all(r.done for r in got)
    for r, want, trunc in zip(got, ref, ref_trunc):
        assert r.output == want, (r.rid, r.output, want)
        assert r.truncated == trunc
    counts = srv.compile_counts()
    assert all(0 <= v <= 1 for v in counts.values()), counts
    assert stats["peak_blocks_in_use"] <= stats["pool_blocks"]


def test_compile_count_independent_of_prompt_lengths(setup):
    """8 prompts of 8 distinct lengths -> a bounded number of compiled
    programs; a second batch with 8 MORE distinct lengths compiles
    nothing new."""
    cfg, params = setup
    rng = np.random.default_rng(0)

    def batch(lengths, rid0):
        return [Request(rid=rid0 + i,
                        prompt=rng.integers(0, cfg.vocab_size, n
                                            ).astype(np.int32),
                        max_new=4)
                for i, n in enumerate(lengths)]

    srv = ChunkedServer(cfg, params, batch_slots=4, max_len=64,
                        chunk=8, span=4)
    srv.serve(batch(range(3, 11), 0))            # 8 distinct lengths
    counts = srv.compile_counts()
    assert all(v >= 0 for v in counts.values()), counts
    assert sum(counts.values()) <= 3, counts

    srv.serve(batch(range(11, 19), 100))         # 8 new distinct lengths
    assert srv.compile_counts() == counts


def test_chunked_server_respects_limits(setup):
    cfg, params = setup
    srv = ChunkedServer(cfg, params, batch_slots=3, max_len=64,
                        chunk=8, span=4)
    reqs = sharegpt_like_requests(5, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=2)
    stats = srv.serve(reqs)
    assert all(r.done for r in reqs)
    assert stats["requests"] == 5
    assert stats["tokens_per_s"] > 0
    assert stats["prefill_tokens"] == sum(len(r.prompt) for r in reqs)
    assert stats["decode_tokens"] == sum(len(r.output) for r in reqs)
    for r in reqs:
        assert 1 <= len(r.output) <= r.max_new
        assert all(0 <= t < cfg.vocab_size for t in r.output)


def test_max_new_one_and_oversized_prompt(setup):
    """max_new=1 yields exactly one token, the reference's; prompts
    longer than max_len are rejected loudly instead of being served
    cut."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    reqs = [Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, 5).astype(np.int32), max_new=1)]
    ref, _ = greedy_reference(cfg, params, reqs, max_len=32)
    b = clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=2, max_len=32,
                  chunk=4, span=2).serve(b)
    assert len(b[0].output) == 1
    assert b[0].output == ref[0]

    too_long = [Request(rid=1, prompt=rng.integers(
        0, cfg.vocab_size, 40).astype(np.int32), max_new=4)]
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=32,
                        chunk=4, span=2)
    with pytest.raises(ValueError, match="exceeds max_len"):
        srv.serve(clone_requests(too_long))


def test_chunk_larger_than_longest_prompt(setup):
    """Whole-prompt-in-one-chunk degenerate case still serves."""
    cfg, params = setup
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64,
                        chunk=32, span=2)
    reqs = sharegpt_like_requests(3, cfg.vocab_size, max_input=12,
                                  max_output=6, seed=5)
    srv.serve(reqs)
    assert all(r.done for r in reqs)
    assert all(len(r.output) >= 1 for r in reqs)


# ----------------------------------------------------------------------
# paged KV cache
# ----------------------------------------------------------------------

def test_paged_block_reuse_no_stale_kv(setup):
    """Two request waves through the same pool: wave 2 decodes on
    recycled physical blocks and must match a fresh server bit for bit
    (any stale wave-1 KV leaking through the block table would split
    the outputs).  With the prefix cache on by default, wave-1 blocks
    stay tree-resident (refcount 0, evictable) after harvest instead of
    returning to the free list; wave 2's disjoint prompts match nothing
    and recycle them through LRU eviction."""
    cfg, params = setup
    wave1 = sharegpt_like_requests(5, cfg.vocab_size, max_input=16,
                                   max_output=8, seed=21)
    wave2 = sharegpt_like_requests(5, cfg.vocab_size, max_input=16,
                                   max_output=8, seed=22)
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64,
                        chunk=8, span=4, block_size=8)
    srv.serve(wave1)
    # every block reference dropped at harvest; blocks are either free
    # or cached-and-evictable, never leaked
    assert int(srv.pool.refcount.sum()) == 0
    assert (srv.pool.num_free() + srv.prefix_cache.cached_block_count()
            == srv.num_blocks)
    assert (srv.block_table == -1).all()
    reused = clone_requests(wave2)
    srv.serve(reused)
    fresh = clone_requests(wave2)
    ChunkedServer(cfg, params, batch_slots=2, max_len=64,
                  chunk=8, span=4, block_size=8).serve(fresh)
    for ra, rb in zip(reused, fresh):
        assert ra.output == rb.output, (ra.rid, ra.output, rb.output)


def test_paged_pool_backpressure(setup):
    """A pool too small for every slot at once stalls admission until a
    harvest frees blocks, instead of failing or corrupting state."""
    cfg, params = setup
    reqs = sharegpt_like_requests(6, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=13)
    # each request reserves at most ceil(24/8)=3 blocks; 4 blocks force
    # one-at-a-time admission even though 3 slots exist
    srv = ChunkedServer(cfg, params, batch_slots=3, max_len=64,
                        chunk=8, span=4, block_size=8,
                        num_blocks=4)
    stats = srv.serve(clone_requests(reqs))
    assert stats["admission_stalls"] > 0
    assert stats["peak_blocks_in_use"] <= 4
    # throttled admission must not change the greedy outputs
    throttled = clone_requests(reqs)
    srv2 = ChunkedServer(cfg, params, batch_slots=3, max_len=64,
                         chunk=8, span=4, block_size=8,
                         num_blocks=4)
    srv2.serve(throttled)
    roomy = clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=3, max_len=64,
                  chunk=8, span=4, block_size=8).serve(roomy)
    for ra, rb in zip(throttled, roomy):
        assert ra.output == rb.output, (ra.rid, ra.output, rb.output)


def test_paged_pool_too_small_raises(setup):
    """A request that can never fit the pool raises instead of hanging."""
    cfg, params = setup
    rng = np.random.default_rng(4)
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64,
                        chunk=8, span=4, block_size=8,
                        num_blocks=2)
    req = Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, 30).astype(np.int32), max_new=8)
    with pytest.raises(ValueError, match="grow num_blocks"):
        srv.serve([req])


def test_truncation_flagged_both_engines(setup):
    """in_len + max_new past the pos cap is no longer a silent short
    harvest: the request is flagged truncated at admission and capped at
    max_len - in_len tokens, with the reference's tokens and flags."""
    cfg, params = setup
    rng = np.random.default_rng(9)
    in_len, max_len = 28, 32
    prompt = rng.integers(0, cfg.vocab_size, in_len).astype(np.int32)
    reqs = [Request(rid=0, prompt=prompt, max_new=16),
            Request(rid=1, prompt=prompt.copy(), max_new=2)]
    ref, ref_trunc = greedy_reference(cfg, params, reqs, max_len=max_len)
    b = clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=2, max_len=max_len,
                  chunk=8, span=4).serve(b)
    assert b[0].truncated and ref_trunc[0]
    assert len(b[0].output) == max_len - in_len
    assert not b[1].truncated and not ref_trunc[1]
    assert len(b[1].output) == 2
    assert [r.output for r in b] == ref


def test_host_mirror_dtypes_are_int32(setup):
    """Host mirror arrays feed jit operands; any drift (the old
    prompt_off was int64) risks a retrace or a silent upcast."""
    cfg, params = setup
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=32,
                        chunk=4, span=2)
    assert srv.pos.dtype == np.int32
    assert srv.out_len.dtype == np.int32
    assert srv.prompt_off.dtype == np.int32
    assert srv.block_table.dtype == np.int32
    reqs = sharegpt_like_requests(3, cfg.vocab_size, max_input=8,
                                  max_output=4, seed=6)
    srv.serve(reqs)
    assert srv.pos.dtype == np.int32
    assert srv.out_len.dtype == np.int32
    assert srv.prompt_off.dtype == np.int32
    assert srv.block_table.dtype == np.int32


def test_decode_span_serve_is_transfer_free(setup):
    """The serve loop's decode spans must run under
    jax.transfer_guard("disallow"): every host->device operand crosses
    through the server's explicit device_put and readbacks are
    explicit device_get — the dynamic pin of the transfer-free
    contract the static analyzer (repro.analysis, AST001) checks at
    the source level.  The first wave compiles the work units outside
    the guard (compilation materializes jit constants, a one-time
    cost); the second wave dispatches fully guarded."""
    cfg, params = setup
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64,
                        chunk=8, span=4)
    warm = sharegpt_like_requests(4, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=11)
    srv.serve(warm)
    wave = sharegpt_like_requests(4, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=12)
    with jax.transfer_guard("disallow"):
        stats = srv.serve(wave)
    assert all(r.done for r in wave)
    assert stats["decode_steps"] > 0


@pytest.mark.parametrize("step", ["chunk", "decode"])
def test_serving_attention_contracts_on_mxu(step):
    """The gather-path attention of chunk_step and decode_step is two
    dot_generals (scores and PV) on the bf16 operands as they are, at
    Precision.HIGHEST with a float32 result — the MXU form — and no
    float32 value of rank >= 6 (the [B,KH,G,C,T,hd] broadcast product
    a multiply + jnp.sum form materializes) appears anywhere."""
    cfg = dataclasses.replace(reduced_config("yi-6b"), num_heads=8,
                              num_kv_heads=2)            # GQA, G=4
    params = api.init(cfg, jax.random.PRNGKey(0))
    B, C, max_len, bs = 2, 4, 32, 8
    T = max_len
    cache = api.init_cache(cfg, B, max_len, paged=True, block_size=bs)
    bt = jnp.arange(B * (max_len // bs), dtype=jnp.int32).reshape(B, -1)
    pos = jnp.zeros((B,), jnp.int32)
    if step == "chunk":
        fn = lambda c: transformer.chunk_step(
            cfg, params, c, jnp.zeros((B, C), jnp.int32), pos,
            jnp.full((B,), C, jnp.int32), bt)
    else:
        fn = lambda c: transformer.decode_step(
            cfg, params, c, jnp.zeros((B,), jnp.int32), pos, bt)
    eqns = list(iter_eqns(jax.make_jaxpr(fn)(cache).jaxpr))

    attn = [e for e in eqns if e.primitive.name == "dot_general"
            and e.params["precision"] is not None
            and jax.lax.Precision.HIGHEST in e.params["precision"]]
    assert len(attn) == 2, [e.params for e in attn]
    scores, pv = attn
    for e in attn:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
        assert e.params["preferred_element_type"] == jnp.float32
        assert e.outvars[0].aval.dtype == jnp.float32
        assert e.invars[1].aval.shape == (B, T, cfg.num_kv_heads,
                                          cfg.head_dim)
    assert scores.outvars[0].aval.shape[-1] == T
    assert pv.outvars[0].aval.shape[-1] == cfg.head_dim

    wide = [v.aval.str_short() for e in eqns for v in e.outvars
            if getattr(v.aval, "ndim", 0) >= 6
            and v.aval.dtype == jnp.float32]
    assert not wide, wide

"""Speculative decoding subsystem: n-gram proposer unit math, greedy
bit-parity of ``spec_decode=K`` vs ``K=0`` across the benchmark mixes,
block-table rollback hygiene, EOS-inside-a-draft-run handling, and the
O(1) compile budget (`verify_step` compiles exactly once)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import api
from repro.runtime import spec_decode as spec
from repro.runtime.server import (ChunkedServer, Request, clone_requests,
                                  repetitive_requests,
                                  sharegpt_like_requests,
                                  sysprompt_sharegpt_requests)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("yi-6b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _outputs_match(a, b):
    assert all(r.done for r in a) and all(r.done for r in b)
    for ra, rb in zip(a, b):
        assert ra.output == rb.output, (ra.rid, ra.output, rb.output)


# ----------------------------------------------------------------------
# proposer / acceptance unit math (pure jnp, no model)
# ----------------------------------------------------------------------

def test_accept_greedy_longest_prefix():
    drafts = jnp.asarray([[5, 6, 7], [5, 6, 7], [1, 2, 3], [9, 9, 9]],
                         jnp.int32)
    preds = jnp.asarray([[5, 6, 7, 8],      # all accepted
                         [5, 0, 7, 8],      # mismatch at 1 stops there
                         [0, 2, 3, 4],      # first draft wrong: none
                         [9, 9, 9, 9]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(spec.accept_greedy(drafts, preds)), [3, 1, 0, 3])


def test_ngram_update_then_propose_roundtrip():
    """Runs learned from the output buffer come back as drafts for
    their 2-token context; contexts reaching into the prompt (p < 2)
    and inactive slots are dropped."""
    K, n_ctx, T = 3, 64, 16
    table = spec.init_ngram_table(K, n_ctx)
    out_buf = jnp.zeros((2, T), jnp.int32)
    seq = jnp.asarray([11, 12, 13, 14, 15, 16, 17], jnp.int32)
    out_buf = out_buf.at[0, :7].set(seq)
    out_len = jnp.asarray([7, 0], jnp.int32)
    active = jnp.asarray([True, True])
    table = spec.update_ngram(table, out_buf, out_len, active)
    # context (13, 14) -> the run that followed: [15, 16, 17]
    drafts = spec.propose(table, jnp.asarray([14, 0], jnp.int32),
                          out_buf, jnp.asarray([4, 0], jnp.int32))
    np.testing.assert_array_equal(np.asarray(drafts[0]), [15, 16, 17])
    # slot 1 never emitted: its (0-sentinel) context must stay unset
    np.testing.assert_array_equal(np.asarray(drafts[1]), [0, 0, 0])


# ----------------------------------------------------------------------
# end-to-end bit-parity with the span loop
# ----------------------------------------------------------------------

def test_spec_matches_span_on_sharegpt_mix(setup):
    """spec_decode=K must be greedy bit-identical to K=0 on the
    log-normal ShareGPT mix (paged pool + prefix cache on), with the
    verify program compiled exactly once."""
    cfg, params = setup
    reqs = sharegpt_like_requests(6, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=3)
    a, b = clone_requests(reqs), clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=3, max_len=64, chunk=8,
                  span=4).serve(a)
    srv = ChunkedServer(cfg, params, batch_slots=3, max_len=64, chunk=8,
                        span=4, spec_decode=4)
    stats = srv.serve(b)
    _outputs_match(a, b)
    counts = srv.compile_counts()
    assert counts["verify_step"] == 1, counts
    assert sum(max(v, 0) for v in counts.values()) <= 3, counts
    assert stats["spec_steps"] > 0
    # every dispatch emits at least the bonus token per active slot
    assert stats["spec_tokens_per_step"] >= 1.0


def test_spec_matches_span_on_sysprompt_mix(setup):
    """Shared-prefix traffic with the radix cache AND spec decode on:
    still bit-identical to the plain span loop, tree invariants hold."""
    cfg, params = setup
    reqs = sysprompt_sharegpt_requests(8, cfg.vocab_size, num_templates=2,
                                       template_len=24, max_input=40,
                                       max_output=8, seed=3)
    a, b = clone_requests(reqs), clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=3, max_len=64, chunk=8,
                  span=4).serve(a)
    srv = ChunkedServer(cfg, params, batch_slots=3, max_len=64, chunk=8,
                        span=4, spec_decode=4)
    stats = srv.serve(b)
    _outputs_match(a, b)
    assert stats["prefix_hit_requests"] > 0    # sharing really happened
    srv.prefix_cache.check_invariants()
    # warm wave: tree hits + spec decode together, still bit-identical
    c = clone_requests(reqs)
    srv.serve(c)
    _outputs_match(a, c)
    srv.prefix_cache.check_invariants()


def test_spec_off_by_default_keeps_span_path(setup):
    cfg, params = setup
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=32,
                        chunk=4, span=2)
    assert srv.spec_decode == 0
    stats = srv.serve(sharegpt_like_requests(2, cfg.vocab_size,
                                             max_input=8, max_output=4,
                                             seed=1))
    assert "verify_step" not in srv.compile_counts()
    assert "spec_steps" not in stats


# ----------------------------------------------------------------------
# acceptance rate + rollback hygiene
# ----------------------------------------------------------------------

def test_ngram_acceptance_on_repetitive_workload(setup):
    """Warm re-serve of a repetitive mix: the shared suffix table has
    seen every continuation, so most drafts must be accepted (> 0.5)
    and each verify dispatch must emit well over one token per slot."""
    cfg, params = setup
    reqs = repetitive_requests(4, cfg.vocab_size, motif_len=8, reps=3,
                               max_output=32, seed=0)
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=96, chunk=8,
                        span=4, spec_decode=4)
    srv.serve(clone_requests(reqs))            # cold wave learns the mix
    warm = clone_requests(reqs)
    stats = srv.serve(warm)
    assert stats["spec_acceptance_rate"] > 0.5, stats
    assert stats["spec_tokens_per_step"] > 1.5, stats
    base = clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=2, max_len=96, chunk=8,
                  span=4).serve(base)
    _outputs_match(base, warm)


def test_rollback_no_stale_kv_across_waves(setup):
    """Rejected drafts write KV beyond the accepted frontier; rollback
    truncates the block-table frontier and returns over-allocated
    blocks.  Recycling those blocks in a later, disjoint wave must be
    bit-identical to a fresh server — any stale draft KV leaking
    through a reused block would split the outputs."""
    cfg, params = setup
    wave1 = sharegpt_like_requests(4, cfg.vocab_size, max_input=16,
                                   max_output=8, seed=31)
    wave2 = sharegpt_like_requests(4, cfg.vocab_size, max_input=16,
                                   max_output=8, seed=32)
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                        span=4, spec_decode=4)
    srv.serve(wave1)
    # rollback restored every reservation and dropped every reference
    assert srv._reserved_total == 0
    assert int(srv.pool.refcount.sum()) == 0
    assert (srv.block_table == -1).all()
    reused = clone_requests(wave2)
    srv.serve(reused)
    fresh = clone_requests(wave2)
    ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                  span=4, spec_decode=4).serve(fresh)
    _outputs_match(reused, fresh)
    srv.prefix_cache.check_invariants()


def test_spec_pool_accounting_under_pressure(setup):
    """Spec decode over a tight pool: admission backpressure, verify
    over-allocation and rollback must keep the refcount partition and
    reservations exact across waves (and outputs bit-identical)."""
    cfg, params = setup
    reqs = sharegpt_like_requests(6, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=13)
    srv = ChunkedServer(cfg, params, batch_slots=3, max_len=64, chunk=8,
                        span=4, block_size=8, num_blocks=4, spec_decode=4)
    stats = srv.serve(clone_requests(reqs))
    assert stats["admission_stalls"] > 0
    assert stats["peak_blocks_in_use"] <= 4
    assert srv._reserved_total == 0
    assert int(srv.pool.refcount.sum()) == 0
    roomy = clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=3, max_len=64, chunk=8,
                  span=4, block_size=8).serve(roomy)
    got = clone_requests(reqs)
    srv2 = ChunkedServer(cfg, params, batch_slots=3, max_len=64, chunk=8,
                         span=4, block_size=8, num_blocks=4, spec_decode=4)
    srv2.serve(got)
    _outputs_match(roomy, got)


# ----------------------------------------------------------------------
# EOS inside an accepted draft run
# ----------------------------------------------------------------------

def test_eos_in_draft_run_parity(setup):
    """A slot finishing mid-verify (EOS lands inside the accepted
    window) must truncate its output at the EOS position — identical
    to the span loop's one-at-a-time stopping — and the truncated
    prefix must be inserted cleanly (warm re-serve stays identical)."""
    cfg, params = setup
    reqs = repetitive_requests(3, cfg.vocab_size, motif_len=8, reps=3,
                               max_output=24, seed=2)
    ref = clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=2, max_len=96, chunk=8,
                  span=4).serve(ref)
    # an EOS from late in a long output: by then the warm table drafts
    # whole windows, so the EOS falls inside an accepted run
    donor = max(ref, key=lambda r: len(r.output))
    eos = donor.output[int(len(donor.output) * 3 / 4)]

    def truncated(out):
        return out[:out.index(eos) + 1] if eos in out else out

    span_srv = ChunkedServer(cfg, params, batch_slots=2, max_len=96,
                             chunk=8, span=4, eos_id=eos)
    spec_srv = ChunkedServer(cfg, params, batch_slots=2, max_len=96,
                             chunk=8, span=4, eos_id=eos, spec_decode=4)
    spec_srv.serve(clone_requests(reqs))       # warm the suffix table
    span_srv.serve(clone_requests(reqs))
    a, b = clone_requests(reqs), clone_requests(reqs)
    span_srv.serve(a)
    stats = spec_srv.serve(b)
    stopped_early = 0
    for rr, ra, rb in zip(ref, a, b):
        want = truncated(rr.output)
        assert ra.output == want, rr.rid
        assert rb.output == want, rr.rid
        stopped_early += len(want) < len(rr.output)
    assert stopped_early > 0
    # the warm wave really was speculative when the EOS hit
    assert stats["spec_tokens_per_step"] > 1.0
    spec_srv.prefix_cache.check_invariants()


def test_eos_none_spec_matches_eos_none_span(setup):
    """eos_id=None with spec decode: length-only stopping, still
    bit-identical to the span loop."""
    cfg, params = setup
    reqs = sysprompt_sharegpt_requests(3, cfg.vocab_size, num_templates=1,
                                       template_len=8, max_input=16,
                                       max_output=6, seed=5)
    a, b = clone_requests(reqs), clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                  span=4, eos_id=None).serve(a)
    ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                  span=4, eos_id=None, spec_decode=3).serve(b)
    for ra, rb in zip(a, b):
        assert len(ra.output) == ra.max_new
        assert ra.output == rb.output


def test_spec_decode_serve_is_transfer_free(setup):
    """Speculative serving under jax.transfer_guard("disallow"):
    draft/verify/accept bookkeeping syncs through explicit device_get
    and every scheduler operand through explicit device_put, so the
    data-dependent spec path is exactly as transfer-disciplined as the
    length-only span path (the contract repro.analysis AST001 pins
    statically).  Wave 1 compiles outside the guard; wave 2 serves
    fully guarded and must stay bit-identical to the span oracle."""
    cfg, params = setup
    reqs = sharegpt_like_requests(4, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=21)
    a, b = clone_requests(reqs), clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                  span=4).serve(a)
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64,
                        chunk=8, span=4, spec_decode=3)
    warm = sharegpt_like_requests(4, cfg.vocab_size, max_input=16,
                                  max_output=8, seed=22)
    srv.serve(warm)
    with jax.transfer_guard("disallow"):
        stats = srv.serve(b)
    for ra, rb in zip(a, b):
        assert ra.output == rb.output
    assert stats["spec_steps"] > 0

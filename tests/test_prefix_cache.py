"""Radix-tree prefix cache: tree/pool unit tests plus end-to-end
sharing semantics through ChunkedServer — copy-on-write divergence,
refcount invariants across admit/harvest/evict waves, LRU eviction
under pool pressure, and cache-aware admission."""

import jax
import numpy as np
import pytest
from conftest import greedy_reference

from repro.configs import reduced_config
from repro.models import api
from repro.runtime.prefix_cache import BlockPool, RadixPrefixCache
from repro.runtime.server import (ChunkedServer, Request, clone_requests,
                                  sysprompt_sharegpt_requests)

BS = 4  # block size for the unit tests


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("yi-6b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _tree(num_blocks=32):
    pool = BlockPool(num_blocks)
    return pool, RadixPrefixCache(pool, BS)


def _toks(*vals):
    return np.asarray(vals, np.int32)


def _run(rng, nblocks):
    return rng.integers(0, 100, nblocks * BS).astype(np.int32)


# ----------------------------------------------------------------------
# radix tree unit tests
# ----------------------------------------------------------------------

def test_insert_match_roundtrip():
    pool, tree = _tree()
    rng = np.random.default_rng(0)
    run = _run(rng, 3)
    blocks = [pool.alloc() for _ in range(3)]
    assert tree.insert(run, blocks) == 3
    full, partial, plen = tree.match(run)
    assert full == blocks and partial is None and plen == 0
    # a longer prompt matches the cached prefix only
    longer = np.concatenate([run, _toks(1, 2, 3, 4, 5)])
    full, partial, plen = tree.match(longer)
    assert full == blocks and partial is None and plen == 0
    # a shorter block-aligned prompt matches its covered blocks
    full, partial, plen = tree.match(run[:2 * BS])
    assert full == blocks[:2]
    tree.check_invariants()


def test_match_partial_block():
    pool, tree = _tree()
    rng = np.random.default_rng(1)
    run = _run(rng, 2)
    blocks = [pool.alloc(), pool.alloc()]
    tree.insert(run, blocks)
    # diverge 2 tokens into the second block
    probe = run.copy()
    probe[BS + 2] += 1
    full, partial, plen = tree.match(probe)
    assert full == blocks[:1]
    assert partial == blocks[1] and plen == 2
    # prompt shorter than one block: partial hit on the first block
    full, partial, plen = tree.match(run[:BS - 1])
    assert full == [] and partial == blocks[0] and plen == BS - 1


def test_insert_split_and_dedup():
    pool, tree = _tree()
    rng = np.random.default_rng(2)
    a = _run(rng, 4)
    b = a.copy()
    b[2 * BS] += 1                       # diverge at block 2
    blocks_a = [pool.alloc() for _ in range(4)]
    blocks_b = [pool.alloc() for _ in range(4)]
    assert tree.insert(a, blocks_a) == 4
    # shared prefix blocks are deduplicated: only b's divergent suffix
    # is adopted, its duplicate prefix blocks stay with the caller
    assert tree.insert(b, blocks_b) == 2
    assert not pool.cached[blocks_b[0]] and not pool.cached[blocks_b[1]]
    full, _, _ = tree.match(a)
    assert full == blocks_a
    full, _, _ = tree.match(b)
    assert full == blocks_a[:2] + blocks_b[2:]
    # re-inserting an exact duplicate adopts nothing
    dup = [pool.alloc() for _ in range(4)]
    assert tree.insert(a, dup) == 0
    tree.check_invariants()


def test_lru_eviction_order():
    pool, tree = _tree()
    rng = np.random.default_rng(3)
    a, b = _run(rng, 2), _run(rng, 2)
    blocks_a = [pool.alloc(), pool.alloc()]
    blocks_b = [pool.alloc(), pool.alloc()]
    tree.insert(a, blocks_a)
    tree.insert(b, blocks_b)
    for blk in blocks_a + blocks_b:
        pool.decref(blk)                 # harvest: all refs dropped
    tree.match(a)                        # bump a: b becomes LRU
    assert tree.evict(2) == 2
    assert not pool.cached[blocks_b[0]] and not pool.cached[blocks_b[1]]
    assert pool.cached[blocks_a[0]] and pool.cached[blocks_a[1]]
    full, _, _ = tree.match(a)
    assert full == blocks_a
    assert tree.match(b)[0] == []
    tree.check_invariants()


def test_per_block_lru_evicts_hot_nodes_cold_tail_first():
    """LRU stamps are per block, not per node: a lookup that matched
    only the head of an edge must leave the edge's tail colder than a
    later-inserted leaf, so eviction takes the hot node's cold tail
    BEFORE the warmer leaf (node-granular stamps would have pinned the
    whole hot edge and evicted the leaf first)."""
    pool, tree = _tree()
    rng = np.random.default_rng(5)
    a, b = _run(rng, 3), _run(rng, 1)
    blocks_a = [pool.alloc() for _ in range(3)]
    blocks_b = [pool.alloc()]
    tree.insert(a, blocks_a)                 # t1: a[0..2]
    tree.insert(b, blocks_b)                 # t2: b[0] (warmer than a's)
    for blk in blocks_a + blocks_b:
        pool.decref(blk)
    full, _, _ = tree.match(a[:BS])          # t3: bumps ONLY a's head
    assert full == blocks_a[:1]
    assert tree.evict(1) == 1                # coldest: a's tail (t1)
    assert not pool.cached[blocks_a[2]]
    assert pool.cached[blocks_b[0]], "warmer leaf evicted before cold tail"
    assert tree.evict(1) == 1                # next coldest: a[1] (t1)
    assert not pool.cached[blocks_a[1]]
    assert pool.cached[blocks_b[0]]
    assert tree.evict(1) == 1                # then the leaf (t2) ...
    assert not pool.cached[blocks_b[0]]
    assert pool.cached[blocks_a[0]], "hot head outlives everything"
    full, _, _ = tree.match(a)               # surviving prefix served
    assert full == blocks_a[:1]
    tree.check_invariants()


def test_eviction_skips_refcounted_blocks():
    pool, tree = _tree()
    rng = np.random.default_rng(4)
    a = _run(rng, 3)
    blocks = [pool.alloc() for _ in range(3)]
    tree.insert(a, blocks)
    pool.decref(blocks[2])               # only the tail is unpinned
    assert tree.evict(3) == 1            # pinned blocks never evicted
    assert pool.cached[blocks[0]] and pool.cached[blocks[1]]
    assert not pool.cached[blocks[2]]
    full, _, _ = tree.match(a)
    assert full == blocks[:2]            # surviving prefix still served
    pool.decref(blocks[0])
    pool.decref(blocks[1])
    assert tree.evict(3) == 2
    assert tree.cached_block_count() == 0
    assert pool.num_free() == pool.num_blocks
    tree.check_invariants()


def test_pool_free_is_decref():
    pool = BlockPool(4)
    b = pool.alloc()
    pool.incref(b)
    pool.decref(b)
    assert pool.num_free() == 3          # still referenced once
    pool.mark_cached(b)
    pool.decref(b)
    assert pool.num_free() == 3          # refcount 0 but tree-resident
    assert pool.num_evictable() == 1
    pool.release_cached(b)
    assert pool.num_free() == 4
    with pytest.raises(AssertionError, match="double free"):
        pool.decref(b)


# ----------------------------------------------------------------------
# end-to-end sharing through ChunkedServer
# ----------------------------------------------------------------------

def test_shared_prefix_outputs_bit_identical(setup):
    """Greedy outputs with prefix_cache=True must match the no-sharing
    path bit for bit, on both a cold and a fully warm tree."""
    cfg, params = setup
    reqs = sysprompt_sharegpt_requests(8, cfg.vocab_size, num_templates=2,
                                       template_len=24, max_input=40,
                                       max_output=8, seed=3)
    base = clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=3, max_len=64, chunk=8,
                  span=4, block_size=8,
                  prefix_cache=False).serve(base)
    srv = ChunkedServer(cfg, params, batch_slots=3, max_len=64, chunk=8,
                        span=4, block_size=8,
                        prefix_cache=True)
    cold = clone_requests(reqs)
    stats = srv.serve(cold)
    assert stats["prefix_hit_requests"] > 0       # intra-wave sharing
    warm = clone_requests(reqs)
    warm_stats = srv.serve(warm)
    for rb, rc, rw in zip(base, cold, warm):
        assert rb.output == rc.output == rw.output, rb.rid
    # warm wave: every request hits, most prompt tokens cached
    assert warm_stats["prefix_hit_rate"] == 1.0
    assert warm_stats["cached_token_fraction"] >= 0.5
    counts = srv.compile_counts()
    assert sum(max(v, 0) for v in counts.values()) <= 3, counts
    srv.prefix_cache.check_invariants()


def test_cow_divergence_no_cross_request_corruption(setup):
    """Two requests share a prefix then diverge mid-block: the second
    must copy-on-write instead of mutating the shared block, so both
    its own outputs and later re-reads of the original entry stay
    bit-identical to unshared runs."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    base = rng.integers(0, cfg.vocab_size, 22).astype(np.int32)
    div = base.copy()
    div[20:] = (div[20:] + 1) % cfg.vocab_size    # diverge inside block 2
    ra, rb = (Request(rid=0, prompt=base, max_new=6),
              Request(rid=1, prompt=div, max_new=6))
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                        span=4, block_size=8)
    srv.serve([clone_requests([ra])[0]])          # cache the base prefix
    got_a, got_b = clone_requests([ra])[0], clone_requests([rb])[0]
    stats = srv.serve([got_b, got_a])
    assert stats["prefix_cached_tokens"] > 0
    # COW actually ran: the copy program compiled exactly once
    assert srv.compile_counts()["cow_copy"] == 1
    for req in (clone_requests([ra])[0], clone_requests([rb])[0]):
        ref = clone_requests([req])[0]
        ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                      span=4, block_size=8,
                      prefix_cache=False).serve([ref])
        got = got_a if req.rid == 0 else got_b
        assert got.output == ref.output, req.rid
    srv.prefix_cache.check_invariants()


def test_refcount_invariants_across_waves(setup):
    """After every admit/harvest/evict wave: no outstanding references,
    every block either free or tree-resident, partition intact."""
    cfg, params = setup
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                        span=4, block_size=8, num_blocks=10)
    for seed in range(4):
        reqs = sysprompt_sharegpt_requests(
            4, cfg.vocab_size, num_templates=2, template_len=16,
            max_input=32, max_output=6, seed=seed)
        srv.serve(reqs)
        assert all(r.done for r in reqs)
        assert int(srv.pool.refcount.sum()) == 0
        assert (srv.pool.num_free() + srv.prefix_cache.cached_block_count()
                == srv.num_blocks)
        assert (srv.block_table == -1).all()
        assert srv._reserved_total == 0
        srv.prefix_cache.check_invariants()


def test_lru_eviction_under_pool_pressure(setup):
    """A pool far smaller than the traffic's cached footprint keeps
    serving bit-identical outputs by evicting refcount-0 blocks."""
    cfg, params = setup
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                        span=4, block_size=8, num_blocks=8)
    evictions = 0.0
    for seed in range(4):
        wave = sysprompt_sharegpt_requests(
            3, cfg.vocab_size, num_templates=1, template_len=16,
            max_input=32, max_output=6, seed=200 + seed)
        stats = srv.serve(wave)
        evictions += stats["cache_evictions"]
        fresh = clone_requests(wave)
        ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                      span=4, block_size=8,
                      prefix_cache=False).serve(fresh)
        for rw, rf in zip(wave, fresh):
            assert rw.output == rf.output, (seed, rw.rid)
        srv.prefix_cache.check_invariants()
    assert evictions > 0


def test_fully_cached_prompt_admits_under_memory_pressure(setup):
    """Admission subtracts cache-covered blocks from the worst-case
    reservation: a fully-cached prompt admits (and stays bit-identical)
    even when the free pool alone could not hold its total footprint."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                        span=4, block_size=8, num_blocks=6)
    first = Request(rid=0, prompt=prompt, max_new=8)
    srv.serve([first])
    # total worst case is 5 blocks but the free list holds fewer: only
    # the cache hit makes the re-admission feasible without eviction
    assert srv.pool.num_free() < srv._blocks_needed(first)
    again = Request(rid=1, prompt=prompt.copy(), max_new=8)
    stats = srv.serve([again])
    assert stats["admission_stalls"] == 0
    assert stats["cache_evictions"] == 0
    assert stats["cached_token_fraction"] > 0.9
    assert again.output == first.output


def test_cow_pin_does_not_starve_tight_pool(setup):
    """When the pool is so tight that pinning the partial-match (COW)
    block would starve the supply check, admission must drop the
    partial match (recomputing its < block_size tokens) instead of
    raising 'grow num_blocks' on an idle server."""
    cfg, params = setup
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab_size, 28).astype(np.int32)
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                        span=4, block_size=8, num_blocks=5)
    first = Request(rid=0, prompt=prompt, max_new=6)
    srv.serve([first])                    # tree retains 4 of 5 blocks
    again = Request(rid=1, prompt=prompt.copy(), max_new=6)
    srv.serve([again])                    # must not raise
    assert again.output == first.output
    srv.prefix_cache.check_invariants()


def test_empty_prompt_serves_with_prefix_cache(setup):
    """A zero-length prompt must keep serving (immediate emit) instead
    of tripping the prefix-match index math."""
    cfg, params = setup
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=32, chunk=4,
                        span=2, block_size=8)
    reqs = [Request(rid=0, prompt=np.zeros(0, np.int32), max_new=3),
            Request(rid=1, prompt=np.zeros(0, np.int32), max_new=3)]
    srv.serve(reqs)                      # second request re-matches the
    assert all(r.done for r in reqs)     # first's cached run
    assert reqs[0].output == reqs[1].output
    srv.prefix_cache.check_invariants()


def test_peak_blocks_measures_working_set_not_residency(setup):
    """Refcount-0 tree residue is reclaimable on demand and must not
    inflate the peak/pool-utilization footprint metrics."""
    cfg, params = setup
    srv = ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                        span=4, block_size=8)
    prompt = np.arange(24, dtype=np.int32) % cfg.vocab_size
    stats1 = srv.serve([Request(rid=0, prompt=prompt, max_new=6)])
    assert srv.prefix_cache.cached_block_count() > 0   # residue retained
    # a warm re-serve of the same prompt pins only the shared blocks
    # plus its small uncovered tail — far below full residency
    stats2 = srv.serve([Request(rid=1, prompt=prompt.copy(), max_new=6)])
    assert stats2["peak_blocks_in_use"] <= stats1["peak_blocks_in_use"]
    assert stats2["peak_blocks_in_use"] < srv.num_blocks


# ----------------------------------------------------------------------
# EOS stopping
# ----------------------------------------------------------------------

def test_eos_stopping_matches_both_engines(setup):
    """Device-side tok == eos_id folds into the stop mask: outputs are
    the no-eos outputs truncated at (and including) the first EOS, as
    the scheduler-free reference stops them."""
    cfg, params = setup
    reqs = sysprompt_sharegpt_requests(5, cfg.vocab_size, num_templates=2,
                                       template_len=8, max_input=16,
                                       max_output=10, seed=3)
    ref, _ = greedy_reference(cfg, params, reqs, max_len=64)
    # pick an eos that provably fires mid-stream for some request
    donor = max(ref, key=len)
    eos = donor[len(donor) // 2]

    def truncated(out):
        return out[:out.index(eos) + 1] if eos in out else out

    ref_eos, _ = greedy_reference(cfg, params, reqs, max_len=64,
                                  eos_id=eos)
    chunked = clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=3, max_len=64, chunk=8,
                  span=4, eos_id=eos).serve(chunked)
    stopped_early = 0
    for rc, full, want in zip(chunked, ref, ref_eos):
        assert want == truncated(full), rc.rid
        assert rc.output == want, rc.rid
        stopped_early += len(want) < len(full)
    assert stopped_early > 0


def test_slot_server_serves_full_queue_on_instant_stops(setup):
    """Every admitted request stopping on its first token (max_new=1,
    or an immediate EOS) must not abandon the still-queued rest: five
    such requests through two slots all come back, each with the
    reference's one token."""
    cfg, params = setup
    rng = np.random.default_rng(17)
    reqs = [Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, 6).astype(np.int32), max_new=1)
            for i in range(5)]
    ref, _ = greedy_reference(cfg, params, reqs, max_len=32)
    stats = ChunkedServer(cfg, params, batch_slots=2, max_len=32,
                          chunk=4, span=2).serve(reqs)
    assert all(r.done for r in reqs)
    assert [r.output for r in reqs] == ref
    assert all(len(r.output) == 1 for r in reqs)
    assert stats["tokens"] == sum(len(r.prompt) + 1 for r in reqs)

    # the same prompts stopped by EOS instead: request 0's first token
    # is the eos, so it stops at once and the rest stop where they
    # emit it (or at max_new)
    eos = ref[0][0]
    again = [Request(rid=r.rid, prompt=r.prompt.copy(), max_new=4)
             for r in reqs]
    want, _ = greedy_reference(cfg, params, again, max_len=32, eos_id=eos)
    ChunkedServer(cfg, params, batch_slots=2, max_len=32, chunk=4,
                  span=2, eos_id=eos).serve(again)
    assert all(r.done for r in again)
    assert [r.output for r in again] == want
    assert want[0] == [eos]


def test_eos_none_preserves_length_only_stopping(setup):
    """eos_id=None (default) must reproduce the pre-EOS behavior."""
    cfg, params = setup
    reqs = sysprompt_sharegpt_requests(3, cfg.vocab_size, num_templates=1,
                                       template_len=8, max_input=16,
                                       max_output=6, seed=5)
    a, b = clone_requests(reqs), clone_requests(reqs)
    ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                  span=4).serve(a)
    ChunkedServer(cfg, params, batch_slots=2, max_len=64, chunk=8,
                  span=4, eos_id=None).serve(b)
    for ra, rb in zip(a, b):
        assert len(ra.output) == ra.max_new
        assert ra.output == rb.output


# ----------------------------------------------------------------------
# randomized scheduler audit (seeded tier; tests/test_property.py
# widens the same harness with hypothesis-generated seeds)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 7, 19, 42])
def test_random_traffic_scheduler_audit(seed):
    """Random admit/harvest/evict/COW/rollback traffic through the REAL
    ChunkedServer host machinery (model-free device-step stand-ins,
    runtime/fuzz.py): RadixPrefixCache.check_invariants plus exact
    reservation accounting assert after every host transition, and the
    pool must be quiescent (no leaked refs/reservations) after every
    wave."""
    from repro.runtime.fuzz import run_fuzz_trace
    srv = run_fuzz_trace(seed)
    assert srv.audits > 0

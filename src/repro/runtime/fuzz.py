"""Scheduler fuzz harness: the REAL ChunkedServer host machinery under
random traffic, with model-free device steps and an invariant audit
after every state transition.

The serving scheduler's correctness surface — refcounted block
accounting, radix-tree residency, copy-on-write pins, speculative
rollback, admission backpressure and LRU eviction — is entirely
host-side; the jitted model steps only decide WHICH tokens come out.
``AuditedChunkedServer`` therefore replaces the three jitted work units
(and the COW pool copy) with seeded-random stand-ins that honor the
exact device-step contracts (emit rules, span stop masks, verify
acceptance bounds, EOS truncation) and drives the untouched scheduler:
every admit / block-assignment / rollback / harvest / eviction path
runs for real, at python speed, so property-based tests can push
thousands of randomized traffic patterns through it
(tests/test_prefix_cache.py seeds a fixed set; tests/test_property.py
widens it with hypothesis).

``_audit`` — called after every host transition — asserts:

  * ``RadixPrefixCache.check_invariants`` (block-aligned edges,
    refcount/residency/free-list partition, per-block LRU stamps);
  * exact reservation accounting: per slot,
    ``owned + reserved == blocks_needed(req) + cow_pending`` (the
    admission promise is conserved by every draw/rollback), reserved
    totals match, and the free + evictable supply covers every
    outstanding reservation;
  * the pool refcounts equal the multiset of slot block-table
    references (the tree pins residency via ``cached``, never via
    refcount);
  * each block-table row mirrors its slot's owned-block list exactly.
"""

from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.runtime.server import ChunkedServer, Request

__all__ = ["AuditedChunkedServer", "fuzz_config", "run_fuzz_trace"]


def fuzz_config(vocab: int = 32) -> ModelConfig:
    """Minimal dense config: the fakes never run the model, but the
    server still sizes its cache/pool arrays from it (kept tiny)."""
    return ModelConfig(name="fuzz", family="dense", num_layers=1,
                       d_model=8, num_heads=1, num_kv_heads=1,
                       head_dim=4, d_ff=16, vocab_size=vocab,
                       remat="none")


class AuditedChunkedServer(ChunkedServer):
    """ChunkedServer whose device steps are seeded-random fakes and
    whose host transitions are followed by a full invariant audit."""

    def __init__(self, cfg: ModelConfig, *, rng: np.random.Generator,
                 **kw):
        super().__init__(cfg, params=None, **kw)
        self._rng = rng
        self.audits = 0
        self._chunk_fn = self._fake_chunk
        self._span_fn = self._fake_span
        if self.spec_decode:
            self._verify_fn = self._fake_verify
        self._cow_fn = lambda cache, src, dst: cache

    # -- model-free device-step stand-ins ---------------------------------
    # Each fake honors the corresponding jitted unit's contract exactly
    # (see ChunkedServer._chunk_impl/_span_impl/_spec_impl): random
    # tokens are as good as real logits for the host machinery, and a
    # small vocab makes EOS / repeated-prefix traffic frequent.

    def _tok(self, n: int = 1) -> np.ndarray:
        return self._rng.integers(0, self.cfg.vocab_size, n,
                                  dtype=np.int32)

    def _fake_chunk(self, params, cache, cur_tok, out_buf, tokens_host,
                    pos, n_tokens, is_decode, emit, out_len, samp_temp,
                    samp_top_k, samp_top_p, samp_seed, block_table):
        ct = np.asarray(cur_tok).copy()
        ob = np.asarray(out_buf).copy()
        T = ob.shape[1]
        nxt = self._tok(self.B)
        for s in range(self.B):
            if emit[s]:
                ct[s] = nxt[s]
                ob[s, min(int(out_len[s]), T - 1)] = nxt[s]
        return cache, jnp.asarray(ct), jnp.asarray(ob)

    def _fake_span(self, params, cache, cur_tok, out_buf, pos, out_len,
                   active, max_new, samp_temp, samp_top_k, samp_top_p,
                   samp_seed, block_table):
        ct = np.asarray(cur_tok).copy()
        ob = np.asarray(out_buf).copy()
        # operands arrive as device arrays (the server device_puts its
        # scheduler state explicitly); pull them back to mutable numpy
        pos, out_len, act = (np.asarray(pos).copy(),
                             np.asarray(out_len).copy(),
                             np.asarray(active).copy())
        T, cap = ob.shape[1], self.max_len - 1
        for _ in range(self.span):
            for s in np.flatnonzero(act):
                nxt = int(self._tok()[0])
                ob[s, min(int(out_len[s]), T - 1)] = nxt
                out_len[s] += 1
                pos[s] += 1
                ct[s] = nxt
                act[s] = (out_len[s] < max_new[s] and pos[s] < cap
                          and (self.eos_id is None or nxt != self.eos_id))
        return (cache, jnp.asarray(ct), jnp.asarray(ob),
                jnp.asarray(pos), jnp.asarray(out_len), jnp.asarray(act))

    def _fake_verify(self, params, cache, table, cur_tok, out_buf, pos,
                     out_len, active, max_new, samp_temp, samp_top_k,
                     samp_top_p, samp_seed, block_table):
        K1 = self.spec_decode + 1
        ct = np.asarray(cur_tok).copy()
        ob = np.asarray(out_buf).copy()
        pos, out_len, act = (np.asarray(pos).copy(),
                             np.asarray(out_len).copy(),
                             np.asarray(active).copy())
        emit = np.zeros(self.B, np.int32)
        T, cap = ob.shape[1], self.max_len - 1
        for s in np.flatnonzero(act):
            # acceptance is data-dependent in [1, min(K+1, budget)] —
            # random here, which exercises every rollback depth
            budget = min(int(max_new[s]) - int(out_len[s]),
                         cap - int(pos[s]))
            w = int(self._rng.integers(1, min(K1, max(budget, 1)) + 1))
            toks = self._tok(w)
            eos_stop = False
            if self.eos_id is not None and self.eos_id in toks:
                w = int(np.flatnonzero(toks == self.eos_id)[0]) + 1
                toks = toks[:w]
                eos_stop = True
            for j in range(w):
                ob[s, min(int(out_len[s]) + j, T - 1)] = toks[j]
            out_len[s] += w
            pos[s] += w
            ct[s] = toks[-1]
            emit[s] = w
            act[s] = (out_len[s] < max_new[s] and pos[s] < cap
                      and not eos_stop)
        return (cache, table, jnp.asarray(ct), jnp.asarray(ob),
                jnp.asarray(pos), jnp.asarray(out_len),
                jnp.asarray(act), jnp.asarray(emit))

    # -- invariant audit ---------------------------------------------------

    def _audit(self) -> None:
        self.audits += 1
        if self.prefix_cache is not None:
            self.prefix_cache.check_invariants()
        assert (self._reserved >= 0).all(), "negative slot reservation"
        assert self._reserved_total == int(self._reserved.sum()), \
            "reservation total out of sync with per-slot reservations"
        evictable = (self.prefix_cache.evictable_blocks()
                     if self.prefix_cache is not None else 0)
        assert self._reserved_total <= self.pool.num_free() + evictable, \
            "outstanding reservations exceed the reclaimable supply"
        counts = np.zeros(self.num_blocks, np.int64)
        for s in range(self.B):
            owned = self._slot_blocks[s]
            row = self.block_table[s]
            assert [int(b) for b in row[:len(owned)]] == owned, \
                f"slot {s}: block table diverged from owned list"
            assert (row[len(owned):] == -1).all(), \
                f"slot {s}: stale block-table entries past the frontier"
            for b in owned:
                counts[b] += 1
            req = self.slot_req[s]
            if req is None:
                assert not owned and self._reserved[s] == 0
                continue
            # exact reservation accounting: the admission promise
            # (worst case + a mapped-but-unresolved COW block) is
            # conserved by every draw, COW resolve and rollback
            assert (len(owned) + int(self._reserved[s])
                    == self._blocks_needed(req)
                    + bool(self._cow_pending[s])), \
                f"slot {s}: owned+reserved drifted from blocks_needed"
        assert (self.pool.refcount == counts).all(), \
            "pool refcounts diverged from slot references"

    # -- audited host transitions -----------------------------------------

    def _admit(self, queue):
        super()._admit(queue)
        self._audit()

    def _ensure_blocks(self, s, upto):
        super()._ensure_blocks(s, upto)
        self._audit()

    def _truncate_blocks(self, s, upto):
        freed = super()._truncate_blocks(s, upto)
        self._audit()
        return freed

    def _harvest(self):
        served = super()._harvest()
        self._audit()
        return served


def _fuzz_requests(rng: np.random.Generator, n: int, vocab: int,
                   max_in: int, max_out: int,
                   templates: List[np.ndarray]) -> List[Request]:
    """Random mix biased toward shared prefixes cut at NON-block-
    aligned points (partial radix matches -> copy-on-write) plus
    genuinely fresh prompts and exact repeats."""
    reqs = []
    for i in range(n):
        kind = rng.random()
        if kind < 0.5 and templates:
            t = templates[int(rng.integers(len(templates)))]
            cut = int(rng.integers(1, len(t) + 1))
            tail = rng.integers(0, vocab,
                                int(rng.integers(0, 4)), dtype=np.int32)
            prompt = np.concatenate([t[:cut], tail])[:max_in]
        else:
            prompt = rng.integers(0, vocab,
                                  int(rng.integers(1, max_in + 1)),
                                  dtype=np.int32)
        reqs.append(Request(rid=i, prompt=prompt.astype(np.int32),
                            max_new=int(rng.integers(1, max_out + 1))))
    return reqs


def run_fuzz_trace(seed: int, *, waves: int = 2,
                   requests_per_wave: int = 6) -> AuditedChunkedServer:
    """One randomized serving trace: random knobs (block size, pool
    pressure, spec window, EOS), random shared-prefix traffic, `waves`
    serve() calls against a warm tree, an audit after every host
    transition, and a final quiescence check.  Returns the server so
    callers can assert on coverage counters."""
    rng = np.random.default_rng(seed)
    vocab = int(rng.integers(6, 48))
    cfg = fuzz_config(vocab)
    block_size = int(rng.choice([2, 3, 4, 8]))
    slots = int(rng.integers(2, 5))
    max_out = int(rng.integers(1, 10))
    max_in = int(rng.integers(2, 17))
    max_len = max_in + max_out + int(rng.integers(0, 5))
    templates = [rng.integers(0, vocab, int(rng.integers(2, max_in + 1)),
                              dtype=np.int32)
                 for _ in range(int(rng.integers(1, 4)))]
    wave_reqs = [_fuzz_requests(rng, requests_per_wave, vocab, max_in,
                                max_out, templates)
                 for _ in range(waves)]
    worst = max(-(-min(len(r.prompt) + r.max_new, max_len) // block_size)
                for w in wave_reqs for r in w)
    # a pool barely above the single-request worst case keeps the
    # allocator under constant backpressure/eviction pressure
    num_blocks = worst + int(rng.integers(0, 4))
    srv = AuditedChunkedServer(
        cfg, rng=rng, batch_slots=slots, max_len=max_len,
        chunk=int(rng.choice([2, 4, 8])), span=int(rng.choice([1, 2, 4])),
        block_size=block_size, num_blocks=num_blocks, prefix_cache=True,
        eos_id=(1 if rng.random() < 0.5 else None),
        spec_decode=int(rng.choice([0, 2, 3])), spec_n_ctx=64)
    for reqs in wave_reqs:
        srv.serve(reqs)
        assert all(r.done for r in reqs)
        # quiescence between waves: every reference dropped, every
        # reservation restored, nothing leaked — blocks are either
        # free or tree-resident (evictable)
        assert int(srv.pool.refcount.sum()) == 0
        assert srv._reserved_total == 0
        assert (srv.block_table == -1).all()
        assert (srv.pool.num_free()
                + srv.prefix_cache.cached_block_count()
                == srv.num_blocks)
    assert srv.audits > 0
    return srv

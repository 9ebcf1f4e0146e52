"""Speculative decoding: device-resident n-gram drafting + batched
verify over the serving KV cache.

The decode span loop (runtime/server.py) is latency-bound the same way
the paper's Hopper microbenchmarks show tensor-core pipelines are
issue-bound when fed one operation at a time: every model dispatch
emits exactly one token per slot, so the per-step weight sweep —
reading every parameter once — is amortized over a single token.
Speculative decoding widens the in-flight work per dispatch without
changing the emitted tokens: a cheap proposer drafts K continuation
tokens per slot, ONE `verify_step` call (the same fixed program shape
as a prefill chunk, models/transformer.py) scores all B×(K+1) tokens
against the cache, and the server accepts the longest draft prefix
that matches the model's own next-token chain — exact-parity rejection
for greedy decoding, so ``spec_decode=K`` emits the tokens of ``K=0``
wherever the verify and decode programs' logits agree (they differ
only in the order of the attention's float32 sums).

**Speculative sampling.** With per-request sampling on, acceptance is
the standard speculative-sampling rule (Leviathan et al. 2023 /
Chen et al. 2023): draft token ``d ~ q`` is accepted with probability
``min(1, p(d) / q(d))`` where ``p`` is the target distribution and
``q`` the draft distribution, and on the first rejection the emitted
token is resampled from the residual ``norm(max(p - q, 0))`` — which
provably preserves the target distribution ``p`` exactly (sum the
accept and residual cases: ``q(x) min(1, p(x)/q(x)) +
(1 - alpha) norm(max(p - q, 0))(x) = p(x)`` with
``alpha = sum_x min(p(x), q(x))``).  The n-gram drafter is a *point
mass* ``q = delta_d``, for which the rule collapses to something the
greedy machinery already implements: accept ``d`` with probability
``p(d)``, else resample from ``norm(max(p - delta_d, 0))`` — and both
cases are realized at once by drawing ``x_j ~ p_j`` at every verify
row (the sample head keyed by ``(seed, position)``) and accepting the
longest draft prefix with ``draft_j == x_j``.  P(accept) = P(x = d) =
p(d), and the first mismatching ``x`` is distributed as
``p`` conditioned on ``x != d`` = ``norm(max(p - delta_d, 0))`` —
exactly the residual resample.  ``accept_greedy`` therefore does
double duty: ``preds`` is the argmax chain under greedy and the
sampled chain under sampling, and because each row's draw is a pure
function of ``(seed, emission position)``, the emitted chain is
exact-match-given-seed to the non-speculative sampled span loop (CI
asserts both this and a K>0-vs-K=0 distribution-level KS test).

Drafting is a **device-resident n-gram suffix table**: one
``[n_ctx, K]`` int32 table, shared by every slot, mapping a hash of
the last two emitted tokens to the K tokens that most recently
followed that context anywhere in the batch — repeated traffic (the
production pattern the prefix cache already exploits for prompts)
re-serves its own continuations no matter which slot it lands on.  Both
the lookup (propose) and the update (learn from the tokens just
emitted, read back out of the device-side output buffer) happen inside
the jitted step — no host round-trip touches a token.  Hash collisions
and stale entries only lower the acceptance rate, never correctness:
every draft is verified against the model's own argmax before it can
be emitted.

Cache semantics: `verify_step` writes KV for ALL K+1 window rows at
positions [pos, pos+K].  After acceptance the valid frontier is
``pos + n_emit``; the rejected suffix rows' writes sit beyond it,
where the position masks of `chunk_attention`/`decode_attention`
give them exact-zero weight and the next window's writes land first —
or, beyond the slot's allocated block-table entries, were dropped at
scatter time (attention.update_paged_cache).  The server additionally rolls the
slot's block-table frontier back host-side (ChunkedServer.
_truncate_blocks) so over-allocated blocks return to the pool and the
refcount/copy-on-write invariants of runtime/prefix_cache.py survive
rollback.

Everything here is shape-fixed by (B, K): one compiled program no
matter how drafts are accepted, keeping the serving runtime's O(1)
compile budget at {chunk_step, decode_span, verify_step}.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import api

# Context-hash multiplier: a prime that spreads (prev, cur) token pairs
# over the table without degenerating modulo the power-of-two default
# n_ctx (a multiplier ≡ ±1 mod n_ctx would collapse the hash onto the
# token difference/sum).
_HASH_PRIME = 7919
DEFAULT_N_CTX = 32768


def ngram_hash(t_prev: jax.Array, t_cur: jax.Array, n_ctx: int
               ) -> jax.Array:
    """Bucket of the 2-token context (t_prev, t_cur).  int32 overflow
    for vocab sizes past ~270k wraps deterministically — collisions
    cost acceptance rate, not correctness."""
    return (t_prev * _HASH_PRIME + t_cur) % n_ctx


def init_ngram_table(k: int, n_ctx: int = DEFAULT_N_CTX) -> jax.Array:
    """Suffix-lookup table [n_ctx, K] int32, shared across every slot
    (what one request's decode teaches, the next request drafts from —
    repeated traffic re-serves its own suffixes no matter which slot
    it lands on).  Zero-init: an unseen context drafts token 0, which
    is verified like any other draft (accepted only when the model's
    argmax IS token 0)."""
    return jnp.zeros((n_ctx, k), jnp.int32)


def propose(table: jax.Array, cur_tok: jax.Array, out_buf: jax.Array,
            out_len: jax.Array) -> jax.Array:
    """Draft K tokens per slot from the suffix table.

    Context is the last two emitted tokens — ``cur_tok`` (the slot's
    pending token, == out_buf[out_len-1]) and its predecessor from the
    device-side output buffer (0-sentinel while out_len < 2).  Pure
    gather: [n_ctx, K] -> [B, K], no host involvement.
    """
    n_ctx = table.shape[0]
    B, T = out_buf.shape
    row = jnp.arange(B)
    i2 = jnp.clip(out_len - 2, 0, T - 1)
    t_prev = jnp.where(out_len >= 2, out_buf[row, i2], 0)
    ctx = ngram_hash(t_prev, cur_tok, n_ctx)
    return table[ctx]                                         # [B, K]


def accept_greedy(drafts: jax.Array, preds: jax.Array) -> jax.Array:
    """Longest-prefix greedy acceptance: n_acc[b] = number of leading
    drafts matching the model's argmax chain.  drafts [B, K] vs
    preds [B, K+1] (verify_step row j predicts the token AFTER window
    row j, so draft j is checked against preds[:, j])."""
    K = drafts.shape[1]
    match = (drafts == preds[:, :K]).astype(jnp.int32)
    return jnp.cumprod(match, axis=1).sum(axis=1)             # [B]


def update_ngram(table: jax.Array, out_buf: jax.Array,
                 out_len: jax.Array, active: jax.Array) -> jax.Array:
    """Learn from the tokens just emitted, inside the jitted step.

    For each run of K output tokens whose last token just landed
    (starts p in (out_len_before - K, out_len - K], at most K+1 of
    them), store ``out_buf[p : p+K]`` under the hash of its 2-token
    context ``(out_buf[p-2], out_buf[p-1])``.  Runs reaching into the
    prompt (p < 2) and inactive slots scatter to a dropped index.
    Duplicate contexts within one window (or across slots) resolve
    arbitrarily — either value is a genuinely observed continuation.
    """
    n_ctx, K = table.shape
    B, T = out_buf.shape
    j = jnp.arange(K + 1, dtype=jnp.int32)
    p = out_len[:, None] - K - j[None, :]                     # [B, K+1]
    ok = active[:, None] & (p >= 2)
    c_prev = jnp.take_along_axis(out_buf, jnp.clip(p - 2, 0, T - 1),
                                 axis=1)
    c_cur = jnp.take_along_axis(out_buf, jnp.clip(p - 1, 0, T - 1),
                                axis=1)
    ctx = ngram_hash(c_prev, c_cur, n_ctx)                    # [B, K+1]
    run_idx = jnp.clip(p[:, :, None] + jnp.arange(K)[None, None, :],
                       0, T - 1)
    runs = jnp.take_along_axis(out_buf, run_idx.reshape(B, (K + 1) * K),
                               axis=1).reshape(B, K + 1, K)
    ctx = jnp.where(ok, ctx, n_ctx)                           # drop sink
    return table.at[ctx.reshape(-1)].set(
        runs.reshape(B * (K + 1), K), mode="drop")


def spec_decode_step(cfg, params, cache, table: jax.Array,
                     cur_tok: jax.Array, out_buf: jax.Array,
                     pos: jax.Array, out_len: jax.Array,
                     active: jax.Array, max_new: jax.Array,
                     samp_temp: jax.Array, samp_top_k: jax.Array,
                     samp_top_p: jax.Array, samp_seed: jax.Array,
                     block_table: Optional[jax.Array], *,
                     max_len: int, eos_id: Optional[int],
                     fwd_kw: Optional[dict] = None
                     ) -> Tuple[jax.Array, ...]:
    """One draft → verify → accept step for every decoding slot.

    Jit-able as a single program (the server wraps it in one jax.jit,
    its only spec-decode compile).  Per active slot it emits
    ``n_emit = accepted drafts + 1 bonus`` tokens (>= 1, so progress
    never stalls), capped by the slot's remaining budget
    ``min(max_new - out_len, max_len - 1 - pos)`` and truncated at the
    first emitted ``eos_id`` (the EOS itself is emitted, then the slot
    stops — a slot finishing mid-verify gets its out_len cut at the
    EOS position so harvest/prefix-insertion never see post-EOS
    tokens).  Emitted tokens are always the model's own next-token
    chain ``preds[:, :n_emit]`` — the argmax chain for greedy slots,
    the position-keyed sampled chain for sampled slots
    (``samp_temp``/``samp_top_k``/``samp_top_p``/``samp_seed``, all
    ``[B]``, greedy encoded as temp<=0 per models/sampling) — drafts
    only decide how many rows of it are usable.  Hence the K=0 span
    loop's tokens for greedy slots and exact-match-given-seed for
    sampled ones (the speculative-sampling argument in the module
    docstring), as far as the verify and decode programs' logits agree.

    Returns (cache, table, cur_tok', out_buf', pos', out_len',
    active', n_emit) with n_emit zeroed for inactive slots; the host
    mirrors bookkeeping from n_emit/active' and rolls each slot's
    block-table frontier back to pos'.
    """
    B, K1 = cur_tok.shape[0], table.shape[1] + 1
    K = K1 - 1
    T = out_buf.shape[1]
    row = jnp.arange(B)
    iota = jnp.arange(K1, dtype=jnp.int32)
    cap = max_len - 1

    drafts = propose(table, cur_tok, out_buf, out_len)        # [B, K]
    window = jnp.concatenate([cur_tok[:, None], drafts], axis=1)
    sample = (samp_temp, samp_top_k, samp_top_p, samp_seed)
    preds, cache = api.verify_step(cfg, params, cache, window, pos,
                                   block_table, sample=sample,
                                   **(fwd_kw or {}))          # [B, K+1]

    n_acc = accept_greedy(drafts, preds)
    budget = jnp.maximum(
        jnp.minimum(max_new - out_len, cap - pos), 0)
    n_emit = jnp.minimum(n_acc + 1, budget)
    eos_stop = jnp.zeros((B,), bool)
    if eos_id is not None:
        eos_j = jnp.min(jnp.where(preds == eos_id, iota[None, :], K1),
                        axis=1)
        n_emit = jnp.minimum(n_emit, eos_j + 1)
        eos_stop = eos_j < n_emit
    n_emit = jnp.where(active, n_emit, 0)

    # scatter the emitted window preds[:, :n_emit] into the output
    # buffer; masked rows target an out-of-range index and drop
    idx = out_len[:, None] + iota[None, :]
    ok = active[:, None] & (iota[None, :] < n_emit[:, None])
    flat = jnp.where(ok, row[:, None] * T + idx, B * T)
    out_buf = (out_buf.reshape(-1)
               .at[flat.reshape(-1)].set(preds.reshape(-1), mode="drop")
               .reshape(B, T))

    out_len = out_len + n_emit
    pos = pos + n_emit
    last = jnp.take_along_axis(
        preds, jnp.clip(n_emit - 1, 0, K)[:, None], axis=1)[:, 0]
    cur_tok = jnp.where(n_emit > 0, last, cur_tok)
    active = (active & (out_len < max_new) & (pos < cap) & ~eos_stop)
    table = update_ngram(table, out_buf, out_len, n_emit > 0)
    return cache, table, cur_tok, out_buf, pos, out_len, active, n_emit


def record_dispatch(metrics, tracer, *, t0: float, t1: float, k: int,
                    n_active: int, emitted: int, accepted: int,
                    step: int = 0) -> None:
    """Host-side per-dispatch acceptance accounting for one verify
    step (called by the serving loop AFTER block_until_ready + the
    pos/out_len sync — every argument is a python scalar already on
    the host, so this can never add a device transfer).

    Feeds the per-dispatch acceptance histograms
    (``serving.spec.tokens_per_slot`` — mean emitted tokens per active
    slot, the >1.0 speculative win — and ``serving.spec.accept_rate``
    — accepted / drafted for the dispatch, the distribution-match
    signal under sampling) and a ``verify_dispatch`` trace event
    carrying the scheduler loop counter ``step``.  The dispatch count
    and its wall/host/wait split are the server's
    (``ChunkedServer._record_dispatch``).
    """
    metrics.counter("serving.spec.drafted").inc(k * n_active)
    metrics.counter("serving.spec.accepted").inc(accepted)
    metrics.counter("serving.spec.emitted").inc(emitted)
    if n_active:
        metrics.histogram("serving.spec.tokens_per_slot").record(
            emitted / n_active)
    if k * n_active:
        metrics.histogram("serving.spec.accept_rate").record(
            accepted / (k * n_active))
    if tracer.enabled:
        tracer.span("verify_dispatch", t0, t1, steps=1,
                    n_active=n_active, emitted=emitted,
                    accepted=accepted, step=step)

"""Stochastic sampling heads for the serving engine.

Temperature / top-k / top-p sampling with per-request seeds, built so
the serving paths can share ONE compiled program with greedy decoding
and stay inside the transfer-free span contract:

* **Device-resident RNG, position-keyed.** The draw for the token that
  will sit at sequence position ``q`` of slot ``b`` uses
  ``jax.random.fold_in(jax.random.PRNGKey(seed_b), q)`` computed
  *inside* the jitted body — threefry compiles natively, so no host
  RNG round-trip ever appears in a span (JX001/AST001 enforce this
  statically).  Keying by position rather than carrying a split-chain
  makes the draw a pure function of ``(seed, position)``: the chunked
  path, the span loop, and the speculative verify path all compute the
  *same* key for the same emitted position, which is what makes
  spec-decode sampling exact-match-given-seed to the non-speculative
  sampled path (and K=0 vs K>0 distributions identical by
  construction).
* **Always-present operands, work chosen per batch.** Greedy is
  encoded in the operand *values* (``temperature=0`` / ``top_k=1``),
  not the program, so flipping a request between greedy and sampled
  never recompiles (JX005).  The head always computes the argmax token
  on the original-dtype logits (bit-identical to the historical greedy
  head); batch-level ``lax.cond`` branches, on whether any row
  samples and whether any sampled row truncates to its top-k, skip
  the vocabulary-wide work no row needs.  The serving steps pass the
  rows whose tokens they discard (slots that emit nothing this step)
  as greedy, so only the kept rows decide.  The nucleus comes from one
  value sort, with no permutation gather or scatter (``_nucleus``).
* **fp32 distribution.** The sampled distribution is always formed in
  float32 — logits are upcast before temperature scaling, the softmax
  runs in fp32, and the gumbel noise is fp32 — so bf16 serving samples
  from the same distribution as fp32 serving up to logit rounding.

The draw itself is gumbel-max: ``argmax(z + g)`` over the masked,
temperature-scaled fp32 logits ``z`` with ``g ~ Gumbel(0,1)`` is an
exact sample from ``softmax(z)`` restricted to the unmasked support,
so no inverse-CDF search is needed and top-k/top-p masking composes as
plain ``-inf`` writes.

``ks_two_sample`` is a scipy-free two-sample Kolmogorov–Smirnov test
(asymptotic p-value, Numerical-Recipes series) that
tests/test_sampling.py uses to check the K>0 token distribution
against K=0.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration.

    ``temperature <= 0`` or ``top_k == 1`` selects greedy decoding
    (bit-identical to the historical argmax head).  ``top_k == 0``
    means "no top-k truncation"; ``top_p == 1.0`` means "no nucleus
    truncation".  ``seed`` is the per-request RNG seed — two requests
    with the same seed and the same emission positions draw the same
    noise.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, "
                             f"got {self.temperature}")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")

    @property
    def is_greedy(self) -> bool:
        return self.temperature <= 0.0 or self.top_k == 1

    def __str__(self) -> str:
        if self.is_greedy:
            return "greedy"
        parts = [f"t{self.temperature:g}"]
        if self.top_k:
            parts.append(f"k{self.top_k}")
        if self.top_p < 1.0:
            parts.append(f"p{self.top_p:g}")
        parts.append(f"s{self.seed}")
        return ":".join(parts)


GREEDY = SamplingParams()


def row_flags(temp, top_k, vocab):
    """Which rows sample, and which of those truncate to their top-k.

    The predicate of the head's batch-level branches.  It is written
    with operators only, so the scheduler applies the same test to its
    numpy mirrors (``batch_path``) that the program applies on device.
    """
    sampled = (temp > 0) & (top_k != 1)
    return sampled, sampled & (top_k > 0) & (top_k < vocab)


def batch_path(sampled, k_on, live) -> str:
    """The branch ``sample_tokens`` takes for a batch whose kept rows
    are ``live`` (host numpy, over the batch's ``row_flags``)."""
    if not (sampled & live).any():
        return "greedy"
    return "top_k" if (k_on & live).any() else "nucleus"


def _top_k_mask(z, top_k, k_on):
    """``-inf`` below each ``k_on`` row's k-th largest value; ties at
    the k-th value all survive."""
    vocab = z.shape[-1]
    kth = jnp.take_along_axis(
        jnp.sort(z, axis=-1, stable=False),
        (vocab - 1 - jnp.clip(top_k - 1, 0, vocab - 1))[:, None], axis=-1)
    return jnp.where(~k_on[:, None] | (z >= kth), z, -jnp.inf)


def _nucleus(probs, top_p):
    """The smallest prefix of the probability-sorted vocabulary whose
    mass reaches ``top_p`` (the head always stays), as a ``[B, V]``
    mask in vocabulary order.

    The prefix is that of a stable ``argsort(-probs)``, found without
    the permutation: with ``R`` kept ranks ending at value ``t``, it is
    every entry above ``t`` and the first ``R - #(probs > t)`` entries
    equal to ``t``, in vocabulary order.
    """
    vocab = probs.shape[-1]
    desc = -jnp.sort(-probs, axis=-1, stable=False)
    csum = jnp.cumsum(desc, axis=-1)
    keep_sorted = (csum - desc) < top_p[:, None]
    run = jnp.where(keep_sorted.all(axis=-1), vocab,
                    jnp.argmin(keep_sorted, axis=-1))[:, None]
    t = jnp.take_along_axis(desc, run - 1, axis=-1)
    above = probs > t
    tie = probs == t
    tie_rank = jnp.cumsum(tie, axis=-1, dtype=jnp.int32) - tie
    return above | (tie & (tie_rank < run - above.sum(-1, keepdims=True)))


def _gumbel(seed, index, vocab):
    key = jax.random.fold_in(jax.random.PRNGKey(seed), index)
    return jax.random.gumbel(key, (vocab,), jnp.float32)


def sample_tokens(logits, temp, top_k, top_p, seed, index):
    """Vectorized sample head.

    ``logits`` is ``[B, V]`` (chunk/span heads) or ``[B, C, V]``
    (verify head); ``temp``/``top_p`` are f32 ``[B]``,
    ``top_k``/``seed`` int32 ``[B]``; ``index`` holds the emission
    positions, shaped ``[B]`` or ``[B, C]`` to match.  Returns int32
    tokens shaped like ``index``.  Every sampled row decides the work
    of the batch, so a caller that discards some rows' tokens passes
    them as greedy (``temp`` 0): see ``batch_path``.
    """
    if logits.ndim == 3:
        B, C, V = logits.shape
        rep = lambda a: jnp.repeat(a, C)  # noqa: E731
        return sample_tokens(
            logits.reshape(B * C, V), rep(temp), rep(top_k), rep(top_p),
            rep(seed), index.reshape(B * C)).reshape(B, C)
    vocab = logits.shape[-1]
    # greedy token on the ORIGINAL dtype logits: bit-identical to the
    # historical `jnp.argmax(logits, -1)` head
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled, k_on = row_flags(temp, top_k, vocab)

    def draw():
        z = logits.astype(jnp.float32) / jnp.where(
            temp > 0.0, temp, jnp.float32(1.0))[:, None]
        z = lax.cond(jnp.any(k_on),
                     lambda z: _top_k_mask(z, top_k, k_on),
                     lambda z: z, z)
        # fp32 softmax of the temperature-scaled, top-k-masked
        # distribution; top-p masks outside its nucleus
        keep_p = _nucleus(jax.nn.softmax(z, axis=-1), top_p)
        z = jnp.where((top_p < 1.0)[:, None] & ~keep_p, -jnp.inf, z)
        # gumbel-max: argmax(z + g) is an exact draw from softmax(z) on
        # the surviving support, keyed purely by (seed, position)
        g = jax.vmap(_gumbel, in_axes=(0, 0, None))(seed, index, vocab)
        tok = jnp.argmax(z + g, axis=-1).astype(jnp.int32)
        return jnp.where(sampled, tok, greedy_tok)

    # batch-level branches, outside any vmap (under vmap a cond becomes
    # a select that runs both sides): an all-greedy batch does no
    # vocabulary-wide work beyond its argmax
    return lax.cond(jnp.any(sampled), draw, lambda: greedy_tok)


def ks_two_sample(a, b):
    """Two-sample Kolmogorov–Smirnov test, scipy-free.

    Returns ``(D, p)`` where ``D`` is the sup-distance between the
    empirical CDFs and ``p`` the asymptotic two-sided p-value via the
    Kolmogorov series ``p = 2 * sum_j (-1)^{j-1} exp(-2 j^2 lam^2)``
    with ``lam = (en + 0.12 + 0.11/en) * D``,
    ``en = sqrt(n*m/(n+m))`` (Numerical Recipes §14.3).  Empty inputs
    return ``(nan, nan)``.
    """
    a = np.sort(np.asarray(a, np.float64))
    b = np.sort(np.asarray(b, np.float64))
    n, m = len(a), len(b)
    if n == 0 or m == 0:
        return float("nan"), float("nan")
    grid = np.concatenate([a, b])
    cdf_a = np.searchsorted(a, grid, side="right") / n
    cdf_b = np.searchsorted(b, grid, side="right") / m
    d = float(np.max(np.abs(cdf_a - cdf_b)))
    en = math.sqrt(n * m / (n + m))
    lam = (en + 0.12 + 0.11 / en) * d
    p = 0.0
    converged = False
    for j in range(1, 101):
        term = 2.0 * (-1.0) ** (j - 1) * math.exp(-2.0 * j * j
                                                  * lam * lam)
        p += term
        if abs(term) < 1e-12:
            converged = True
            break
    if not converged:
        # lam ~ 0 (identical samples): the alternating series never
        # settles; the distribution-function limit there is p = 1
        p = 1.0
    return d, float(min(max(p, 0.0), 1.0))

"""Stochastic sampling: the device-resident temperature/top-k/top-p
head (models/sampling.py) and its serving integration.

The correctness bar, in three layers:

  * **head exactness** — gumbel-max over the masked fp32 distribution
    is a draw from exactly softmax(z/T) on the truncated support
    (KS-tested against ``jax.random.categorical``), truncation masks
    match the top-k / nucleus definitions, and the draw is a pure
    function of ``(seed, emission position)``;
  * **greedy degeneracy** — ``temperature=0`` and ``top_k=1`` are
    bit-identical to the historical argmax head on every workload mix
    and flag combo (the greedy<->sampled flip lives in operand VALUES,
    so it must also add zero compiled programs);
  * **speculative sampling** — the n-gram-drafted verify path with
    sampling on is *exact-match-given-seed* with the non-speculative
    sampled path (accept-longest-prefix against per-row target draws
    realizes the min(1, p/q) + residual rule for a point-mass
    drafter), and distribution-identical across disjoint seeds
    (seeded KS over >= 200 emitted tokens, K>0 vs K=0).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import api
from repro.models.api import (GREEDY, SamplingParams, ks_two_sample,
                              sample_tokens)
from repro.runtime.server import (ChunkedServer, clone_requests,
                                  repetitive_requests,
                                  sharegpt_like_requests,
                                  sysprompt_sharegpt_requests)

# ----------------------------------------------------------------------
# SamplingParams
# ----------------------------------------------------------------------


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)


def test_sampling_params_is_greedy_and_str():
    assert GREEDY.is_greedy and str(GREEDY) == "greedy"
    assert SamplingParams(temperature=0.0, seed=9).is_greedy
    assert SamplingParams(temperature=0.8, top_k=1).is_greedy
    sp = SamplingParams(temperature=0.8, top_k=40, top_p=0.95, seed=7)
    assert not sp.is_greedy
    assert str(sp) == "t0.8:k40:p0.95:s7"


# ----------------------------------------------------------------------
# sample head unit behavior (eager, tiny vocab)
# ----------------------------------------------------------------------

def _draws(logits_row, n, *, temp=1.0, top_k=0, top_p=1.0, seed=0):
    """n independent draws of one logits row: distinct emission
    positions under one seed (exactly the serving keying)."""
    V = logits_row.shape[-1]
    logits = jnp.tile(jnp.asarray(logits_row, jnp.float32), (n, 1))
    f = jnp.full((n,), 0, jnp.float32)
    i = jnp.zeros((n,), jnp.int32)
    toks = sample_tokens(logits, f + temp, i + top_k, f + top_p,
                         i + seed, jnp.arange(n, dtype=jnp.int32))
    return np.asarray(toks)


def test_temperature_zero_and_topk_one_are_argmax():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(16, 33)).astype(np.float32)
    ref = np.argmax(logits, axis=-1)
    z = jnp.asarray(logits)
    f = jnp.zeros((16,), jnp.float32)
    i = jnp.zeros((16,), jnp.int32)
    idx = jnp.arange(16, dtype=jnp.int32)
    t0 = sample_tokens(z, f, i, f + 1.0, i + 5, idx)
    assert np.array_equal(np.asarray(t0), ref)
    k1 = sample_tokens(z, f + 0.9, i + 1, f + 1.0, i + 5, idx)
    assert np.array_equal(np.asarray(k1), ref)


def test_draws_are_pure_functions_of_seed_and_position():
    row = np.random.default_rng(1).normal(size=7).astype(np.float32)
    a = _draws(row, 64, seed=3)
    b = _draws(row, 64, seed=3)
    assert np.array_equal(a, b)            # same (seed, position)
    c = _draws(row, 64, seed=4)
    assert not np.array_equal(a, c)        # seed moves the stream
    assert len(set(a.tolist())) > 1        # positions move it too


def test_top_k_restricts_support():
    row = np.array([3.0, 2.5, 0.0, -1.0, -2.0], np.float32)
    toks = _draws(row, 200, temp=1.5, top_k=2)
    assert set(toks.tolist()) == {0, 1}


def test_top_p_nucleus_mask():
    # probs ~ [0.6, 0.25, 0.1, 0.05]; nucleus keeps tokens while the
    # cumulative mass BEFORE them is < top_p (the head token always
    # survives)
    p = np.array([0.6, 0.25, 0.1, 0.05])
    row = np.log(p).astype(np.float32)
    only_head = _draws(row, 100, top_p=0.5)
    assert set(only_head.tolist()) == {0}
    nucleus = _draws(row, 400, top_p=0.9)
    assert set(nucleus.tolist()) == {0, 1, 2}


def test_gumbel_max_matches_categorical_distribution():
    """The head is an EXACT sampler: KS between its draws and
    jax.random.categorical on the same logits cannot reject."""
    row = np.random.default_rng(2).normal(size=11).astype(np.float32)
    ours = _draws(row, 600, temp=1.0, seed=0)
    ref = np.asarray(jax.random.categorical(
        jax.random.PRNGKey(10_000), jnp.asarray(row), shape=(600,)))
    d, pval = ks_two_sample(ours, ref)
    assert pval > 0.01, (d, pval)


# ----------------------------------------------------------------------
# the head against the per-row head it replaced
# ----------------------------------------------------------------------

def _reference_row(logits, temp, top_k, top_p, seed, index):
    """The head before batch-level branching: every row computes the
    top-k sort, the nucleus by argsort, gather and scatter, and the
    gumbel draw, then keeps the argmax where it is greedy."""
    vocab = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    x = logits.astype(jnp.float32)
    safe_t = jnp.where(temp > 0.0, temp, jnp.float32(1.0))
    z = x / safe_t
    k_on = (top_k > 0) & (top_k < vocab)
    sorted_z = jnp.sort(z)[::-1]
    kth = sorted_z[jnp.clip(top_k - 1, 0, vocab - 1)]
    keep_k = jnp.where(k_on, z >= kth, True)
    z = jnp.where(keep_k, z, -jnp.inf)
    probs = jax.nn.softmax(z)
    order = jnp.argsort(-probs)
    csum = jnp.cumsum(probs[order])
    keep_sorted = (csum - probs[order]) < top_p
    keep_p = jnp.zeros((vocab,), bool).at[order].set(keep_sorted)
    z = jnp.where((top_p < 1.0) & ~keep_p, -jnp.inf, z)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), index)
    g = jax.random.gumbel(key, (vocab,), jnp.float32)
    sampled = jnp.argmax(z + g).astype(jnp.int32)
    return jnp.where((temp <= 0.0) | (top_k == 1), greedy_tok, sampled)


@jax.jit
def _reference_head(logits, temp, top_k, top_p, seed, index):
    if logits.ndim == 2:
        return jax.vmap(_reference_row)(logits, temp, top_k, top_p, seed,
                                        index)
    row = jax.vmap(_reference_row, in_axes=(0, None, None, None, None, 0))
    return jax.vmap(row)(logits, temp, top_k, top_p, seed, index)


HEAD_B, HEAD_V, HEAD_C = 24, 97, 3
TEMPS = (0.5, 0.7, 1.0, 1.3)
TOP_KS = (0, 1, 5, 50)
TOP_PS = (0.5, 0.9, 0.95, 1.0)
HEAD_BATCHES = ("all_greedy", "top_p_no_top_k", "mixed", "live_greedy",
                "live_top_p")


def _head_logits(rng, lead):
    """Three families of rows: continuous logits; logits on a coarse
    grid, so the k-th value and the nucleus boundary fall inside runs
    of exact ties; and probabilities 0.4, 0.2, 0.2, 0.2 over a far
    tail, whose 0.5-nucleus ends inside the tie."""
    x = 3.0 * rng.normal(size=lead + (HEAD_V,))
    grid = np.round(2.0 * rng.normal(size=x.shape)) / 2.0
    x = np.where((np.arange(HEAD_B) % 3 == 1).reshape(
        (-1,) + (1,) * (len(lead))), grid, x)
    tied = np.full(HEAD_V, -30.0)
    tied[[7, 3, 50, 11]] = np.log([0.4, 0.2, 0.2, 0.2])
    x[2::3] = np.where(rng.random(x[2::3].shape[:-1] + (1,)) < 0.5,
                       tied, x[2::3])
    return x


def _head_batch(batch, rng):
    """Per-row sampling operands and the live mask of one batch kind."""
    B = HEAD_B
    temp = rng.choice(TEMPS, B)
    top_k = rng.choice(TOP_KS, B)
    top_p = rng.choice(TOP_PS, B)
    live = rng.random(B) < 0.6
    greedy = rng.random(B) < 0.3
    if batch == "all_greedy":
        temp = np.where(rng.random(B) < 0.5, 0.0, temp)
        top_k = np.where(temp > 0, 1, top_k)
        live[:] = True
    elif batch == "top_p_no_top_k":
        top_k[:] = 0
        temp = np.where(greedy, 0.0, temp)
        live[:] = True
    elif batch == "mixed":
        temp = np.where(greedy, 0.0, temp)
        live[:] = True
    elif batch == "live_greedy":
        # the live rows are greedy, the others sample with top-k
        temp = np.where(live, 0.0, temp)
        top_k = np.where(live, top_k, 5)
    elif batch == "live_top_p":
        # top-k only on rows whose tokens are thrown away
        top_k = np.where(live, 0, 5)
        temp = np.where(live & greedy, 0.0, temp)
    return (temp.astype(np.float32), top_k.astype(np.int32),
            top_p.astype(np.float32), live)


@pytest.mark.parametrize("shape", ["rows", "verify"])
@pytest.mark.parametrize("batch", HEAD_BATCHES)
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_head_matches_per_row_reference(dtype, batch, shape):
    """The batch-level head gives the reference head's token on every
    kept row: greedy rows bit for bit, sampled rows given the seed."""
    rng = np.random.default_rng([HEAD_BATCHES.index(batch),
                                 int(shape == "verify"),
                                 int(dtype == "float32")])
    lead = (HEAD_B,) if shape == "rows" else (HEAD_B, HEAD_C)
    logits = jnp.asarray(_head_logits(rng, lead), jnp.dtype(dtype))
    temp, top_k, top_p, live = _head_batch(batch, rng)
    seed = rng.integers(0, 2**31 - 1, HEAD_B).astype(np.int32)
    index = rng.integers(0, 4096, lead).astype(np.int32)
    ops = tuple(map(jnp.asarray, (temp, top_k, top_p, seed, index)))
    want = np.asarray(_reference_head(logits, *ops))
    # the serving steps pass the rows whose tokens they discard as
    # greedy
    kept = jnp.asarray(np.where(live, temp, 0.0).astype(np.float32))
    got = np.asarray(jax.jit(sample_tokens)(logits, kept, *ops[1:]))
    assert np.array_equal(got[live], want[live])


def test_ks_two_sample_sanity():
    same = np.arange(500) % 7
    d, p = ks_two_sample(same, same)
    assert d == 0.0 and p == 1.0
    d, p = ks_two_sample(np.zeros(300), np.ones(300))
    assert d == 1.0 and p < 1e-6
    d, p = ks_two_sample(np.array([]), np.ones(3))
    assert np.isnan(d) and np.isnan(p)


# ----------------------------------------------------------------------
# serving integration
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("yi-6b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


BASE_KW = dict(batch_slots=3, max_len=64, chunk=8, span=4,
               block_size=8, prefix_cache=True)


def _mixes(cfg):
    return {
        "sharegpt": sharegpt_like_requests(
            6, cfg.vocab_size, max_input=16, max_output=8, seed=3),
        "sysprompt": sysprompt_sharegpt_requests(
            6, cfg.vocab_size, num_templates=2, template_len=12,
            max_input=20, max_output=6, seed=4),
        "repetitive": repetitive_requests(
            4, cfg.vocab_size, motif_len=4, reps=3, max_output=10,
            seed=5),
    }


def _serve(cfg, params, reqs, *, sampling=None, per_req=None, **kw):
    srv = ChunkedServer(cfg, params, sampling=sampling,
                        **{**BASE_KW, **kw})
    rs = clone_requests(reqs)
    if per_req is not None:
        for i, r in enumerate(rs):
            r.sampling = per_req(i)
    srv.serve(rs)
    assert all(r.done for r in rs)
    return [r.output for r in rs], srv


def test_degenerate_sampling_is_bitwise_greedy_on_every_mix(setup):
    """temperature=0 (server-wide) and top_k=1 (per-request, nonzero
    temperature) reproduce the argmax head bit for bit on all three
    workload mixes."""
    cfg, params = setup
    for name, reqs in _mixes(cfg).items():
        ref, _ = _serve(cfg, params, reqs)
        t0, _ = _serve(cfg, params, reqs,
                       sampling=SamplingParams(temperature=0.0, seed=9))
        assert t0 == ref, name
        k1, _ = _serve(cfg, params, reqs, per_req=lambda i:
                       SamplingParams(temperature=0.7, top_k=1,
                                      seed=50 + i))
        assert k1 == ref, name


@pytest.mark.parametrize("combo", [
    {"spec_decode": 3},
    {"kernel": True},
    {"prefix_cache": False},
], ids=["spec", "kernel", "dense"])
def test_degenerate_sampling_is_bitwise_greedy_across_combos(
        setup, combo):
    cfg, params = setup
    reqs = _mixes(cfg)["sharegpt"]
    ref, _ = _serve(cfg, params, reqs, **combo)
    t0, _ = _serve(cfg, params, reqs,
                   sampling=SamplingParams(temperature=0.0), **combo)
    assert t0 == ref, combo


def test_sampled_outputs_are_stochastic_and_seed_deterministic(setup):
    cfg, params = setup
    reqs = _mixes(cfg)["sharegpt"]
    sp = lambda i: SamplingParams(temperature=0.8, top_k=20,  # noqa: E731
                                  seed=100 + i)
    ref, _ = _serve(cfg, params, reqs)
    a, _ = _serve(cfg, params, reqs, per_req=sp)
    b, _ = _serve(cfg, params, reqs, per_req=sp)
    assert a == b                       # same seeds: same tokens
    assert all(x != r for x, r in zip(a, ref))   # really stochastic
    c, _ = _serve(cfg, params, reqs, per_req=lambda i:
                  SamplingParams(temperature=0.8, top_k=20,
                                 seed=900 + i))
    assert a != c                       # different seeds: new draws


def test_speculative_sampling_exact_match_given_seed(setup):
    """Sampled spec-decode (accept-longest-prefix against per-row
    target draws) emits EXACTLY the tokens the non-speculative sampled
    path emits, request by request — the point-mass collapse of the
    min(1, p/q) + residual rule is an identity, not an approximation."""
    cfg, params = setup
    reqs = _mixes(cfg)["repetitive"]   # n-gram drafter actually hits
    sp = lambda i: SamplingParams(temperature=0.9, top_k=30,  # noqa: E731
                                  top_p=0.95, seed=200 + i)
    plain, _ = _serve(cfg, params, reqs, per_req=sp)
    spec, srv = _serve(cfg, params, reqs, per_req=sp, spec_decode=3)
    assert spec == plain
    counts = dict(srv.compile_counts())
    assert sum(max(v, 0) for v in counts.values()) <= 3


def test_sampled_spec_distribution_matches_nonspec_ks(setup):
    """Disjoint seeds, >= 200 emitted tokens per side: K>0 and K=0
    draw from the same distribution (seeded KS cannot reject)."""
    cfg, params = setup
    reqs = repetitive_requests(16, cfg.vocab_size, motif_len=4, reps=3,
                               max_output=16, seed=6)
    k0, _ = _serve(cfg, params, reqs, per_req=lambda i:
                   SamplingParams(temperature=1.0, seed=i))
    k3, _ = _serve(cfg, params, reqs, spec_decode=3, per_req=lambda i:
                   SamplingParams(temperature=1.0, seed=1000 + i))
    a = np.concatenate([np.asarray(o) for o in k0])
    b = np.concatenate([np.asarray(o) for o in k3])
    assert len(a) >= 200 and len(b) >= 200
    d, pval = ks_two_sample(a, b)
    assert pval > 0.01, (d, pval)


def test_greedy_sampled_flips_add_zero_programs(setup):
    """One server, greedy -> sampled -> greedy -> new-seed sampled:
    the program set is compiled once and never grows (the flip is in
    operand values; JX005 proves the same statically)."""
    cfg, params = setup
    reqs = _mixes(cfg)["sharegpt"]
    srv = ChunkedServer(cfg, params, spec_decode=3, **BASE_KW)

    def wave(per_req=None):
        rs = clone_requests(reqs)
        if per_req is not None:
            for i, r in enumerate(rs):
                r.sampling = per_req(i)
        srv.serve(rs)
        return [r.output for r in rs]

    PROGRAMS = ("chunk_step", "decode_span", "verify_step")

    def prog_counts():
        counts = srv.compile_counts()
        return {k: counts[k] for k in PROGRAMS}

    g1 = wave()
    counts = prog_counts()
    assert sum(max(v, 0) for v in counts.values()) <= 3
    wave(lambda i: SamplingParams(temperature=0.8, top_k=40,
                                  top_p=0.95, seed=i))
    g2 = wave()
    wave(lambda i: SamplingParams(temperature=1.2, seed=77 + i))
    assert g2 == g1                     # greedy unchanged by traffic
    assert prog_counts() == counts


def test_sampling_path_counter(setup):
    """``serving.sampling.path.*`` counts every chunk step and span step
    once, by the head's branch: all greedy, then one top-p request
    among greedy ones, on one server whose programs do not grow."""
    cfg, params = setup
    reqs = _mixes(cfg)["sharegpt"]
    srv = ChunkedServer(cfg, params, **BASE_KW)

    def paths(per_req=lambda i: None):
        rs = clone_requests(reqs)
        for i, r in enumerate(rs):
            r.sampling = per_req(i)
        srv.serve(rs)
        m = srv.metrics
        steps = (m.counter_value("serving.dispatches.prefill")
                 + srv.span * m.counter_value("serving.dispatches.span"))
        got = {p: m.counter_value("serving.sampling.path." + p)
               for p in ("greedy", "nucleus", "top_k")}
        assert sum(got.values()) == steps
        return got, steps

    def programs():
        counts = srv.compile_counts()
        return counts["chunk_step"], counts["decode_span"]

    got, steps = paths()
    assert got == {"greedy": steps, "nucleus": 0, "top_k": 0}
    before = programs()
    got, _ = paths(lambda i: SamplingParams(temperature=0.8, top_p=0.9,
                                            seed=3) if i == 2 else None)
    assert got["nucleus"] > 0 and got["greedy"] > 0 and got["top_k"] == 0
    assert programs() == before == (1, 1)

"""CodeQwen1.5-shaped serving against the benchmark's float32 reference
(bench/reference.py), on the CPU at a small size.

The program's CodeQwen1.5-7B preset, widened to keep grouped-query
attention (8 query heads over 2 KV heads of 16, G = 4), with q/k/v
biases and no MLP biases, serves greedy requests through
``ChunkedServer``: chunked prefill over several chunks, then paged
decode spans and decodes piggybacked on chunk steps.  Its weights are
drawn from the seed by bench/weights.py's rules (so the q/k/v biases
are non-zero), and the reference regenerates the same values layer by
layer.  Every logit the served path sampled a token from is captured
(a host callback around the sampling head) and compared with the
reference's logits at that position; the served-token logit gap is the
benchmark's own check.  Planted faults must each exceed a tolerance:
the q/k/v biases dropped from the serving path, the 2 KV heads read as
8 (the old CodeQwen1.5-7B config's MHA), and weights served in fp8.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, reference, weights  # noqa: E402
from repro.configs import get_config, reduced_config  # noqa: E402
from repro.models import api, sampling  # noqa: E402
from repro.obs import Tracer  # noqa: E402
from repro.runtime.server import ChunkedServer, Request  # noqa: E402

SEED = 2**31 + 15
PACK = 512                      # one reference row (a multiple of Q_BLOCK)
CONF = {"num_hidden_layers": 2, "hidden_size": 64,
        "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "vocab_size": 256,
        "rope_theta": 1000000.0,
        # the program's epsilon, so that only precision separates them
        "rms_norm_eps": 1e-06, "attention_bias": True}
PROMPTS = (21, 37, 50)          # 3, 5 and 7 chunks of 8
MAX_NEW = 12

# The program serves the reference's own bfloat16 weights, so what
# separates them is its bf16 activations (q/k/v, attention, MLP and
# head inputs) against float32 at "highest".  Readings over five seeds
# at this size: the widest logit difference 0.024-0.029, the widest
# served-token gap 0-0.008.  Planted faults: q/k/v biases dropped 0.64-
# 0.78 and 0.21-0.47; 2 KV heads read as 8 2.6 and 1.6; weights in fp8
# (e4m3, the program's own fp8_linear path: a precision below the
# configuration's bf16) 0.25-0.31 and 0.008-0.15.  So the logit
# tolerance sits near the geometric middle of 0.029 and 0.25, and fails
# every fault; the gap tolerance fails the two structural faults (an
# fp8 head can keep its argmax, so the gap alone need not see it).
LOGIT_TOL = 0.08
GAP_TOL = 0.04

STRUCTURAL = (None, "qkv_bias_dropped", "kv_heads_read_as_heads")
FAULTS = STRUCTURAL + ("fp8_weights",)


def _cfg(kv_heads: int):
    return dataclasses.replace(reduced_config("codeqwen1.5-7b"),
                               num_heads=8, num_kv_heads=kv_heads,
                               head_dim=16)


def _prompts():
    rng = np.random.default_rng(3)
    return [rng.integers(0, CONF["vocab_size"], n).astype(np.int32)
            for n in PROMPTS]


def _reference_logits(seqs):
    """[len(seq), V] float32 logits of each sequence, packed in one row
    through bench/reference.py's layer at "highest" precision."""
    dm = weights.dims(CONF)
    tok, pos, seg, _, _ = reference.pack(
        seqs, [(len(s), np.zeros(0, np.int32)) for s in seqs], PACK)
    f32 = jnp.float32
    eps = CONF["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = weights.make_leaf(SEED, "embed", (dm["V"], dm["d"]), dm)[
            jnp.asarray(tok)].astype(f32)
        for layer in range(dm["L"]):
            w = {k: weights.layer_slice(SEED, "layers." + k, s, layer,
                                        dm).astype(f32)
                 for k, s in reference.layer_shapes(dm).items()}
            x = reference._layer(x, w, jnp.asarray(pos), jnp.asarray(seg),
                                 eps=eps, theta=CONF["rope_theta"],
                                 bias=True)
        fnorm = weights.make_leaf(SEED, "final_norm.scale", (dm["d"],), dm)
        unembed = weights.make_leaf(SEED, "unembed", (dm["d"], dm["V"]), dm)
        logits = reference._rmsnorm(x, fnorm.astype(f32), eps) @ \
            unembed.astype(f32)
    logits = np.asarray(logits)
    out, off = [], 0
    for s in seqs:
        out.append(logits[off:off + len(s)])
        off += len(s)
    return out


@functools.lru_cache(maxsize=None)
def _served(fault):
    """Serve the prompts under ``fault``.  Returns (outputs, captured
    logits by (slot, emitted position), slot of each request)."""
    cfg = _cfg(8 if fault == "kv_heads_read_as_heads" else 2)
    conf = dict(CONF, num_key_value_heads=cfg.num_kv_heads)
    params = harness.make_params(cfg, conf, SEED)
    if fault == "qkv_bias_dropped":
        attn = params["layers"]["attn"]
        for b in ("bq", "bk", "bv"):
            attn[b] = jnp.zeros_like(attn[b])
    seen = {}

    def keep(logits, index):
        # first write wins: a freed slot's row is recomputed later
        # with a stale position, after its request is finished
        for s in range(logits.shape[0]):
            seen.setdefault((s, int(index[s])),
                            np.asarray(logits[s], np.float32))

    real = sampling.sample_tokens

    def sample_tokens(logits, temp, top_k, top_p, seed, index):
        jax.debug.callback(keep, logits, index, ordered=True)
        return real(logits, temp, top_k, top_p, seed, index)

    reqs = [Request(rid=i, prompt=p, max_new=MAX_NEW)
            for i, p in enumerate(_prompts())]
    tracer = Tracer()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sampling, "sample_tokens", sample_tokens)
        # more slots than requests: no slot is reused
        srv = ChunkedServer(cfg, params, batch_slots=4, max_len=128,
                            chunk=8, span=4, block_size=8, tracer=tracer,
                            fp8_linear=fault == "fp8_weights")
        srv.serve(reqs)
    assert all(r.done and len(r.output) == MAX_NEW for r in reqs)
    assert srv.metrics.counter_value("serving.dispatches.span") > 0
    return ([r.output for r in reqs], seen,
            [tracer.requests[r.rid].slot for r in reqs])


@pytest.mark.parametrize("fault", FAULTS)
def test_served_logits_match_reference(fault):
    """Chunked prefill, then paged decode: every logit row a served
    token was drawn from, against the reference's full forward pass."""
    outputs, seen, slots = _served(fault)
    prompts = _prompts()
    seqs = [np.concatenate([p, np.asarray(o[:-1], np.int32)])
            for p, o in zip(prompts, outputs)]
    ref = _reference_logits(seqs)
    worst = 0.0
    for rid, (p, o) in enumerate(zip(prompts, outputs)):
        for j in range(len(o)):
            got = seen[(slots[rid], len(p) + j)]
            # the logits at position len(p) - 1 + j predict token j
            want = ref[rid][len(p) - 1 + j]
            worst = max(worst, float(np.abs(got - want).max()))
            if fault is None:
                assert int(np.argmax(got)) == o[j]
    if fault is None:
        assert worst <= LOGIT_TOL, worst
    else:
        assert worst > LOGIT_TOL, (fault, worst)


@pytest.mark.parametrize("fault", STRUCTURAL)
def test_served_token_logit_gap(fault):
    """The benchmark's check: how far below the reference's best logit
    each served token's logit lies."""
    outputs, _, _ = _served(fault)
    res = reference.run(dict(CONF), SEED, _prompts(), outputs, PACK)
    gap = float(reference.gaps(res).max())
    if fault is None:
        assert gap <= GAP_TOL, gap
    else:
        assert gap > GAP_TOL, (fault, gap)


def test_codeqwen_config_is_gqa_with_qkv_bias_only():
    cfg = get_config("codeqwen1.5-7b")
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 4, 128)
    assert cfg.use_bias and not cfg.mlp_bias
    assert cfg.param_count() == 7_250_284_544
    names = harness.leaf_names(jax.eval_shape(
        lambda: api.init(cfg, jax.random.PRNGKey(0))))
    assert {"layers.attn.bq", "layers.attn.bk", "layers.attn.bv"} <= \
        set(names)
    assert not any(n.startswith("layers.mlp.b_") for n in names)
    assert not any(n.endswith("attn.bo") for n in names)
    assert not hasattr(cfg, "qkv_bias")


# leaf names before MLP biases got a flag of their own (use_bias used
# to switch them on with the q/k/v biases): the trees must not change
_WHISPER_BLOCK = ("ln1.bias", "ln1.scale", "ln2.bias", "ln2.scale",
                  "mlp.b_down", "mlp.b_up", "mlp.w_down", "mlp.w_up")
_QKVO_BIAS = ("bk", "bq", "bv", "wk", "wo", "wq", "wv")
TREES = {
    "whisper-small": sorted(
        [f"dec_layers.{a}.{n}" for a in ("cross_attn", "self_attn")
         for n in _QKVO_BIAS]
        + [f"dec_layers.{n}" for n in _WHISPER_BLOCK]
        + ["dec_layers.ln3.bias", "dec_layers.ln3.scale", "dec_pos",
           "embed"]
        + [f"enc_layers.attn.{n}" for n in _QKVO_BIAS]
        + [f"enc_layers.{n}" for n in _WHISPER_BLOCK]
        + ["enc_norm.bias", "enc_norm.scale", "enc_pos",
           "final_norm.bias", "final_norm.scale"]),
    "te-layer": ["ln1.scale", "mlp.down.w", "mlp.gate.w", "mlp.ln.scale",
                 "mlp.up.w", "wk.w", "wo.w", "wq.w", "wv.w"],
    "yi-6b": ["embed", "final_norm.scale", "layers.attn.wk",
              "layers.attn.wo", "layers.attn.wq", "layers.attn.wv",
              "layers.ln1.scale", "layers.ln2.scale", "layers.mlp.w_down",
              "layers.mlp.w_gate", "layers.mlp.w_up", "unembed"],
}


@pytest.mark.parametrize("name", sorted(TREES))
def test_mlp_bias_split_keeps_trees(name):
    from repro.configs.llama_te import te_layer_config
    from repro.models.common import ParamSpec
    from repro.te.layer import transformer_layer_specs
    specs = (transformer_layer_specs(te_layer_config(1024))
             if name == "te-layer" else api.param_shapes(get_config(name)))
    paths = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))[0]
    got = sorted(".".join(str(getattr(k, "key", k)) for k in p)
                 for p, _ in paths)
    assert got == TREES[name]

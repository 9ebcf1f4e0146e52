"""Per-arch smoke tests (reduced configs): fwd/train/decode on CPU.

One test per assigned architecture — instantiates the same-family
reduced config, runs a forward/loss/grad step and a cached decode step,
asserting output shapes and finiteness (the deliverable-(f) smoke
tests).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ASSIGNED, get_config, list_archs,
                           reduced_config, reduced_shape)
from repro.models import api


@pytest.mark.parametrize("arch", ASSIGNED)
def test_full_config_registered(arch):
    cfg = get_config(arch)
    assert cfg.name == arch
    assert cfg.num_layers > 0 and cfg.d_model > 0
    # exact spec spot-checks
    if arch == "command-r-35b":
        assert (cfg.num_layers, cfg.d_model, cfg.num_heads,
                cfg.num_kv_heads, cfg.d_ff, cfg.vocab_size) == \
            (40, 8192, 64, 8, 22528, 256000)
    if arch == "dbrx-132b":
        assert (cfg.num_experts, cfg.top_k) == (16, 4)
    if arch == "moonshot-v1-16b-a3b":
        assert (cfg.num_experts, cfg.top_k) == (64, 6)
    if arch == "falcon-mamba-7b":
        assert cfg.ssm_state == 16 and cfg.num_heads == 0
    if arch == "zamba2-2.7b":
        assert cfg.ssm_state == 64 and cfg.attn_every == 6
    if arch == "whisper-small":
        assert cfg.enc_layers == 12 and cfg.dec_layers == 12


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_train_step(arch, rng):
    cfg = reduced_config(arch)
    params = api.init(cfg, rng)
    shape = reduced_shape("train")
    batch = api.make_batch(cfg, shape, rng)
    loss, grads = jax.value_and_grad(
        lambda p: api.loss_fn(cfg, p, batch))(params)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_decode_step(arch, rng):
    cfg = reduced_config(arch)
    params = api.init(cfg, rng)
    cache = api.init_cache(cfg, 2, 16)
    tok = jnp.array([3, 5], jnp.int32)
    logits, new_cache = api.decode_step(cfg, params, cache, tok,
                                        jnp.asarray(4, jnp.int32))
    assert logits.shape == (2, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # cache structure is preserved
    assert (jax.tree_util.tree_structure(cache)
            == jax.tree_util.tree_structure(new_cache))


def test_decode_matches_prefill_dense(rng):
    """Greedy decode logits == teacher-forced logits (dense family)."""
    cfg = reduced_config("yi-6b")
    params = api.init(cfg, rng)
    B, S = 2, 8
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size, jnp.int32)
    from repro.models import transformer
    h, _ = transformer.forward(cfg, params, toks)
    full_logits = transformer.logits_fn(cfg, params, h)

    cache = api.init_cache(cfg, B, S)
    for t in range(S):
        logits, cache = api.decode_step(cfg, params, cache, toks[:, t],
                                        jnp.asarray(t, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(full_logits[:, t], np.float32),
            rtol=0.15, atol=0.15)   # bf16 accumulation differences


@pytest.mark.parametrize("arch", ["yi-6b", "codeqwen1.5-7b"])
@pytest.mark.parametrize("proj", ["out_project", "mlp"])
def test_serving_projections_match_f32_reference(arch, proj, rng):
    """The serving forward's row-parallel projections (attention out, MLP
    down; ``fp32_reduce=True``) are one contraction accumulating in fp32
    and cast to bf16 once: each output
    is an fp32 ``precision="highest"`` einsum of the same bf16 operands
    rounded to bf16.  Rounding to nearest moves a value by at most half
    a bf16 ulp, 2**-9 of it, and the fp32 sums may run in another order
    (about 1e-7 of the sum of magnitudes); the tolerance, 2**-8
    relative plus 1e-4 of the largest output, holds both with room,
    and fails a contraction that drops or repeats a head or a hidden
    group (an error of a whole term)."""
    from repro.models import attention as attn
    from repro.models import mlp as mlp_mod
    from repro.models.common import activation
    cfg = reduced_config(arch)
    lp = jax.tree_util.tree_map(lambda a: a[0],
                                api.init(cfg, rng)["layers"])
    k1, k2 = jax.random.split(jax.random.fold_in(rng, 1))
    hi = functools.partial(jnp.einsum, precision="highest",
                           preferred_element_type=jnp.float32)
    if proj == "out_project":
        o = jax.random.normal(
            k1, (3, 5, cfg.num_heads, cfg.head_dim)).astype(jnp.bfloat16)
        got = attn.out_project(lp["attn"], o, fp32_reduce=True)
        want = hi("bshk,hkd->bsd", o, lp["attn"]["wo"].astype(o.dtype))
    else:
        p = lp["mlp"]
        x = jax.random.normal(k2, (3, 5, cfg.d_model)).astype(jnp.bfloat16)
        got = mlp_mod.mlp(cfg, p, x, fp32_reduce=True)
        # the hidden h exactly as the served path forms it, in bf16
        gate = x @ p["w_gate"].astype(x.dtype) if "w_gate" in p else None
        h = activation(cfg, x @ p["w_up"].astype(x.dtype), gate)
        want = hi("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want)
    np.testing.assert_allclose(
        got, want, rtol=2 ** -8, atol=1e-4 * np.abs(want).max())


@pytest.mark.parametrize("proj", ["out_project", "mlp"])
def test_training_projections_keep_bf16_dot_outputs(proj, rng):
    """Training calls the projections without ``fp32_reduce``: every dot
    outputs the bf16 activation dtype, so remat="dots" saves bf16
    residuals and a tp plan all-reduces bf16, and no ``xshard_`` tag
    enters the training loss's trace.  The serving variant's last dot
    outputs fp32 under its tag, so the check tells the two apart."""
    from repro.analysis.jaxpr_check import collect_tags, iter_eqns
    from repro.models import attention as attn
    from repro.models import mlp as mlp_mod
    from repro.models import transformer
    cfg = reduced_config("yi-6b")
    params = api.init(cfg, rng)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    if proj == "out_project":
        arg = jnp.ones((3, 5, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
        f = lambda a, r: attn.out_project(lp["attn"], a, fp32_reduce=r)  # noqa: E731
    else:
        arg = jnp.ones((3, 5, cfg.d_model), jnp.bfloat16)
        f = lambda a, r: mlp_mod.mlp(cfg, lp["mlp"], a, fp32_reduce=r)  # noqa: E731

    def dots_and_tags(reduce32):
        jaxpr = jax.make_jaxpr(lambda a: f(a, reduce32))(arg).jaxpr
        dots = [e.outvars[0].aval.dtype for e in iter_eqns(jaxpr)
                if e.primitive.name == "dot_general"]
        return dots, [t for t, _ in collect_tags(jaxpr)]

    dots, tags = dots_and_tags(False)
    assert dots and set(dots) == {jnp.dtype(jnp.bfloat16)} and not tags
    dots, tags = dots_and_tags(True)
    assert dots[-1] == jnp.float32 and len(tags) == 1, (dots, tags)
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32)}
    loss = jax.make_jaxpr(jax.grad(
        lambda p: transformer.loss_fn(cfg, p, batch)))(params)
    assert not [t for t, _ in collect_tags(loss.jaxpr)
                if t.startswith("xshard_")]


def test_decode_matches_prefill_ssm(rng):
    """Step-by-step SSM decode == full-sequence forward."""
    cfg = reduced_config("falcon-mamba-7b")
    params = api.init(cfg, rng)
    B, S = 2, 6
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size, jnp.int32)
    from repro.models import ssm_lm, transformer
    h, _ = ssm_lm.forward(cfg, params, toks)
    full_logits = transformer.logits_fn(cfg, params, h)

    cache = api.init_cache(cfg, B, S)
    for t in range(S):
        logits, cache = api.decode_step(cfg, params, cache, toks[:, t],
                                        jnp.asarray(t, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(full_logits[:, t], np.float32),
            rtol=0.2, atol=0.2)


def test_vlm_prefix_embeds(rng):
    cfg = reduced_config("internvl2-1b")
    params = api.init(cfg, rng)
    from repro.configs import reduced_shape
    shape = reduced_shape("train")
    batch = api.make_batch(cfg, shape, rng)
    assert "prefix_embeds" in batch
    loss_a = api.loss_fn(cfg, params, batch)
    batch2 = dict(batch)
    batch2["prefix_embeds"] = batch["prefix_embeds"] + 1.0
    loss_b = api.loss_fn(cfg, params, batch2)
    assert float(loss_a) != float(loss_b), "prefix embeds must be consumed"


def test_moe_capacity_drops_are_bounded(rng):
    cfg = reduced_config("dbrx-132b")
    params = api.init(cfg, rng)
    from repro.models import moe
    x = jax.random.normal(rng, (2, 16, cfg.d_model), jnp.float32)
    lp = jax.tree_util.tree_map(lambda p: p[0], params["layers"])
    y, aux = moe.moe_mlp(cfg, lp["moe"], x)
    assert y.shape == x.shape
    assert float(aux) >= 1.0 - 1e-3   # switch aux loss lower bound is 1


def test_param_counts_plausible():
    # full configs: analytic N close to the published sizes
    n = get_config("yi-6b").param_count()
    assert 5.5e9 < n < 7.0e9, n
    n = get_config("deepseek-coder-33b").param_count()
    assert 30e9 < n < 36e9, n
    n = get_config("dbrx-132b").param_count()
    assert 125e9 < n < 140e9, n
    n = get_config("falcon-mamba-7b").param_count()
    assert 6e9 < n < 8.5e9, n


def test_active_params_moe():
    cfg = get_config("dbrx-132b")
    total = cfg.param_count()
    active = api.active_param_count(cfg)
    assert active < 0.5 * total          # 4/16 experts active + shared
    cfg2 = get_config("moonshot-v1-16b-a3b")
    a2 = api.active_param_count(cfg2)
    assert 2e9 < a2 < 5e9, a2            # the "a3b" in the name

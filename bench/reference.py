"""Plain float32 reference of the served decoder, and its fp8 control.

The reference follows the published architecture of a Llama-style
decoder (Yi) or Qwen1.5 (q/k/v bias), written straight from the
equations in ``jax.numpy`` at ``highest`` matmul precision: RMSNorm
with the published epsilon, rotary embeddings on the two halves of each
head, grouped-query causal softmax attention, SwiGLU, an untied head.
It imports nothing of the program: its weights are regenerated from the
seed one layer at a time (bench/weights.py), in the bfloat16 values the
program serves, upcast to float32.

Sequences are packed into one fixed-length row (``pack_tokens``), each
with its own positions and a block-diagonal causal mask, so one compiled
layer serves every run.  The pass returns, at every position asked for,
the reference's best logit, its logit for the served token, and, with
``control=True``, its logit for the token that the same pass with fp8
(e4m3, per-output-channel scaled) weights puts first.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import dims, layer_slice, make_leaf

Q_BLOCK = 512          # query rows per attention block
ROW_BLOCK = 1024       # positions per block of the head


def layer_shapes(dm: Dict[str, int]) -> Dict[str, tuple]:
    d, H, KH, hd, f = dm["d"], dm["H"], dm["KH"], dm["hd"], dm["f"]
    out = {"ln1.scale": (d,), "ln2.scale": (d,),
           "attn.wq": (d, H, hd), "attn.wk": (d, KH, hd),
           "attn.wv": (d, KH, hd), "attn.wo": (H, hd, d),
           "mlp.w_gate": (d, f), "mlp.w_up": (d, f), "mlp.w_down": (f, d)}
    if dm["bias"]:
        out.update({"attn.bq": (H, hd), "attn.bk": (KH, hd),
                    "attn.bv": (KH, hd)})
    return out


def fp8_round(w: jax.Array, contract_axes: Sequence[int]) -> jax.Array:
    """``w`` through e4m3 with one scale per output channel (the amax
    over the contracted axes), back in float32."""
    amax = jnp.max(jnp.abs(w), axis=tuple(contract_axes), keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_CONTRACT = {"attn.wq": (0,), "attn.wk": (0,), "attn.wv": (0,),
             "attn.wo": (0, 1), "mlp.w_gate": (0,), "mlp.w_up": (0,),
             "mlp.w_down": (0,)}


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None, None] * inv        # [T,1,hd/2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "bias"))
def _layer(x, w, pos, seg, *, eps, theta, bias):
    T = x.shape[0]
    KH, hd = w["attn.wk"].shape[1:]
    H = w["attn.wq"].shape[1]
    G = H // KH
    h = _rmsnorm(x, w["ln1.scale"], eps)
    q = jnp.einsum("td,dhk->thk", h, w["attn.wq"])
    k = jnp.einsum("td,dhk->thk", h, w["attn.wk"])
    v = jnp.einsum("td,dhk->thk", h, w["attn.wv"])
    if bias:
        q, k, v = q + w["attn.bq"], k + w["attn.bk"], v + w["attn.bv"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(T // Q_BLOCK, Q_BLOCK, KH, G, hd) * hd ** -0.5
    qi = jnp.arange(T).reshape(T // Q_BLOCK, Q_BLOCK)
    kj = jnp.arange(T)

    def block(args):
        qb, rows = args
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k)
        ok = (seg[rows][:, None] == seg[None, :]) & (kj[None, :] <= rows[:, None])
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    o = jax.lax.map(block, (q, qi)).reshape(T, H, hd)
    x = x + jnp.einsum("thk,hkd->td", o, w["attn.wo"])
    h = _rmsnorm(x, w["ln2.scale"], eps)
    g = jnp.einsum("td,df->tf", h, w["mlp.w_gate"])
    u = jnp.einsum("td,df->tf", h, w["mlp.w_up"])
    return x + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, w["mlp.w_down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, rows, fnorm, unembed, served, *, eps):
    """Per asked position: (best logit, logit of the served token, the
    argmax token)."""
    h = _rmsnorm(x[rows], fnorm, eps)
    logits = jnp.einsum("td,dv->tv", h, unembed)
    return (logits.max(-1), jnp.take_along_axis(logits, served[:, None],
                                                1)[:, 0],
            jnp.argmax(logits, -1).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("eps",))
def _logit_at(x, rows, fnorm, unembed, tok, *, eps):
    h = _rmsnorm(x[rows], fnorm, eps)
    logits = jnp.einsum("td,dv->tv", h, unembed)
    return jnp.take_along_axis(logits, tok[:, None], 1)[:, 0]


def pack(seqs: List[np.ndarray], targets: List[np.ndarray],
         pack_tokens: int):
    """Concatenate sequences into one row of ``pack_tokens``.  Returns
    (tokens, positions, segments, asked rows, served tokens)."""
    tok = np.zeros(pack_tokens, np.int32)
    pos = np.zeros(pack_tokens, np.int32)
    seg = np.full(pack_tokens, -1, np.int32)
    rows, served = [], []
    off = 0
    for i, (s, (first, toks)) in enumerate(zip(seqs, targets)):
        n = len(s)
        assert off + n <= pack_tokens, "packed sequences overflow"
        tok[off:off + n] = s
        pos[off:off + n] = np.arange(n)
        seg[off:off + n] = i
        # the logits at position first - 1 + j predict served token j
        rows.extend(off + first - 1 + np.arange(len(toks)))
        served.extend(toks)
        off += n
    return tok, pos, seg, np.asarray(rows, np.int32), np.asarray(served,
                                                                 np.int32)


def run(conf: Dict, seed: int, prompts: List[np.ndarray],
        outputs: List[List[int]], pack_tokens: int, *,
        control: bool = False) -> Dict[str, np.ndarray]:
    """Reference pass over each prompt followed by its served tokens.

    Returns float arrays over every served token: ``best`` (the
    reference's top logit), ``served`` (its logit for the served token)
    and, with ``control``, ``control`` (its logit for the fp8 pass's
    top token)."""
    dm = dims(conf)
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    seqs = [np.concatenate([p, np.asarray(o[:-1], np.int32)])
            for p, o in zip(prompts, outputs)]
    targets = [(len(p), np.asarray(o, np.int32))
               for p, o in zip(prompts, outputs)]
    tok, pos, seg, rows, served = pack(seqs, targets, pack_tokens)
    n = len(rows)
    npad = -(-n // ROW_BLOCK) * ROW_BLOCK
    rows_p = np.zeros(npad, np.int32)
    rows_p[:n] = rows
    served_p = np.zeros(npad, np.int32)
    served_p[:n] = served
    f32 = jnp.float32
    shapes = layer_shapes(dm)
    with jax.default_matmul_precision("highest"):
        emb = make_leaf(seed, "embed", (dm["V"], dm["d"]), dm)
        x = emb[jnp.asarray(tok)].astype(f32)
        xc = _fp8_embed(emb, tok) if control else None
        del emb
        pos_d, seg_d = jnp.asarray(pos), jnp.asarray(seg)
        for layer in range(dm["L"]):
            w = {k: layer_slice(seed, "layers." + k, s, layer,
                                dm).astype(f32)
                 for k, s in shapes.items()}
            x = _layer(x, w, pos_d, seg_d, eps=eps, theta=theta,
                       bias=dm["bias"])
            if control:
                wc = {k: (fp8_round(v, _CONTRACT[k]) if k in _CONTRACT
                          else v) for k, v in w.items()}
                xc = _layer(xc, wc, pos_d, seg_d, eps=eps, theta=theta,
                            bias=dm["bias"])
            del w
        fnorm = make_leaf(seed, "final_norm.scale", (dm["d"],), dm
                          ).astype(f32)
        unembed = make_leaf(seed, "unembed", (dm["d"], dm["V"]), dm
                            ).astype(f32)
        out = {"best": [], "served": [], "control": []}
        unembed_c = fp8_round(unembed, (0,)) if control else None
        for b in range(0, npad, ROW_BLOCK):
            r = jnp.asarray(rows_p[b:b + ROW_BLOCK])
            s = jnp.asarray(served_p[b:b + ROW_BLOCK])
            best, at, _ = _head(x, r, fnorm, unembed, s, eps=eps)
            out["best"].append(np.asarray(best))
            out["served"].append(np.asarray(at))
            if control:
                _, _, ctop = _head(xc, r, fnorm, unembed_c, s, eps=eps)
                out["control"].append(np.asarray(
                    _logit_at(x, r, fnorm, unembed, ctop, eps=eps)))
    return {k: np.concatenate(v)[:n] for k, v in out.items() if v}


def _fp8_embed(emb: jax.Array, tok: np.ndarray) -> jax.Array:
    """Embedding rows through e4m3 with one scale per row."""
    rows = emb[jnp.asarray(tok)].astype(jnp.float32)
    return fp8_round(rows, (1,))


def gaps(result: Dict[str, np.ndarray], key: str = "served"
         ) -> Optional[np.ndarray]:
    """How far below the reference's best logit each chosen token's
    reference logit lies (0 where it is the reference's own choice)."""
    if key not in result or not len(result[key]):
        return None
    return result["best"] - result[key]

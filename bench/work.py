"""Operations and bytes that serving work needs, counted from shapes.

Only the work any implementation of the model must do is counted: each
weight read once per step, the live KV (each slot's own context, not the
``max_len`` extent of a slot), the new KV written, and the model FLOPs
of the tokens processed.  Nothing here reads the compiled program, so a
faster implementation of the same step raises its share and the count
cannot exceed what the chip must do.
"""

from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


def layer_params(dm: Dict[str, int]) -> int:
    """Weights of one decoder layer (matrices, biases, norms)."""
    d, H, KH, hd, f = dm["d"], dm["H"], dm["KH"], dm["hd"], dm["f"]
    n = d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * f + 2 * d
    if dm["bias"]:
        n += (H + 2 * KH) * hd
    return n


def matmul_params(dm: Dict[str, int]) -> int:
    """Parameters each token multiplies through, head excluded: the
    non-embedding parameters of the layers."""
    return dm["L"] * layer_params(dm)


def head_params(dm: Dict[str, int]) -> int:
    return dm["d"] * dm["V"] + dm["d"]          # unembed + final norm


def weight_bytes(dm: Dict[str, int], per: int = BF16) -> int:
    """Bytes of every weight a decode step reads (the embedding is
    gathered a row per token and counted with the tokens)."""
    return (matmul_params(dm) + head_params(dm)) * per


def kv_bytes_per_token(dm: Dict[str, int], per: int = BF16) -> int:
    return 2 * dm["L"] * dm["KH"] * dm["hd"] * per


def token_flops(dm: Dict[str, int], ctx: int) -> float:
    """Model FLOPs of one token through the layers at context ``ctx``
    (it attends ``ctx`` positions, itself included)."""
    attn = 4.0 * dm["L"] * dm["H"] * dm["hd"] * ctx
    return 2.0 * matmul_params(dm) + attn


def head_flops(dm: Dict[str, int]) -> float:
    """The head, for a position whose logits pick a token."""
    return 2.0 * dm["d"] * dm["V"]


def decode_step_work(dm: Dict[str, int], kv_lens: Iterable[int]):
    """(FLOPs, bytes) of one decode step over the slots whose context
    before the step is ``kv_lens``."""
    kv_lens = list(kv_lens)
    n = len(kv_lens)
    flops = sum(token_flops(dm, k + 1) for k in kv_lens) + n * head_flops(dm)
    kvb = kv_bytes_per_token(dm)
    nbytes = (weight_bytes(dm) + n * dm["d"] * BF16          # embed rows
              + sum(k for k in kv_lens) * kvb                # live KV read
              + n * kvb)                                     # new KV
    return flops, nbytes


def span_work(dm: Dict[str, int], kv_lens: Iterable[int], steps: int,
              emitted: int):
    """(FLOPs, bytes) of a decode span of ``steps`` steps that started
    with the active slots at ``kv_lens`` and emitted ``emitted`` tokens.
    Slots that stop inside the span are taken to be those with the
    longest contexts, stopping as early as the count allows, and steps
    after the last emission are not work: a lower bound."""
    kv_lens = sorted(kv_lens)
    if not kv_lens or emitted <= 0:
        return 0.0, 0.0
    n = len(kv_lens)
    full, part = divmod(emitted, n)
    flops = nbytes = 0.0
    for j in range(min(full + bool(part), steps)):
        live = n if j < full else part
        f, b = decode_step_work(dm, [k + j for k in kv_lens[:live]])
        flops += f
        nbytes += b
    return flops, nbytes


def chunk_work(dm: Dict[str, int], rows):
    """(FLOPs, bytes) of a chunk step: ``rows`` holds, per slot, (first
    position written, tokens written, emits a token).  Bytes: every
    weight once, the embedding rows, each slot's live KV read once (its
    context before the step) and its new KV written."""
    flops = 0.0
    kvb = kv_bytes_per_token(dm)
    nbytes = float(weight_bytes(dm))
    for first, n, emits in rows:
        flops += sum(token_flops(dm, first + i + 1) for i in range(n))
        flops += head_flops(dm) if emits else 0.0
        nbytes += n * dm["d"] * BF16 + first * kvb + n * kvb
    return flops, nbytes


def least_time(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])

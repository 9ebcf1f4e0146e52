"""The benchmark's arithmetic on the CPU: peaks, work counts, traffic,
weights, packing.  No program is run here."""

import json
import os

import numpy as np
import pytest

import tiny_cell  # noqa: F401  (puts the checkout on the path)
from bench import peaks, reference, traffic, weights, work

ROOT = tiny_cell.ROOT


CODEQWEN_16L = {"num_hidden_layers": 16, "hidden_size": 4096,
                "num_attention_heads": 32, "num_key_value_heads": 32,
                "head_dim": 128, "intermediate_size": 13440,
                "vocab_size": 92416, "attention_bias": True}


def conf(name):
    if name == "codeqwen-16l":
        return CODEQWEN_16L
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_peaks_known_and_unknown():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", "", None):
        with pytest.raises(KeyError):
            peaks.peaks(kind)


@pytest.mark.parametrize("name,layer,kv_token", [
    # Yi-6B: 4096*(32*128) + 2*4096*(4*128) + 4096*4096 + 3*4096*11008
    # + 2*4096 per layer; KV 2 * 32 layers * 4 heads * 128 * 2 bytes
    ("yi-6b", 4096 * 4096 * 2 + 2 * 4096 * 512 + 3 * 4096 * 11008
     + 2 * 4096, 2 * 32 * 4 * 128 * 2),
    # CodeQwen1.5's widths at 16 layers: MHA (32 KV heads), q/k/v bias
    ("codeqwen-16l", 4 * 4096 * 4096 + 3 * 4096 * 13440 + 2 * 4096
     + 3 * 4096, 2 * 16 * 32 * 128 * 2),
])
def test_work_counts_by_hand(name, layer, kv_token):
    dm = weights.dims(conf(name))
    assert work.layer_params(dm) == layer
    assert work.kv_bytes_per_token(dm) == kv_token
    head = dm["d"] * dm["V"] + dm["d"]
    assert work.weight_bytes(dm) == 2 * (dm["L"] * layer + head)
    # one decode step over two slots at contexts 10 and 20
    f, b = work.decode_step_work(dm, [10, 20])
    attn = 4 * dm["L"] * dm["H"] * dm["hd"] * (11 + 21)
    assert f == 2 * (2 * dm["L"] * layer) + attn + 2 * 2 * dm["d"] * dm["V"]
    assert b == (work.weight_bytes(dm) + 2 * dm["d"] * 2
                 + 30 * kv_token + 2 * kv_token)
    # a chunk step: 16 rows from position 32 of one slot (emits), one
    # decode row at 20 of another
    cf, cb = work.chunk_work(dm, [(32, 16, True), (20, 1, False)])
    assert cf == (sum(work.token_flops(dm, 33 + i) for i in range(16))
                  + work.token_flops(dm, 21) + work.head_flops(dm))
    assert cb == (work.weight_bytes(dm) + 17 * dm["d"] * 2
                  + (32 + 20) * kv_token + 17 * kv_token)
    # a span that emitted 3 tokens over 2 slots in 8 steps: 2 steps
    f2, b2 = work.span_work(dm, [20, 10], 8, 3)
    s1 = work.decode_step_work(dm, [10, 20])
    s2 = work.decode_step_work(dm, [11])
    assert (f2, b2) == (s1[0] + s2[0], s1[1] + s2[1])
    pk = peaks.peaks("TPU v5 lite")
    assert work.least_time(f, b, pk) == b / 819e9     # memory bound


def test_yi_weights_match_the_published_size():
    dm = weights.dims(conf("yi-6b"))
    n = work.matmul_params(dm) + work.head_params(dm) + dm["V"] * dm["d"]
    assert 6.0e9 < n < 6.1e9          # "6B": 6.06 billion


@pytest.mark.parametrize("mix", ["chat", "sysprompt", "waves"])
def test_traffic_is_seeded_and_clipped(mix):
    m = (traffic.load_mix(mix) if mix == "chat"
         else dict(tiny_cell.MIXES[mix], prompt={"median": 300, "sigma": 0.5,
                                                 "min": 20, "max": 900}))
    gen = ((lambda s: traffic.wave(m, s, 1000, 0))
           if m["kind"] == "waves"
           else (lambda s: traffic.open_loop(m, s, 1000, 20.0)))
    a, b, c = gen(2**33 + 1), gen(2**33 + 1), gen(2**33 + 2)
    assert [x.prompt.tolist() for x in a] == [x.prompt.tolist() for x in b]
    assert [x.t_arrival for x in a] == [x.t_arrival for x in b]
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    # every seed gets the same multiset of sizes, in another order
    key = lambda xs: sorted((len(x.prompt), x.max_new) for x in xs)  # noqa
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt)
                                                      for x in c)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in c)
    assert key(a) != [] and len(a) == len(c)
    pre = m.get("prefix", {}).get("length", 0)
    for x in a:
        assert m["prompt"]["min"] <= len(x.prompt) - pre <= m["prompt"]["max"]
        assert m["output"]["min"] <= x.max_new <= m["output"]["max"]
        assert x.prompt.dtype == np.int32 and x.prompt.max() < 1000
    if m["kind"] == "poisson":
        t = [x.t_arrival for x in a]
        assert t == sorted(t) and abs(t[-1] - 20.0) < 20.0 * 0.25
        greedy = sum(x.greedy for x in a)
        assert greedy == round(len(a) * m["greedy_share"])


def test_zipf_counts_sum_and_order():
    c = traffic.zipf_counts(100, 8, 1.0)
    assert c.sum() == 100 and list(c) == sorted(c, reverse=True)
    assert c[0] == 37                      # 100 / H_8 = 36.8


def test_layer_slice_is_bitwise_the_stacked_slice():
    dm = weights.dims(tiny_cell.CONF)
    whole = np.asarray(weights.make_leaf(9, "layers.mlp.w_up", (2, 64, 128),
                                         dm))
    for layer in range(2):
        part = np.asarray(weights.layer_slice(9, "layers.mlp.w_up",
                                              (64, 128), layer, dm))
        assert np.array_equal(whole[layer].view(np.uint16),
                              part.view(np.uint16))
    std = float(whole.astype(np.float32).std())
    assert abs(std - 64 ** -0.5) < 0.05 * 64 ** -0.5
    with pytest.raises(KeyError):
        weights.rule("layers.attn.w_unknown", dm)


def test_pack_positions_and_targets():
    seqs = [np.arange(5, dtype=np.int32), np.arange(3, dtype=np.int32)]
    targets = [(3, np.array([7, 8, 9])), (2, np.array([4, 5]))]
    tok, pos, seg, rows, served = reference.pack(seqs, targets, 16)
    assert pos[:8].tolist() == [0, 1, 2, 3, 4, 0, 1, 2]
    assert seg.tolist() == [0] * 5 + [1] * 3 + [-1] * 8
    assert rows.tolist() == [2, 3, 4, 6, 7] and served.tolist() == [7, 8, 9,
                                                                     4, 5]

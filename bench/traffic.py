"""The one traffic generator: turns a mix's data file and a seed into
requests.

A mix is a JSON file under ``bench/traffic/`` (found by the cell's
``traffic`` name).  Its keys:

    kind          "poisson" (open loop at ``rate_rps``) or "waves"
                  (offline: ``wave_size`` requests at once, wave after
                  wave, until the window has passed)
    rate_rps      arrival rate of an open-loop mix
    wave_size     requests per wave of an offline mix
    prompt        {"median", "sigma", "min", "max"}: log-normal length of
                  the prompt (of its unique tail where ``prefix`` is set)
    output        the same, for the number of tokens to generate
    prefix        optional {"count", "length", "zipf"}: shared system
                  prompts, each request one of them (Zipf popularity)
                  plus its own tail; they are primed during set-up
    greedy_share  share of requests decoded greedily (the rest sample)
    sampling      {"temperature", "top_p"} of the sampled requests
    check         {"pack_tokens"}: size of the packed reference pass

Every seed gets the same multiset of lengths, gaps and prefix choices:
the log-normal and exponential laws are drawn at stratified quantiles
and only their order, and every token id, comes from the seed.  Runs
on different seeds then do the same amount of work, and differ only in
the order and content the scheduler sees.
"""

from __future__ import annotations

import dataclasses
import json
import os
import statistics
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Item:
    """One generated request, in plain numbers (no program types)."""
    rid: int
    t_arrival: float
    prompt: np.ndarray          # int32 token ids
    max_new: int
    temperature: float          # 0 = greedy
    top_p: float
    sample_seed: int
    prefix_id: int = -1         # which shared prefix, -1 for none

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def load_mix(name: str, root: str = HERE) -> Dict:
    with open(os.path.join(root, "traffic", name + ".json")) as f:
        return json.load(f)


def stratified_lengths(n: int, law: Dict) -> np.ndarray:
    """``n`` log-normal lengths at the quantiles (i + 1/2) / n, clipped
    to [min, max]: the same multiset for every seed."""
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(law["median"] * np.exp(law["sigma"] * z))
    return np.clip(x, law["min"], law["max"]).astype(np.int64)


def stratified_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at stratified quantiles."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def zipf_counts(n: int, count: int, s: float) -> np.ndarray:
    """How many of ``n`` requests use each of ``count`` prefixes under
    Zipf(s) popularity (largest remainder, so they sum to ``n``)."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    rest = np.argsort(-(exact - out), kind="stable")[: n - out.sum()]
    out[rest] += 1
    return out


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, stream])


def prefixes(mix: Dict, seed: int, vocab: int) -> List[np.ndarray]:
    """The shared system prompts of a mix (empty without ``prefix``)."""
    p = mix.get("prefix")
    if not p:
        return []
    rng = _rng(seed, 1)
    return [rng.integers(0, vocab, size=p["length"]).astype(np.int32)
            for _ in range(p["count"])]


def _requests(mix: Dict, seed: int, vocab: int, n: int, rid0: int,
              stream: int, times: np.ndarray) -> List[Item]:
    rng = _rng(seed, stream)
    plen = rng.permutation(stratified_lengths(n, mix["prompt"]))
    olen = rng.permutation(stratified_lengths(n, mix["output"]))
    n_greedy = int(round(n * mix.get("greedy_share", 1.0)))
    greedy = rng.permutation(np.arange(n) < n_greedy)
    pre = prefixes(mix, seed, vocab)
    if pre:
        p = mix["prefix"]
        which = rng.permutation(np.repeat(
            np.arange(p["count"]), zipf_counts(n, p["count"], p["zipf"])))
    samp = mix.get("sampling", {})
    items = []
    for i in range(n):
        tail = rng.integers(0, vocab, size=int(plen[i])).astype(np.int32)
        pid = int(which[i]) if pre else -1
        prompt = np.concatenate([pre[pid], tail]) if pre else tail
        items.append(Item(
            rid=rid0 + i, t_arrival=float(times[i]), prompt=prompt,
            max_new=int(olen[i]),
            temperature=0.0 if greedy[i] else float(samp["temperature"]),
            top_p=1.0 if greedy[i] else float(samp.get("top_p", 1.0)),
            sample_seed=int(rng.integers(1, 2**31 - 1)), prefix_id=pid))
    return items


def open_loop(mix: Dict, seed: int, vocab: int, seconds: float
              ) -> List[Item]:
    """An open-loop mix: ``round(rate * seconds)`` requests whose
    arrivals span about ``seconds`` (sorted by arrival)."""
    assert mix["kind"] == "poisson", mix["kind"]
    n = max(int(round(mix["rate_rps"] * seconds)), 1)
    gaps = _rng(seed, 2).permutation(stratified_gaps(n, mix["rate_rps"]))
    return _requests(mix, seed, vocab, n, 0, 3, np.cumsum(gaps))


def wave(mix: Dict, seed: int, vocab: int, k: int) -> List[Item]:
    """Wave ``k`` of an offline mix: ``wave_size`` requests at t=0."""
    assert mix["kind"] == "waves", mix["kind"]
    n = mix["wave_size"]
    return _requests(mix, seed, vocab, n, k * n, 100 + k, np.zeros(n))


def warmup_items(mix: Dict, seed: int, vocab: int, slots: int,
                 chunk: int) -> List[Item]:
    """Set-up traffic: each shared prefix once (primes the radix tree
    and runs the chunk step), then one short request per slot that
    decodes for a few spans (runs the decode span with every slot
    busy).  Token ids are drawn apart from the measured traffic's."""
    rng = _rng(seed, 7)
    items = [Item(rid=-1 - i, t_arrival=0.0, prompt=p, max_new=1,
                  temperature=0.0, top_p=1.0, sample_seed=1, prefix_id=i)
             for i, p in enumerate(prefixes(mix, seed, vocab))]
    for s in range(slots):
        items.append(Item(
            rid=-1000 - s, t_arrival=0.0,
            prompt=rng.integers(0, vocab, size=chunk + 3).astype(np.int32),
            max_new=24, temperature=0.0 if s % 2 else 0.8,
            top_p=0.95, sample_seed=s + 1))
    return items


def check_pack_tokens(mix: Dict, default: int = 4096) -> int:
    return int(mix.get("check", {}).get("pack_tokens", default))

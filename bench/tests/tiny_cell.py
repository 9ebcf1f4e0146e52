"""A tiny cell for the CPU tests: the program's own reduced Yi-6B preset
(2 layers, d_model 64, 4 heads of 16, d_ff 128, vocab 256) under small
versions of the benchmark's three mixes."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

CONF = {"arch": "yi-6b", "program_preset": "reduced",
        "num_hidden_layers": 2, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "intermediate_size": 128, "vocab_size": 256,
        "rope_theta": 5000000.0, "rms_norm_eps": 1e-05,
        "attention_bias": False, "batch_slots": 4, "max_len": 96}

_LAW = {"median": 20, "sigma": 1.0, "min": 4, "max": 48}
MIXES = {
    "chat": {"kind": "poisson", "rate_rps": 4.0, "prompt": _LAW,
             "output": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
             "greedy_share": 0.5,
             "sampling": {"temperature": 0.8, "top_p": 0.95},
             "check": {"pack_tokens": 1024}},
    "sysprompt": {"kind": "poisson", "rate_rps": 4.0,
                  "prefix": {"count": 3, "length": 32, "zipf": 1.0},
                  "prompt": {"median": 8, "sigma": 0.8, "min": 2, "max": 20},
                  "output": {"median": 12, "sigma": 0.8, "min": 4,
                             "max": 30},
                  "greedy_share": 0.5,
                  "sampling": {"temperature": 0.8, "top_p": 0.95},
                  "check": {"pack_tokens": 1024}},
    "waves": {"kind": "waves", "wave_size": 6,
              "prompt": {"median": 30, "sigma": 0.5, "min": 10, "max": 60},
              "output": {"median": 10, "sigma": 0.6, "min": 4, "max": 30},
              "greedy_share": 1.0, "check": {"pack_tokens": 1024}},
}

# widest logit gap a sound tiny run may show (readings at this size:
# 0 to 0.009 for the program, 0.14 to 0.19 for the fp8 control)
LIMIT = 0.05


# the harness's end-to-end metrics: the open-loop cells report the
# first two and TPOT, an offline cell TPOT and tokens per second
E2E = {"setup_s": "s", "ttft_p90_ms": "ms", "tpot_p90_ms": "ms",
       "output_tok_s": "tokens/s"}


def cell(mix: str = "chat"):
    from bench import harness
    bm = harness.load_benchmark()
    names = (["setup_s", "tpot_p90_ms", "output_tok_s"]
             if MIXES[mix]["kind"] == "waves"
             else ["setup_s", "ttft_p90_ms", "tpot_p90_ms"])
    return {"cell": {"name": "tiny-" + mix, "chips": 1}, "conf": CONF,
            "mix": MIXES[mix], "limits": {"max_logit_gap": LIMIT},
            "end_to_end": [{"name": n, "unit": E2E[n]} for n in names],
            "per_layer": bm["per_layer"], "run_seconds": 2}


def run(mix: str = "chat", *, seed: int = 2**31 + 5, seconds: float = 2.0,
        control: bool = False, trace: bool = False):
    import time
    from bench import harness
    return harness.run_cell(cell(mix), seed=seed, seconds=seconds,
                            trace=trace, devs=None,
                            t_process=time.perf_counter(),
                            log=lambda _: None, control=control)

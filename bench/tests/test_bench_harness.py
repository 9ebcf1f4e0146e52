"""The harness at a tiny size on the CPU: every cell's files are found
by name, the measuring path refuses a machine without a chip, a whole
run (set-up, window, check) comes out correct, and runs with the timed
path broken underneath come out not correct."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import tiny_cell
from bench import harness, traffic

ROOT = tiny_cell.ROOT


def test_every_cell_is_found_by_name():
    bm = harness.load_benchmark()
    for w in bm["workloads"]:
        c = harness.load_cell(w["name"])
        assert c["conf"]["batch_slots"] > 0 and c["limits"]["max_logit_gap"]
        names = {m["name"] for m in c["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2 and c["per_layer"]
        cfg = harness.program_config(c["conf"])     # widths agree
        assert cfg.num_layers == c["conf"]["num_hidden_layers"]
        assert traffic.load_mix(w["traffic"])["kind"] in ("poisson", "waves")
    for m in bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_measuring_path_needs_a_chip():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", "yi6b-chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


@pytest.mark.parametrize("mix", ["chat", "sysprompt", "waves"])
def test_tiny_run_is_correct(mix):
    r = tiny_cell.run(mix)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 3
    assert r["window_compiles"] == 0
    names = set(r["metrics"])
    assert names == {m["name"] for m in tiny_cell.cell(mix)["end_to_end"]}
    gap = r["check"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"] == tiny_cell.LIMIT


def test_control_reads_above_the_limit():
    """The fp8 control at the tiny size: it has to fail the limit that
    the program meets (the chip readings are in PERF.md)."""
    r = tiny_cell.run("chat", control=True)
    assert r["check"]["max_logit_gap"]["value"] <= tiny_cell.LIMIT
    assert r["control"]["max_logit_gap"] > tiny_cell.LIMIT


def _stale_cache(monkeypatch):
    """Each step returns the KV cache it was given: nothing is written."""
    from repro.models import transformer
    chunk, decode = transformer.chunk_step, transformer.decode_step

    def chunk_step(cfg, params, cache, *a, **k):
        return chunk(cfg, params, cache, *a, **k)[0], cache

    def decode_step(cfg, params, cache, *a, **k):
        return decode(cfg, params, cache, *a, **k)[0], cache
    monkeypatch.setattr(transformer, "chunk_step", chunk_step)
    monkeypatch.setattr(transformer, "decode_step", decode_step)


def _altered_token(monkeypatch):
    """One token in five is changed where the sampling head makes it."""
    from repro.models import sampling
    head = sampling.sample_tokens

    def sample_tokens(logits, temp, top_k, top_p, seed, index):
        tok = head(logits, temp, top_k, top_p, seed, index)
        return jnp.where(index % 5 == 2, (tok + 1) % logits.shape[-1], tok)
    monkeypatch.setattr(sampling, "sample_tokens", sample_tokens)


def _half_batch(monkeypatch):
    """The upper half of the slots is left out of each step: its rows
    get the logits of the lower half."""
    from repro.models import transformer
    chunk, decode = transformer.chunk_step, transformer.decode_step

    def lower_half(step):
        def run(*a, **k):
            logits, cache = step(*a, **k)
            h = logits.shape[0] // 2
            return jnp.concatenate([logits[:h], logits[:h]]), cache
        return run
    monkeypatch.setattr(transformer, "chunk_step", lower_half(chunk))
    monkeypatch.setattr(transformer, "decode_step", lower_half(decode))


@pytest.mark.parametrize("fault", [_stale_cache, _half_batch,
                                   _altered_token])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    """On the offline mix, whose waves fill every slot."""
    fault(monkeypatch)
    r = tiny_cell.run("waves")
    assert not r["correct"]
    assert r["check"]["max_logit_gap"]["value"] > 2 * tiny_cell.LIMIT


def test_traced_run_reports_per_layer_metrics_only():
    """On the CPU the profiler sees no device, so the device metrics
    stay silent; the scheduler and step metrics are read."""
    r = tiny_cell.run("sysprompt", trace=True)
    assert r["correct"]
    names = set(r["metrics"])
    assert {"queue_wait_p90_ms", "prefix_hit_frac",
            "chunk_step_ms.online"} <= names
    assert not names & {"setup_s", "ttft_p90_ms", "device_idle",
                        "chunk_step_roofline", "step_mfu"}
    assert r["metrics"]["prefix_hit_frac"]["value"] > 0.3
    jax.clear_caches()

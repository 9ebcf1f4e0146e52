import os
import sys

# Tests run on the single default CPU device; distributed-semantics tests
# spawn subprocesses with their own XLA_FLAGS (test_distributed.py).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from repro.models import api  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "real_hardware: needs real multi-accelerator hardware; CPU CI "
        "exercises the same paths via forced host-device fan-out "
        "(tests/test_distributed.py, tests/test_tp_serving.py) and "
        "these tests self-skip there")


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def greedy_reference(cfg, params, reqs, *, max_len, eos_id=None):
    """Greedy outputs of ``reqs`` with no scheduler: each request alone
    on a contiguous batch-1 cache, its prompt fed one token at a time
    through the model layer's ``api.decode_step``, then argmax decoding
    until ``max_new`` tokens, ``eos_id`` (emitted, then stop) or the
    ``max_len`` position cap.  The serving engine's stop and truncation
    rules, stated independently of ``runtime/``: a request emits at
    most ``max_len - in_len`` tokens and is ``truncated`` when
    ``in_len + max_new`` overruns ``max_len``.

    Returns (outputs, truncated): a token list and a flag per request.
    """
    step = jax.jit(lambda c, t, p: api.decode_step(cfg, params, c, t, p))
    outputs, truncated = [], []
    for r in reqs:
        n = len(r.prompt)
        if n > max_len:
            raise ValueError(f"request {r.rid}: prompt length {n} "
                             f"exceeds max_len {max_len}")
        cache = api.init_cache(cfg, 1, max_len + 1)
        for pos, tok in enumerate(r.prompt):
            logits, cache = step(cache, jnp.asarray([tok], jnp.int32),
                                 jnp.asarray([pos], jnp.int32))
        out = []
        pos = n
        while True:
            tok = int(jnp.argmax(logits[0]))
            out.append(tok)
            if (len(out) >= r.max_new or pos >= max_len - 1
                    or tok == eos_id):
                break
            logits, cache = step(cache, jnp.asarray([tok], jnp.int32),
                                 jnp.asarray([pos], jnp.int32))
            pos += 1
        outputs.append(out)
        truncated.append(n + r.max_new > max_len)
    return outputs, truncated

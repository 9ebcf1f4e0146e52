"""The repo's serving contracts, in one registry the analyzer layers
share.  Adding a hot-path function, a parity-critical body or a flag
combo here is how the gate learns about new code paths — the checks
themselves stay generic.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional, Set

# ----------------------------------------------------------------------
# trace hooks (jax.ad_checkpoint.checkpoint_name tags in the model)
# ----------------------------------------------------------------------

# every cross-shard grouped reduction tags its fp32 partials with this
# prefix (common.fixed_tree_sum(tag=...)); JX004 asserts the tagged
# aval is float32, JX006 that serving traces carry at least one tag
XSHARD_TAG_PREFIX = "xshard_"

# the serving forward tags its final hidden state; a serving program
# whose jaxpr lacks it did not go through models/transformer's
# _serving_scan (JX006)
SERVING_TAG = "serving_hot_path"

# ----------------------------------------------------------------------
# layer 3 (AST) scope
# ----------------------------------------------------------------------

# jitted hot-path roots: (module, [Class.]function).  ast_lint builds a
# static call graph from these across the scanned modules and applies
# AST001 to everything reachable.
HOT_PATH_ROOTS = [
    ("repro.runtime.server", "ChunkedServer._chunk_impl"),
    ("repro.runtime.server", "ChunkedServer._span_impl"),
    ("repro.runtime.server", "ChunkedServer._spec_impl"),
]

# function bodies that must phrase their contractions as explicit
# multiply+sum (AST002), for a bitwise agreement that dot/einsum forms
# cannot promise.  path suffix (repo-relative) -> function names.  No
# body needs it today: the serving attention contracts on the MXU and
# is held to float32 references within stated tolerances.
PARITY_BODIES: Dict[str, Set[str]] = {}

# packages scanned by ast_lint (plus the PARITY_BODIES files);
# src/repro/obs is included so instrumentation helpers stay visible to
# the hot-path reachability scan — the telemetry layer must never put
# a host transfer on a jitted path (ROADMAP "Serving telemetry")
AST_SCAN_PACKAGES = ["src/repro/runtime", "src/repro/models",
                     "src/repro/obs"]

# ----------------------------------------------------------------------
# layer 2 (Pallas) budgets
# ----------------------------------------------------------------------

LANE = 128          # minor-most tile multiple the hardware wants
SUBLANE = 8         # second-minor multiple (fp32; coarser dtypes pack)
VMEM_BUDGET_BYTES = 16 * 1024 * 1024   # per-core VMEM working set
GRID_EVAL_CAP = 4096    # max grid cells to enumerate for KL002

# ----------------------------------------------------------------------
# layer 1 (jaxpr) serving flag matrix
# ----------------------------------------------------------------------


def serving_combos(device_count: int = 1,
                   max_combos: Optional[int] = None
                   ) -> List[Dict[str, Any]]:
    """Valid ChunkedServer flag combos, honoring the constructor's own
    constraints (fp8_linear is tp=1 dense; tp needs devices).
    Paired-down but covering every flag both ways and the interesting
    interactions."""
    from repro.models.sampling import SamplingParams
    sampled = SamplingParams(temperature=0.8, top_k=40, top_p=0.95,
                             seed=7)
    combos: List[Dict[str, Any]] = [
        {},                                         # defaults
        {"prefix_cache": False},
        {"spec_decode": 3},
        {"eos_id": 5},
        {"spec_decode": 3, "eos_id": 5},
        {"kernel": True},
        {"kernel": True, "spec_decode": 3},
        {"fp8_kv": True},
        {"fp8_kv": True, "kernel": True},
        {"fp8_kv": True, "kernel": True, "spec_decode": 3},
        {"fp8_linear": True},
        {"fp8_linear": True, "fp8_kv": True, "kernel": True},
        # stochastic sampling: greedy<->sampled must share one
        # signature per program (sampling operands are always present;
        # the flip is in the VALUES) — JX005 proves no recompile, and
        # JX001 that the device-side threefry draw smuggles no host
        # callback into the span
        {"sampling": sampled},
        {"sampling": sampled, "spec_decode": 3},
    ]
    if device_count >= 2:
        combos += [
            {"tp": 2},
            {"tp": 2, "spec_decode": 3},
            {"tp": 2, "kernel": True},
            {"tp": 2, "fp8_kv": True, "kernel": True},
        ]
    if max_combos is not None:
        combos = combos[:max_combos]
    return combos


def combo_label(combo: Dict[str, Any]) -> str:
    base = {"prefix_cache": True, "spec_decode": 0,
            "kernel": False, "fp8_kv": False, "fp8_linear": False,
            "tp": 1, "eos_id": None, "sampling": None}
    base.update(combo)
    parts = []
    for k, v in base.items():
        if isinstance(v, bool):
            parts.append(f"{k}={int(v)}")
        else:
            parts.append(f"{k}={v}")
    return ",".join(parts)


def signature_class(combo: Dict[str, Any]) -> str:
    """Combos agreeing on this key MUST produce identical abstract
    signatures per program (JX005): only the cache dtype (fp8_kv) may
    change operand shapes/dtypes."""
    return f"fp8_kv={int(combo.get('fp8_kv', False))}"


def iter_pairs(items):
    return itertools.combinations(items, 2)

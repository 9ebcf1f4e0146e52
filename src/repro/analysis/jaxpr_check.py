"""Layer 1: trace the serving programs and walk their closed jaxprs.

A tiny ``ChunkedServer`` (reduced yi-6b config) is built per flag
combo from contracts.serving_combos; the three jitted work units
(`_chunk_impl` / `_span_impl` / `_spec_impl`) are traced with
``jax.make_jaxpr`` over *abstract* operands shaped exactly like the
dispatch sites', so nothing executes and the audit covers the real
serving programs, not test doubles.

Rules:

* **JX001** — callback/infeed/outfeed primitives anywhere in the
  program (a host round-trip on the hot path).
* **JX002** — a non-static dimension in any equation output aval.
* **JX003** — the KV-cache operand is not donated (the lowered text
  must carry one ``tf.aliasing_output`` per cache leaf; without
  donation XLA materializes a second pool per step).
* **JX004** — a ``checkpoint_name`` tag starting with ``xshard_``
  (the cross-shard reduction hooks) whose aval is not fp32.
* **JX005** — abstract-signature drift: combos sharing a cache layout
  (contracts.signature_class) must present identical operand
  signatures per program, or the switch recompiles.
* **JX006** — a serving trace missing its hooks: no ``serving_hot_path``
  tag (the forward didn't go through ``_serving_scan``), or no
  ``xshard_`` tag when the combo uses the bf16 (not fp8) linears.
"""

from __future__ import annotations

import hashlib
import json
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis import contracts
from repro.analysis.report import Finding, Report

_HOST_PRIMS = {"infeed", "outfeed"}


# ----------------------------------------------------------------------
# jaxpr walking
# ----------------------------------------------------------------------

def _sub_jaxprs(val):
    if hasattr(val, "jaxpr") and hasattr(val, "consts"):   # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns"):                             # Jaxpr
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr):
    """Every equation, recursing into sub-jaxprs (scan/cond/pjit...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def collect_tags(jaxpr) -> List[Tuple[str, Any]]:
    """(tag, out_aval) for every checkpoint_name equation."""
    tags = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name == "name":
            tags.append((eqn.params.get("name", ""),
                         eqn.outvars[0].aval))
    return tags


def _check_jaxpr(label: str, program: str, jaxpr, combo: Dict[str, Any],
                 report: Report) -> None:
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if "callback" in name or name in _HOST_PRIMS:
            report.add(Finding(
                "JX001",
                f"{program} [{label}]: host primitive `{name}` on the "
                f"serving hot path",
                detail={"program": program, "combo": label,
                        "primitive": name}))
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if any(not isinstance(d, int) for d in shape):
                report.add(Finding(
                    "JX002",
                    f"{program} [{label}]: non-static shape {shape} "
                    f"from `{name}`",
                    detail={"program": program, "combo": label,
                            "primitive": name,
                            "shape": [str(d) for d in shape]}))

    tags = collect_tags(jaxpr)
    for tag, aval in tags:
        if tag.startswith(contracts.XSHARD_TAG_PREFIX) \
                and str(aval.dtype) != "float32":
            report.add(Finding(
                "JX004",
                f"{program} [{label}]: cross-shard reduction tag "
                f"`{tag}` accumulates in {aval.dtype}, not float32",
                detail={"program": program, "combo": label,
                        "tag": tag, "dtype": str(aval.dtype)}))
    tag_names = {t for t, _ in tags}
    if contracts.SERVING_TAG not in tag_names:
        report.add(Finding(
            "JX006",
            f"{program} [{label}]: `{contracts.SERVING_TAG}` tag "
            f"missing — the trace did not go through the serving "
            f"forward",
            detail={"program": program, "combo": label,
                    "missing": contracts.SERVING_TAG}))
    if not combo.get("fp8_linear", False) and not any(
            t.startswith(contracts.XSHARD_TAG_PREFIX)
            for t in tag_names):
        report.add(Finding(
            "JX006",
            f"{program} [{label}]: no `{contracts.XSHARD_TAG_PREFIX}*` "
            f"reduction tags — the fp32 row-parallel projections are "
            f"not in the trace",
            detail={"program": program, "combo": label,
                    "missing": contracts.XSHARD_TAG_PREFIX + "*"}))


# ----------------------------------------------------------------------
# server construction / operand abstraction
# ----------------------------------------------------------------------

def tiny_setup():
    from repro.configs import reduced_config
    from repro.models import api
    cfg = reduced_config("yi-6b")
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def build_server(cfg, params, combo: Dict[str, Any]):
    from repro.runtime.server import ChunkedServer
    kw = dict(batch_slots=2, max_len=64, chunk=8, span=4, block_size=8)
    kw.update(combo)
    return ChunkedServer(cfg, params, **kw)


def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), tree)


def serving_programs(srv) -> List[Tuple[str, Any, Any, tuple]]:
    """(program, impl, jitted, abstract_operands) mirroring the real
    dispatch sites in runtime/server.py."""
    B, C = srv.B, srv.chunk
    i32 = np.int32
    vec = np.zeros(B, i32)
    fvec = np.zeros(B, np.float32)
    flag = np.zeros(B, bool)
    tokens = np.zeros((B, C), i32)
    bt = srv._device_block_table()
    # per-slot sampling operands (temperature, top_k, top_p, seed) —
    # always present, greedy is encoded in the values (JX005 proves
    # greedy<->sampled flips share one signature)
    samp = (fvec, vec, np.ones(B, np.float32), vec)
    chunk_ops = (srv.params, srv.cache, srv.cur_tok, srv.out_buf,
                 tokens, vec, vec, flag, flag, vec) + samp + (bt,)
    span_ops = (srv.params, srv.cache, srv.cur_tok, srv.out_buf,
                vec, vec, flag, vec) + samp + (bt,)
    programs = [
        ("chunk_step", srv._chunk_impl, srv._chunk_fn,
         _abstract(chunk_ops)),
        ("decode_span", srv._span_impl, srv._span_fn,
         _abstract(span_ops)),
    ]
    if srv.spec_decode:
        verify_ops = (srv.params, srv.cache, srv.ngram_table,
                      srv.cur_tok, srv.out_buf, vec, vec, flag,
                      vec) + samp + (bt,)
        programs.append(("verify_step", srv._spec_impl, srv._verify_fn,
                         _abstract(verify_ops)))
    return programs


def _signature(abstract_ops) -> Tuple[str, list]:
    leaves, _ = jax.tree_util.tree_flatten_with_path(abstract_ops)
    entries = [[jax.tree_util.keystr(path), list(leaf.shape),
                str(leaf.dtype)] for path, leaf in leaves]
    digest = hashlib.sha256(
        json.dumps(entries, sort_keys=True).encode()).hexdigest()[:16]
    return digest, entries


def register_signature(registry: Dict[str, Dict[str, Dict[str, Any]]],
                       program: str, sig_class: str, label: str,
                       abstract_ops, report: Report) -> None:
    """Record a program's abstract signature; JX005 on drift within
    its signature class."""
    digest, entries = _signature(abstract_ops)
    slot = registry.setdefault(program, {}).setdefault(
        sig_class, {"hash": digest, "combos": [],
                    "n_operands": len(entries)})
    if slot["hash"] != digest:
        report.add(Finding(
            "JX005",
            f"{program} [{label}]: abstract signature {digest} drifts "
            f"from {slot['hash']} ({slot['combos'][0]}) within "
            f"signature class `{sig_class}` — flag switches would "
            f"recompile",
            detail={"program": program, "combo": label,
                    "class": sig_class, "hash": digest,
                    "expected": slot["hash"]}))
    else:
        slot["combos"].append(label)


def _check_donation(label: str, program: str, jitted, abstract_ops,
                    cache, report: Report) -> None:
    n_leaves = len(jax.tree_util.tree_leaves(cache))
    text = jitted.lower(*abstract_ops).as_text()
    aliased = text.count("tf.aliasing_output")
    if aliased < n_leaves:
        report.add(Finding(
            "JX003",
            f"{program} [{label}]: cache not donated — "
            f"{aliased}/{n_leaves} operand leaves aliased to outputs; "
            f"each step would materialize a second KV pool",
            detail={"program": program, "combo": label,
                    "aliased": aliased, "cache_leaves": n_leaves}))


# ----------------------------------------------------------------------
# entry point
# ----------------------------------------------------------------------

def run(report: Report, *, device_count: Optional[int] = None,
        max_combos: Optional[int] = None,
        check_donation: bool = True) -> None:
    if device_count is None:
        device_count = jax.device_count()
    cfg, params = tiny_setup()
    registry: Dict[str, Dict[str, Dict[str, Any]]] = {}
    combos = contracts.serving_combos(device_count, max_combos)
    for combo in combos:
        label = contracts.combo_label(combo)
        sig_class = contracts.signature_class(combo)
        srv = build_server(cfg, params, combo)
        for program, impl, jitted, abstract_ops in serving_programs(srv):
            closed = jax.make_jaxpr(impl)(*abstract_ops)
            _check_jaxpr(label, program, closed.jaxpr, combo, report)
            if check_donation:
                _check_donation(label, program, jitted, abstract_ops,
                                srv.cache, report)
            register_signature(registry, program, sig_class, label,
                               abstract_ops, report)
    report.extras["signatures"] = registry
    report.extras["combos"] = [contracts.combo_label(c) for c in combos]

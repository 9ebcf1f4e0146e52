"""Serving-contract static analyzer.

Three layers, one report (run ``python -m repro.analysis``):

* ``jaxpr_check``  — traces the serving programs (chunk_step,
  decode_span, verify_step) under every flag combo and walks the
  closed jaxprs: no host callbacks, no data-dependent shapes, cache
  donation, fp32 cross-shard reductions, abstract-signature drift.
* ``kernel_lint``  — captures every Pallas launch in ``kernels/``
  (monkeypatched ``pallas_call`` under ``jax.eval_shape``) and checks
  BlockSpec/grid contracts: oversize tiles, grid coverage, lane /
  sublane alignment, estimated VMEM footprint.
* ``ast_lint``     — repo-specific AST rules over ``runtime/`` and
  ``models/``: host transfers in hot-path bodies, dot/einsum in
  parity-critical bodies, mutable server state captured in
  jitted closures (the seed SlotServer frozen-``self.pos`` bug class).

The checked invariants, their rule IDs and the suppression mechanism
are documented in ROADMAP.md ("Serving contracts").
"""

from repro.analysis.report import Finding, Report, RULES  # noqa: F401

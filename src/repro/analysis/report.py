"""Finding/report plumbing shared by the three analyzer layers.

Every check emits ``Finding`` records keyed by a stable rule ID; the
``Report`` collects them, applies per-rule suppression, and serializes
to the JSON artifact CI uploads.  Severity semantics:

* ``error``   — a contract violation; fails the run (exit 1).
* ``warning`` — a diagnostic (e.g. a tile the hardware would pad);
  fails the run only under ``--strict``.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional

# rule id -> (severity, one-line contract description)
RULES: Dict[str, tuple] = {
    # layer 1: jaxpr walks over the traced serving programs
    "JX001": ("error", "host callback primitive on the serving hot path"),
    "JX002": ("error", "data-dependent / non-static shape in a serving "
                       "program"),
    "JX003": ("error", "KV cache operand is not donated to the serving "
                       "step (a second pool would be materialized)"),
    "JX004": ("error", "cross-shard grouped reduction does not "
                       "accumulate in fp32 (tp-vs-1 parity contract)"),
    "JX005": ("error", "abstract signature drift between flag combos "
                       "sharing a cache layout (would recompile)"),
    "JX006": ("error", "serving program traced without its trace hooks "
                       "(checkpoint_name tags missing)"),
    # layer 2: captured Pallas launch geometry
    "KL001": ("error", "BlockSpec tile larger than its operand extent"),
    "KL002": ("error", "grid x index_map does not cover the operand "
                       "extent (rows would be silently skipped)"),
    "KL003": ("warning", "lane-misaligned tile: last block dim is "
                         "neither a multiple of 128 nor the full "
                         "operand dim"),
    "KL004": ("warning", "sublane-misaligned tile: second-minor block "
                         "dim is neither a multiple of 8 nor the full "
                         "operand dim"),
    "KL005": ("error", "estimated VMEM working set exceeds the "
                       "per-core budget"),
    # layer 3: AST rules over runtime/ + models/
    "AST001": ("error", "host transfer (.item()/np.asarray/"
                        "jax.device_get/...) inside a hot-path body"),
    "AST002": ("error", "dot/@/einsum in a parity-critical body "
                        "that must stay explicit multiply+sum"),
    "AST003": ("error", "mutable server state read inside a jitted "
                        "body (jit freezes it per-trace: the seed "
                        "SlotServer frozen-self.pos bug class)"),
}


@dataclasses.dataclass
class Finding:
    rule: str
    message: str
    path: str = ""
    line: int = 0
    detail: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def severity(self) -> str:
        return RULES[self.rule][0]

    def to_dict(self) -> Dict[str, Any]:
        return {"rule": self.rule, "severity": self.severity,
                "message": self.message, "path": self.path,
                "line": self.line, "detail": self.detail}

    def __str__(self) -> str:
        loc = f"{self.path}:{self.line}: " if self.path else ""
        return f"{loc}{self.rule} [{self.severity}] {self.message}"


class Report:
    """Collects findings across layers; applies per-rule suppression."""

    def __init__(self, suppress: Optional[List[str]] = None):
        unknown = set(suppress or ()) - set(RULES)
        if unknown:
            raise ValueError(f"unknown rule id(s) in --suppress: "
                             f"{sorted(unknown)}")
        self.suppress = set(suppress or ())
        self.findings: List[Finding] = []
        self.suppressed: List[Finding] = []
        self.extras: Dict[str, Any] = {}

    def add(self, finding: Finding) -> None:
        if finding.rule not in RULES:
            raise ValueError(f"unknown rule id {finding.rule!r}")
        if finding.rule in self.suppress:
            self.suppressed.append(finding)
        else:
            self.findings.append(finding)

    def count(self, rule: str) -> int:
        return sum(1 for f in self.findings if f.rule == rule)

    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "error"]

    def exit_code(self, strict: bool = False) -> int:
        if self.errors():
            return 1
        if strict and self.findings:
            return 1
        return 0

    def summary(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out

    def to_json(self, *, strict: bool = False) -> str:
        return json.dumps({
            "strict": strict,
            "exit_code": self.exit_code(strict),
            "summary": self.summary(),
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [f.to_dict() for f in self.suppressed],
            **self.extras,
        }, indent=2, sort_keys=False)

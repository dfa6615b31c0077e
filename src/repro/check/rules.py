"""Rule registry: R1-R7 (R7: the device kind has no modelled peaks) as typed
:class:`Rule` records binding an id, severity, description, and the
detector functions from the jaxpr / HLO / trace-evidence passes.

Every rule registered here must have a triggering and a clean fixture in
``repro.check.fixtures`` — ``tests/test_check_meta.py`` enforces that, so
a new rule cannot land silently untested.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

from repro.check import hlo_pass, jaxpr_pass, static_pass
from repro.check.diagnostics import Diagnostic, Severity

__all__ = ["Rule", "all_rules", "run_rules", "register_rule"]


@dataclasses.dataclass(frozen=True)
class Rule:
    rule_id: str
    name: str
    severity: Severity
    description: str
    detectors: tuple     # each: CheckedProgram -> list[Diagnostic]


_RULES: dict[str, Rule] = {}


def register_rule(rule_id: str, name: str, severity: Severity,
                  description: str, detectors: Sequence[Callable]) -> Rule:
    if rule_id in _RULES:
        raise ValueError(f"duplicate rule {rule_id}")
    rule = Rule(rule_id, name, severity, description, tuple(detectors))
    _RULES[rule_id] = rule
    return rule


def all_rules() -> dict[str, Rule]:
    return dict(_RULES)


def run_rules(program, rules: Sequence[str] | None = None
              ) -> list[Diagnostic]:
    """Run every registered rule (or the named subset) over one program."""
    out: list[Diagnostic] = []
    for rid in sorted(rules or _RULES):
        for detect in _RULES[rid].detectors:
            out.extend(detect(program))
    return out


register_rule(
    "R1", "silent-densify", Severity.ERROR,
    "A GroupedNM/FixedMask operand reaches a dense dot/einsum without an "
    "explicit densify site: dispatcher fallback counters, jaxpr "
    "scatter-to-dot reachability, and the same check on the compiled HLO.",
    (static_pass.static_r1, jaxpr_pass.jaxpr_r1, hlo_pass.hlo_r1),
)
register_rule(
    "R2", "conversion-churn", Severity.WARNING,
    "The same weight is converted between layouts more than once per "
    "traced program.",
    (static_pass.static_r2,),
)
register_rule(
    "R3", "dtype-promotion", Severity.ERROR,
    "An op on the decode path promotes past the model dtype outside "
    "matmul/reduction accumulation, breaking the bitwise decode contract.",
    (jaxpr_pass.jaxpr_r3, hlo_pass.hlo_r3),
)
register_rule(
    "R4", "host-sync-in-loop", Severity.ERROR,
    "A host callback (or host custom-call) lives inside the lax.scan / "
    "while decode chunk — one host round-trip per iteration.",
    (jaxpr_pass.jaxpr_r4, hlo_pass.hlo_r4),
)
register_rule(
    "R5", "recompile-hazard", Severity.WARNING,
    "Weak-typed program inputs/outputs fragment the jit cache on retrace.",
    (jaxpr_pass.jaxpr_r5,),
)
register_rule(
    "R6", "vmem-overrun", Severity.ERROR,
    "The routed Pallas (tm/tn, target_depth, stream) config's estimated "
    "per-grid-step working set exceeds the per-device VMEM budget.",
    (static_pass.static_r6,),
)
register_rule(
    "R7", "unmodelled-device", Severity.ERROR,
    "The running device kind has no HW_BY_KIND entry, so there are no "
    "peaks or VMEM budget to judge it by (none is assumed).",
    (static_pass.static_r7,),
)

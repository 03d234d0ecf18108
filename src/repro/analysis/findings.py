"""Structured findings + the rule registry + the process exit-code contract.

Every analyzer layer (jaxpr / HLO / AST lint) reports violations as
:class:`Finding`s — severity, stable rule id, human location, and a
machine-readable ``details`` dict — collected into a :class:`Report`
that renders as text or JSON and maps onto the repo-wide exit-code
contract (shared with ``launch/dryrun.py``):

  * ``EXIT_OK`` (0)       — clean run, no findings.
  * ``EXIT_ERROR`` (1)    — the tool itself failed (bad config, crash).
  * ``EXIT_BUDGET`` (2)   — dryrun memory-budget overrun (PR-6 gate).
  * ``EXIT_CONTRACT`` (3) — one or more contract findings.

(Argparse usage errors also exit 2 by Python convention — scripts that
need to distinguish should check stderr.)

DESIGN.md §Static contracts enumerates every rule; intentional
violations are waived inline with ``# repro: noqa(RULE)`` (AST rules
only — jaxpr/HLO contracts have no legitimate waivers, fix the plan).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Iterable, List, Optional

EXIT_OK = 0
EXIT_ERROR = 1
EXIT_BUDGET = 2
EXIT_CONTRACT = 3

SEVERITY_ERROR = "error"
SEVERITY_WARNING = "warning"

#: rule id -> (layer, one-line contract) — the single source of truth the
#: CLI/docs enumerate. Adding a rule without registering it here raises.
RULES: Dict[str, Dict[str, str]] = {
    "JX001": {"layer": "jaxpr",
              "contract": "micro-gradients accumulate in the plan's "
                          "accum_dtype (fp32 by default)"},
    "JX002": {"layer": "jaxpr",
              "contract": "the remat policy the planner chose is applied "
                          "to the traced step (remat sub-jaxpr census "
                          "matches the MBSPlan lattice row)"},
    "JX003": {"layer": "jaxpr",
              "contract": "no io_callback/debug_callback/host-sync "
                          "primitives inside the jitted train step"},
    "JX004": {"layer": "jaxpr",
              "contract": "collective census: exactly one gradient psum "
                          "per mini-batch when defer_sync, >= N_Smu "
                          "otherwise, zero without a mesh"},
    "JX005": {"layer": "jaxpr",
              "contract": "pipelined (1F1B) collective census: "
                          "stage-boundary ppermute count matches the "
                          "closed-form schedule exactly; deferred sync "
                          "keeps ONE data-axis gradient psum per "
                          "mini-batch plus ONE (data, model) psum for "
                          "shared grads/loss/metrics; the per-micro "
                          "baseline issues >= N_Smu data-axis psums"},
    "HLO001": {"layer": "hlo",
               "contract": "input_output_aliases covers every donated "
                           "param/opt/accumulator buffer (zero-copy "
                           "update)"},
    "HLO002": {"layer": "hlo",
               "contract": "no unexpected all-gather at stage boundaries "
                           "of a replicated-state step"},
    "HLO003": {"layer": "hlo",
               "contract": "compiled peak bytes agree with "
                           "core/memory_model within declared tolerance"},
    "HLO004": {"layer": "hlo",
               "contract": "compiled gradient sync, counted in "
                           "non-scalar all-reduce operands: one per "
                           "mini-batch (deferred) / >= N_Smu (per-micro "
                           "baseline) / no all-reduce (mesh-free)"},
    "HLO005": {"layer": "hlo",
               "contract": "compiled pipelined schedule: exactly two "
                           "non-scalar all-reduce operands (staged-grad "
                           "data psum + shared data-model psum) when "
                           "deferred, >= N_Smu when per-micro; "
                           "collective-permute count bounded by the "
                           "jaxpr schedule census (XLA may merge "
                           "adjacent permutes, never add them)"},
    "LINT001": {"layer": "ast",
                "contract": "no float()/.item()/jax.device_get host syncs "
                            "in engine hot-loop modules"},
    "LINT002": {"layer": "ast",
                "contract": "no jnp.pad in kernels/ (the PR-3 no-copy "
                            "rule)"},
    "LINT003": {"layer": "ast",
                "contract": "every jax.jit(..., donate_argnums=...) site "
                            "exposes a donate=False opt-out"},
    "LINT004": {"layer": "ast",
                "contract": "every pallas_call plumbs interpret="},
    "LINT005": {"layer": "ast",
                "contract": "production code imports kernels through the "
                            "repro.kernels public surface, not deep "
                            "submodule paths"},
    "LINT006": {"layer": "ast",
                "contract": "bare except Exception in src/repro/engine/ "
                            "routes through the supervisor's fault "
                            "taxonomy (faults.classify/is_oom/...) or "
                            "carries # repro: noqa"},
    "SRV001": {"layer": "hlo",
               "contract": "the compiled decode step aliases the donated "
                           "KV pool in place (input_output_aliases covers "
                           "the full cache footprint — a non-donated path "
                           "keeps two full KV copies live)"},
    "SRV002": {"layer": "hlo",
               "contract": "compiled decode peak agrees with "
                           "core/memory_model.serve_estimate within the "
                           "declared band AND stays under the budget the "
                           "ServePlan was admitted against"},
}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One contract violation (or advisory)."""
    rule: str
    severity: str
    message: str
    location: str = ""  # file:line for AST rules; jaxpr/HLO path otherwise
    details: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.rule not in RULES:
            raise ValueError(f"unregistered rule id {self.rule!r}; "
                             f"known: {sorted(RULES)}")
        if self.severity not in (SEVERITY_ERROR, SEVERITY_WARNING):
            raise ValueError(f"bad severity {self.severity!r}")

    def format(self) -> str:
        loc = f" @ {self.location}" if self.location else ""
        return f"[{self.rule}:{self.severity}]{loc} {self.message}"

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Report:
    """Findings from one analysis run + the context it ran under."""
    findings: List[Finding] = dataclasses.field(default_factory=list)
    context: Dict[str, Any] = dataclasses.field(default_factory=dict)
    checks_run: List[str] = dataclasses.field(default_factory=list)

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == SEVERITY_ERROR]

    @property
    def warnings(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == SEVERITY_WARNING]

    @property
    def ok(self) -> bool:
        """True only when there are NO findings at all — the CI gate is
        strict (warnings fail too; waive intentional ones at the source)."""
        return not self.findings

    def exit_code(self) -> int:
        return EXIT_OK if self.ok else EXIT_CONTRACT

    def extend(self, findings: Iterable[Finding], check: Optional[str] = None
               ) -> "Report":
        self.findings.extend(findings)
        if check is not None and check not in self.checks_run:
            self.checks_run.append(check)
        return self

    def merge(self, other: "Report") -> "Report":
        self.findings.extend(other.findings)
        for c in other.checks_run:
            if c not in self.checks_run:
                self.checks_run.append(c)
        for k, v in other.context.items():
            self.context.setdefault(k, v)
        return self

    def to_dict(self) -> Dict[str, Any]:
        return {
            "ok": self.ok,
            "exit_code": self.exit_code(),
            "context": self.context,
            "checks_run": list(self.checks_run),
            "findings": [f.to_dict() for f in self.findings],
        }

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent, default=str)

    def format(self) -> str:
        head = ", ".join(f"{k}={v}" for k, v in self.context.items())
        lines = [f"analysis [{head}]" if head else "analysis",
                 f"  checks: {', '.join(self.checks_run) or '(none)'}"]
        if self.ok:
            lines.append("  OK — zero findings")
        else:
            lines.append(f"  {len(self.errors)} error(s), "
                         f"{len(self.warnings)} warning(s):")
            lines += [f"  {f.format()}" for f in self.findings]
        return "\n".join(lines)

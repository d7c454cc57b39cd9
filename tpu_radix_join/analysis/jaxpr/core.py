"""graftcheck core: jaxpr-level findings, IR rule registry, baseline.

graftlint (``analysis/core.py``) enforces conventions the *source text*
can show; the invariants that actually break accelerator runs — an
implicit host transfer in a hot program, a collective naming the wrong
mesh axis, a uint32 lane silently widening on the wire path, a dead
input buffer the program never donated, a live set that cannot fit the
HBM budget — live in the *lowered program*.  This package traces the
engine's jitted entry points abstractly (``jax.make_jaxpr`` over
``ShapeDtypeStruct`` inputs: no devices, no dispatch, CPU tier-1 safe)
and walks the ClosedJaxpr with the same finding/waiver/baseline/exit
discipline graftlint established:

* An **IR rule** is ``fn(program: ProgramView, ctx: AuditContext)
  -> [Finding]`` registered with :func:`ir_rule` (id, doc, token).
* A **ProgramView** is one traced entry point flattened to
  :class:`EqnView` rows — primitive name, operand/result avals with
  byte sizes, params, the active mesh axes, and the ``source_info``
  summary that points a finding back at the Python line that staged
  the equation.
* **Waivers** are per-entry, per-rule, with a mandatory reason — the
  IR has no comment lines to annotate, so the entry registry
  (``trace.py``) declares them where the entry is defined (e.g. the
  fused pipeline's inputs are deliberately undonated: the retry loop
  re-feeds them).  A reasonless waiver suppresses nothing.
* **Baseline** (:data:`JXAUDIT_BASELINE`): committed suppressions with
  mandatory reasons; stale entries are reported and fail ``--strict``
  — same contract, same schema as ``LINT_BASELINE.json``.

Findings reuse :class:`analysis.core.Finding` verbatim: ``path`` is the
repo-relative source file the equation's ``source_info`` names (or the
entry name for program-scope findings), ``key`` is a stable
``entry:detail`` token, so baseline entries survive retraces.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from tpu_radix_join.analysis.core import Finding, LintError

JXAUDIT_BASELINE = "JXAUDIT_BASELINE.json"


# --------------------------------------------------------------------- model
@dataclass(frozen=True)
class AvalView:
    """One abstract value: static shape, dtype name, and byte size."""

    shape: Tuple[int, ...]
    dtype: str
    bytes: int

    @classmethod
    def of(cls, aval) -> "AvalView":
        shape = tuple(int(d) for d in getattr(aval, "shape", ()) or ())
        dtype = str(getattr(aval, "dtype", "abstract"))
        itemsize = int(getattr(getattr(aval, "dtype", None), "itemsize", 0)
                       or 0)
        n = 1
        for d in shape:
            n *= d
        return cls(shape=shape, dtype=dtype, bytes=n * itemsize)


@dataclass(frozen=True)
class EqnView:
    """One equation of the flattened program, in rule vocabulary."""

    prim: str                        # primitive name ("all_to_all", ...)
    invals: Tuple[AvalView, ...]
    outvals: Tuple[AvalView, ...]
    params: dict
    source: str                      # "<file>:<line> (<function>)" or ""
    #: mesh axes live at this equation (inside shard_map): name -> size.
    #: Empty outside any shard_map body.
    mesh_axes: Dict[str, int] = field(default_factory=dict)
    depth: int = 0                   # nesting depth (pjit/shard_map/scan)

    def in_bytes(self) -> int:
        return sum(v.bytes for v in self.invals)

    def source_path_line(self) -> Tuple[str, int]:
        """(repo-relative-ish path, line) parsed from the source summary;
        falls back to ("", 0) for equations with no user frame."""
        s = self.source.split(" ")[0] if self.source else ""
        # "path:line" or, since JAX 0.9, "path:line:column"
        m = re.fullmatch(r"(.+?):(\d+)(?::\d+)?", s)
        if m is None:
            return "", 0
        return m.group(1), int(m.group(2))


@dataclass
class ProgramView:
    """One traced entry point, ready for the IR rules.

    ``donated`` aligns with ``in_avals`` (flattened python-arg pytree
    leaves); ``waivers`` maps rule id -> reason for deliberate
    violations declared at the entry registry.  ``jaxpr`` keeps the
    underlying ClosedJaxpr for rules that need var identity (the
    static-memory live-set walk).
    """

    name: str
    eqns: List[EqnView]
    in_avals: List[AvalView]
    out_avals: List[AvalView]
    donated: List[bool]
    mesh_axes: Dict[str, int]
    num_devices: int = 1
    waivers: Dict[str, str] = field(default_factory=dict)
    meta: dict = field(default_factory=dict)
    jaxpr: object = None             # ClosedJaxpr (opaque to most rules)

    def waived(self, rule_id: str) -> bool:
        return bool(self.waivers.get(rule_id, "").strip())


@dataclass
class AuditContext:
    """Knobs the rules read: thresholds and the optional memory budget.

    ``transfer_min_bytes`` keeps scalar re-placements (e.g. a traced
    int donated across a cond) out of the transfer rule — a scalar
    device_put is a no-op on every backend; the rule hunts *bulk*
    implicit traffic.  ``memory_budget_bytes`` arms the static-memory
    rule; None leaves it informational (peak recorded, no finding).
    """

    transfer_min_bytes: int = 4096
    width_min_bytes: int = 4096
    donation_min_bytes: int = 1 << 16
    memory_budget_bytes: Optional[int] = None


@dataclass(frozen=True)
class IRRule:
    id: str
    doc: str
    token: str
    fn: Callable[[ProgramView, AuditContext], List[Finding]]


IR_RULES: Dict[str, IRRule] = {}


def ir_rule(rule_id: str, doc: str, token: str):
    """Register an IR rule function under ``rule_id``."""
    def deco(fn):
        if rule_id in IR_RULES:
            raise LintError(f"duplicate IR rule id {rule_id!r}")
        IR_RULES[rule_id] = IRRule(rule_id, doc, token, fn)
        return fn
    return deco


# ------------------------------------------------------------------ baseline
def load_ir_baseline(path: str) -> List[dict]:
    """Validated suppressions — graftlint's schema, graftcheck's rule
    table.  Every entry carries a non-empty reason or loading fails
    (exit 2 at the CLI)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except OSError as e:
        raise LintError(f"cannot read baseline {path}: {e}") from e
    except ValueError as e:
        raise LintError(f"baseline {path} is not valid JSON: {e}") from e
    entries = data.get("suppressions")
    if not isinstance(entries, list):
        raise LintError(f"baseline {path} has no 'suppressions' list")
    for i, e in enumerate(entries):
        for k in ("rule", "path", "key", "reason"):
            if not isinstance(e.get(k), str) or not e[k].strip():
                raise LintError(
                    f"baseline {path} entry {i} needs a non-empty {k!r} "
                    f"(every suppression carries a reason)")
        if e["rule"] not in IR_RULES:
            raise LintError(
                f"baseline {path} entry {i} names unknown IR rule "
                f"{e['rule']!r}")
    return entries


# --------------------------------------------------------------------- runner
@dataclass
class AuditResult:
    findings: List[Finding]
    suppressed: List[Finding]
    stale: List[dict]
    rules: List[str]
    entries: List[str]               # entry names audited
    #: informational per-entry measurements (peak bytes, exchange bytes)
    stats: Dict[str, dict] = field(default_factory=dict)

    def exit_code(self, strict: bool = False) -> int:
        if self.findings:
            return 1
        if strict and self.stale:
            return 1
        return 0


def run_audit(programs: List[ProgramView],
              rule_ids: Optional[List[str]] = None,
              baseline_path: Optional[str] = None,
              ctx: Optional[AuditContext] = None) -> AuditResult:
    """Run ``rule_ids`` (default: all registered) over the traced
    programs, applying per-entry waivers then the baseline."""
    from tpu_radix_join.analysis.jaxpr import register_ir_rules
    register_ir_rules()
    ctx = ctx or AuditContext()
    ids = list(IR_RULES) if rule_ids is None else list(rule_ids)
    unknown = [r for r in ids if r not in IR_RULES]
    if unknown:
        raise LintError(f"unknown IR rule id(s): {', '.join(unknown)} "
                        f"(known: {', '.join(sorted(IR_RULES))})")
    findings: List[Finding] = []
    stats: Dict[str, dict] = {}
    for view in programs:
        stats[view.name] = view.meta.setdefault("stats", {})
        for rid in ids:
            if view.waived(rid):
                continue
            findings.extend(IR_RULES[rid].fn(view, ctx))
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.key))
    entries: List[dict] = []
    if baseline_path and os.path.exists(baseline_path):
        entries = load_ir_baseline(baseline_path)
    kept, suppressed = [], []
    used = [False] * len(entries)
    for f in findings:
        hit = None
        for i, e in enumerate(entries):
            if (e["rule"] == f.rule and e["path"] == f.path
                    and e["key"] == f.key):
                hit = i
                break
        if hit is None:
            kept.append(f)
        else:
            used[hit] = True
            suppressed.append(f)
    stale = [e for i, e in enumerate(entries)
             if not used[i] and e["rule"] in ids]
    return AuditResult(findings=kept, suppressed=suppressed, stale=stale,
                       rules=ids, entries=[v.name for v in programs],
                       stats=stats)

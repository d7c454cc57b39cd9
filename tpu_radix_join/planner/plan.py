"""Strategy selection: enumerate, cost, pick, explain.

``plan_join`` turns a (profile, workload) pair into a concrete
:class:`JoinPlan` — the knobs the driver feeds ``JoinConfig`` — plus the
full per-strategy cost table, so ``--plan explain`` can show *why* the
winner won and a misprediction is debuggable against the chip logs
(compare the losing row's terms to the measured phase columns).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Optional, Tuple

from tpu_radix_join.planner.cost_model import (StrategyCost, Workload,
                                               enumerate_strategies,
                                               network_fanout_bits,
                                               pick_chunk_tuples,
                                               plan_exchange)
from tpu_radix_join.planner.profile import DeviceProfile

# v2 adds ``grid_pipeline`` (the chunked engine's pipelined/synchronous
# knob); v3 adds ``exchange_codec``/``exchange_stages`` (the bit-packed
# wire codec and staged all_to_all); v4 adds ``predicted_terms`` (the
# winning row's per-term ms breakdown, the predicted half of the
# plan-vs-actual audit — planner/audit.py); v5 added ``sort_impl``, which
# v6 drops with the radix sort it chose against.  Older files load with
# the fields' defaults ("auto" pipeline, "off" codec, fused exchange,
# empty term table), and a v5 file's ``sort_impl`` is ignored.
PLAN_SCHEMA_VERSION = 6


class PlanError(ValueError):
    """No feasible strategy, or a malformed plan file."""


class PlanInfeasibleError(PlanError):
    """The workload cannot fit the armed memory budget — refused at plan
    time with a retry-taxonomy class so callers classify it like every
    other failure (robustness/retry.py), instead of OOMing at dispatch.
    Raised both by the analytic gate (no feasible cost row) and by the
    graftcheck static-memory gate (traced live-set peak exceeds the
    budget: ``static_memory_gate``)."""

    failure_class = "plan_infeasible"   # == robustness.retry.PLAN_INFEASIBLE


@dataclasses.dataclass(frozen=True)
class JoinPlan:
    """The planner's decision, in driver vocabulary.

    ``engine`` routes between the in-core SPMD pipeline (HashJoin) and the
    out-of-core chunked grid (ops/chunked.py).  The remaining fields map
    1:1 onto JoinConfig / CLI flags; ``strategy``/``predicted_ms`` record
    the winning cost row for BENCH artifacts and cache provenance.
    """

    engine: str                       # "incore" | "chunked"
    fused: bool = True                # False -> measure_phases (phase split)
    probe: str = "sort"               # "sort" | "bucket"
    two_level: bool = False
    key_range: str = "auto"           # "narrow" | "full" | "auto"
    network_fanout_bits: int = 5
    local_fanout_bits: int = 5
    chunk_tuples: Optional[int] = None   # chunked engine only
    grid_pipeline: str = "auto"          # chunked engine: "off"|"on"|"auto"
    exchange_codec: str = "off"          # wire codec: "off" | "pack"
    exchange_stages: int = 1             # 1 = fused all_to_all, k>1 staged
    pipeline_repeats: bool = False
    strategy: str = ""
    predicted_ms: float = 0.0
    #: the winning StrategyCost row's per-term ms breakdown (sort, scan,
    #: shuffle, ...) — what the plan-vs-actual audit compares measured
    #: phase columns against
    predicted_terms: dict = dataclasses.field(default_factory=dict)
    profile_name: str = ""
    schema_version: int = PLAN_SCHEMA_VERSION

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, doc: dict) -> "JoinPlan":
        doc = dict(doc)
        doc.pop("sort_impl", None)        # schema v5's retired field
        version = int(doc.get("schema_version", 1))
        if version > PLAN_SCHEMA_VERSION:
            raise PlanError(
                f"plan schema_version {version} is newer than this build "
                f"understands (<= {PLAN_SCHEMA_VERSION})")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(doc) - known
        if unknown:
            raise PlanError(f"unknown plan fields {sorted(unknown)}")
        if doc.get("engine") not in ("incore", "chunked"):
            raise PlanError(f"plan engine must be incore|chunked, "
                            f"got {doc.get('engine')!r}")
        return cls(**doc)

    def save(self, path: str) -> str:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path: str) -> "JoinPlan":
        try:
            with open(path) as f:
                return cls.from_dict(json.load(f))
        except (OSError, json.JSONDecodeError) as e:
            raise PlanError(f"unreadable plan file {path}: {e!r}") from e

    def config_kwargs(self) -> dict:
        """JoinConfig overrides this plan implies (in-core engine only)."""
        return {
            "probe_algorithm": self.probe,
            "two_level": self.two_level,
            "key_range": self.key_range,
            "network_fanout_bits": self.network_fanout_bits,
            "local_fanout_bits": self.local_fanout_bits,
            "measure_phases": not self.fused,
            "exchange_codec": self.exchange_codec,
            "exchange_stages": self.exchange_stages,
        }


# network radix bits now live in cost_model.network_fanout_bits so the
# exchange pricing (plan_exchange) derives the wire geometry from the same
# fanout the plan binds
_fanout_bits = network_fanout_bits


def static_memory_gate(workload: Workload) -> int:
    """graftcheck feasibility gate: trace the fused pipeline at this
    workload's geometry (abstract — no arrays, no dispatch) and walk its
    live set.  Returns the machine-wide static peak bytes; raises
    :class:`PlanInfeasibleError` when the workload arms a
    ``memory_budget_bytes`` the peak cannot fit — a *classified* refusal
    at plan time where the analytic ``incore_resident_bytes`` gate (a
    resident-set model) would have admitted the plan and the dispatch
    would have OOMed on the transient live set.

    Lazy-imports ``analysis.jaxpr`` (the planner stays importable
    without tracing) and needs ``workload.num_nodes`` host devices."""
    from tpu_radix_join.analysis.jaxpr.memory import peak_live_bytes
    from tpu_radix_join.analysis.jaxpr.trace import build_entries

    n = max(1, workload.num_nodes)
    per_node = max(8, -(-max(workload.r_tuples, workload.s_tuples) // n))
    cap = max(8, 1 << (-(-per_node // n) - 1).bit_length())
    view = build_entries(num_nodes=n, per_node=per_node, cap=cap,
                         entries=("pipeline",))[0]
    peak = peak_live_bytes(view.jaxpr)
    budget = workload.memory_budget_bytes
    if budget is not None and peak > budget:
        raise PlanInfeasibleError(
            f"static-memory gate: the fused pipeline's traced live-set "
            f"peak is {peak} bytes at {per_node} tuples/node x {n} nodes "
            f"(wire cap {cap}), exceeding the armed memory budget "
            f"{budget} bytes ({peak / max(1, budget):.2f}x) — refusing "
            f"at plan time; shrink the workload, raise the budget, or "
            f"route through the chunked engine")
    return int(peak)


def plan_join(profile: DeviceProfile, workload: Workload,
              static_gate: bool = False
              ) -> Tuple[JoinPlan, List[StrategyCost]]:
    """Pick the cheapest feasible strategy (ties break toward the earlier
    row — fused before split, narrow before full) and bind it to driver
    knobs.

    ``static_gate=True`` additionally runs :func:`static_memory_gate`
    on incore winners when the workload arms a memory budget — the
    jaxpr-derived live-set check on top of the analytic resident-set
    row gate."""
    costs = enumerate_strategies(profile, workload)
    feasible = [c for c in costs if c.feasible]
    if not feasible:
        raise PlanInfeasibleError(
            "no feasible strategy for this workload — every cost row is "
            "infeasible:\n" + explain_table(costs))
    best = min(feasible, key=lambda c: c.cost_ms)
    bits = _fanout_bits(workload)
    xplan = plan_exchange(profile, workload, fanout_bits=bits)
    kw = dict(network_fanout_bits=bits,
              exchange_codec=xplan.codec,
              exchange_stages=xplan.stages,
              pipeline_repeats=workload.repeats > 1,
              strategy=best.strategy, predicted_ms=best.cost_ms,
              predicted_terms={k: round(v, 4)
                               for k, v in best.terms.items()},
              profile_name=profile.name)
    if best.strategy in ("chunked_grid", "chunked_grid_pipelined"):
        # the single-node grid engine never exchanges — keep the plan's
        # codec fields at their inert defaults
        plan = JoinPlan(engine="chunked",
                        chunk_tuples=pick_chunk_tuples(profile, workload),
                        grid_pipeline=("on" if best.strategy.endswith(
                            "_pipelined") else "off"),
                        key_range="auto" if workload.key_bound is None
                        else ("full" if not _narrow(workload) else "narrow"),
                        pipeline_repeats=False,
                        **{k: v for k, v in kw.items()
                           if k not in ("pipeline_repeats", "exchange_codec",
                                        "exchange_stages")})
    elif best.strategy == "incore_fused_twolevel":
        plan = JoinPlan(engine="incore", probe="bucket", two_level=True,
                        key_range="auto", **kw)
    else:
        # incore_{fused,split}_sort_{narrow,full}
        fused = "_fused_" in best.strategy
        narrow = best.strategy.endswith("_narrow")
        key_range = "narrow" if narrow else "full"
        if workload.key_bits == 64:
            key_range = "auto"     # wide keys have no range discipline
        plan = JoinPlan(engine="incore", fused=fused, key_range=key_range,
                        **kw)
        if not fused:
            # the split cannot pipeline (fence per program)
            plan = dataclasses.replace(plan, pipeline_repeats=False)
    if (static_gate and plan.engine == "incore"
            and workload.memory_budget_bytes is not None):
        static_memory_gate(workload)
    return plan, costs


def _narrow(w: Workload) -> bool:
    from tpu_radix_join.ops.merge_count import MAX_MERGE_KEY
    return (w.key_bits == 32
            and (w.key_bound is None or w.key_bound - 1 <= MAX_MERGE_KEY))


def explain_table(costs: List[StrategyCost],
                  chosen: Optional[JoinPlan] = None,
                  actuals: Optional[dict] = None,
                  static: Optional[dict] = None,
                  critpath: Optional[dict] = None) -> str:
    """Human-readable per-strategy predicted-cost table (the ``--plan
    explain`` payload).  Terms are columns so a reader can line each up
    against the measured phase columns in a chip perf artifact.

    ``actuals`` (a plan-vs-actual audit summary — planner/audit.py
    ``actuals_for_explain``) adds measured ``actual_ms``/``drift%``
    columns, filled on the row of the strategy that actually ran.
    ``static`` (a graftcheck cross-validation summary —
    analysis/jaxpr/crossval.py ``static_for_explain``) adds the
    ``STATIC-DRIFT`` column: jaxpr-derived exchange bytes/tuple vs the
    cost model's ``bytes_per_tuple``, filled on the chosen row — an
    execution-free grounding signal next to the runtime drift.
    ``critpath`` (planner/audit.py ``critpath_for_explain``) adds the
    ``critical_path`` column: the *measured bounding rank's* path length
    — what predicted_ms should be priced against on a skewed mesh, where
    the mean flatters the plan."""
    term_keys: List[str] = []
    for c in costs:
        for k in c.terms:
            if k not in term_keys:
                term_keys.append(k)
    header = (["strategy", "feasible", "predicted_ms"]
              + (["actual_ms", "drift%"] if actuals else [])
              + (["critical_path"] if critpath else [])
              + (["STATIC-DRIFT"] if static else [])
              + [f"{k}_ms" for k in term_keys] + ["note"])
    rows = []
    for c in costs:
        is_chosen = chosen is not None and c.strategy == chosen.strategy
        mark = " *" if is_chosen else ""
        act_cells = []
        if actuals:
            if c.strategy == actuals.get("strategy"):
                a, d = actuals.get("actual_ms"), actuals.get("drift_pct")
                act_cells = [f"{a:.1f}" if a is not None else "-",
                             f"{d:.1f}" if d is not None else "-"]
            else:
                act_cells = ["", ""]
        cp_cells = []
        if critpath:
            b = critpath.get("bound_ms")
            if c.strategy == critpath.get("strategy") and b is not None:
                cp_cells = [f"{b:.1f}@r{critpath.get('bound_rank')}"]
            else:
                cp_cells = [""]
        static_cells = []
        if static:
            sd = static.get("drift_pct")
            static_cells = [f"{sd:+.2f}%" if is_chosen and sd is not None
                            else ""]
        rows.append([c.strategy + mark,
                     "yes" if c.feasible else "NO",
                     f"{c.cost_ms:.1f}" if c.feasible else "-"]
                    + act_cells
                    + cp_cells
                    + static_cells
                    + [f"{c.terms[k]:.1f}" if k in c.terms else ""
                       for k in term_keys]
                    + [c.note])
    widths = [max(len(header[i]), *(len(r[i]) for r in rows))
              for i in range(len(header))]
    fmt = lambda cells: "| " + " | ".join(
        c.ljust(widths[i]) for i, c in enumerate(cells)) + " |"
    lines = [fmt(header),
             "|" + "|".join("-" * (w + 2) for w in widths) + "|"]
    lines += [fmt(r) for r in rows]
    if chosen is not None:
        lines.append(f"chosen: {chosen.strategy} "
                     f"(predicted {chosen.predicted_ms:.1f} ms/join, "
                     f"profile {chosen.profile_name})")
        if chosen.engine == "incore":
            lines.append(
                f"exchange: codec={chosen.exchange_codec} "
                f"stages={chosen.exchange_stages} "
                f"({'fused' if chosen.exchange_stages <= 1 else 'staged'} "
                f"all_to_all)")
    if critpath and critpath.get("bound_ms") is not None:
        wf = critpath.get("wait_fraction")
        lines.append(
            f"critical path: {critpath['bound_ms']:.1f} ms bound by "
            f"rank {critpath.get('bound_rank')}"
            + (f" (wait fraction {wf * 100:.1f}%)" if wf is not None
               else "")
            + " — plan terms priced against the bounding rank")
    if static:
        lines.append(
            f"static: jaxpr {static.get('entry', '?')} ships "
            f"{static.get('static_bytes', 0)} B/node over "
            f"{sum(static.get('collectives', {}).values())} collectives "
            f"({static.get('static_bytes_per_tuple', 0.0):.3f} B/tuple "
            f"vs plan {static.get('plan_bytes_per_tuple', 0.0):.3f}; "
            f"drift {static.get('drift_pct', 0.0):+.2f}%)")
    return "\n".join(lines)

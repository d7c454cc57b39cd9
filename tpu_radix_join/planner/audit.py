"""Plan-vs-actual audit: close the loop between cost model and clock.

The planner predicts a per-join cost (JoinPlan.predicted_ms, with the
winning row's per-term breakdown in ``predicted_terms``); the
Measurements registry records what actually happened.  This module
compares the two after every planned join and emits:

  * ``counters["PLANDRIFT"]`` — |actual - predicted| as a percent of the
    prediction (gauge, lower is better, regress-gated via
    tools_check_regress.py) — the continuously-measured calibration
    signal ROADMAP item 2's layout search needs, and the canary for
    stale device profiles;
  * ``meta["plan_vs_actual"]`` — the full audit table (strategy,
    predicted/actual ms, drift, per-term rows with best-effort measured
    twins), which rides into forensics bundles and the ``--plan
    explain`` actuals column;
  * a ``plan_drift`` trace event.

Term-to-tag honesty: only the shuffle term has a 1:1 measured twin
(JMPI) and only under the split discipline; fused strategies run as one
program, so per-term actuals stay None and the headline JTOTAL
comparison carries the signal.  ``times0`` (a pre-join ``times_us``
snapshot) makes the audit delta-based, so accumulated registries
(resident sessions, repeated drivers) audit the *last* join, not the
running total.
"""

from __future__ import annotations

from typing import Dict, Optional

from tpu_radix_join.performance.measurements import (JHIST, JMPI, JPROC,
                                                     JTOTAL, PLANDRIFT,
                                                     SDISPATCH, SWINALLOC)

#: phase tags the audit snapshots/deltas (the measured side of the table)
PHASE_TAGS = (JTOTAL, JHIST, JMPI, JPROC, SWINALLOC, SDISPATCH)

#: cost-model term -> measured tag, where a 1:1 mapping exists.  The
#: local-processing terms (sort/scan/scatter/probe/stage/overlap) all
#: land in JPROC together, so none of them gets an individual twin.
_TERM_TAG = {"shuffle": JMPI}


def phase_snapshot(measurements) -> Dict[str, float]:
    """Pre-join ``times_us`` snapshot for delta-based auditing."""
    return {k: measurements.times_us.get(k, 0.0) for k in PHASE_TAGS}


def audit_plan(plan, measurements, repeats: int = 1,
               times0: Optional[Dict[str, float]] = None,
               critical_path: Optional[dict] = None) -> Optional[dict]:
    """Record the plan-vs-actual table for the join that just ran.

    ``plan`` is a JoinPlan or its dict; ``repeats`` divides the measured
    JTOTAL down to the per-join granularity predicted_ms speaks.
    Returns the table (also stamped into ``meta["plan_vs_actual"]``), or
    None when there is nothing to audit (no JTOTAL recorded — the join
    died before the pipeline started).

    ``critical_path`` (an observability/critpath.py result) re-prices the
    drift against the *measured bounding rank* instead of the local mean:
    the PLANDRIFT gauge the fitter calibrates on then tracks the path
    that actually bounds wall-clock, and the table carries the
    bound-rank terms under ``"critical_path"``."""
    m = measurements
    if m is None or plan is None:
        return None
    pd = plan if isinstance(plan, dict) else plan.to_dict()
    t0 = times0 or {}
    delta_ms = {}
    for tag in PHASE_TAGS:
        cur = m.times_us.get(tag)
        if cur is None and tag not in t0:
            continue
        delta_ms[tag] = ((cur or 0.0) - t0.get(tag, 0.0)) / 1e3
    jt_ms = delta_ms.get(JTOTAL, 0.0)
    if jt_ms <= 0:
        return None
    reps = max(1, int(repeats))
    # drift is priced on the 3-decimal values the table shows, so a reader
    # recomputing it from the row agrees even at sub-ms predictions
    actual_ms = round(jt_ms / reps, 3)
    predicted_ms = round(float(pd.get("predicted_ms") or 0.0), 3)
    drift_pct = (round(100.0 * abs(actual_ms - predicted_ms) / predicted_ms,
                       2) if predicted_ms > 0 else None)
    terms = []
    for term, pred in (pd.get("predicted_terms") or {}).items():
        tag = _TERM_TAG.get(term)
        act = (round(delta_ms[tag] / reps, 3)
               if tag is not None and tag in delta_ms else None)
        terms.append({"term": term, "predicted_ms": round(float(pred), 3),
                      "actual_ms": act})
    table = {
        "strategy": pd.get("strategy", ""),
        "engine": pd.get("engine", ""),
        "profile_name": pd.get("profile_name", ""),
        "predicted_ms": predicted_ms,
        "actual_ms": actual_ms,
        "drift_pct": drift_pct,
        "repeats": reps,
        "terms": terms,
        "measured_ms": {k: round(v / reps, 3) for k, v in delta_ms.items()},
    }
    gauge_drift = drift_pct
    if critical_path and not critical_path.get("error"):
        bound_ms = critical_path.get("path_ms")
        if bound_ms:
            # the cost model predicts steady-state joins; the measured
            # path keeps compile wall (the timeline is honest about it),
            # so the on-path JCOMPILE share comes off before pricing —
            # the same exclude-from-running discipline times_us applies
            compile_ms = float((critical_path.get("phase_ms") or {})
                               .get("JCOMPILE", 0.0))
            bound_ms = round(max(0.0, float(bound_ms) - compile_ms)
                             / reps, 3)
            bound_drift = (round(100.0 * abs(bound_ms - predicted_ms)
                                 / predicted_ms, 2)
                           if predicted_ms > 0 else None)
            table["critical_path"] = {
                "bound_ms": bound_ms,
                "bound_rank": critical_path.get("bounding_rank"),
                "wait_fraction": critical_path.get("wait_fraction"),
                "drift_pct": bound_drift,
            }
            if bound_drift is not None:
                # price the gauge against the measured bounding rank,
                # not the local mean — the path that matters
                gauge_drift = bound_drift
    m.meta["plan_vs_actual"] = table
    if gauge_drift is not None:
        # gauge assignment (each audited join overwrites): the regress
        # gate reads the last join's drift, not an accumulated sum
        m.counters[PLANDRIFT] = int(round(gauge_drift))
        m.flightrec.record("gauge", PLANDRIFT, drift_pct=gauge_drift,
                           strategy=table["strategy"])
    m.event("plan_drift", strategy=table["strategy"],
            predicted_ms=table["predicted_ms"],
            actual_ms=table["actual_ms"], drift_pct=drift_pct)
    return table


def actuals_for_explain(table: Optional[dict]) -> Optional[dict]:
    """Shape an audit table for explain_table's ``actuals`` column:
    {strategy, actual_ms, drift_pct}.  None-safe passthrough."""
    if not table:
        return None
    return {"strategy": table.get("strategy"),
            "actual_ms": table.get("actual_ms"),
            "drift_pct": table.get("drift_pct")}


def critpath_for_explain(table: Optional[dict]) -> Optional[dict]:
    """Shape an audit table's bound-rank terms for explain_table's
    measured-critical-path column: {strategy, bound_ms, bound_rank,
    wait_fraction}.  None-safe passthrough (None when the run had no
    timeline to reconstruct a path from)."""
    if not table or not table.get("critical_path"):
        return None
    cp = table["critical_path"]
    return {"strategy": table.get("strategy"),
            "bound_ms": cp.get("bound_ms"),
            "bound_rank": cp.get("bound_rank"),
            "wait_fraction": cp.get("wait_fraction")}

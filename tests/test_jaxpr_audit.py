"""graftcheck (analysis/jaxpr) tests: the tracer registry, a known-bad
fixture per IR rule (each producing exactly one finding), the waiver and
baseline contracts, the WIREBYTES cross-validation A/B, and the
static-memory planner gate's classified refusal.

Everything here is abstract tracing — no compile, no dispatch — except
the cross-validation test, which runs one real 8-way join to produce
the measured WIREBYTES side of the A/B.
"""

import json

import jax
import jax.numpy as jnp
import pytest

from tpu_radix_join.analysis.core import LintError
from tpu_radix_join.analysis.jaxpr import (AuditContext, AvalView, EqnView,
                                           IR_RULES, ProgramView,
                                           register_ir_rules, run_audit)
from tpu_radix_join.analysis.jaxpr.crossval import (collective_counts,
                                                    static_exchange_bytes,
                                                    static_for_explain)
from tpu_radix_join.analysis.jaxpr.trace import (ENTRY_NAMES, build_entries,
                                                 view_from_fn)

register_ir_rules()

N = 8
BIG = jax.ShapeDtypeStruct((1 << 16,), jnp.uint32)     # 256 KiB


# ------------------------------------------------------------ the registry

def test_registry_traces_every_entry_and_is_clean():
    views = build_entries(num_nodes=N)
    assert [v.name for v in views] == list(ENTRY_NAMES)
    res = run_audit(views)
    assert res.findings == []
    assert res.exit_code() == 0
    assert res.exit_code(strict=True) == 0
    # every entry records its live-set peak for the STATICMEM gauge
    for name in ENTRY_NAMES:
        assert res.stats[name]["peak_live_bytes"] > 0


def test_registry_rejects_unknown_entry_and_rule():
    with pytest.raises(LintError, match="unknown entry"):
        build_entries(num_nodes=N, entries=["nope"])
    with pytest.raises(LintError, match="unknown IR rule"):
        run_audit([], rule_ids=["nope"])


def test_all_five_rules_are_registered():
    assert set(IR_RULES) == {"transfer", "collective-axis", "width",
                             "donation", "static-memory"}


# ------------------------------------- known-bad fixtures, one finding each

def test_transfer_rule_fires_on_implicit_device_put():
    def bad(x):
        return jax.device_put(x).sum()

    v = view_from_fn("fx", bad, (BIG,))
    res = run_audit([v], rule_ids=["transfer"])
    assert len(res.findings) == 1
    f = res.findings[0]
    assert f.rule == "transfer" and "device_put" in f.message
    # attribution points at the staging line in THIS file
    assert f.path.endswith("test_jaxpr_audit.py")


def test_transfer_rule_ignores_scalar_placements():
    def ok(x):
        return x + jax.device_put(jnp.uint32(1))

    v = view_from_fn("fx", ok, (BIG,))
    assert run_audit([v], rule_ids=["transfer"]).findings == []


def test_width_rule_fires_on_silent_widening():
    def bad(x):
        return (x.astype(jnp.float32) * 2.0).sum()

    v = view_from_fn("fx", bad, (BIG,))
    res = run_audit([v], rule_ids=["width"])
    assert len(res.findings) == 1
    assert "float32" in res.findings[0].message


def test_donation_rule_fires_with_concrete_argnums():
    def bad(x):
        return x.sum()

    v = view_from_fn("fx", bad, (BIG,))
    res = run_audit([v], rule_ids=["donation"])
    assert len(res.findings) == 1
    assert "donate_argnums=(0,)" in res.findings[0].message
    # donating silences it
    v2 = view_from_fn("fx", bad, (BIG,), donate_argnums=(0,))
    assert run_audit([v2], rule_ids=["donation"]).findings == []


def test_static_memory_rule_fires_over_budget():
    def bad(x):
        return x.sum()

    v = view_from_fn("fx", bad, (BIG,))
    res = run_audit([v], rule_ids=["static-memory"],
                    ctx=AuditContext(memory_budget_bytes=1024))
    assert len(res.findings) == 1
    assert "exceeds the armed budget" in res.findings[0].message
    # unarmed budget: informational only, peak still recorded
    v2 = view_from_fn("fx", bad, (BIG,))
    res2 = run_audit([v2], rule_ids=["static-memory"])
    assert res2.findings == []
    assert res2.stats["fx"]["peak_live_bytes"] >= BIG.size * 4


def _mis_axised_program():
    """JAX refuses to *stage* a collective over a dead axis, so the
    collective-axis fixture is a hand-built ProgramView — the rule reads
    only the EqnView vocabulary, which is the point of the layer."""
    psum = EqnView(prim="psum",
                   invals=(AvalView((128,), "uint32", 512),),
                   outvals=(AvalView((128,), "uint32", 512),),
                   params={"axes": ("cols",)}, source="fx.py:1 (f)",
                   mesh_axes={"nodes": N}, depth=2)
    return ProgramView(name="fx", eqns=[psum], in_avals=[], out_avals=[],
                       donated=[], mesh_axes={"nodes": N})


def test_collective_axis_rule_fires_on_dead_axis():
    res = run_audit([_mis_axised_program()], rule_ids=["collective-axis"])
    assert len(res.findings) == 1
    assert "'cols'" in res.findings[0].message


def test_collective_axis_rule_fires_on_indivisible_split():
    a2a = EqnView(prim="all_to_all",
                  invals=(AvalView((6, 100), "uint32", 2400),),
                  outvals=(AvalView((6, 100), "uint32", 2400),),
                  params={"axis_name": "nodes", "split_axis": 0,
                          "concat_axis": 0},
                  source="fx.py:2 (f)", mesh_axes={"nodes": N}, depth=2)
    pv = ProgramView(name="fx", eqns=[a2a], in_avals=[], out_avals=[],
                     donated=[], mesh_axes={"nodes": N})
    res = run_audit([pv], rule_ids=["collective-axis"])
    assert len(res.findings) == 1
    assert "not divisible" in res.findings[0].message


# --------------------------------------------------------- waiver + baseline

def test_waiver_suppresses_only_with_reason():
    def bad(x):
        return x.sum()

    waived = view_from_fn("fx", bad, (BIG,),
                          waivers={"donation": "fixture: re-fed upstream"})
    assert run_audit([waived], rule_ids=["donation"]).findings == []
    # a reasonless waiver suppresses nothing (graftlint's contract)
    hollow = view_from_fn("fx", bad, (BIG,), waivers={"donation": "  "})
    assert len(run_audit([hollow], rule_ids=["donation"]).findings) == 1


def test_baseline_suppresses_and_reports_stale(tmp_path):
    def bad(x):
        return x.sum()

    v = view_from_fn("fx", bad, (BIG,))
    live = run_audit([v], rule_ids=["donation"]).findings[0]
    bl = tmp_path / "JXAUDIT_BASELINE.json"
    bl.write_text(json.dumps({"suppressions": [
        {"rule": live.rule, "path": live.path, "key": live.key,
         "reason": "known, tracked"},
        {"rule": "donation", "path": "jaxpr:gone", "key": "gone:in0",
         "reason": "finding was fixed"}]}))
    res = run_audit([v], rule_ids=["donation"], baseline_path=str(bl))
    assert res.findings == [] and len(res.suppressed) == 1
    assert len(res.stale) == 1
    assert res.exit_code() == 0 and res.exit_code(strict=True) == 1
    # a reasonless entry fails loading (exit-2 path at the CLI)
    bl.write_text(json.dumps({"suppressions": [
        {"rule": "donation", "path": "p", "key": "k", "reason": ""}]}))
    with pytest.raises(LintError, match="reason"):
        run_audit([v], rule_ids=["donation"], baseline_path=str(bl))


# ------------------------------------------- engine donation ground truth

def test_engine_probe_entries_are_donated_and_front_half_waived():
    views = {v.name: v for v in build_entries(num_nodes=N)}
    # split probe: the shuffled payloads are donated at the jit site
    assert any(views["probe"].donated)
    assert any(views["bp_build"].donated)
    # front half keeps inputs undonated, with the reason on record
    for name in ("hist", "pipeline", "shuffle"):
        assert not any(views[name].donated)
        assert views[name].waivers.get("donation", "").strip()


# --------------------------------------------------- WIREBYTES A/B (< 10%)

@pytest.mark.slow
def test_static_exchange_bytes_match_measured_wirebytes():
    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.performance import Measurements
    from tpu_radix_join.performance.measurements import (WINCAPR, WINCAPS,
                                                         WIREBYTES)

    inner = Relation(N << 10, N, "unique", seed=31)
    outer = Relation(N << 10, N, "unique", seed=32)
    m = Measurements(node_id=0, num_nodes=N)
    eng = HashJoin(JoinConfig(num_nodes=N, network_fanout_bits=5),
                   measurements=m)
    res = eng.join(inner, outer)
    assert res.ok
    measured = m.counters[WIREBYTES]
    cap_r, cap_s = m.counters[WINCAPR], m.counters[WINCAPS]
    assert cap_r == cap_s  # symmetric workload
    # trace the SAME geometry the engine dispatched
    view = build_entries(num_nodes=N, per_node=1 << 10, cap=cap_r,
                         entries=["pipeline"])[0]
    static = static_exchange_bytes(view)
    assert static > 0
    drift = abs(static - measured) / measured
    assert drift < 0.10, (static, measured, drift)
    counts = collective_counts(view)
    assert counts["all_to_all"] >= 2       # keys + rids, both relations


# ----------------------------------------- STATIC-DRIFT + the planner gate

def test_static_for_explain_agrees_with_cost_model():
    from tpu_radix_join.planner import Workload, load_profile
    from tpu_radix_join.planner.cost_model import plan_exchange

    view = build_entries(num_nodes=N, entries=["pipeline"])[0]
    w = Workload(r_tuples=N * 8192, s_tuples=N * 8192,
                 key_bound=N * 8192, num_nodes=N)
    # a codec-off plan (packing priced above the wire it saves), the
    # geometry the traced pipeline ships by default
    slow_hbm = load_profile().replace_constants(
        hbm_gbps={"value": 50.0, "source": "test: slow HBM"})
    xplan = plan_exchange(slow_hbm, w, fanout_bits=5)
    assert xplan.codec == "off"
    payload = static_for_explain(view, xplan)
    assert payload is not None
    # per-slot basis: pow2 capacity slack cancels, so raw codec-off
    # geometry must agree to well under the 10% A/B bar
    assert abs(payload["drift_pct"]) < 10.0
    assert payload["static_bytes"] > 0


def test_explain_table_grows_static_drift_column():
    from tpu_radix_join.planner import Workload, load_profile, plan_join
    from tpu_radix_join.planner.plan import explain_table

    profile = load_profile()
    w = Workload(r_tuples=N * 4096, s_tuples=N * 4096,
                 key_bound=N * 4096, num_nodes=N)
    plan, costs = plan_join(profile, w)
    payload = {"entry": "pipeline", "static_bytes": 65600,
               "static_bytes_per_tuple": 8.002,
               "plan_bytes_per_tuple": 8.0, "drift_pct": 0.02,
               "collectives": {"all_to_all": 6, "psum": 8}}
    out = explain_table(costs, plan, static=payload)
    assert "STATIC-DRIFT" in out
    assert "+0.02%" in out
    assert "static: jaxpr pipeline" in out
    # without the payload the column stays absent (old renderings stable)
    assert "STATIC-DRIFT" not in explain_table(costs, plan)


def test_planner_static_memory_gate_refuses_classified():
    from tpu_radix_join.planner import (PlanInfeasibleError, Workload,
                                        load_profile, plan_join,
                                        static_memory_gate)
    from tpu_radix_join.robustness.retry import PLAN_INFEASIBLE

    profile = load_profile()
    w = Workload(r_tuples=N * 8192, s_tuples=N * 8192,
                 key_bound=N * 8192, num_nodes=N)
    peak = static_memory_gate(w)        # unarmed budget: returns the peak
    assert peak > 0
    # a budget between the analytic resident set and the traced live-set
    # peak: the cost-model row gate admits, the static gate must refuse
    from tpu_radix_join.planner.cost_model import incore_resident_bytes
    assert incore_resident_bytes(w) < peak
    undersized = Workload(r_tuples=N * 8192, s_tuples=N * 8192,
                          key_bound=N * 8192, num_nodes=N,
                          memory_budget_bytes=int(peak * 0.8))
    with pytest.raises(PlanInfeasibleError) as ei:
        plan_join(profile, undersized, static_gate=True)
    assert ei.value.failure_class == PLAN_INFEASIBLE
    assert "refusing" in str(ei.value) and "at plan time" in str(ei.value)
    # the class is a first-class taxonomy member, not a hand-rolled string
    from tpu_radix_join.analysis.rules_failure import taxonomy
    assert PLAN_INFEASIBLE in taxonomy()

"""ISSUE 8 end to end: the always-on flight recorder, the hang watchdog,
plan-vs-actual drift telemetry, and post-mortem forensics bundles.

Acceptance criteria covered directly:

  * a simulated hang (``backend.stall`` fault site) under a running
    watchdog terminates as a *classified* ``backend_unavailable`` failure
    within the watchdog timeout — never a silent stall — and leaves a
    bundle carrying all-thread stacks and the plan-vs-actual table;
  * every planned strategy exercised here emits a ``PLANDRIFT`` gauge the
    regression gate pins lower-is-better;
  * a chaos VIOLATION's shrunk repro artifact names its forensics bundle;
  * bundles round-trip through the tools_postmortem.py renderer/merger.
"""

import json
import os
import time

import pytest

from tpu_radix_join.observability import postmortem
from tpu_radix_join.observability.flightrec import (FlightRecorder,
                                                    dump_all_stacks)
from tpu_radix_join.observability.watchdog import (HangDetected, Watchdog,
                                                   engine_killer)
from tpu_radix_join.performance.measurements import (PLANDRIFT, PMBUNDLE,
                                                     WDOGTRIP, Measurements)
from tpu_radix_join.planner.audit import (actuals_for_explain, audit_plan,
                                          phase_snapshot)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ flight recorder

def test_ring_bounded_and_ordered():
    fr = FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("event", f"e{i}")
    snap = fr.snapshot()
    assert snap["capacity"] == 8 and snap["recorded"] == 20
    assert len(snap["records"]) == 8
    # oldest evicted, newest retained, in order
    assert [r["name"] for r in snap["records"]] == [f"e{i}"
                                                    for i in range(12, 20)]


def test_ring_context_stamps_and_clears():
    fr = FlightRecorder(capacity=4)
    fr.set_context(query_id="q7", tenant="t")
    fr.record("incr", "X", by=1)
    fr.clear_context("query_id", "tenant")
    fr.record("incr", "Y", by=1)
    recs = fr.records()
    assert recs[0]["query_id"] == "q7" and recs[0]["tenant"] == "t"
    assert "query_id" not in recs[1]


def test_ring_idle_clock():
    fr = FlightRecorder(capacity=4)
    fr.record("event", "tick")
    t0 = fr.idle_s()
    time.sleep(0.05)
    assert fr.idle_s() >= t0 + 0.04


def test_measurements_ring_always_on():
    """The recorder exists on EVERY registry — no tracer, no flag."""
    m = Measurements(node_id=0, num_nodes=1)
    assert isinstance(m.flightrec, FlightRecorder)
    m.start("JTOTAL")
    m.incr("RETRYN", 2)
    m.event("plan_decision", strategy="x")
    m.stop("JTOTAL")
    kinds = [r["kind"] for r in m.flightrec.records()]
    assert kinds == ["begin", "incr", "event", "end"]
    end = m.flightrec.records()[-1]
    assert end["name"] == "JTOTAL" and end["us"] >= 0


def test_dump_all_stacks_sees_this_thread():
    stacks = dump_all_stacks()
    assert any("MainThread" in label for label in stacks)
    joined = "\n".join(fr for frames in stacks.values() for fr in frames)
    assert "test_dump_all_stacks_sees_this_thread" in joined


# ------------------------------------------------------------------ watchdog

def _planned(nodes, per_node, repeats=1):
    from tpu_radix_join.planner import Workload, load_profile, plan_join
    profile = load_profile("v5e_lite")
    plan, costs = plan_join(profile, Workload(
        r_tuples=per_node * nodes, s_tuples=per_node * nodes,
        key_bound=per_node * nodes, num_nodes=nodes, repeats=repeats))
    return plan, costs


def test_watchdog_kills_stalled_join(tmp_path):
    """The tentpole scenario: a hung collective (simulated via the
    ``backend.stall`` site) under a running watchdog terminates within
    the watchdog timeout as classified ``backend_unavailable``, with a
    bundle carrying all-thread stacks + the plan-vs-actual table from
    the join that preceded the hang."""
    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.robustness import faults

    nodes, per_node = 2, 2048
    m = Measurements(node_id=0, num_nodes=nodes)
    eng = HashJoin(JoinConfig(num_nodes=nodes), measurements=m)
    rb = eng.place(Relation(per_node * nodes, nodes, "unique", seed=3))
    sb = eng.place(Relation(per_node * nodes, nodes, "unique", seed=4))

    # one healthy planned join first: the audit stamps plan_vs_actual so
    # the hang's bundle carries the predicted-vs-measured table
    plan, _ = _planned(nodes, per_node)
    times0 = phase_snapshot(m)
    res = eng.join_arrays(rb, sb)
    assert res.ok
    table = audit_plan(plan, m, times0=times0)
    assert table is not None

    inj = faults.FaultInjector(seed=1, measurements=m)
    inj.arm(faults.BACKEND_STALL, at=1)
    timeout_s = 0.5
    wd = Watchdog(m, timeout_s=timeout_s, kill=engine_killer(eng),
                  bundle_dir=str(tmp_path))
    t0 = time.monotonic()
    with pytest.raises(HangDetected) as ei:
        with inj, wd:
            eng.join_arrays(rb, sb)
    elapsed = time.monotonic() - t0
    # trip + kill must land within the timeout plus poll/dump slack, far
    # from the 120s stall cap that guards unwatched runs
    assert elapsed < timeout_s + 10.0
    assert ei.value.failure_class == "backend_unavailable"
    assert wd.tripped and m.counters[WDOGTRIP] == 1

    bundles = postmortem.list_bundles(str(tmp_path))
    assert len(bundles) == 1
    b = postmortem.load_bundle(bundles[0])
    assert b["reason"] == "watchdog_trip"
    assert b["failure_class"] == "backend_unavailable"
    assert b["stacks"], "watchdog bundle must carry all-thread stacks"
    assert "JTOTAL" in b["open_phases"]
    # the plan-vs-actual table in the bundle is the registry's own
    assert b["plan_vs_actual"] == m.meta["plan_vs_actual"]
    assert b["counters"].get("PMBUNDLE", 0) == 0  # snapshot pre-increment
    assert m.counters[PMBUNDLE] == 1


def test_watchdog_no_trip_on_healthy_join(tmp_path):
    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.data.relation import Relation

    m = Measurements(node_id=0, num_nodes=2)
    eng = HashJoin(JoinConfig(num_nodes=2), measurements=m)
    rb = eng.place(Relation(4096, 2, "unique", seed=5))
    sb = eng.place(Relation(4096, 2, "unique", seed=6))
    with Watchdog(m, timeout_s=30.0, kill=engine_killer(eng),
                  bundle_dir=str(tmp_path)) as wd:
        res = eng.join_arrays(rb, sb)
    assert res.ok and not wd.tripped
    assert postmortem.list_bundles(str(tmp_path)) == []
    assert WDOGTRIP not in m.counters


def test_stall_cap_classifies_without_watchdog(monkeypatch):
    """An UNwatched stalled join must still terminate classified: the env
    cap bounds the stall loop and raises the site's TransientFault."""
    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.robustness import faults

    monkeypatch.setenv("TPU_RADIX_STALL_CAP_S", "0.2")
    m = Measurements(node_id=0, num_nodes=2)
    eng = HashJoin(JoinConfig(num_nodes=2), measurements=m)
    rb = eng.place(Relation(4096, 2, "unique", seed=7))
    sb = eng.place(Relation(4096, 2, "unique", seed=8))
    inj = faults.FaultInjector(seed=2, measurements=m)
    inj.arm(faults.BACKEND_STALL, at=1)
    with pytest.raises(faults.TransientFault) as ei:
        with inj:
            eng.join_arrays(rb, sb)
    assert ei.value.failure_class == "backend_unavailable"
    assert "JTOTAL" not in m._starts     # the timer was closed on the way out


# ------------------------------------------------------- plan-vs-actual audit

def test_audit_emits_plandrift_incore():
    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.planner import explain_table

    nodes, per_node = 2, 2048
    m = Measurements(node_id=0, num_nodes=nodes)
    eng = HashJoin(JoinConfig(num_nodes=nodes), measurements=m)
    rb = eng.place(Relation(per_node * nodes, nodes, "unique", seed=9))
    sb = eng.place(Relation(per_node * nodes, nodes, "unique", seed=10))
    plan, costs = _planned(nodes, per_node)
    assert plan.predicted_terms, "plan schema v4 carries per-term breakdown"

    times0 = phase_snapshot(m)
    assert eng.join_arrays(rb, sb).ok
    table = audit_plan(plan, m, times0=times0)
    assert table["strategy"] == plan.strategy
    assert table["actual_ms"] > 0 and table["predicted_ms"] > 0
    assert table["drift_pct"] == pytest.approx(
        100.0 * abs(table["actual_ms"] - table["predicted_ms"])
        / table["predicted_ms"], abs=0.01)
    assert m.counters[PLANDRIFT] == int(round(table["drift_pct"]))
    assert m.meta["plan_vs_actual"] is table
    # term rows keep the cost model's vocabulary
    assert {r["term"] for r in table["terms"]} == set(plan.predicted_terms)

    # the explain table grows actual_ms/drift% on the chosen row only
    rendered = explain_table(costs, plan, actuals=actuals_for_explain(table))
    assert "actual_ms" in rendered and "drift%" in rendered
    chosen_line = next(l for l in rendered.splitlines() if "*" in l)
    assert f"{table['actual_ms']:.1f}" in chosen_line


def test_audit_chunked_strategy_and_delta_semantics():
    """A second audit on an accumulated registry measures only the LAST
    join (delta vs the times0 snapshot), and the chunked vocabulary
    audits through the same path."""
    m = Measurements(node_id=0, num_nodes=1)
    m.start("JTOTAL")
    time.sleep(0.01)
    m.stop("JTOTAL")
    first = dict(m.times_us)
    plan = {"strategy": "chunked_grid", "engine": "chunked",
            "predicted_ms": 10.0, "profile_name": "v5e_lite",
            "predicted_terms": {"sort": 4.0, "scan": 2.0, "dispatch": 4.0}}
    t1 = audit_plan(plan, m, times0={k: 0.0 for k in first})
    assert t1 is not None and t1["strategy"] == "chunked_grid"
    # accumulate a second, longer join; the delta audit must not blend in
    # the first join's time
    times0 = phase_snapshot(m)
    m.start("JTOTAL")
    time.sleep(0.03)
    m.stop("JTOTAL")
    t2 = audit_plan(plan, m, times0=times0)
    assert 0 < t2["actual_ms"] < t1["actual_ms"] + 60.0
    assert t2["actual_ms"] < m.times_us["JTOTAL"] / 1e3  # delta, not total
    assert PLANDRIFT in m.counters


@pytest.mark.parametrize("predicted_ms,actual_us", [
    (0.5914, 1234.5678), (0.0127, 3.3), (250.0, 2.5e6)])
def test_audit_drift_agrees_with_its_row(predicted_ms, actual_us):
    """drift_pct is priced on the rounded values the table shows, so it
    recomputes from the row even at the chip's sub-ms dispatch floor."""
    m = Measurements(node_id=0, num_nodes=1)
    m.times_us["JTOTAL"] = actual_us
    plan = {"strategy": "s", "engine": "incore",
            "predicted_ms": predicted_ms}
    t = audit_plan(plan, m, times0={})
    assert t["drift_pct"] == pytest.approx(
        100.0 * abs(t["actual_ms"] - t["predicted_ms"]) / t["predicted_ms"],
        abs=0.005)


def test_audit_none_paths():
    m = Measurements(node_id=0, num_nodes=1)
    assert audit_plan(None, m) is None           # no plan -> no audit
    plan = {"strategy": "s", "engine": "incore", "predicted_ms": 1.0}
    assert audit_plan(plan, None) is None        # no registry -> no audit
    assert audit_plan(plan, m) is None           # no measured JTOTAL
    assert actuals_for_explain(None) is None


def test_driver_plan_auto_audits(capsys):
    """The CLI path: --plan auto prints the drift line + actuals table
    and stores PLANDRIFT in the perf artifact."""
    from tpu_radix_join.main import main
    rc = main(["--tuples-per-node", "2048", "--nodes", "2",
               "--plan", "auto", "--profile", "v5e_lite"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "[PLAN] actual_ms=" in out and "drift=" in out
    assert "actual_ms" in out          # explain table actuals column
    assert "PLANDRIFT" in out          # [PERF] counter line


# ------------------------------------------------------------------- bundles

def test_bundle_roundtrip_render_merge(tmp_path):
    m = Measurements(node_id=3, num_nodes=4)
    m.flightrec.set_context(query_id="q42")
    m.start("JTOTAL")
    m.incr("RETRYN")
    path = postmortem.write_bundle(
        str(tmp_path), m, reason="query_failed",
        failure_class="data_corruption",
        config={"nodes": 4}, stacks=dump_all_stacks(),
        extra={"note": "unit"})
    b = postmortem.load_bundle(path)
    assert b["bundle_version"] == 1
    assert b["rank"] == 3 and b["nodes"] == 4
    assert b["query_id"] == "q42"
    assert b["config_fingerprint"] == postmortem.config_fingerprint(
        {"nodes": 4})
    assert b["open_phases"] == ["JTOTAL"]
    text = postmortem.render_bundle(b)
    assert "query_failed" in text and "q42" in text and "RETRYN" in text
    merged = postmortem.merge_bundles([path])
    assert merged["bundles"] == 1
    assert merged["by_reason"] == {"query_failed": 1}
    assert merged["rows"][0]["query_id"] == "q42"
    # bundle emission is itself observable
    assert m.counters[PMBUNDLE] == 1
    assert any(e.get("event") == "bundle" for e in m.meta["events"])


def test_bundle_without_measurements(tmp_path):
    """A death outside any engine writes a bundle with no registry."""
    path = postmortem.write_bundle(
        str(tmp_path), None, reason="backend_unavailable",
        failure_class="backend_unavailable",
        extra={"probe_attempts": 9})
    b = postmortem.load_bundle(path)
    assert b["reason"] == "backend_unavailable"
    assert "ring" not in b and b["extra"]["probe_attempts"] == 9
    assert "backend_unavailable" in postmortem.render_bundle(b)


def test_tools_postmortem_cli(tmp_path, capsys):
    import tools_postmortem
    m = Measurements(node_id=0, num_nodes=1)
    postmortem.write_bundle(str(tmp_path), m, reason="watchdog_trip",
                            failure_class="backend_unavailable")
    assert tools_postmortem.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "== bundle: watchdog_trip" in out
    assert tools_postmortem.main([str(tmp_path), "--merge"]) == 0
    out = capsys.readouterr().out
    assert "by reason:" in out and "watchdog_trip" in out
    # an unreadable input is rc=1, not a crash
    bad = tmp_path / "bundle_bad_r0_1.json"
    bad.write_text("{torn")
    assert tools_postmortem.main([str(bad)]) == 1


# ------------------------------------------------------------- chaos bundles

def test_chaos_violation_carries_bundle(tmp_path):
    """A soak VIOLATION's repro artifact names its forensics bundle; the
    bundle replays the (seed, arms) schedule."""
    from tpu_radix_join.robustness import chaos, faults

    sched = chaos.Schedule(
        seed=5, arms=((faults.EXCHANGE_CORRUPT, (("at", 1),)),))
    runner = chaos.ChaosRunner(num_nodes=4, size=1 << 12, verify="off",
                               bundle_dir=str(tmp_path))
    out = runner.run(sched)
    assert out.status == chaos.VIOLATION
    assert out.bundle and os.path.exists(out.bundle)
    assert out.to_json()["bundle"] == out.bundle
    b = postmortem.load_bundle(out.bundle)
    assert b["reason"] == "chaos_violation"
    assert b["chaos"]["seed"] == 5
    assert b["chaos"]["arms"][0][0] == faults.EXCHANGE_CORRUPT
    # repro JSON line (what tools_chaos writes) round-trips the path
    line = chaos.write_repro(out, tmp_path / "repro.json")
    assert json.loads(line)["bundle"] == out.bundle
    # a protected runner (verify=check) classifies: no bundle emitted
    protected = chaos.ChaosRunner(num_nodes=4, size=1 << 12, verify="check",
                                  bundle_dir=str(tmp_path))
    out2 = protected.run(sched)
    assert out2.status == chaos.CLASSIFIED and out2.bundle is None
    assert "bundle" not in out2.to_json()


# ------------------------------------------------------------- serve bundles

def test_session_failed_query_bundle(tmp_path):
    from tpu_radix_join.core.config import JoinConfig, ServiceConfig
    from tpu_radix_join.service import JoinSession, QueryRequest

    m = Measurements(node_id=0, num_nodes=2)
    session = JoinSession(JoinConfig(num_nodes=2), ServiceConfig(),
                          measurements=m, forensics_dir=str(tmp_path))
    try:
        session.submit(QueryRequest(query_id="dead", tuples_per_node=2048,
                                    deadline_s=1e-6))
        out = session.run_next()
        assert out.status == "failed"
        assert out.failure_class == "deadline_exceeded"
        assert out.bundle and os.path.exists(out.bundle)
        assert out.to_json()["bundle"] == out.bundle
        b = postmortem.load_bundle(out.bundle)
        assert b["reason"] == "deadline_exceeded"
        assert b["query_id"] == "dead"       # stamped via the ring context
        # the context is scoped to the query, not leaked onto the session
        assert "query_id" not in m.flightrec.context
        session.submit(QueryRequest(query_id="ok1", tuples_per_node=2048))
        ok = session.run_next()
        assert ok.status == "ok" and ok.bundle is None
        assert "bundle" not in ok.to_json()
    finally:
        session.close()


# -------------------------------------------------------- timeline / regress

def test_timeline_missing_ranks(tmp_path):
    """A 3-rank world where only rank 0 left a span file: the merge names
    the gap instead of silently narrowing the world."""
    from tpu_radix_join.observability.timeline import merge_timeline

    doc0 = {"traceEvents": [{"name": "JTOTAL", "ph": "X", "ts": 0.0,
                             "dur": 5.0, "pid": 0, "tid": 0}],
            "metadata": {"rank": 0, "epoch_s": 100.0, "trace_id": "t",
                         "tags": {"nodes": 3}}}
    (tmp_path / "0.spans.json").write_text(json.dumps(doc0))
    (tmp_path / "1.spans.json").write_text("{torn")
    merged = merge_timeline(str(tmp_path))
    md = merged["metadata"]
    assert md["expected_ranks"] == 3
    assert md["missing_ranks"] == [1, 2]
    assert md["corrupt_files"] == ["1.spans.json"]
    assert md["partial"] is True


def test_regress_pins_observability_counters():
    from tpu_radix_join.observability.regress import (compare_tags,
                                                      higher_is_better)
    for tag in ("PLANDRIFT", "PMBUNDLE", "WDOGTRIP"):
        assert not higher_is_better(tag)
    rows = compare_tags({"PLANDRIFT": 10.0, "PMBUNDLE": 0.0},
                        {"PLANDRIFT": 40.0, "PMBUNDLE": 2.0},
                        threshold=0.25)
    by = {r["tag"]: r["status"] for r in rows}
    assert by == {"PLANDRIFT": "regressed", "PMBUNDLE": "regressed"}
    rows = compare_tags({"PLANDRIFT": 10.0}, {"PLANDRIFT": 9.0})
    assert rows[0]["status"] == "ok"         # drift shrinking is fine

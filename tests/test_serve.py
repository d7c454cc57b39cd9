"""Engine-integrated tests for the resident join service: the tier-1
serve-mode smoke (3 queries through one session via the CLI), warm
plan/capacity reuse, deadline expiry mid-phase, admission rejection
through the serve loop, breaker trip/recovery driven by FaultInjector
arms, thread-lifecycle stability, and a session chaos mini-soak.
"""

import json
import threading
import time

import pytest

from tpu_radix_join.core.config import JoinConfig, ServiceConfig
from tpu_radix_join.performance.measurements import (JHIST, QDEADLINE,
                                                     QDEGRADED, QWARM,
                                                     Measurements)
from tpu_radix_join.robustness import faults
from tpu_radix_join.robustness.faults import TransientFault
from tpu_radix_join.robustness.retry import (BACKEND_UNAVAILABLE,
                                             DEADLINE_EXCEEDED)
from tpu_radix_join.service import (AdmissionRejected, JoinSession,
                                    QueryRequest)

NODES = 8
TPN = 1 << 10          # 1K tuples/node: compile-bound, not data-bound


def _req(qid, tenant="default", **kw):
    kw.setdefault("tuples_per_node", TPN)
    kw.setdefault("seed", 7)
    return QueryRequest(query_id=qid, tenant=tenant, **kw)


def _outcome_lines(out):
    recs = [json.loads(line) for line in out.splitlines()
            if line.startswith("{")]
    return ([r for r in recs if r.get("event") == "outcome"],
            next((r for r in recs if r.get("event") == "summary"), None))


# ----------------------------------------------------------- CLI serve smoke

def test_serve_smoke_three_queries_one_session(capsys, tmp_path):
    """Tier-1 serve smoke: 3 queries through ONE resident session on host
    CPU — all ok, later same-shape queries warm (sizing pre-pass
    skipped), summary carries the SLO percentiles."""
    from tpu_radix_join.main import main
    reqs = tmp_path / "reqs.jsonl"
    reqs.write_text("".join(
        json.dumps({"query_id": f"q{i}", "tuples_per_node": TPN,
                    "seed": 7}) + "\n"
        for i in range(3)))
    rc = main(["--serve", str(reqs), "--nodes", str(NODES)])
    outcomes, summary = _outcome_lines(capsys.readouterr().out)
    assert rc == 0
    assert [o["query_id"] for o in outcomes] == ["q0", "q1", "q2"]
    assert all(o["status"] == "ok" for o in outcomes)
    expect = TPN * NODES
    assert all(o["matches"] == expect for o in outcomes)
    assert not outcomes[0]["warm"]
    assert outcomes[1]["warm"] and outcomes[2]["warm"]
    assert summary is not None
    assert summary["queries_ok"] == 3 and summary["queries_failed"] == 0
    assert summary["warm_queries"] == 2
    assert summary["slo_p50_ms"] > 0 and summary["slo_p99_ms"] > 0
    # cold pays compile + sizing; warm must be far under it
    assert outcomes[1]["latency_ms"] < outcomes[0]["latency_ms"]


def test_serve_rejections_classified_no_hang(capsys, tmp_path):
    """Over-quota and queue-full submissions come back as classified
    rejection outcomes through the CLI — and rejections alone do not fail
    the run (backpressure is the feature working)."""
    from tpu_radix_join.main import main
    reqs = tmp_path / "reqs.jsonl"
    reqs.write_text("".join(
        json.dumps({"query_id": f"q{i}", "tenant": "noisy",
                    "tuples_per_node": TPN, "seed": 7}) + "\n"
        for i in range(5)))
    rc = main(["--serve", str(reqs), "--nodes", str(NODES),
               "--serve-batch", "10", "--serve-tenant-quota", "2"])
    outcomes, summary = _outcome_lines(capsys.readouterr().out)
    assert rc == 0
    rejected = [o for o in outcomes if o["status"] == "rejected"]
    assert len(rejected) == 3
    assert all(o["failure_class"] == "admission_rejected" for o in rejected)
    assert all("tenant_quota" in o["detail"] for o in rejected)
    assert summary["queries_ok"] == 2 and summary["queries_rejected"] == 3
    assert summary["admission_rejection_rate"] == pytest.approx(0.6)


def test_serve_malformed_line_fails_run_but_not_session(capsys, tmp_path):
    from tpu_radix_join.main import main
    reqs = tmp_path / "reqs.jsonl"
    reqs.write_text(
        json.dumps({"query_id": "good", "tuples_per_node": TPN,
                    "seed": 7}) + "\n"
        + "this is not json\n"
        + json.dumps({"query_id": "also_good", "tuples_per_node": TPN,
                      "seed": 7}) + "\n")
    rc = main(["--serve", str(reqs), "--nodes", str(NODES)])
    out = capsys.readouterr().out
    outcomes, summary = _outcome_lines(out)
    assert rc == 1                       # a client bug fails the run...
    assert [o["query_id"] for o in outcomes] == ["good", "also_good"]
    assert all(o["status"] == "ok" for o in outcomes)   # ...not the session
    assert '"event": "request_error"' in out


# --------------------------------------------------------- resident session

@pytest.fixture(scope="module")
def session():
    m = Measurements()
    sess = JoinSession(JoinConfig(num_nodes=NODES),
                       ServiceConfig(breaker_threshold=2,
                                     breaker_cooldown_s=0.05,
                                     cpu_fallback=True),
                       measurements=m)
    yield sess
    sess.close()


def test_warm_queries_skip_sizing_pre_pass(session):
    m = session.measurements
    session.submit(_req("w0", seed=21))
    cold = session.run_next()
    jhist_after_cold = m.times_us.get(JHIST, 0.0)
    qwarm0 = m.counters.get(QWARM, 0)
    session.submit(_req("w1", seed=21))
    warm = session.run_next()
    assert cold.status == "ok" and warm.status == "ok"
    assert warm.warm and warm.matches == cold.matches
    # the observable: NO new JHIST time (the sizing pre-pass never ran)
    assert m.times_us.get(JHIST, 0.0) == jhist_after_cold
    assert m.counters.get(QWARM, 0) == qwarm0 + 1


def test_deadline_expires_mid_phase_and_session_survives(session):
    m = session.measurements
    qdl0 = m.counters.get(QDEADLINE, 0)
    # generous enough to pass admission, far too tight for placement+join
    # of a cold shape (different seed -> new relations, same compiled fn)
    session.submit(_req("dl", seed=99, deadline_s=1e-6))
    out = session.run_next()
    assert out.status == "failed"
    assert out.failure_class == DEADLINE_EXCEEDED
    assert "at phase" in out.detail      # aborted AT a phase boundary
    assert m.counters.get(QDEADLINE, 0) == qdl0 + 1
    # failure isolation: the next query is unaffected
    session.submit(_req("after_dl", seed=21))
    assert session.run_next().status == "ok"


def test_breaker_trip_degrade_probe_recover(session):
    m = session.measurements
    qdeg0 = m.counters.get(QDEGRADED, 0)
    trips0 = session.breaker.trips
    inj = faults.FaultInjector(seed=5, measurements=m)
    inj.arm(faults.BACKEND_DISPATCH, at=(1, 2), exc=TransientFault)
    with inj:
        outs = []
        for i in range(3):
            session.submit(_req(f"brk{i}", seed=21))
            outs.append(session.run_next())
    # threshold 2: two classified outages trip the breaker...
    assert [o.failure_class for o in outs[:2]] == [BACKEND_UNAVAILABLE] * 2
    assert session.breaker.trips == trips0 + 1
    # ...and the third query is served degraded, correctly, while open
    assert outs[2].status == "ok" and outs[2].engine == "cpu_fallback"
    assert m.counters.get(QDEGRADED, 0) == qdeg0 + 1
    time.sleep(0.06)                     # cooldown (0.05s) elapses
    session.submit(_req("probe", seed=21))
    probe = session.run_next()
    assert probe.status == "ok" and probe.engine == "primary"
    assert session.breaker.state == "closed"


def test_session_threads_stable_across_queries_and_close(tmp_path):
    n0 = threading.active_count()
    m = Measurements()
    sess = JoinSession(JoinConfig(num_nodes=4), measurements=m)
    sess.attach_heartbeat(str(tmp_path / "hb.metrics.jsonl"),
                          interval_s=0.05)
    assert threading.active_count() == n0 + 1   # exactly the heartbeat
    for i in range(3):
        sess.submit(_req(f"t{i}", tuples_per_node=256))
        assert sess.run_next().status == "ok"
        # no thread accumulates per query (the daemon-leak satellite)
        assert threading.active_count() == n0 + 1
    sess.close()
    deadline = time.monotonic() + 5.0
    while threading.active_count() > n0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() == n0       # heartbeat joined
    recs = [json.loads(ln) for ln in
            (tmp_path / "hb.metrics.jsonl").read_text().splitlines()]
    assert recs and "slo" in recs[-1] and "breaker" in recs[-1]
    assert recs[-1]["slo"]["queries_ok"] == 3
    with pytest.raises(RuntimeError):
        sess.submit(_req("late"))               # closed session refuses


def test_session_close_is_idempotent():
    sess = JoinSession(JoinConfig(num_nodes=2))
    sess.close()
    sess.close()


# ------------------------------------------------------------- chaos soak

@pytest.mark.slow
def test_session_chaos_soak_no_isolation_violations():
    from tpu_radix_join.robustness import chaos
    runner = chaos.SessionChaosRunner(num_nodes=4, size=1 << 10, queries=4)
    outcomes, summary = chaos.soak_session(3, base_seed=100, runner=runner)
    assert summary["violations"] == 0, [o.detail for o in outcomes
                                        if o.status == chaos.VIOLATION]
    assert summary["pass"] + summary["classified"] == 3


def test_session_chaos_single_stream_classifies_backend_outage():
    from tpu_radix_join.robustness import chaos
    runner = chaos.SessionChaosRunner(num_nodes=4, size=1 << 10, queries=3)
    out = runner.run(chaos.Schedule(
        seed=1, arms=((faults.BACKEND_DISPATCH, (("at", 2),)),)))
    assert out.status == chaos.CLASSIFIED
    assert BACKEND_UNAVAILABLE in out.failure_class
    # breaker threshold 1 + zero cooldown: the stream recovers in-line
    assert "q2=ok" in out.detail


def test_open_breaker_fails_fast_without_cpu_fallback():
    """The default session never answers on the CPU: while the breaker is
    open, queries fail as backend_unavailable from no engine at all."""
    m = Measurements()
    sess = JoinSession(JoinConfig(num_nodes=NODES),
                       ServiceConfig(breaker_threshold=1,
                                     breaker_cooldown_s=60.0),
                       measurements=m)
    try:
        inj = faults.FaultInjector(seed=5, measurements=m)
        inj.arm(faults.BACKEND_DISPATCH, at=1, exc=TransientFault)
        with inj:
            outs = []
            for i in range(2):
                sess.submit(_req(f"open{i}", seed=21))
                outs.append(sess.run_next())
        assert sess.breaker.state == "open"
        shed = outs[1]
        assert shed.status == "failed"
        assert shed.failure_class == BACKEND_UNAVAILABLE
        assert shed.engine == "primary" and not shed.degraded
        assert "--cpu-fallback" in shed.detail
        assert sess._cpu_engine is None
        assert m.counters.get(QDEGRADED, 0) == 0
    finally:
        sess.close()

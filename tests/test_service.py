"""Unit tests for the service building blocks (no engine, no devices):
deadlines, admission control, the circuit breaker, SLO percentiles, the
shared retryability predicate, and the regress gate's direction pins.
The engine-integrated serve tests live in tests/test_serve.py.
"""

import pytest

from tpu_radix_join.core.config import ServiceConfig
from tpu_radix_join.observability.regress import higher_is_better
from tpu_radix_join.robustness.retry import (ADMISSION_REJECTED,
                                             BACKEND_UNAVAILABLE,
                                             CAPACITY_OVERFLOW,
                                             COORDINATOR_TIMEOUT,
                                             DATA_CORRUPTION,
                                             DEADLINE_EXCEEDED, KEY_CONTRACT,
                                             RETRYABLE_SIZING, RetryPolicy,
                                             is_retryable_class)
from tpu_radix_join.service import (CLOSED, HALF_OPEN, OPEN, AdmissionQueue,
                                    AdmissionRejected, CircuitBreaker,
                                    Deadline, DeadlineExceeded, SLORecorder,
                                    nearest_rank)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class _Req:
    def __init__(self, tenant="default", query_id="q"):
        self.tenant = tenant
        self.query_id = query_id


# ---------------------------------------------------------------- deadlines

def test_deadline_expires_with_fake_clock():
    clock = FakeClock()
    d = Deadline(1.0, clock=clock)
    d.check("early")                       # within budget: no raise
    clock.advance(0.5)
    assert d.remaining_s() == pytest.approx(0.5)
    clock.advance(0.6)
    with pytest.raises(DeadlineExceeded) as ei:
        d.check("probe")
    assert ei.value.failure_class == DEADLINE_EXCEEDED
    assert ei.value.phase == "probe"
    assert ei.value.elapsed_s == pytest.approx(1.1)


def test_deadline_unlimited_never_expires():
    clock = FakeClock()
    d = Deadline(None, clock=clock)
    clock.advance(1e9)
    d.check("whenever")
    assert not d.expired()
    assert d.remaining_s() is None
    Deadline.unlimited().check()


def test_deadline_rejects_negative_budget():
    with pytest.raises(ValueError):
        Deadline(-1.0)


# ---------------------------------------------------------------- admission

def test_admission_queue_full_rejects_classified():
    q = AdmissionQueue(max_depth=2, tenant_quota=8)
    q.submit(_Req())
    q.submit(_Req())
    with pytest.raises(AdmissionRejected) as ei:
        q.submit(_Req())
    assert ei.value.failure_class == ADMISSION_REJECTED
    assert ei.value.reason == "queue_full"
    assert q.rejected == 1 and q.admitted == 2


def test_admission_tenant_quota_isolates_noisy_neighbor():
    q = AdmissionQueue(max_depth=16, tenant_quota=2)
    q.submit(_Req("noisy"))
    q.submit(_Req("noisy"))
    with pytest.raises(AdmissionRejected) as ei:
        q.submit(_Req("noisy"))
    assert ei.value.reason == "tenant_quota"
    q.submit(_Req("quiet"))                # the quiet tenant still admits


def test_admission_quota_covers_in_flight_not_just_queued():
    q = AdmissionQueue(max_depth=16, tenant_quota=1)
    r = _Req("t")
    q.submit(r)
    popped = q.pop()
    assert popped is r and q.depth() == 0
    # popped but not done: still counts against the tenant
    with pytest.raises(AdmissionRejected):
        q.submit(_Req("t"))
    q.done(r)
    q.submit(_Req("t"))
    assert q.rejection_rate() == pytest.approx(1 / 3)


def test_admission_queue_validates_bounds():
    with pytest.raises(ValueError):
        AdmissionQueue(max_depth=0)
    with pytest.raises(ValueError):
        AdmissionQueue(tenant_quota=0)


# ------------------------------------------------------------------ breaker

def test_breaker_trips_on_consecutive_failures_only():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=3, cooldown_s=10.0, clock=clock)
    for _ in range(2):
        b.record_failure(BACKEND_UNAVAILABLE)
    b.record_success()                     # streak broken
    for _ in range(2):
        b.record_failure(BACKEND_UNAVAILABLE)
    assert b.state == CLOSED
    assert b.record_failure(BACKEND_UNAVAILABLE) is True
    assert b.state == OPEN and b.trips == 1


def test_breaker_nontripping_classes_reset_streak():
    b = CircuitBreaker(failure_threshold=2, cooldown_s=10.0,
                       clock=FakeClock())
    b.record_failure(BACKEND_UNAVAILABLE)
    b.record_failure(CAPACITY_OVERFLOW)    # query's fault, not the backend's
    b.record_failure(BACKEND_UNAVAILABLE)
    assert b.state == CLOSED
    b.record_failure(DATA_CORRUPTION)
    b.record_failure(DEADLINE_EXCEEDED)
    assert b.state == CLOSED and b.trips == 0


def test_breaker_open_half_open_closed_cycle():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=1, cooldown_s=5.0, clock=clock)
    b.record_failure(BACKEND_UNAVAILABLE)
    assert b.state == OPEN
    assert b.allow_primary() is False      # cooling down: degraded serving
    clock.advance(5.1)
    assert b.allow_primary() is True       # the half-open health probe
    assert b.state == HALF_OPEN and b.probes == 1
    b.record_success()
    assert b.state == CLOSED


def test_breaker_failed_probe_reopens():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=1, cooldown_s=5.0, clock=clock)
    b.record_failure(BACKEND_UNAVAILABLE)
    clock.advance(5.1)
    assert b.allow_primary() is True
    assert b.record_failure(BACKEND_UNAVAILABLE) is True   # probe failed
    assert b.state == OPEN and b.trips == 2
    assert b.allow_primary() is False      # cooldown restarted


# ---------------------------------------------------------------------- slo

def test_nearest_rank_is_an_observed_sample():
    vals = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert nearest_rank(vals, 50) == 3.0
    assert nearest_rank(vals, 99) == 5.0
    assert nearest_rank([7.0], 50) == 7.0
    with pytest.raises(ValueError):
        nearest_rank([], 50)


def test_slo_snapshot_rates_and_per_tenant_percentiles():
    s = SLORecorder()
    for ms in (10.0, 20.0, 30.0):
        s.record("a", ms, ok=True)
    s.record("b", 100.0, ok=False, failure_class=DEADLINE_EXCEEDED)
    s.record("b", 50.0, ok=True, degraded=True)
    s.record_rejection()
    snap = s.snapshot()
    assert snap["queries_submitted"] == 6
    assert snap["queries_ok"] == 4 and snap["queries_failed"] == 1
    assert snap["admission_rejection_rate"] == pytest.approx(1 / 6, abs=1e-3)
    assert snap["deadline_miss_rate"] == pytest.approx(1 / 6, abs=1e-3)
    assert snap["degraded_rate"] == pytest.approx(1 / 6, abs=1e-3)
    assert snap["slo_p50_ms"] == 30.0          # 5 samples, nearest-rank
    assert snap["slo_a_p99_ms"] == 30.0
    assert snap["slo_b_p50_ms"] == 50.0
    assert snap["slo_b_p99_ms"] == 100.0


def test_slo_empty_snapshot_has_no_percentiles():
    snap = SLORecorder().snapshot()
    assert snap["queries_submitted"] == 0
    assert "slo_p50_ms" not in snap


# -------------------------------------------------- retryability predicate

def test_retryable_default_policy_covers_transients():
    assert is_retryable_class(CAPACITY_OVERFLOW)
    assert is_retryable_class(BACKEND_UNAVAILABLE)
    assert is_retryable_class(COORDINATOR_TIMEOUT)
    assert not is_retryable_class(KEY_CONTRACT)
    assert not is_retryable_class(DATA_CORRUPTION)
    assert not is_retryable_class(ADMISSION_REJECTED)
    assert not is_retryable_class(DEADLINE_EXCEEDED)


def test_retryable_policy_narrows_the_predicate():
    sizing = RetryPolicy(retryable_classes=RETRYABLE_SIZING)
    # the engine's capacity-regrow loop must NOT spin on a backend outage
    assert is_retryable_class(CAPACITY_OVERFLOW, sizing)
    assert not is_retryable_class(BACKEND_UNAVAILABLE, sizing)
    custom = RetryPolicy(retryable_classes=frozenset({KEY_CONTRACT}))
    assert is_retryable_class(KEY_CONTRACT, custom)
    assert not is_retryable_class(CAPACITY_OVERFLOW, custom)


# ----------------------------------------------------------- service config

def test_service_config_validates_and_replaces():
    svc = ServiceConfig()
    assert svc.max_queue_depth == 64 and svc.breaker_threshold == 3
    narrowed = svc.replace(tenant_quota=2, default_deadline_s=1.5)
    assert narrowed.tenant_quota == 2
    assert narrowed.default_deadline_s == 1.5
    with pytest.raises(ValueError):
        ServiceConfig(max_queue_depth=0)
    with pytest.raises(ValueError):
        ServiceConfig(breaker_cooldown_s=-1.0)
    with pytest.raises(ValueError):
        ServiceConfig(default_deadline_s=-0.1)


# ------------------------------------------------- regress direction pins

def test_regress_direction_slo_tags_are_lower_better():
    # "rate" normally marks a throughput, but MORE rejections is worse:
    # the lower-better override must win the substring scan
    assert not higher_is_better("admission_rejection_rate")
    assert not higher_is_better("deadline_miss_rate")
    assert not higher_is_better("degraded_rate")
    assert not higher_is_better("slo_p99_ms")
    assert not higher_is_better("warm_latency_p50_ms")
    # and the existing vocabulary keeps its direction
    assert higher_is_better("JRATE")
    assert higher_is_better("warm_speedup")
    assert higher_is_better("value")

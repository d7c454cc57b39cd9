"""Retry policies and the machine-readable failure-class taxonomy.

Generalizes the engine's ad-hoc detect-and-retry window-grow loop
(operators/hash_join.py) into a reusable :class:`RetryPolicy` — max
attempts, exponential backoff, deterministic jitter — and gives every
terminal failure a *failure class* string derived from the existing
``JoinResult.diagnostics`` flag taxonomy, so callers branch on data
instead of parsing asserts (the reference's only contract was
``JOIN_ASSERT``, Window.cpp:180-191).

Classes (stable strings, stamped into ``diagnostics["failure_class"]``
and surfaced by main.py / bench reports):

  * ``ok``                   — no failure flags raised.
  * ``capacity_overflow``    — a measured buffer was too small (shuffle
    window, local partition slack, skew hot cap, rate cap).  RETRYABLE:
    regrow and rerun.
  * ``key_contract``         — input keys violate the declared key-range
    contract.  FATAL: growth cannot fix data.
  * ``conservation``         — tuples lost/duplicated across the shuffle.
    FATAL: indicates a bug, not a sizing problem.
  * ``count_overflow_risk``  — match count near the uint32 accumulator
    edge.  FATAL for the current dtype config.
  * ``data_corruption``      — a per-partition integrity checksum
    (verify.py: count / sum / xor-fold of key lanes) disagreed across
    pipeline stages, or the join-level cross-check failed.  FATAL for
    the attempt — but partition-granular (``--verify repair``
    recomputes only the damaged partitions, hash_join.py).
  * ``device_unavailable``   — accelerator/mesh init failed (degrade.py).
  * ``coordinator_timeout``  — distributed init could not reach the
    coordinator within policy (multihost.initialize).
  * ``interrupted``          — run killed mid-flight (resume via
    checkpoint.py).
  * ``checkpoint_mismatch``  — checkpoint fingerprint does not match the
    run configuration.
  * ``retries_exhausted``    — a retryable class persisted through every
    attempt (possibly after a failed fallback).
  * ``backend_unavailable``  — the device backend failed or stopped
    answering a dispatch (a hung collective, a dispatch error, an open
    circuit breaker); distinct from ``device_unavailable`` (init
    *failed*) because the remedy is "retry later", not "fall back to
    CPU".
  * ``admission_rejected``   — the resident service refused the query at
    the door (queue depth or per-tenant quota, service/admission.py).
    The query never ran; resubmitting later is safe by construction.
  * ``request_error``        — the request line itself was malformed or
    unservable, so a serve worker refused it (service/fleet.py).  FATAL
    and worker-independent: the same line fails on every worker, so the
    fleet classifies instead of failing over — the fix is the client's.
  * ``deadline_exceeded``    — the query's latency budget expired between
    pipeline phases (service/deadline.py cooperative cancellation).
  * ``rank_lost``            — a peer rank's membership lease lapsed
    mid-run (robustness/membership.py).  NOT blind-retryable: the remedy
    is the explicit elastic-recovery path (robustness/recovery.py) —
    fence the membership epoch, re-plan on the survivor mesh, and resume
    at partition granularity — not a same-shape rerun, which would hang
    on the same dead collective.
"""

from __future__ import annotations

import random
import time
from dataclasses import dataclass
from typing import Callable, Optional, Tuple, Type

from tpu_radix_join.performance.measurements import BACKOFFMS, RETRYN

# ------------------------------------------------------------ failure classes
OK = "ok"
CAPACITY_OVERFLOW = "capacity_overflow"
KEY_CONTRACT = "key_contract"
CONSERVATION = "conservation"
COUNT_OVERFLOW_RISK = "count_overflow_risk"
DATA_CORRUPTION = "data_corruption"
DEVICE_UNAVAILABLE = "device_unavailable"
COORDINATOR_TIMEOUT = "coordinator_timeout"
INTERRUPTED = "interrupted"
CHECKPOINT_MISMATCH = "checkpoint_mismatch"
RETRIES_EXHAUSTED = "retries_exhausted"
BACKEND_UNAVAILABLE = "backend_unavailable"
ADMISSION_REJECTED = "admission_rejected"
REQUEST_ERROR = "request_error"
DEADLINE_EXCEEDED = "deadline_exceeded"
RANK_LOST = "rank_lost"
RANK_JOIN = "rank_join"
PLAN_INFEASIBLE = "plan_infeasible"

#: diagnostics flags -> class, in priority order (fatal classes outrank
#: capacity: a key-contract violation must never look retryable just because
#: an overflow flag fired in the same attempt)
_FATAL_FLAGS = (
    ("key_contract_violations", KEY_CONTRACT),
    ("conservation_violations", CONSERVATION),
    ("data_corruption_partitions", DATA_CORRUPTION),
    ("count_overflow_risk", COUNT_OVERFLOW_RISK),
)
_CAPACITY_FLAGS = ("shuffle_overflow_r_tuples", "shuffle_overflow_s_tuples",
                   "local_overflow", "hot_overflow")


def classify_diagnostics(diag: dict) -> str:
    """Map a ``JoinResult.diagnostics`` dict to a failure-class string."""
    for flag, cls in _FATAL_FLAGS:
        if diag.get(flag, 0):
            return cls
    if any(diag.get(flag, 0) for flag in _CAPACITY_FLAGS):
        return CAPACITY_OVERFLOW
    return OK


#: classes a same-config rerun can plausibly fix.  Two families:
#:   * sizing — regrow-and-rerun repairs it (the engine's capacity loop);
#:   * transient infrastructure — nothing is wrong with the query, the
#:     substrate hiccupped (grid ``TransientFault`` pairs, a backend
#:     dispatch error): re-dispatch later on the same shapes.
#: Everything else (key contracts, conservation, corruption, admission /
#: deadline verdicts) is fatal for the attempt: retrying cannot fix data,
#: and retrying a rejected or expired query would double-bill its tenant.
RETRYABLE_SIZING = frozenset({CAPACITY_OVERFLOW})
RETRYABLE_TRANSIENT = frozenset({BACKEND_UNAVAILABLE, COORDINATOR_TIMEOUT})
DEFAULT_RETRYABLE = RETRYABLE_SIZING | RETRYABLE_TRANSIENT


def is_retryable_class(failure_class: str,
                       policy: Optional["RetryPolicy"] = None) -> bool:
    """Policy-driven retryability predicate, shared by the engine's
    capacity loop, the grid's transient-pair retries, and the service's
    dispatch path.  Without a policy the :data:`DEFAULT_RETRYABLE` set
    applies; a :class:`RetryPolicy` narrows or widens it through its
    ``retryable_classes`` field (e.g. the engine's regrow loop passes a
    sizing-only policy — a backend outage must fall through to the breaker,
    not spin the capacity doubler)."""
    classes = policy.retryable_classes if policy is not None \
        else DEFAULT_RETRYABLE
    return failure_class in classes


@dataclass(frozen=True)
class RetryPolicy:
    """Exponential backoff with deterministic jitter.

    ``delay_s(attempt)`` is the sleep AFTER failed attempt ``attempt``
    (0-based): ``base_delay_s * multiplier**attempt`` capped at
    ``max_delay_s``, then scaled by a jitter factor in ``[1-jitter,
    1+jitter]`` drawn from ``Random((seed << 16) ^ attempt)`` — the same
    (seed, attempt) always yields the same delay, so backoff schedules are
    replayable in tests (fake clock) and across processes (no thundering
    re-sync because each process seeds with its rank).

    ``max_elapsed_s``: optional wall-clock budget — :func:`execute` stops
    retrying (re-raises) once the clock since the first attempt exceeds it,
    the deadline discipline bench.py's backend wait needs.
    """

    max_attempts: int = 3
    base_delay_s: float = 0.5
    multiplier: float = 2.0
    max_delay_s: float = 30.0
    jitter: float = 0.0
    seed: int = 0
    max_elapsed_s: Optional[float] = None
    #: failure classes :func:`is_retryable_class` accepts under this policy
    retryable_classes: frozenset = DEFAULT_RETRYABLE

    def delay_s(self, attempt: int) -> float:
        d = min(self.max_delay_s,
                self.base_delay_s * self.multiplier ** attempt)
        if self.jitter and d > 0:
            u = random.Random((self.seed << 16) ^ attempt).random()
            d *= 1.0 + self.jitter * (2.0 * u - 1.0)
        return d

    def schedule(self) -> Tuple[float, ...]:
        """The full backoff schedule (one sleep between each attempt pair)."""
        return tuple(self.delay_s(a) for a in range(self.max_attempts - 1))


class RetriesExhausted(RuntimeError):
    """A retryable failure persisted through every attempt."""

    failure_class = RETRIES_EXHAUSTED

    def __init__(self, label: str, attempts: int, last_error: BaseException):
        super().__init__(
            f"{label}: {attempts} attempt(s) exhausted; last error: "
            f"{last_error!r}")
        self.label = label
        self.attempts = attempts
        self.last_error = last_error


def execute(fn: Callable, policy: RetryPolicy, *,
            retryable: Tuple[Type[BaseException], ...] = (
                ConnectionError, TimeoutError, OSError),
            sleep: Callable[[float], None] = time.sleep,
            clock: Callable[[], float] = time.monotonic,
            measurements=None,
            on_retry: Optional[Callable] = None,
            label: str = "retry") -> object:
    """Call ``fn()`` under ``policy``.

    Exceptions in ``retryable`` trigger backoff-and-retry (``RETRYN`` and
    ``BACKOFFMS`` counters + a ``retry`` trace event per attempt), as does
    any exception whose ``failure_class`` satisfies
    :func:`is_retryable_class` under ``policy`` — the one predicate the
    engine's capacity loop, the grid's transient-pair retries, and the
    service's dispatch path all share.  Anything else propagates
    immediately.  When attempts or the ``max_elapsed_s`` budget run out,
    raises :class:`RetriesExhausted` chaining the last error.
    ``sleep``/``clock`` are injectable for fake-clock tests.
    """

    def _should_retry(e: BaseException) -> bool:
        if isinstance(e, retryable):
            return True
        cls = getattr(e, "failure_class", None)
        return cls is not None and is_retryable_class(cls, policy)

    t0 = clock()
    last: Optional[BaseException] = None
    for attempt in range(policy.max_attempts):
        try:
            return fn()
        except Exception as e:
            if not _should_retry(e):
                raise
            last = e
            out_of_time = (policy.max_elapsed_s is not None
                           and clock() - t0 >= policy.max_elapsed_s)
            if attempt == policy.max_attempts - 1 or out_of_time:
                raise RetriesExhausted(label, attempt + 1, last) from last
            delay = policy.delay_s(attempt)
            if measurements is not None:
                measurements.incr(RETRYN)
                measurements.incr(BACKOFFMS, int(delay * 1000))
                measurements.event("retry", site=label, attempt=attempt + 1,
                                   delay_s=round(delay, 6), error=repr(e))
            if on_retry is not None:
                on_retry(attempt, e, delay)
            sleep(delay)
    raise RetriesExhausted(label, policy.max_attempts, last) from last  # pragma: no cover - loop always returns or raises above

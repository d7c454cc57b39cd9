"""JoinSession: the resident, admission-controlled join service.

The one-shot driver (main.py) pays mesh bring-up, XLA compilation, the
JHIST sizing pre-pass, and a host-device dispatch round trip on EVERY
invocation, and a backend outage mid-run can only be reported, not
absorbed.  A :class:`JoinSession` keeps all of that warm across many
queries:

  * the **mesh and compiled executables** — ``HashJoin`` caches compiled
    programs per (shape, capacity) key, so same-shape queries after the
    first skip compilation entirely;
  * the **plan cache** (planner/cache.py) — the first query's converged
    window capacities warm-start every later same-shape query past the
    sizing pre-pass (no JHIST dispatch), via the cache's new in-process
    hot layer;
  * **placed relations** — a small LRU of device-resident inputs, so the
    closed-loop bench's repeated workloads skip generation + transfer;
  * **registered tables** — a user's relations, placed once under a name
    (:meth:`JoinSession.register_table`), joined by name, and updated in
    place under a new version (:meth:`JoinSession.update_table`).  The
    placed-relation LRU and the result cache key a table by (name,
    version); the plan cache keys on shapes, as for seeded specs.  A
    query admitted after an update reads that update or a later one.

In front of the engine sit the robustness pieces this module composes
(each one classified, none of them able to take the session down):

  * :class:`~tpu_radix_join.service.admission.AdmissionQueue` — bounded
    depth + per-tenant quotas -> ``admission_rejected``;
  * :class:`~tpu_radix_join.service.deadline.Deadline` — per-query
    budgets enforced cooperatively between phases (the engine's
    ``cancel`` hook) -> ``deadline_exceeded``;
  * :class:`~tpu_radix_join.service.breaker.CircuitBreaker` — consecutive
    backend failures trip it open: queries then fail fast as
    ``backend_unavailable``, or, with ``ServiceConfig.cpu_fallback``, go
    to the degraded CPU engine (robustness/degrade.py machinery);
    half-open probes recover it;
  * per-query **failure isolation** — every exception inside a query is
    caught, classified via the ``failure_class`` taxonomy, and turned
    into a :class:`QueryOutcome`; only session-construction errors and
    interrupts propagate.

``main.py --serve`` feeds it from a JSONL request file; ``bench.py
--serve-bench`` closes the loop and gates the SLO tags.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

from tpu_radix_join.core.config import JoinConfig, ServiceConfig
from tpu_radix_join.performance.measurements import (BATCHN, BATCHQ,
                                                     COMPILEMS, DELTAMERGE,
                                                     JHIST, MEPOCH, NCOMPILE,
                                                     QDEADLINE, QDEGRADED,
                                                     QEXEC, QFINISH, QSERVE,
                                                     QSTALE, QTABLE,
                                                     QTABLEHIT, QUPDATE,
                                                     QUPDATEN, QWARM, RANKLOST,
                                                     RCHIT, RECOVERMS,
                                                     RECOVERN)
from tpu_radix_join.robustness import faults as _faults
from tpu_radix_join.robustness.retry import (BACKEND_UNAVAILABLE,
                                             DEADLINE_EXCEEDED, OK,
                                             REQUEST_ERROR)
from tpu_radix_join.service.admission import AdmissionQueue, AdmissionRejected
from tpu_radix_join.service.breaker import HALF_OPEN, CircuitBreaker
from tpu_radix_join.service.deadline import Deadline, DeadlineExceeded
from tpu_radix_join.service.slo import SLORecorder

#: unclassified-exception sentinel: a query that dies without a
#: failure_class still yields a terminal outcome (the session survives),
#: but chaos/soak treats this string as an isolation violation
UNCLASSIFIED = "unclassified"
#: a query named a table older than the version it was admitted under
STALE_VERSION = "stale_version"


class BackendUnavailable(ConnectionError):
    """The chip backend failed a query-time dispatch, or the circuit
    breaker is open and CPU degrade is off."""

    failure_class = BACKEND_UNAVAILABLE


class UnknownTable(KeyError):
    """A query or update named a table the session does not hold."""

    failure_class = REQUEST_ERROR


class StaleTable(RuntimeError):
    """A table is older than the version its query was admitted under:
    the session refuses rather than answer from it."""

    failure_class = STALE_VERSION


@dataclasses.dataclass
class _Table:
    """One registered table: its lanes on the engine's mesh, and the
    version they hold."""

    batch: object                   # data.tuples.TupleBatch
    version: int


@dataclasses.dataclass(frozen=True)
class QueryRequest:
    """One join request as the serve loop admits it (JSONL line shape)."""

    query_id: str
    tenant: str = "default"
    tuples_per_node: int = 1 << 16
    outer_kind: str = "unique"          # unique | modulo | zipf
    modulo: Optional[int] = None
    zipf_theta: float = 0.75
    seed: int = 1234
    repeats: int = 1
    deadline_s: Optional[float] = None  # None -> ServiceConfig default
    #: incremental query: this many NEW tuples per node appended to the
    #: session-resident inner relation since the last query — served by
    #: the O(N+Δ) delta-merge fast path when residency is enabled
    #: (ServiceConfig.resident_budget_bytes > 0), full path otherwise
    delta_tuples_per_node: int = 0
    #: registered tables to join (:meth:`JoinSession.register_table`),
    #: both named or neither; named, they replace the seeded spec above
    inner: Optional[str] = None
    outer: Optional[str] = None

    def __post_init__(self):
        if (self.inner is None) != (self.outer is None):
            raise ValueError("a table query names both inner and outer")
        if self.inner is not None and self.delta_tuples_per_node:
            raise ValueError("a table query has no delta_tuples_per_node")

    @classmethod
    def from_json(cls, obj: dict) -> "QueryRequest":
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(obj) - fields
        if unknown:
            raise ValueError(f"unknown request fields: {sorted(unknown)}")
        if "query_id" not in obj:
            raise ValueError("request needs a query_id")
        return cls(**obj)


@dataclasses.dataclass
class QueryOutcome:
    """Terminal, classified verdict for one submitted query."""

    query_id: str
    tenant: str
    status: str                     # ok | failed | rejected
    failure_class: str              # "ok" when status == "ok"
    latency_ms: float
    matches: Optional[int] = None
    expected: Optional[int] = None
    engine: str = "primary"         # primary | cpu_fallback
    degraded: bool = False
    warm: bool = False              # sizing pre-pass skipped (cache hit)
    breaker_state: str = "closed"
    detail: str = ""
    bundle: Optional[str] = None    # forensics bundle path, failed queries
    #: which serving path produced the answer: execute (full engine run),
    #: cache_hit (result cache, no execution), batched (fused multi-query
    #: program), delta_merge (O(N+Δ) incremental path)
    served_by: str = "execute"
    #: {table: version} a table query was computed on
    table_versions: Optional[Dict[str, int]] = None

    def to_json(self) -> dict:
        out = dataclasses.asdict(self)
        out["latency_ms"] = round(self.latency_ms, 3)
        for key in ("bundle", "table_versions"):
            if out.get(key) is None:
                # spec queries that succeed keep the line shape they had
                out.pop(key, None)
        return out


class JoinSession:
    """Resident engine + admission queue + breaker + SLO accounting.

    Single-threaded by design: one mesh, one query at a time (the
    micro-batching direction in ROADMAP item 1 layers onto this API).
    Construction builds the primary engine once; ``submit``/``run_next``/
    ``drain`` serve queries; ``close`` releases everything the session
    owns (and is idempotent).
    """

    def __init__(self, config: JoinConfig,
                 service: Optional[ServiceConfig] = None,
                 measurements=None, plan_cache=None, profile: str = "v5e_lite",
                 clock: Callable[[], float] = time.monotonic,
                 forensics_dir: Optional[str] = None,
                 ledger=None, membership=None, elastic: bool = False,
                 partition_manifest=None, elastic_grow: bool = False,
                 hedge: str = "off", hedge_threshold: float = 0.5):
        from tpu_radix_join.operators.hash_join import HashJoin

        self.config = config
        #: elastic mesh recovery services (robustness/membership +
        #: checkpoint.PartitionManifest), threaded onto every engine the
        #: session builds: the session SURVIVES a mesh change — a
        #: mid-query rank loss recovers inside join_arrays (classified
        #: ``recovered`` diagnostics, exact count), later queries compile
        #: against the new epoch (the engine's compile keys and capacity
        #: fingerprints carry it), and the breaker keeps serving —
        #: degraded if it was already open — instead of the whole session
        #: dying with the rank
        self.membership = membership
        self.elastic = elastic
        self.partition_manifest = partition_manifest
        #: growth + hedging posture, threaded like membership: a session
        #: can admit ranks (elastic_grow) and speculate on stragglers
        #: (hedge/hedge_threshold) on any engine it builds
        self.elastic_grow = elastic_grow
        self.hedge = hedge
        self.hedge_threshold = hedge_threshold
        self.service = service or ServiceConfig()
        self.measurements = measurements
        #: cross-run telemetry ledger (observability/ledger.py): when set,
        #: every executed query appends one ``kind="query"`` row — the
        #: per-query evidence stream a one-shot driver can't produce
        self.ledger = ledger
        self._recompile_storms = 0
        #: when set, every executed-and-failed query (deadline expiry,
        #: backend outage, breaker trip, corruption) drops a forensics
        #: bundle here (observability/postmortem.py), stamped with the
        #: query_id the flight-recorder context carried during the query
        self.forensics_dir = forensics_dir
        self._cache_tmp = None
        if plan_cache is None:
            # a resident session warms by default: without a caller-provided
            # cache dir, own an ephemeral one (first same-shape query pays
            # the sizing pre-pass, every later one skips it via the hot
            # layer; the tempdir dies with the session)
            import tempfile

            from tpu_radix_join.planner import PlanCache, load_profile
            self._cache_tmp = tempfile.TemporaryDirectory(
                prefix="join_session_plan_cache_")
            plan_cache = PlanCache(self._cache_tmp.name,
                                   load_profile(profile),
                                   measurements=measurements)
        self.plan_cache = plan_cache
        self._clock = clock
        self.queue = AdmissionQueue(self.service.max_queue_depth,
                                    self.service.tenant_quota,
                                    measurements=measurements)
        self.breaker = CircuitBreaker(self.service.breaker_threshold,
                                      self.service.breaker_cooldown_s,
                                      clock=clock,
                                      measurements=measurements)
        self.slo = SLORecorder()
        self.engine = HashJoin(config, measurements=measurements,
                               plan_cache=plan_cache)
        self._wire_elastic(self.engine)
        self._cpu_engine = None         # built lazily on first open-state query
        self._place_cache: "collections.OrderedDict" = \
            collections.OrderedDict()
        #: registered tables by name, and the last version handed out:
        #: versions never repeat within a session, so re-registering a
        #: name cannot alias an older (name, version)
        self._tables: Dict[str, _Table] = {}
        self._version = 0
        #: id(queued table query) -> {table: version} current when it was
        #: admitted: it is never answered from an older version
        self._admitted: Dict[int, Dict[str, int]] = {}
        # ------------------------------------------------ serving fast paths
        from tpu_radix_join.service.resident import ResidentStateManager
        from tpu_radix_join.service.resultcache import ResultCache
        #: whole-query reuse keyed by content fingerprint (tier 1; disabled
        #: unless ServiceConfig.result_cache_max > 0)
        self.result_cache = ResultCache(self.service.result_cache_max,
                                        self.service.result_cache_ttl_s,
                                        measurements=measurements,
                                        clock=clock)
        #: device-resident sorted inner lanes for delta-merge (tier 3;
        #: disabled unless ServiceConfig.resident_budget_bytes > 0)
        self.resident = ResidentStateManager(
            self.service.resident_budget_bytes, measurements=measurements)
        #: host mirror of each resident lane's key multiset — the exactness
        #: oracle for incremental queries (base ∪ all absorbed deltas has no
        #: closed-form expected count once the session has grown it)
        self._resident_host: Dict = {}
        #: per-relation incremental-probe state: the outer-spec fingerprint
        #: the running totals were accumulated under, the running device
        #: total and host-oracle expected, and the HOST-sorted outer lane
        #: (the device twin lives in ``self.resident`` under a ("probe",…)
        #: key so it shares the HBM budget and eviction discipline).  Counts
        #: over multisets are additive, so while the outer spec is unchanged
        #: each delta query only counts its Δ — the full-lane probe drops
        #: off the hot path (ops/merge_delta.delta_merge_increment)
        self._resident_probe: Dict = {}
        self.batches_fused = 0          # fused device programs dispatched
        self.batch_queries_fused = 0    # queries served through them
        self._sampler = None            # attached heartbeat, owned if set
        self._closed = False
        #: recent outcomes only (maxlen = service.outcomes_keep): the SLO
        #: recorder is the source of truth for aggregates, so a long-lived
        #: serve worker keeps a bounded window, not its whole history
        self.outcomes: "collections.deque" = collections.deque(
            maxlen=self.service.outcomes_keep)
        #: last N per-query critical paths (observability/critpath.py),
        #: window-sliced from the attached tracer around each executed
        #: query — the ``/statusz`` critical_paths section reads this
        self.recent_critical_paths: "collections.deque" = \
            collections.deque(maxlen=8)

    # ----------------------------------------------------------- admission
    def submit(self, request: QueryRequest) -> None:
        """Admit ``request`` or raise :class:`AdmissionRejected` (already
        SLO-accounted; callers turn it into a rejected outcome via
        :meth:`rejection_outcome`)."""
        if self._closed:
            raise RuntimeError("session is closed")
        if request.inner is not None:
            self._admitted[id(request)] = {
                name: self._tables[name].version
                for name in (request.inner, request.outer)
                if name in self._tables}
        try:
            self.queue.submit(request)
        except AdmissionRejected:
            self._admitted.pop(id(request), None)
            self.slo.record_rejection()
            raise

    def rejection_outcome(self, request: QueryRequest,
                          exc: AdmissionRejected) -> QueryOutcome:
        out = QueryOutcome(
            query_id=request.query_id, tenant=request.tenant,
            status="rejected", failure_class=exc.failure_class,
            latency_ms=0.0, breaker_state=self.breaker.state,
            detail=f"{exc.reason}: {exc}")
        self.outcomes.append(out)
        return out

    # ------------------------------------------------------------- serving
    def run_next(self) -> Optional[QueryOutcome]:
        """Execute the oldest admitted query; None when the queue is
        empty.  The tenant's quota slot is released on every outcome
        path.  Consults the fast-path tiers in price order: result cache
        (no execution), delta merge (O(N+Δ)), full engine execution."""
        request = self.queue.pop()
        if request is None:
            return None
        try:
            with self._timed(QSERVE):
                return self._serve_one(request)
        finally:
            self._release(request)

    def _serve_one(self, request: QueryRequest) -> QueryOutcome:
        hit = self.try_cache(request)
        if hit is not None:
            return hit
        if request.delta_tuples_per_node > 0:
            # incremental query: delta-merge when residency holds the
            # relation, full re-sort otherwise (budget 0 -> every query
            # pays the full sort — the A/B baseline posture)
            return self._execute_delta(request)
        out = self._execute(request)
        self._cache_put(request, out)
        return out

    def drain(self, on_outcome: Optional[Callable] = None,
              batched: Optional[bool] = None) -> List[QueryOutcome]:
        """Serve every admitted query.  ``batched`` (default: whether
        ServiceConfig enables a batch window) groups co-batchable queued
        queries into fused device programs via :meth:`run_next_batch`."""
        if batched is None:
            batched = self.service.batch_window_ms > 0
        outs = []
        while True:
            batch = (self.run_next_batch() if batched
                     else _as_list(self.run_next()))
            if not batch:
                return outs
            for out in batch:
                outs.append(out)
                if on_outcome is not None:
                    on_outcome(out)

    def run_next_batch(self) -> List[QueryOutcome]:
        """Pop the oldest admitted query PLUS every queued query that can
        legally share its fused program (same :func:`batch_signature`, up
        to ``batch_max_queries``) and serve them as one device dispatch.
        Singletons fall through to the normal serving tiers; [] when the
        queue is empty."""
        from tpu_radix_join.service.microbatch import batch_signature
        first = self.queue.pop()
        if first is None:
            return []
        group = [first]
        try:
            if (self.service.batch_window_ms > 0
                    and first.delta_tuples_per_node == 0
                    and first.inner is None):
                sig = batch_signature(first)
                group += self.queue.pop_matching(
                    lambda r: (batch_signature(r) == sig
                               and r.delta_tuples_per_node == 0
                               and r.inner is None),
                    self.service.batch_max_queries - 1)
            if len(group) == 1:
                return [self._serve_one(first)]
            return self._execute_batched(group)
        finally:
            for request in group:
                self._release(request)

    def _release(self, request: QueryRequest) -> None:
        """A popped query is done: free its tenant slot and its record."""
        self._admitted.pop(id(request), None)
        self.queue.done(request)

    # ----------------------------------------------------- result cache tier
    def _epoch(self) -> Optional[int]:
        return self.membership.epoch if self.membership is not None else None

    def _content_fp(self, request: QueryRequest,
                    versions: Optional[Dict[str, int]] = None) -> str:
        """The result cache's key; a table query's carries the versions
        of its tables (those current, unless ``versions`` names the ones
        an answer was computed on)."""
        from tpu_radix_join.service.resultcache import content_fingerprint
        config_fp = dataclasses.asdict(self.config)
        if request.inner is not None:
            config_fp["tables"] = versions or {
                name: self._tables[name].version if name in self._tables
                else None for name in (request.inner, request.outer)}
        return content_fingerprint(request, config_fp=config_fp,
                                   epoch=self._epoch())

    def try_cache(self, request: QueryRequest) -> Optional[QueryOutcome]:
        """Serve ``request`` from the result cache without executing, or
        None on a miss.  Public so callers (the serve loop, the fleet
        supervisor) can short-circuit BEFORE admission — a hit never
        occupies a queue slot or a tenant quota.  Incremental queries
        never cache-serve: their answer depends on session-grown state,
        not the request alone."""
        if (self.result_cache.max_entries == 0
                or request.delta_tuples_per_node > 0):
            return None
        t0 = time.perf_counter()
        payload = self.result_cache.get(self._content_fp(request),
                                        epoch=self._epoch())
        if payload is None:
            return None
        out = QueryOutcome(
            query_id=request.query_id, tenant=request.tenant,
            status="ok", failure_class=OK,
            latency_ms=(time.perf_counter() - t0) * 1e3,
            matches=payload.get("matches"), expected=payload.get("expected"),
            engine=payload.get("engine", "primary"),
            warm=True, breaker_state=self.breaker.state,
            detail="result cache hit", served_by="cache_hit",
            table_versions=payload.get("table_versions"))
        self.slo.record(request.tenant, out.latency_ms, ok=True)
        self.outcomes.append(out)
        return out

    def _cache_put(self, request: QueryRequest, out: QueryOutcome) -> None:
        """Store one freshly-executed outcome for future content hits —
        only clean primary successes (a degraded or failed answer is
        evidence about THIS attempt, not the content)."""
        if (self.result_cache.max_entries == 0
                or request.delta_tuples_per_node > 0
                or out.status != "ok" or out.degraded
                or out.matches is None):
            return
        self.result_cache.put(
            self._content_fp(request, out.table_versions),
            {"matches": out.matches, "expected": out.expected,
             "engine": out.engine, "table_versions": out.table_versions},
            epoch=self._epoch())

    # ------------------------------------------------------ micro-batch tier
    def _host_lanes(self, request: QueryRequest):
        """Host key lanes + exact expected count for one request's
        workload — the serving fast paths run on key lanes through one
        fused program, not the full distributed pipeline, so generation
        stays on host (data/relation.py's bit-identical numpy path)."""
        from tpu_radix_join.data.relation import host_join_count
        inner, outer, expected = self._relations(request)
        r_keys = inner.fill_np(0, inner.global_size)[0]
        s_keys = outer.fill_np(0, outer.global_size)[0]
        if expected is None:
            expected = host_join_count(r_keys, s_keys)
        return r_keys, s_keys, expected, max(inner.key_bound(),
                                             outer.key_bound())

    def _execute_batched(self, group: List[QueryRequest]
                         ) -> List[QueryOutcome]:
        """Serve ``group`` (>= 2 same-signature queries) through ONE fused
        device program (ops/merge_delta.batched_merge_count): Q dispatch
        floors collapse to one, per-query counts stay exact via the
        composite query tag.  Failure isolation: ANY error inside the
        fused path retries the whole group unbatched, one query at a
        time, so a poisoned query classifies alone and its batch-mates
        still succeed."""
        import numpy as np

        from tpu_radix_join.ops.merge_delta import (batch_feasible,
                                                    compiled_batched_merge_count)
        m = self.measurements
        svc = self.service
        t0 = time.perf_counter()
        try:
            lanes = [self._host_lanes(r) for r in group]
            key_bound = max(kb for _, _, _, kb in lanes)
            if not batch_feasible(len(group), key_bound):
                raise ValueError(
                    f"batch of {len(group)} at key_bound {key_bound} "
                    f"overflows the composite word")
            deadlines = []
            for request in group:
                budget = (request.deadline_s if request.deadline_s is not None
                          else svc.default_deadline_s)
                deadline = Deadline(budget, clock=self._clock)
                deadline.check("admitted")
                deadlines.append(deadline)
            r_sizes = tuple(int(rk.shape[0]) for rk, _, _, _ in lanes)
            s_sizes = tuple(int(sk.shape[0]) for _, sk, _, _ in lanes)
            import jax.numpy as jnp
            fn = compiled_batched_merge_count(r_sizes, s_sizes, key_bound)
            r_cat = jnp.asarray(np.concatenate([rk for rk, _, _, _ in lanes]))
            s_cat = jnp.asarray(np.concatenate([sk for _, sk, _, _ in lanes]))
            for _ in range(max(1, group[0].repeats)):
                counts = fn(r_cat, s_cat)
            counts = np.asarray(counts)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:           # noqa: BLE001 — isolation boundary
            if m is not None:
                m.event("batch_fallback", size=len(group),
                        error=repr(e)[:200])
            return [self._serve_one(r) for r in group]
        latency_ms = (time.perf_counter() - t0) * 1e3
        self.batches_fused += 1
        self.batch_queries_fused += len(group)
        if m is not None:
            m.incr(BATCHN)
            m.incr(BATCHQ, len(group))
        outs = []
        for request, (_, _, expected, _), deadline, n in zip(
                group, lanes, deadlines, counts):
            status, cls, detail = "ok", OK, f"fused batch of {len(group)}"
            try:
                deadline.check("batched")
            except DeadlineExceeded as e:
                status, cls, detail = "failed", DEADLINE_EXCEEDED, str(e)
                if m is not None:
                    m.incr(QDEADLINE)
            out = QueryOutcome(
                query_id=request.query_id, tenant=request.tenant,
                status=status, failure_class=cls, latency_ms=latency_ms,
                matches=int(n), expected=int(expected),
                breaker_state=self.breaker.state, detail=detail,
                served_by="batched")
            self.slo.record(request.tenant, latency_ms,
                            ok=(status == "ok"),
                            failure_class=None if cls == OK else cls)
            self.outcomes.append(out)
            if status == "ok":
                self._cache_put(request, out)
            outs.append(out)
        return outs

    # ------------------------------------------------------ delta-merge tier
    def _delta_keys(self, start: int, count: int, seed: int):
        """The Δ new inner keys appended at mirror length ``start`` —
        fresh keys in [start, start+count), deterministically shuffled,
        disjoint from everything the resident union already holds (the
        base is a unique permutation of [0, N), deltas extend it)."""
        import numpy as np

        from tpu_radix_join.ops.merge_delta import MAX_SERVE_KEY
        if start + count > MAX_SERVE_KEY:
            raise ValueError(
                f"resident union would reach {start + count}, past the "
                f"presorted-probe key ceiling {MAX_SERVE_KEY}")
        keys = np.arange(start, start + count, dtype=np.uint32)
        np.random.default_rng(seed + start).shuffle(keys)
        return keys

    def _execute_delta(self, request: QueryRequest) -> QueryOutcome:
        """Serve one incremental query: sort only the Δ delta lane, merge
        it into the device-resident sorted union, probe — O(N+Δ) instead
        of a full re-sort (served_by="delta_merge").  A cold relation
        (first sight, or evicted under the HBM budget) pays one full sort
        and seeds residency for the next delta (served_by="execute")."""
        import numpy as np

        import jax.numpy as jnp
        from tpu_radix_join.data.relation import host_join_count
        from tpu_radix_join.ops.merge_count import (merge_count_presorted,
                                                    presort_keys)
        from tpu_radix_join.ops.merge_delta import (
            compiled_delta_merge_count, compiled_delta_merge_increment)
        m = self.measurements
        svc = self.service
        t0 = time.perf_counter()
        status, cls, detail, served_by = "ok", OK, "", "execute"
        matches = expected = None
        try:
            budget = (request.deadline_s if request.deadline_s is not None
                      else svc.default_deadline_s)
            deadline = Deadline(budget, clock=self._clock)
            deadline.check("admitted")
            inner, outer, _ = self._relations(request)
            nodes = self.config.num_nodes
            delta_n = request.delta_tuples_per_node * nodes
            rkey = ("delta", inner.global_size, request.seed,
                    request.tuples_per_node)
            epoch = self._epoch()
            rprobe = ("probe", inner.global_size, request.seed,
                      request.tuples_per_node)
            outer_fp = (request.outer_kind, request.modulo,
                        request.zipf_theta, request.repeats,
                        outer.global_size)
            lane = self.resident.get(rkey, epoch)
            mirror = self._resident_host.get(rkey)
            if lane is None and mirror is not None:
                # lane evicted under the byte budget but the host mirror
                # survives: rebuild residency with one full sort (and drop
                # the running probe totals — they describe the grown union)
                mirror = None
                self._resident_host.pop(rkey, None)
                self._resident_probe.pop(rkey, None)
            base_len = len(mirror) if mirror is not None else inner.global_size
            delta_np = self._delta_keys(base_len, delta_n, request.seed)
            s_keys = outer.fill_np(0, outer.global_size)[0]
            deadline.check("generated")
            seed_probe = True
            if lane is None:
                base_np = inner.fill_np(0, inner.global_size)[0]
                mirror = np.concatenate([base_np, delta_np])
                union = presort_keys(jnp.asarray(mirror))
                matches = int(merge_count_presorted(union,
                                                    jnp.asarray(s_keys)))
                expected = host_join_count(mirror, s_keys)
                detail = "cold relation: full sort seeded residency"
            else:
                mirror = np.concatenate([mirror, delta_np])
                probe = self._resident_probe.get(rkey)
                s_lane = self.resident.get(rprobe, epoch)
                if (probe is not None and probe["outer_fp"] == outer_fp
                        and probe["union_len"] == base_len
                        and s_lane is not None):
                    # unchanged outer: probe ONLY the Δ against the
                    # resident sorted outer lane; totals are additive over
                    # the multiset union, so the M·log N full-lane probe
                    # (as costly as the re-sort it replaced) never runs
                    fn = compiled_delta_merge_increment(
                        int(lane.shape[0]), int(delta_np.shape[0]),
                        int(s_lane.shape[0]))
                    union, inc = fn(lane, jnp.asarray(delta_np), s_lane)
                    matches = probe["total"] + int(inc)
                    # host oracle stays independent of the device path:
                    # numpy binary search of the Δ in the HOST-sorted outer
                    ds = np.sort(delta_np)
                    sh = probe["s_sorted_host"]
                    expected = probe["expected"] + int(
                        (np.searchsorted(sh, ds, side="right")
                         - np.searchsorted(sh, ds, side="left")).sum())
                    seed_probe = False
                    detail = ("incremental probe: Δ counted against the "
                              "resident sorted outer lane")
                else:
                    fn = compiled_delta_merge_count(int(lane.shape[0]),
                                                    int(delta_np.shape[0]),
                                                    int(s_keys.shape[0]))
                    union, total = fn(lane, jnp.asarray(delta_np),
                                      jnp.asarray(s_keys))
                    matches = int(total)
                    expected = host_join_count(mirror, s_keys)
                self.resident.note_merge(rkey)
                served_by = "delta_merge"
                if m is not None:
                    m.incr(DELTAMERGE)
            deadline.check("merged")
            self.resident.put(rkey, union, epoch)
            self._resident_host[rkey] = mirror
            if seed_probe and self.resident.budget_bytes:
                # (re)seed the incremental-probe state under the same HBM
                # budget; when the outer lane is not admitted (budget too
                # tight) the next query simply pays the full probe.  With
                # residency disabled entirely (budget 0) we must not even
                # sort the outer here — that would tax the full-re-sort
                # baseline with work only the resident tier can use
                s_lane = presort_keys(jnp.asarray(s_keys))
                if self.resident.put(rprobe, s_lane, epoch):
                    self._resident_probe[rkey] = {
                        "outer_fp": outer_fp, "union_len": len(mirror),
                        "total": matches, "expected": expected,
                        "s_sorted_host": np.sort(s_keys)}
                else:
                    self._resident_probe.pop(rkey, None)
            elif not seed_probe:
                probe["union_len"] = len(mirror)
                probe["total"] = matches
                probe["expected"] = expected
        except DeadlineExceeded as e:
            status, cls, detail = "failed", DEADLINE_EXCEEDED, str(e)
            if m is not None:
                m.incr(QDEADLINE)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:           # noqa: BLE001 — isolation boundary
            status = "failed"
            cls = getattr(e, "failure_class", None) or UNCLASSIFIED
            detail = repr(e)[:500]
            if m is not None:
                m.event("query_failed", query_id=request.query_id,
                        failure_class=cls, error=repr(e)[:200])
        latency_ms = (time.perf_counter() - t0) * 1e3
        if m is not None and served_by == "execute":
            m.incr(QEXEC)
        out = QueryOutcome(
            query_id=request.query_id, tenant=request.tenant,
            status=status, failure_class=cls, latency_ms=latency_ms,
            matches=matches, expected=expected,
            breaker_state=self.breaker.state, detail=detail,
            served_by=served_by)
        self.slo.record(request.tenant, latency_ms, ok=(status == "ok"),
                        failure_class=None if cls == OK else cls)
        self.outcomes.append(out)
        return out

    # ---------------------------------------------------- registered tables
    def _next_version(self) -> int:
        self._version += 1
        return self._version

    def _timed(self, tag: str):
        m = self.measurements
        return m.timed(tag) if m is not None else contextlib.nullcontext()

    def register_table(self, name: str, batch) -> int:
        """Hold ``batch`` (a ``TupleBatch``: key and rid lanes, on the host
        or the device) on the engine's mesh under ``name``, and return its
        version.  The session owns the lanes from here on: no query
        donates or consumes them, and :meth:`update_table` rewrites the
        key lane in place.  Registering a name again replaces its table
        under a new version."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpu_radix_join.data.tuples import TupleBatch
        if self._closed:
            raise RuntimeError("session is closed")
        if batch.size % self.config.num_nodes:
            raise ValueError(f"table {name!r} has {batch.size} rows, not a "
                             f"multiple of {self.config.num_nodes} nodes")
        sharding = NamedSharding(self.engine.mesh, P(self.config.mesh_axes))
        placed = TupleBatch(*(None if lane is None
                              else jax.device_put(lane, sharding)
                              for lane in batch))
        self._forget_placed(name)
        self._tables[name] = _Table(placed, self._next_version())
        return self._tables[name].version

    def table_version(self, name: str) -> int:
        return self._table(name).version

    def tables(self) -> Dict[str, dict]:
        """{name: {"version", "rows", "bytes"}} of every registered table:
        the heartbeat's and the summary's view."""
        return {name: {"version": t.version, "rows": int(t.batch.size),
                       "bytes": sum(int(lane.nbytes) for lane in t.batch
                                    if lane is not None)}
                for name, t in sorted(self._tables.items())}

    def update_table(self, name: str, positions, keys) -> Tuple[int, object]:
        """Rewrite table ``name``'s key lane in place: ``keys`` go to
        ``positions`` (distinct row numbers; one at or past the end is
        skipped), and the table takes a new version.  Returns ``(version,
        previous)``: the new version and, as a device array, the keys the
        positions held before (0 where skipped), which a later update can
        put back.  The update is dispatched, not waited for: queries that
        follow read it in device order."""
        import numpy as np

        table = self._table(name)
        positions = np.asarray(positions, np.int32)
        inside = positions[positions < table.batch.size]
        if (positions < 0).any() or np.unique(inside).size != inside.size:
            raise ValueError("update positions must be distinct rows")
        m = self.measurements
        with self._timed(QUPDATE):
            key, previous = _key_rewriter()(table.batch.key, positions,
                                            keys)
            self._forget_placed(name)
            table.batch = table.batch._replace(key=key)
            table.version = self._next_version()
        if m is not None:
            m.incr(QUPDATEN)
        return table.version, previous

    def _table(self, name: str) -> _Table:
        table = self._tables.get(name)
        if table is None:
            raise UnknownTable(f"no table named {name!r} is registered")
        return table

    def _forget_placed(self, name: str) -> None:
        """Drop every placed-relation entry of table ``name``: each names
        a version that is about to be superseded."""
        for key in [k for k in self._place_cache
                    if k[1:3] == ("table", name)]:
            del self._place_cache[key]

    def _resolve_tables(self, engine, request: QueryRequest):
        """(inner batch, outer batch, {name: version}) of a table query,
        placed for ``engine`` through the placed-relation LRU under
        (engine, "table", name, version)."""
        m = self.measurements
        floor = self._admitted.get(id(request), {})
        batches, versions = [], {}
        with self._timed(QTABLE):
            for name in (request.inner, request.outer):
                table = self._table(name)
                if table.version < floor.get(name, 0):
                    if m is not None:
                        m.incr(QSTALE)
                    raise StaleTable(
                        f"table {name!r} is at version {table.version}, "
                        f"the query was admitted under {floor[name]}")
                key = (id(engine), "table", name, table.version)
                batch = self._place_cache.pop(key, None)
                if batch is None:
                    batch = (table.batch if engine is self.engine
                             else _on_mesh(engine, table.batch))
                self._place_cache[key] = batch
                while len(self._place_cache) > self.service.place_cache_max:
                    self._place_cache.popitem(last=False)
                batches.append(batch)
                versions[name] = table.version
                if m is not None:
                    m.incr(QTABLEHIT)
        return batches[0], batches[1], versions

    # ------------------------------------------------------------ internals
    def _wire_elastic(self, engine) -> None:
        """Attach the session's elastic-recovery services to an engine
        (primary at construction, CPU fallback on first build) — both
        must agree on membership so a rank loss observed on either path
        fences the same epoch."""
        engine.membership = self.membership
        engine.elastic = self.elastic
        engine.partition_manifest = self.partition_manifest
        engine.elastic_grow = self.elastic_grow
        engine.hedge = self.hedge
        engine.hedge_threshold = self.hedge_threshold

    def _degraded_engine(self):
        """The CPU fallback engine, built once on first use (the breaker's
        open-state serving path — robustness/degrade.py's construction
        recipe, reused here for query-time degradation)."""
        if self._cpu_engine is None:
            from tpu_radix_join.robustness.degrade import build_cpu_engine
            self._cpu_engine, info = build_cpu_engine(
                self.config, measurements=self.measurements,
                plan_cache=self.plan_cache)
            self._wire_elastic(self._cpu_engine)
            m = self.measurements
            if m is not None:
                m.event("degrade", to="cpu", num_nodes=info["num_nodes"],
                        reason="breaker_open")
        return self._cpu_engine

    def _relations(self, request: QueryRequest):
        """(inner, outer, expected) for the request's workload — the same
        construction main.py's one-shot driver uses, sized by the
        *session* config so primary and degraded engines agree on the
        global shape."""
        from tpu_radix_join.data.relation import Relation

        nodes = self.config.num_nodes
        global_size = request.tuples_per_node * nodes
        inner = Relation(global_size, nodes, "unique", seed=request.seed)
        outer_kw = {}
        if request.outer_kind == "modulo":
            outer_kw["modulo"] = request.modulo or max(1, global_size // 4)
        elif request.outer_kind == "zipf":
            outer_kw["zipf_theta"] = request.zipf_theta
            outer_kw["key_domain"] = global_size
        outer = Relation(global_size, nodes, request.outer_kind,
                         seed=request.seed + 1, **outer_kw)
        return inner, outer, inner.expected_matches(outer)

    def _place(self, engine, rel, tag: str, request: QueryRequest):
        """Placed-batch LRU: a resident session re-serving the same
        workload skips generation + host->device transfer."""
        key = (id(engine), tag, rel.global_size, rel.kind, request.seed,
               request.outer_kind, request.modulo, request.zipf_theta)
        if key in self._place_cache:
            self._place_cache.move_to_end(key)
            return self._place_cache[key]
        batch = engine.place(rel)
        self._place_cache[key] = batch
        while len(self._place_cache) > self.service.place_cache_max:
            self._place_cache.popitem(last=False)
        return batch

    def placed_bytes(self) -> int:
        """Device bytes held by the placed-relation LRU and the registered
        tables (key + rid + wide lanes, each lane once) — the
        heartbeat/statusz gauge that makes ``place_cache_max`` and the
        tables observable."""
        lanes = {}
        for batch in [*self._place_cache.values(),
                      *(t.batch for t in self._tables.values())]:
            for lane in batch:
                if lane is not None and hasattr(lane, "nbytes"):
                    lanes[id(lane)] = int(lane.nbytes)
        return sum(lanes.values())

    def _execute(self, request: QueryRequest) -> QueryOutcome:
        m = self.measurements
        svc = self.service
        budget = (request.deadline_s if request.deadline_s is not None
                  else svc.default_deadline_s)
        deadline = Deadline(budget, clock=self._clock)
        primary = self.breaker.allow_primary()
        probing = primary and self.breaker.state == HALF_OPEN
        # open breaker without CPU degrade: the query fails as
        # backend_unavailable and nothing answers it on the host
        degraded = not primary and svc.cpu_fallback
        engine = self._degraded_engine() if degraded else self.engine
        tracer = m.tracer if m is not None else None
        win0_us = tracer.now_us() if tracer is not None else None
        t0 = time.perf_counter()
        jhist0 = m.times_us.get(JHIST, 0.0) if m is not None else 0.0
        nc0 = m.counters.get(NCOMPILE, 0) if m is not None else 0
        completed_before = self.slo.completed
        span = (m.span("query", query_id=request.query_id,
                       tenant=request.tenant,
                       engine="cpu_fallback" if degraded else "primary",
                       probe=probing)
                if m is not None else _null_ctx())
        engine.cancel = deadline.check
        if m is not None:
            # every ring record and counter delta inside this query carries
            # the query_id: a bundle cut mid-serve attributes its evidence
            m.flightrec.set_context(query_id=request.query_id,
                                    tenant=request.tenant)
        status, cls, detail = "ok", OK, ""
        matches = expected = versions = None
        try:
            with span:
                if not primary and not degraded:
                    raise BackendUnavailable(
                        "circuit breaker open and CPU degrade is off "
                        "(--cpu-fallback)")
                if primary and _faults.fires(_faults.BACKEND_DISPATCH, m):
                    # injectable per-query backend outage (chaos / tests):
                    # the production twin is the except-clause mapping of
                    # raw connection errors below
                    raise BackendUnavailable(
                        f"injected backend outage (query "
                        f"{request.query_id})")
                deadline.check("admitted")
                if request.inner is not None:
                    r_batch, s_batch, versions = self._resolve_tables(
                        engine, request)
                else:
                    inner, outer, expected = self._relations(request)
                    deadline.check("generated")
                    r_batch = self._place(engine, inner, "r", request)
                    s_batch = self._place(engine, outer, "s", request)
                deadline.check("placed")
                result = engine.join_arrays(r_batch, s_batch,
                                            repeats=request.repeats)
                matches = result.matches
                cls = (result.diagnostics or {}).get(
                    "failure_class") or (OK if result.ok else UNCLASSIFIED)
                status = "ok" if result.ok else "failed"
                if (result.diagnostics or {}).get("recovered"):
                    # a mid-query rank loss was absorbed by the elastic
                    # path: the outcome is ok with the exact count, but
                    # the mesh change is first-class evidence
                    if m is not None:
                        m.event("query_recovered",
                                query_id=request.query_id,
                                epoch=result.diagnostics.get(
                                    "membership_epoch"),
                                lost_ranks=result.diagnostics.get(
                                    "lost_ranks"))
                    detail = ("recovered from rank loss: "
                              + str(result.diagnostics.get(
                                    "lost_ranks")))[:500]
                if status == "failed":
                    detail = str({k: v for k, v in
                                  (result.diagnostics or {}).items()
                                  if k != "failure_class"})[:500]
        except DeadlineExceeded as e:
            status, cls, detail = "failed", DEADLINE_EXCEEDED, str(e)
            if m is not None:
                m.incr(QDEADLINE)
        except (KeyboardInterrupt, SystemExit):
            raise                        # the operator's kill stays a kill
        except Exception as e:           # noqa: BLE001 — isolation boundary
            status = "failed"
            cls = getattr(e, "failure_class", None)
            if cls is None and isinstance(
                    e, (ConnectionError, TimeoutError, OSError)):
                # a raw transport error from a dead backend is the
                # production form of backend_unavailable
                cls = BACKEND_UNAVAILABLE
            if cls is None:
                cls = UNCLASSIFIED
            detail = repr(e)[:500]
            if m is not None:
                m.event("query_failed", query_id=request.query_id,
                        failure_class=cls, error=repr(e)[:200])
        finally:
            engine.cancel = None
        # the session's finish, QFINISH: outcome and accounting (every
        # step below that can fail is isolated, so the timer closes)
        if m is not None:
            m.start(QFINISH)
        latency_ms = (time.perf_counter() - t0) * 1e3
        trips0 = self.breaker.trips
        # warm = the sizing pre-pass did not run this query (plan-cache /
        # hot-layer capacity hit): the observable the acceptance criteria
        # gate on, measured from the JHIST column's delta
        warm = (status == "ok" and m is not None
                and m.times_us.get(JHIST, 0.0) == jhist0
                and self.slo.completed > 0)
        if m is not None:
            if warm:
                m.incr(QWARM)
            if degraded:
                m.incr(QDEGRADED)
            m.incr(QEXEC)
        if primary:
            if cls == OK:
                self.breaker.record_success()
            else:
                self.breaker.record_failure(cls)
        bundle = None
        if status == "failed" and self.forensics_dir:
            reason = ("breaker_trip" if self.breaker.trips > trips0
                      else ("deadline_exceeded" if cls == DEADLINE_EXCEEDED
                            else "query_failed"))
            bundle = self._write_bundle(request, reason, cls, detail)
        # recompile-storm canary: NCOMPILE rising after the session has
        # completed queries means XLA is recompiling warm shapes — the
        # amortization win a resident session exists for is leaking
        nc_delta = (m.counters.get(NCOMPILE, 0) - nc0) if m is not None else 0
        if nc_delta and completed_before > 0:
            self._recompile_storms += 1
            if m is not None:
                m.event("recompile_storm", query_id=request.query_id,
                        ncompile_delta=nc_delta,
                        completed=completed_before)
            if self._recompile_storms <= 3:      # warn loudly, don't spam
                print(f"[OBS] recompile storm: query {request.query_id} "
                      f"triggered {nc_delta} backend compile(s) after "
                      f"{completed_before} completed queries",
                      file=sys.stderr)
        if m is not None:
            m.flightrec.clear_context("query_id", "tenant")
        out = QueryOutcome(
            query_id=request.query_id, tenant=request.tenant,
            status=status, failure_class=cls, latency_ms=latency_ms,
            matches=matches, expected=expected,
            engine="cpu_fallback" if degraded else "primary",
            degraded=degraded, warm=warm,
            breaker_state=self.breaker.state, detail=detail,
            bundle=bundle, table_versions=versions)
        self.slo.record(request.tenant, latency_ms, ok=(status == "ok"),
                        failure_class=None if cls == OK else cls,
                        degraded=degraded)
        self.outcomes.append(out)
        if tracer is not None:
            # per-query critical path: slice this query's window out of
            # the resident tracer stream so each query gets its own
            # attribution (read by /statusz; a path failure is evidence,
            # never a new failure for the query)
            try:
                from tpu_radix_join.observability.critpath import (
                    critical_path_from_tracer)
                cp = critical_path_from_tracer(
                    tracer, window_us=(win0_us, tracer.now_us()))
                cp["query_id"] = request.query_id
                self.recent_critical_paths.append(cp)
            except Exception as e:   # noqa: BLE001 — isolation boundary
                m.event("critpath_error", error=repr(e)[:200])
        if self.ledger is not None:
            # one ledger row per executed query; a ledger write failure is
            # an event, never a new failure for the query
            try:
                self.ledger.append("query", {
                    "query_id": request.query_id, "tenant": request.tenant,
                    "trace_id": (m.meta.get("trace_id")
                                 if m is not None else None),
                    "status": status, "failure_class": cls,
                    "latency_ms": round(latency_ms, 3),
                    "warm": warm, "engine": out.engine,
                    "tuples_per_node": request.tuples_per_node,
                    "repeats": request.repeats,
                    "ncompile": nc_delta or None})
            except Exception as e:   # noqa: BLE001 — isolation boundary
                if m is not None:
                    m.event("ledger_error", error=repr(e)[:200])
        if m is not None:
            m.stop(QFINISH)
        return out

    def _write_bundle(self, request: QueryRequest, reason: str,
                      cls: str, detail: str) -> Optional[str]:
        """Forensics bundle for one failed query.  Must never escalate:
        a bundle-write error is an event on the registry, not a new
        failure for the query (the isolation boundary stays sealed)."""
        try:
            from tpu_radix_join.observability.postmortem import write_bundle
            return write_bundle(
                self.forensics_dir, self.measurements, reason=reason,
                failure_class=cls, config=self.config,
                extra={"query_id": request.query_id,
                       "tenant": request.tenant,
                       "breaker_state": self.breaker.state,
                       "detail": detail})
        except Exception as e:     # noqa: BLE001 — forensics must not mask
            if self.measurements is not None:
                self.measurements.event("bundle_error", error=repr(e)[:200])
            return None

    # ----------------------------------------------------------- lifecycle
    def attach_heartbeat(self, path: str, interval_s: float):
        """Start a metrics heartbeat owned by this session (stopped by
        :meth:`close`): every tick carries the SLO snapshot next to the
        counter registry, so ``tail -f`` shows live percentiles."""
        from tpu_radix_join.observability import MetricsSampler
        self._sampler = MetricsSampler(path, interval_s,
                                       measurements=self.measurements,
                                       extra=self._heartbeat_extra)
        self._sampler.start()
        return self._sampler

    def fastpath_stats(self) -> dict:
        """Live fast-path state for ``/statusz``: result-cache hit rates,
        residency bytes, and fused-batch totals (the serve loop's
        MicroBatcher contributes window occupancy on top)."""
        return {"cache": self.result_cache.stats(),
                "resident": self.resident.stats(),
                "batch": {"fused_batches": self.batches_fused,
                          "fused_queries": self.batch_queries_fused},
                "placed_bytes": self.placed_bytes(),
                "place_cache_entries": len(self._place_cache),
                "place_cache_max": self.service.place_cache_max,
                "tables": self.tables()}

    def _heartbeat_extra(self) -> dict:
        out = {"slo": self.slo.snapshot(),
               "breaker": self.breaker.snapshot(),
               "queue_depth": self.queue.depth(),
               "placed_bytes": self.placed_bytes()}
        if self._tables:
            out["tables"] = self.tables()
        if self.result_cache.max_entries:
            out["result_cache"] = self.result_cache.stats()
        if self.resident.budget_bytes:
            out["resident"] = self.resident.stats()
        if self.membership is not None:
            out["membership"] = {"epoch": self.membership.epoch,
                                 "lost": sorted(self.membership.lost),
                                 "survivors": self.membership.survivors}
        return out

    def summary(self) -> dict:
        """Final serve report: SLO tags + breaker/queue/cache state."""
        out = self.slo.snapshot()
        out.update(breaker_state=self.breaker.state,
                   breaker_trips=self.breaker.trips,
                   breaker_probes=self.breaker.probes,
                   queue_rejected=self.queue.rejected,
                   placed_bytes=self.placed_bytes(),
                   tenant_queries=self.slo.counts())
        if self._tables:
            out["tables"] = self.tables()
        if self.result_cache.max_entries:
            cache = self.result_cache.stats()
            out["cache_hits"] = cache["hits"]
            out["cache_hit_rate"] = cache["hit_rate"]
        if self.batches_fused:
            out["fused_batches"] = self.batches_fused
            out["fused_queries"] = self.batch_queries_fused
        if self.resident.budget_bytes:
            res = self.resident.stats()
            out["resident_bytes"] = res["resident_bytes"]
            out["delta_merges"] = res["merges"]
        m = self.measurements
        if m is not None:
            out["warm_queries"] = int(m.counters.get(QWARM, 0))
            out["degraded_queries"] = int(m.counters.get(QDEGRADED, 0))
            out["ncompile"] = int(m.counters.get(NCOMPILE, 0))
            out["compile_ms"] = int(m.counters.get(COMPILEMS, 0))
            out["recompile_storms"] = self._recompile_storms
            counter = m.counters.get
            out["served_by"] = {"execute": int(counter(QEXEC, 0)),
                                "cache_hit": int(counter(RCHIT, 0)),
                                "batched": int(counter(BATCHQ, 0)),
                                "delta_merge": int(counter(DELTAMERGE, 0))}
            out["table_hits"] = int(counter(QTABLEHIT, 0))
            out["table_updates"] = int(counter(QUPDATEN, 0))
            out["stale_rejections"] = int(counter(QSTALE, 0))
            if m.counters.get(RANKLOST):
                out["ranks_lost"] = int(m.counters.get(RANKLOST, 0))
                out["membership_epoch"] = int(m.counters.get(MEPOCH, 0))
                out["recovered_partitions"] = int(m.counters.get(RECOVERN, 0))
                out["recover_ms"] = int(m.counters.get(RECOVERMS, 0))
        return out

    def close(self) -> None:
        """Release everything the session owns: the heartbeat sampler
        thread, placed-batch device references, and the engines' compile
        caches.  Idempotent; the session refuses new submissions after."""
        if self._closed:
            return
        self._closed = True
        if self._sampler is not None:
            self._sampler.stop()
            self._sampler = None
        self._place_cache.clear()
        self._tables.clear()
        self._admitted.clear()
        self.result_cache.invalidate()
        self.resident.invalidate()
        self._resident_host.clear()
        self._resident_probe.clear()
        for eng in (self.engine, self._cpu_engine):
            if eng is not None:
                eng._compiled.clear()
        self._cpu_engine = None
        if self._cache_tmp is not None:
            self._cache_tmp.cleanup()
            self._cache_tmp = None

    def __enter__(self) -> "JoinSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _null_ctx():
    return contextlib.nullcontext()


@functools.cache
def _key_rewriter():
    """A jitted ``rewrite(key, pos, new) -> (key', previous)`` that writes
    ``new`` into ``key`` at ``pos`` in place (``key`` is donated) and
    returns what was there; a position past the end is skipped."""
    import jax
    import jax.numpy as jnp

    def rewrite(key, pos, new):
        previous = key.at[pos].get(mode="fill", fill_value=0)
        return (key.at[pos].set(jnp.asarray(new, key.dtype), mode="drop"),
                previous)

    return jax.jit(rewrite, donate_argnums=0)


def _on_mesh(engine, batch):
    """``batch``'s lanes laid out over ``engine``'s mesh (a copy when the
    engine is not the one the table was registered on)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(engine.mesh, P(engine.config.mesh_axes))
    return type(batch)(*(None if lane is None
                         else jax.device_put(lane, sharding)
                         for lane in batch))


def _as_list(out: Optional[QueryOutcome]) -> List[QueryOutcome]:
    return [out] if out is not None else []

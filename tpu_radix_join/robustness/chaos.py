"""Seeded chaos/soak harness with shrinking fault-schedule repros.

The soak invariant this module enforces end to end: **every join run,
under any schedule of injected faults, either passes verification or
terminates with a classified failure** (``diagnostics["failure_class"]``
or an exception carrying one).  A run that returns ``ok=True`` with a
wrong count, or dies with an unclassified exception, is a VIOLATION —
the silent-corruption outcome the integrity checksums
(robustness/verify.py) exist to rule out.

Pieces:

  * :func:`generate_schedule` — a seeded schedule of fault arms drawn from
    the :data:`CHAOS_SITES` subset of :data:`faults.SITES` (the sites the
    array-join path actually consults; arming the grid/checkpoint sites
    here would just warn and never fire).
  * :class:`ChaosRunner` — executes one schedule against a cached engine
    on known-oracle inputs and classifies the outcome
    (``pass`` | ``classified`` | ``violation``).
  * :func:`soak` — N seeded runs; returns outcomes plus a summary the
    callers (bench.py ``--chaos``, tools_chaos.py, tests/test_chaos.py)
    assert the invariant over.
  * :func:`shrink` — greedy delta-debugging of a violating schedule down
    to a minimal still-violating arm set; :func:`write_repro` persists the
    ``(seed, arms)`` pair that replays it deterministically.

Engine-heavy: import lazily (the robustness/__init__ discipline for
degrade.py), e.g. ``from tpu_radix_join.robustness import chaos``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from tpu_radix_join.robustness import faults
from tpu_radix_join.robustness.retry import DEVICE_UNAVAILABLE

#: sites the ``join_arrays`` path consults, i.e. the arms that can fire in
#: a soak run (faults.SITES minus the grid/checkpoint/stream/coordinator
#: vocabulary, which only the out-of-core and multihost paths hit)
CHAOS_SITES: Tuple[str, ...] = (
    faults.SHUFFLE_OVERFLOW,
    faults.DEVICE_INIT,
    faults.EXCHANGE_CORRUPT,
)

#: failure class carried by an :class:`faults.InjectedFault` raised at a
#: site (exceptions from *corrupting* sites instead surface through the
#: engine's own classification)
_SITE_CLASSES = {faults.DEVICE_INIT: DEVICE_UNAVAILABLE}

PASS = "pass"
CLASSIFIED = "classified"
VIOLATION = "violation"


def _violation_bundle(m, schedule: "Schedule", detail: str,
                      bundle_dir: Optional[str]) -> Optional[str]:
    """Forensics bundle for a soak VIOLATION: the run's registry + ring
    plus the violating ``(seed, arms)`` schedule.  Never escalates — a
    bundle-write error must not turn the harness's verdict into a crash."""
    if not bundle_dir:
        return None
    try:
        from tpu_radix_join.observability.postmortem import write_bundle
        return write_bundle(bundle_dir, m, reason="chaos_violation",
                            failure_class=None, chaos=schedule,
                            extra={"detail": detail})
    except Exception:           # noqa: BLE001 — forensics must not mask
        return None


@dataclasses.dataclass(frozen=True)
class Schedule:
    """A replayable fault schedule: the injector seed plus the armed
    ``(site, arm-kwargs)`` pairs.  Determinism is inherited from
    :class:`faults.FaultInjector` (per-site ``random.Random(seed:site)``),
    so ``(seed, arms)`` IS the repro."""

    seed: int
    arms: Tuple[Tuple[str, Tuple[Tuple[str, int], ...]], ...]

    def arm_dicts(self) -> List[Tuple[str, Dict[str, int]]]:
        return [(site, dict(kw)) for site, kw in self.arms]

    def to_json(self) -> Dict[str, Any]:
        return {"seed": self.seed,
                "arms": [[site, dict(kw)] for site, kw in self.arms]}

    @classmethod
    def from_json(cls, obj: Dict[str, Any]) -> "Schedule":
        return cls(seed=int(obj["seed"]),
                   arms=tuple((str(site),
                               tuple(sorted((str(k), int(v))
                                            for k, v in kw.items())))
                              for site, kw in obj["arms"]))

    def without(self, index: int) -> "Schedule":
        return dataclasses.replace(
            self, arms=self.arms[:index] + self.arms[index + 1:])


@dataclasses.dataclass(frozen=True)
class RunOutcome:
    schedule: Schedule
    status: str                       # PASS | CLASSIFIED | VIOLATION
    failure_class: Optional[str]      # set when CLASSIFIED
    matches: Optional[int]            # set when the join returned
    detail: str = ""
    bundle: Optional[str] = None      # forensics bundle path (violations)

    def to_json(self) -> Dict[str, Any]:
        out = {"schedule": self.schedule.to_json(), "status": self.status,
               "failure_class": self.failure_class,
               "matches": self.matches, "detail": self.detail}
        if self.bundle:
            # the repro artifact names the evidence next to the (seed,
            # arms) pair; absent for non-violating runs (shape stable)
            out["bundle"] = self.bundle
        return out


def generate_schedule(seed: int) -> Schedule:
    """1-3 distinct arms over :data:`CHAOS_SITES`, fully determined by
    ``seed``.  The corruption and device-init sites are consulted once per
    run, so their arm is always ``at=1``; the shuffle-overflow site is
    consulted once per retry attempt, so its hit index varies — ``at=2``
    exercises injection into an already-retried attempt."""
    rng = random.Random(seed)
    sites = rng.sample(CHAOS_SITES, rng.randint(1, len(CHAOS_SITES)))
    arms = []
    for site in sites:
        at = rng.randint(1, 2) if site == faults.SHUFFLE_OVERFLOW else 1
        arms.append((site, (("at", at),)))
    return Schedule(seed=seed, arms=tuple(arms))


class ChaosRunner:
    """Executes fault schedules against one cached engine.

    The engine, its mesh, and its compile cache are built once and reused
    across the soak (per-run construction would recompile the pipeline
    every time); the ``engine.device_init`` site — which in production
    fires in the constructor — is therefore consulted explicitly at the
    top of each run, modeling a fresh bring-up per schedule.

    Inputs are oracle-friendly by construction: R's keys are a permutation
    of 1..n (unique, covering) and S's are uniform over 1..n, so every
    outer tuple matches exactly one inner tuple and the true count is
    exactly ``n`` — any bit of injected corruption moves the count off the
    oracle, making silent wrong answers detectable without a second join.
    """

    def __init__(self, num_nodes: int = 4, size: int = 1 << 12,
                 verify: str = "check", data_seed: int = 0,
                 config_overrides: Optional[Dict[str, Any]] = None,
                 bundle_dir: Optional[str] = None):
        from tpu_radix_join.core.config import JoinConfig
        from tpu_radix_join.operators.hash_join import HashJoin
        from tpu_radix_join.performance.measurements import Measurements
        self._measurements_cls = Measurements
        self.bundle_dir = bundle_dir
        self.oracle = size
        rng = np.random.default_rng(data_seed)
        self._rk = (rng.permutation(size) + 1).astype(np.uint32)
        self._sk = rng.integers(1, size + 1, size=size).astype(np.uint32)
        self._rid = np.arange(size, dtype=np.uint32)
        cfg = JoinConfig(num_nodes=num_nodes, verify=verify,
                         **(config_overrides or {}))
        self.config = cfg
        self.engine = HashJoin(cfg)
        self.measurements: List[Any] = []   # one registry per run, in order

    def _batches(self):
        import jax.numpy as jnp
        from tpu_radix_join.data.tuples import TupleBatch
        # fresh uncommitted arrays per run: the exchange-corruption site
        # mutates its input host-side, and a shared committed batch would
        # leak one run's damage into the next
        return (TupleBatch(key=jnp.asarray(self._rk),
                           rid=jnp.asarray(self._rid), key_hi=None),
                TupleBatch(key=jnp.asarray(self._sk),
                           rid=jnp.asarray(self._rid), key_hi=None))

    def run(self, schedule: Schedule) -> RunOutcome:
        out = self._run(schedule)
        if out.status == VIOLATION:
            out = dataclasses.replace(out, bundle=_violation_bundle(
                self.measurements[-1], schedule, out.detail,
                self.bundle_dir))
        return out

    def _bind(self, m) -> None:
        """Per-run registry hook: the base runner's engine records no
        counters (matching production one-shot runs where the registry
        outlives the engine); :class:`RecoveryChaosRunner` overrides to
        point the cached engine at this run's registry so RANKLOST /
        RECOVERN / MEPOCH land where the soak can read them."""

    def _run(self, schedule: Schedule) -> RunOutcome:
        m = self._measurements_cls()
        self.measurements.append(m)
        self._bind(m)
        inj = faults.FaultInjector(seed=schedule.seed, measurements=m)
        for site, kw in schedule.arm_dicts():
            inj.arm(site, **kw)
        try:
            with inj:
                # the constructor-time site, consulted per run because the
                # engine is cached (see class docstring)
                faults.check(faults.DEVICE_INIT, m)
                result = self.engine.join_arrays(*self._batches())
        except faults.InjectedFault as e:
            # the exception's own class wins (TransientFault carries
            # backend_unavailable); the site table covers the bare
            # InjectedFault sites
            cls = getattr(e, "failure_class", None) or _SITE_CLASSES.get(
                e.site)
            if cls is None:
                return RunOutcome(schedule, VIOLATION, None, None,
                                  f"unclassified injected fault: {e!r}")
            return RunOutcome(schedule, CLASSIFIED, cls, None, repr(e))
        except Exception as e:
            cls = getattr(e, "failure_class", None)
            if cls is None:
                return RunOutcome(schedule, VIOLATION, None, None,
                                  f"unclassified exception: {e!r}")
            return RunOutcome(schedule, CLASSIFIED, cls, None, repr(e))
        if result.ok:
            if result.matches != self.oracle:
                return RunOutcome(
                    schedule, VIOLATION, None, result.matches,
                    f"silent wrong count: {result.matches} != oracle "
                    f"{self.oracle}")
            return RunOutcome(schedule, PASS, None, result.matches)
        cls = (result.diagnostics or {}).get("failure_class")
        if not cls or cls == "ok":
            return RunOutcome(schedule, VIOLATION, cls, result.matches,
                              "ok=False without a failure class")
        return RunOutcome(schedule, CLASSIFIED, cls, result.matches)


def soak(runs: int, base_seed: int = 0, runner: Optional[ChaosRunner] = None,
         verify: str = "check",
         on_outcome: Optional[Callable[[RunOutcome], None]] = None):
    """N seeded schedules (seeds ``base_seed .. base_seed+runs-1``) through
    one runner.  Returns ``(outcomes, summary)``; asserting the no-violation
    invariant is the caller's job (tests want to assert it, the violation
    demo wants to harvest them)."""
    runner = runner or ChaosRunner(verify=verify)
    outcomes = []
    for i in range(runs):
        out = runner.run(generate_schedule(base_seed + i))
        outcomes.append(out)
        if on_outcome:
            on_outcome(out)
    summary = {
        "runs": runs,
        "base_seed": base_seed,
        "verify": runner.config.verify,
        "pass": sum(o.status == PASS for o in outcomes),
        "classified": sum(o.status == CLASSIFIED for o in outcomes),
        "violations": sum(o.status == VIOLATION for o in outcomes),
        "failure_classes": sorted({o.failure_class for o in outcomes
                                   if o.failure_class}),
    }
    return outcomes, summary


#: the elastic-recovery soak vocabulary: every array-path site PLUS the
#: membership sites — rank death and rank join (both consulted at every
#: ``_check_cancel`` phase boundary — hit 1 is "start", 2 is "sized", 3+
#: are the per-attempt "probe" boundaries, so a seeded hit index IS a
#: seeded phase boundary) — and the compute-straggle site (consulted
#: once per attempt, inside the pipeline)
RECOVERY_SITES: Tuple[str, ...] = CHAOS_SITES + (
    faults.RANK_DEATH, faults.RANK_JOIN, faults.COMPUTE_STRAGGLE)


def generate_recovery_schedule(seed: int) -> Schedule:
    """Always one ``membership.rank_death`` arm at a seeded phase
    boundary (``at`` in 1..3 — start/sized/probe), plus 0-2 arms from
    :data:`CHAOS_SITES` so rank loss composes with the faults it can
    race (a corruption before the death, an overflow retry around it).

    The membership interleavings ride the same seed: roughly half the
    schedules also arm ``membership.rank_join`` at its own seeded
    boundary (join-during-recovery when the admission lands around the
    death's boundary), and roughly half arm ``compute.straggle``
    (straggle-then-die: a live-but-slow rank races the death — whichever
    site's boundary fires first owns the abort, and the invariant is the
    same either way: oracle-exact or classified, never a double count)."""
    rng = random.Random(seed)
    arms = [(faults.RANK_DEATH, (("at", rng.randint(1, 3)),))]
    if rng.random() < 0.5:
        arms.append((faults.RANK_JOIN, (("at", rng.randint(1, 3)),)))
    if rng.random() < 0.5:
        arms.append((faults.COMPUTE_STRAGGLE, (("at", 1),)))
    for site in rng.sample(CHAOS_SITES, rng.randint(0, 2)):
        at = rng.randint(1, 2) if site == faults.SHUFFLE_OVERFLOW else 1
        arms.append((site, (("at", at),)))
    return Schedule(seed=seed, arms=tuple(arms))


class RecoveryChaosRunner(ChaosRunner):
    """:class:`ChaosRunner` with the elastic path armed.

    The cached engine runs with ``elastic=True``: a fired
    ``membership.rank_death`` must end in the exact oracle count
    (recovered, PASS) — never a hang, never an overclaim; any escaping
    rank loss still classifies as ``rank_lost``.  The default geometry
    shrinks to 8 network partitions (``network_fanout_bits=3``): each
    recovered partition is its own masked out-of-core join, and partition
    count is the knob that bounds the soak's recompute wall.

    The growth/hedging sites get real state per run (:meth:`_bind`): a
    fresh single-process membership view (so ``membership.rank_join``
    admissions land in a clean epoch sequence) with ``elastic_grow`` on,
    and a fresh :class:`PartitionManifest` (the hedge's fence).  The
    straggle slowdown factor is seeded per schedule
    (``random.Random(f"{seed}:straggle")`` — the faults.py determinism
    convention) and hedging is on, so a fired ``compute.straggle``
    exercises detect→hedge→score instead of just sleeping.  After every
    run the manifest is audited: a PASS whose winning-line total differs
    from the oracle is a double-count — a VIOLATION even though the
    splice looked right (the invariant hedge-never-double-counts)."""

    def __init__(self, num_nodes: int = 4, size: int = 1 << 11,
                 verify: str = "check", data_seed: int = 0,
                 config_overrides: Optional[Dict[str, Any]] = None,
                 bundle_dir: Optional[str] = None):
        overrides = dict(config_overrides or {})
        overrides.setdefault("network_fanout_bits", 3)
        super().__init__(num_nodes=num_nodes, size=size, verify=verify,
                         data_seed=data_seed, config_overrides=overrides,
                         bundle_dir=bundle_dir)
        self.engine.elastic = True
        self.engine.elastic_grow = True
        self.engine.hedge = "on"
        self.engine.straggle_unit_s = 0.02   # bounded soak wall
        self.audits: List[Dict[str, Any]] = []   # one manifest audit per run

    def _bind(self, m) -> None:
        import tempfile

        from tpu_radix_join.robustness.checkpoint import PartitionManifest
        from tpu_radix_join.robustness.membership import (LeaseBoard,
                                                          MembershipView)
        self.engine.measurements = m
        # fresh membership + manifest per run: epochs, admissions, and
        # fence lines must not leak across schedules (a large lease so an
        # injected joiner's one-shot lease never lapses mid-soak)
        run_dir = tempfile.mkdtemp(prefix="tpu_rj_chaos_")
        board = LeaseBoard(run_dir, rank=0, num_ranks=1, lease_s=300.0,
                           measurements=m)
        self.engine.membership = MembershipView(board, measurements=m)
        self.engine.partition_manifest = PartitionManifest(
            os.path.join(run_dir, "parts.manifest"),
            fingerprint={"chaos_oracle": self.oracle}, measurements=m)

    def run(self, schedule: Schedule) -> RunOutcome:
        self.engine.straggle_factor = random.Random(
            f"{schedule.seed}:straggle").uniform(2.0, 6.0)
        out = super().run(schedule)
        aud = self.engine.partition_manifest.audit()
        self.audits.append(aud)
        if out.status == PASS and aud["total"] != self.oracle:
            out = dataclasses.replace(
                out, status=VIOLATION,
                detail=f"manifest double-count: winning lines sum to "
                       f"{aud['total']} != oracle {self.oracle} "
                       f"(fenced_duplicates={aud['fenced_duplicates']})")
            out = dataclasses.replace(out, bundle=_violation_bundle(
                self.measurements[-1], schedule, out.detail,
                self.bundle_dir))
        return out


def soak_recovery(runs: int, base_seed: int = 0,
                  runner: Optional[RecoveryChaosRunner] = None,
                  on_outcome: Optional[Callable[[RunOutcome], None]] = None):
    """Rank-death soak: N seeded recovery schedules through one elastic
    runner.  The summary adds the recovery acceptance signals on top of
    the base invariant fields: ``ranklost``/``recovered_partitions``/
    ``max_epoch`` totals across the soak, and ``wdogtrip`` — which must
    stay 0 (a recovered run never books a watchdog death; a nonzero
    value means a stall was killed instead of triaged).  The growth and
    hedging arms add their own: ``rankjoin`` (admissions), ``hedged`` /
    ``hedgewin`` / ``specwaste`` (speculation accounting), and
    ``manifest_exact`` — runs whose post-run manifest audit summed
    exactly to the oracle (the zero-double-count invariant; audited
    mismatches on PASS runs are already VIOLATIONs)."""
    from tpu_radix_join.performance.measurements import (HEDGED, HEDGEWIN,
                                                         MEPOCH, RANKJOIN,
                                                         RANKLOST, RECOVERN,
                                                         SPECWASTE, WDOGTRIP)
    runner = runner or RecoveryChaosRunner()
    outcomes = []
    for i in range(runs):
        out = runner.run(generate_recovery_schedule(base_seed + i))
        outcomes.append(out)
        if on_outcome:
            on_outcome(out)
    regs = runner.measurements[-runs:]
    summary = {
        "runs": runs,
        "base_seed": base_seed,
        "verify": runner.config.verify,
        "pass": sum(o.status == PASS for o in outcomes),
        "classified": sum(o.status == CLASSIFIED for o in outcomes),
        "violations": sum(o.status == VIOLATION for o in outcomes),
        "failure_classes": sorted({o.failure_class for o in outcomes
                                   if o.failure_class}),
        "ranklost": sum(int(m.counters.get(RANKLOST, 0)) for m in regs),
        "rankjoin": sum(int(m.counters.get(RANKJOIN, 0)) for m in regs),
        "hedged": sum(int(m.counters.get(HEDGED, 0)) for m in regs),
        "hedgewin": sum(int(m.counters.get(HEDGEWIN, 0)) for m in regs),
        "specwaste": sum(int(m.counters.get(SPECWASTE, 0)) for m in regs),
        "recovered_partitions": sum(int(m.counters.get(RECOVERN, 0))
                                    for m in regs),
        "max_epoch": max((int(m.counters.get(MEPOCH, 0)) for m in regs),
                         default=0),
        "wdogtrip": sum(int(m.counters.get(WDOGTRIP, 0)) for m in regs),
        "manifest_exact": sum(
            a["total"] == runner.oracle
            for a in getattr(runner, "audits", [])[-runs:]),
    }
    return outcomes, summary


#: sites a resident serve loop consults per query: the per-query dispatch
#: outage (service/session.py) plus the engine-interior sites join_arrays
#: hits — a session soak exercises breaker trips and engine failures in
#: the same stream.  serve.cache_poison corrupts a stored result-cache
#: entry in place (service/resultcache.py); the digest re-verification
#: must drop it and re-execute, so a poisoned cache can cause a miss but
#: never a silent wrong count.
SESSION_SITES: Tuple[str, ...] = (
    faults.BACKEND_DISPATCH,
    faults.SHUFFLE_OVERFLOW,
    faults.EXCHANGE_CORRUPT,
    faults.CACHE_POISON,
)


def generate_session_schedule(seed: int, queries: int = 6) -> Schedule:
    """1-3 arms over :data:`SESSION_SITES`, each firing at a seeded query
    index within the stream (every session site is consulted once per
    query, so the hit index IS the query index)."""
    rng = random.Random(seed)
    sites = rng.sample(SESSION_SITES, rng.randint(1, len(SESSION_SITES)))
    arms = []
    for site in sites:
        arms.append((site, (("at", rng.randint(1, max(1, queries - 1))),)))
    return Schedule(seed=seed, arms=tuple(arms))


class SessionChaosRunner:
    """Executes fault schedules against a resident :class:`JoinSession`.

    Each ``run`` streams ``queries`` requests through ONE freshly built
    session while the schedule's arms fire at seeded query indices.  The
    soak invariant is the service's failure-isolation contract: **every
    query ends in a classified outcome and the session survives the whole
    stream** — a query that dies unclassified, a silent wrong count, or an
    exception escaping the serve loop is a VIOLATION.  The breaker is
    configured aggressively (threshold 1, zero cooldown) so a single
    armed ``backend.dispatch`` outage exercises the full
    trip -> degraded-serve -> half-open-probe -> close cycle inside one
    short stream.
    """

    def __init__(self, num_nodes: int = 4, size: int = 1 << 12,
                 verify: str = "check", queries: int = 6,
                 data_seed: int = 0,
                 config_overrides: Optional[Dict[str, Any]] = None,
                 bundle_dir: Optional[str] = None):
        from tpu_radix_join.core.config import JoinConfig, ServiceConfig
        from tpu_radix_join.performance.measurements import Measurements
        self._measurements_cls = Measurements
        self.bundle_dir = bundle_dir
        self.size = size
        self.queries = queries
        self.data_seed = data_seed
        self.config = JoinConfig(num_nodes=num_nodes, verify=verify,
                                 **(config_overrides or {}))
        # the result cache is LIVE in the soak (every query shares one
        # content fingerprint, so queries 2..N are cache hits) — that is
        # what gives the serve.cache_poison arm a stored entry to corrupt
        self.service = ServiceConfig(breaker_threshold=1,
                                     breaker_cooldown_s=0.0,
                                     cpu_fallback=True,
                                     result_cache_max=4)
        self.measurements: List[Any] = []   # one registry per run, in order

    def run(self, schedule: Schedule) -> RunOutcome:
        out = self._run(schedule)
        if out.status == VIOLATION:
            out = dataclasses.replace(out, bundle=_violation_bundle(
                self.measurements[-1], schedule, out.detail,
                self.bundle_dir))
        return out

    def _run(self, schedule: Schedule) -> RunOutcome:
        from tpu_radix_join.service import (UNCLASSIFIED, JoinSession,
                                            QueryRequest)
        m = self._measurements_cls()
        self.measurements.append(m)
        inj = faults.FaultInjector(seed=schedule.seed, measurements=m)
        for site, kw in schedule.arm_dicts():
            inj.arm(site, **kw)
        session = JoinSession(self.config, self.service, measurements=m)
        outs = []
        try:
            with inj:
                for i in range(self.queries):
                    # cycle 3 distinct contents: the first lap of the
                    # stream executes (misses), later laps hit the result
                    # cache — so engine-interior arms and the cache-poison
                    # arm both get live consultations in one stream
                    request = QueryRequest(
                        query_id=f"q{i}", tuples_per_node=self.size,
                        seed=self.data_seed + (i % 3))
                    session.submit(request)
                    outs.append(session.run_next())
        except Exception as e:      # noqa: BLE001 — the invariant itself
            return RunOutcome(schedule, VIOLATION, None, None,
                              f"session died at query {len(outs)}: {e!r}")
        finally:
            session.close()
        detail = " ".join(f"{o.query_id}={o.status}/{o.failure_class}"
                          for o in outs)
        for o in outs:
            if o.failure_class == UNCLASSIFIED:
                return RunOutcome(schedule, VIOLATION, None, o.matches,
                                  f"unclassified query outcome: {detail}")
            if (o.status == "ok" and o.expected is not None
                    and o.matches != o.expected):
                return RunOutcome(
                    schedule, VIOLATION, None, o.matches,
                    f"silent wrong count on {o.query_id}: {o.matches} != "
                    f"oracle {o.expected} ({detail})")
        classes = sorted({o.failure_class for o in outs
                          if o.failure_class != "ok"})
        last_ok = next((o.matches for o in reversed(outs)
                        if o.status == "ok"), None)
        if not classes:
            return RunOutcome(schedule, PASS, None, last_ok, detail)
        return RunOutcome(schedule, CLASSIFIED, ",".join(classes),
                          last_ok, detail)


def soak_session(runs: int, base_seed: int = 0,
                 runner: Optional[SessionChaosRunner] = None,
                 verify: str = "check",
                 on_outcome: Optional[Callable[[RunOutcome], None]] = None):
    """N seeded session streams (:func:`generate_session_schedule`) through
    one :class:`SessionChaosRunner`; same return shape as :func:`soak`.
    A violating schedule shrinks with the same :func:`shrink` (the
    session runner's decisions are seed-deterministic too)."""
    runner = runner or SessionChaosRunner(verify=verify)
    outcomes = []
    for i in range(runs):
        out = runner.run(generate_session_schedule(base_seed + i,
                                                   runner.queries))
        outcomes.append(out)
        if on_outcome:
            on_outcome(out)
    summary = {
        "runs": runs,
        "base_seed": base_seed,
        "verify": runner.config.verify,
        "queries_per_run": runner.queries,
        "pass": sum(o.status == PASS for o in outcomes),
        "classified": sum(o.status == CLASSIFIED for o in outcomes),
        "violations": sum(o.status == VIOLATION for o in outcomes),
        "failure_classes": sorted({c for o in outcomes if o.failure_class
                                   for c in o.failure_class.split(",")}),
    }
    return outcomes, summary


def shrink(schedule: Schedule,
           violates: Callable[[Schedule], bool]) -> Schedule:
    """Greedy ddmin over arms: repeatedly drop any single arm whose removal
    keeps the schedule violating, to a fixpoint.  Every candidate is
    re-executed (the fault decisions are seed-deterministic, so a kept
    reduction is guaranteed replayable), giving a 1-minimal repro: removing
    any remaining arm makes the violation disappear."""
    if not violates(schedule):
        raise ValueError("shrink() needs a violating schedule to start from")
    shrunk = True
    while shrunk and len(schedule.arms) > 1:
        shrunk = False
        for i in range(len(schedule.arms)):
            cand = schedule.without(i)
            if violates(cand):
                schedule = cand
                shrunk = True
                break
    return schedule


def write_repro(outcome: RunOutcome, path) -> str:
    """Persist a violating run's minimal repro as one JSON object — the
    ``(seed, arms)`` pair plus what went wrong — and return the JSON line
    (printed by the soak CLIs so the repro survives even if the artifact
    dir does not)."""
    line = json.dumps(outcome.to_json(), sort_keys=True)
    with open(path, "w") as f:
        f.write(line + "\n")
    return line


# --------------------------------------------------------------------- fleet
#: sites the fleet supervisor's dispatch loop consults (service/fleet.py):
#: the worker-kill site fires right after a query hits a worker's pipe, so
#: the hit index IS the dispatched-query index (replay attempts re-consult
#: it — a schedule can kill the replay's worker too)
FLEET_SITES: Tuple[str, ...] = (
    faults.FLEET_WORKER_KILL,
)


def generate_fleet_schedule(seed: int, queries: int = 4) -> Schedule:
    """One ``fleet.worker_kill`` arm at a seeded dispatch index — mid-
    stream worker death, fully determined by ``seed``.  Kept to a single
    site (the only one the supervisor consults) so shrinking degenerates
    to "the kill did it"; the interesting variation is WHERE in the
    stream the kill lands."""
    rng = random.Random(seed)
    site = rng.choice(FLEET_SITES)
    return Schedule(seed=seed,
                    arms=((site, (("at", rng.randint(1, max(1, queries))),)),))


class FleetChaosRunner:
    """Executes ``fleet.worker_kill`` schedules against ONE resident
    :class:`~tpu_radix_join.service.fleet.FleetSupervisor`.

    The supervisor is shared across runs by design: worker boot is the
    expensive part (a JAX import + device init per subprocess), and a
    crash-only supervisor is *supposed* to keep serving across arbitrary
    worker deaths — reusing it across schedules IS the soak.  The
    invariant per run: **every dispatched query returns exactly one
    outcome, oracle-exact (``matches == expected``) or classified, the
    journal audit counts zero double-executions, and the supervisor
    survives the stream**.  An escaped exception, an unclassified
    outcome, a silent wrong count, or ``double_exec > 0`` is a
    VIOLATION.

    ``batched=True`` dispatches each run's queries as ONE co-batchable
    group through ``dispatch_batch`` (the supervisor must have a batch
    window armed) — the worker-kill site then fires between the group's
    back-to-back request writes, i.e. MID-BATCH, and the invariant holds
    that failover re-dispatches the stranded members without a single
    double-execution.
    """

    def __init__(self, supervisor, queries: int = 3, size: int = 1 << 10,
                 data_seed: int = 0, bundle_dir: Optional[str] = None,
                 batched: bool = False):
        self.supervisor = supervisor
        self.queries = queries
        self.size = size
        self.data_seed = data_seed
        self.bundle_dir = bundle_dir
        self.batched = batched
        self.measurements: List[Any] = []

    def run(self, schedule: Schedule) -> RunOutcome:
        out = self._run(schedule)
        if out.status == VIOLATION and self.measurements:
            out = dataclasses.replace(out, bundle=_violation_bundle(
                self.measurements[-1], schedule, out.detail,
                self.bundle_dir))
        return out

    def _run(self, schedule: Schedule) -> RunOutcome:
        from tpu_radix_join.service import UNCLASSIFIED
        sup = self.supervisor
        m = sup.measurements
        if m is not None:
            self.measurements.append(m)
        inj = faults.FaultInjector(seed=schedule.seed, measurements=m)
        for site, kw in schedule.arm_dicts():
            inj.arm(site, **kw)
        outs = []
        try:
            with inj:
                # seed-qualified ids keep fingerprints distinct across
                # runs — the journal dedup must only collapse genuine
                # re-submissions, not the soak's fresh queries
                requests = [{"query_id": f"s{schedule.seed}q{i}",
                             "tenant": f"t{i % 2}",
                             "tuples_per_node": self.size,
                             "seed": self.data_seed}
                            for i in range(self.queries)]
                if self.batched:
                    # one co-batchable group through dispatch_batch: the
                    # kill arm lands between the group's request writes
                    outs = sup.dispatch_batch(requests)
                else:
                    for request in requests:
                        outs.append(sup.dispatch(request))
        except Exception as e:      # noqa: BLE001 — the invariant itself
            return RunOutcome(schedule, VIOLATION, None, None,
                              f"supervisor died at query {len(outs)}: {e!r}")
        detail = " ".join(
            f"{o.get('query_id')}={o.get('status')}/{o.get('failure_class')}"
            for o in outs)
        audit = sup.journal.audit()
        if audit.double_exec:
            return RunOutcome(schedule, VIOLATION, None, None,
                              f"{audit.double_exec} double-executed "
                              f"fingerprint(s) in the journal: {detail}")
        for o in outs:
            if o is None:
                return RunOutcome(schedule, VIOLATION, None, None,
                                  f"query vanished without an outcome: "
                                  f"{detail}")
            if o.get("failure_class") == UNCLASSIFIED:
                return RunOutcome(schedule, VIOLATION, None, o.get("matches"),
                                  f"unclassified query outcome: {detail}")
            if (o.get("status") == "ok" and o.get("expected") is not None
                    and o.get("matches") != o.get("expected")):
                return RunOutcome(
                    schedule, VIOLATION, None, o.get("matches"),
                    f"silent wrong count on {o.get('query_id')}: "
                    f"{o.get('matches')} != oracle {o.get('expected')} "
                    f"({detail})")
        classes = sorted({o["failure_class"] for o in outs
                          if o.get("failure_class")
                          and o["failure_class"] != "ok"})
        last_ok = next((o.get("matches") for o in reversed(outs)
                        if o.get("status") == "ok"), None)
        if not classes:
            return RunOutcome(schedule, PASS, None, last_ok, detail)
        return RunOutcome(schedule, CLASSIFIED, ",".join(classes),
                          last_ok, detail)


def soak_fleet(runs: int, base_seed: int = 0,
               runner: Optional[FleetChaosRunner] = None,
               supervisor=None,
               on_outcome: Optional[Callable[[RunOutcome], None]] = None):
    """N seeded ``fleet.worker_kill`` streams through one
    :class:`FleetChaosRunner`; same return shape as :func:`soak_session`,
    plus the supervisor-side exactly-once accounting (failovers, replays,
    restarts, the final journal audit)."""
    if runner is None:
        if supervisor is None:
            raise ValueError("soak_fleet needs a runner or a supervisor")
        runner = FleetChaosRunner(supervisor)
    outcomes = []
    for i in range(runs):
        out = runner.run(generate_fleet_schedule(base_seed + i,
                                                 runner.queries))
        outcomes.append(out)
        if on_outcome:
            on_outcome(out)
    sup = runner.supervisor
    audit = sup.journal.audit()
    summary = {
        "runs": runs,
        "base_seed": base_seed,
        "queries_per_run": runner.queries,
        "pass": sum(o.status == PASS for o in outcomes),
        "classified": sum(o.status == CLASSIFIED for o in outcomes),
        "violations": sum(o.status == VIOLATION for o in outcomes),
        "failure_classes": sorted({c for o in outcomes if o.failure_class
                                   for c in o.failure_class.split(",")}),
        "failovers": sup.failovers,
        "replays": sup.replays,
        "worker_restarts": sup.restarts,
        "double_exec": audit.double_exec,
        "unacked": audit.unacked,
    }
    return outcomes, summary

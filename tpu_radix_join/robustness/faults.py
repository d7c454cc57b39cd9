"""Seeded, deterministic fault injection.

The engine consults this registry at *named sites* (the constants below);
an armed :class:`FaultInjector` decides — deterministically, from its seed —
whether the site fires on each hit.  A fired site either raises (simulated
kill, connect timeout, I/O error) or tells the caller to corrupt its own
state (flag mutation, sentinel-damaged lane), so every failure path in
SURVEY.md §4.3's taxonomy is exercisable on CPU under tier-1 without
touching real hardware.

Usage::

    with FaultInjector(seed=7).arm(faults.GRID_KILL, at=3):
        chunked_join_grid(...)        # third pair probe raises InjectedKill

Injectors nest via a stack; only the innermost (top) injector is consulted,
so a test's injector shadows any ambient one.  Sites are plain strings —
``arm`` still accepts unknown names (forward compatibility for downstream
experiments) but warns with a difflib near-miss suggestion, so a typo'd
chaos schedule doesn't silently no-op; the canonical vocabulary is
:data:`SITES`.

Determinism contract: per-site decisions come from
``random.Random(f"{seed}:{site}")``, so the same seed + same hit sequence
replays the same failures (tested in tests/test_robustness.py).
"""

from __future__ import annotations

import difflib
import random
import warnings
from typing import Dict, List, Optional, Tuple

from tpu_radix_join.performance.measurements import FINJECT
from tpu_radix_join.robustness.retry import BACKEND_UNAVAILABLE

# ---------------------------------------------------------------- site names
SHUFFLE_OVERFLOW = "engine.shuffle_overflow"   # shuffle-block capacity loss
DEVICE_INIT = "engine.device_init"             # accelerator unavailable
COORD_CONNECT = "multihost.coordinator_connect"  # distributed-init timeout
GRID_KILL = "grid.mid_chunk_kill"              # hard kill between slabs
GRID_TRANSIENT = "grid.transient"              # retryable per-pair hiccup
STREAM_CORRUPT = "stream.corrupt_lane"         # sentinel-damaged key lane
EXCHANGE_CORRUPT = "exchange.corrupt_lane"     # bit-flipped key post-exchange
CKPT_SAVE = "checkpoint.save"                  # checkpoint write I/O error
CKPT_LOAD = "checkpoint.load"                  # checkpoint read I/O error
BACKEND_DISPATCH = "backend.dispatch"          # per-query backend dispatch
                                               # error
                                               # (service/session.py probe)
BACKEND_STALL = "backend.stall"                # simulated hung collective:
                                               # the engine spins (checking
                                               # its cancel hook) instead of
                                               # raising — the watchdog's
                                               # hung-collective failure mode
                                               # (operators/hash_join.py)
RANK_DEATH = "membership.rank_death"           # peer rank dies mid-run: its
                                               # lease lapses and the local
                                               # membership view must fence
                                               # the epoch + recover instead
                                               # of hanging (robustness/
                                               # membership.py + recovery.py)
RANK_JOIN = "membership.rank_join"             # a new peer writes a `joining`
                                               # lease mid-run: the view admits
                                               # it with a fenced epoch bump
                                               # and the next plan re-expands
                                               # onto the grown membership
                                               # (membership.py + recovery.py)
COMPUTE_STRAGGLE = "compute.straggle"          # a live rank slows down by a
                                               # seeded factor: alive-but-slow
                                               # is NOT rank_death — the
                                               # straggler detector must hedge
                                               # its unfinished partitions,
                                               # never declare it dead
                                               # (robustness/straggler.py)
FLEET_WORKER_KILL = "fleet.worker_kill"        # SIGKILL a fleet worker right
                                               # after its query hit the pipe:
                                               # the supervisor must journal-
                                               # replay the query on a healthy
                                               # worker, exactly one outcome
                                               # (service/fleet.py dispatch)
CACHE_POISON = "serve.cache_poison"            # corrupt a stored result-cache
                                               # entry in place: the digest/
                                               # epoch re-check on read must
                                               # drop it (count a miss, re-
                                               # execute) — a stale or damaged
                                               # entry is NEVER served
                                               # (service/resultcache.py)

SITES = (SHUFFLE_OVERFLOW, DEVICE_INIT, COORD_CONNECT, GRID_KILL,
         GRID_TRANSIENT, STREAM_CORRUPT, EXCHANGE_CORRUPT, CKPT_SAVE,
         CKPT_LOAD, BACKEND_DISPATCH, BACKEND_STALL, RANK_DEATH,
         RANK_JOIN, COMPUTE_STRAGGLE, FLEET_WORKER_KILL, CACHE_POISON)


class InjectedFault(RuntimeError):
    """Raised by :meth:`FaultInjector.check` when a site fires."""

    def __init__(self, site: str, hit: int):
        super().__init__(f"injected fault at {site!r} (hit {hit})")
        self.site = site
        self.hit = hit


class InjectedKill(InjectedFault):
    """Simulated hard kill (mid-chunk death): never retried in-process."""


class TransientFault(InjectedFault):
    """Simulated transient backend error: safe to retry.  Carries
    the transient infrastructure class so the shared retryability
    predicate (retry.is_retryable_class) and the service's circuit
    breaker classify it without type-sniffing."""

    failure_class = BACKEND_UNAVAILABLE


class _Arm:
    def __init__(self, site: str, seed: int, at, p, times, exc):
        self.site = site
        if at is not None and not isinstance(at, (tuple, list, set, frozenset)):
            at = (at,)
        self.at = frozenset(int(a) for a in at) if at is not None else None
        self.p = p
        self.times = times if times is not None else (
            len(self.at) if self.at is not None else None)
        self.exc = exc
        self.hits = 0
        self.fired = 0
        self._rng = random.Random(f"{seed}:{site}")

    def decide(self) -> bool:
        self.hits += 1
        if self.times is not None and self.fired >= self.times:
            return False
        if self.at is not None:
            fire = self.hits in self.at
        elif self.p is not None:
            fire = self._rng.random() < self.p
        else:
            fire = True
        if fire:
            self.fired += 1
        return fire


class FaultInjector:
    """Context-manager fault registry (see module docstring).

    ``measurements`` (optional) receives an ``FINJECT`` increment and a
    ``fault`` trace event for every fire; sites consulted through the
    module-level helpers may also pass their own registry.
    """

    def __init__(self, seed: int = 0, measurements=None):
        self.seed = seed
        self.measurements = measurements
        self._arms: Dict[str, _Arm] = {}
        #: every (site, hit_index) that fired, in order — the replay record
        self.history: List[Tuple[str, int]] = []

    def arm(self, site: str, *, at=None, p: Optional[float] = None,
            times: Optional[int] = None, exc=None) -> "FaultInjector":
        """Arm ``site``; returns self for chaining.

        ``at``: 1-based hit index (or iterable of them) at which to fire.
        ``p``: per-hit fire probability (seeded per site).  Neither ->
        fire on every hit.  ``times``: cap on total fires (defaults to
        ``len(at)`` when ``at`` is given, else unlimited).  ``exc``:
        exception class/factory ``check`` raises (default
        :class:`InjectedFault`; must accept ``(site, hit)`` or no args).
        """
        if at is None and p is None and times is None:
            times = None   # fire every hit
        if site not in SITES:
            near = difflib.get_close_matches(site, SITES, n=1, cutoff=0.6)
            hint = f"; did you mean {near[0]!r}?" if near else ""
            warnings.warn(
                f"arming unknown fault site {site!r} — no engine code "
                f"consults it, so this arm will never fire{hint}",
                RuntimeWarning, stacklevel=2)
        self._arms[site] = _Arm(site, self.seed, at, p, times, exc)
        return self

    # -------------------------------------------------------------- queries
    def fires(self, site: str, measurements=None) -> bool:
        arm = self._arms.get(site)
        if arm is None:
            return False
        if not arm.decide():
            return False
        self.history.append((site, arm.hits))
        for m in (self.measurements, measurements):
            if m is not None:
                m.incr(FINJECT)
                m.event("fault", site=site, hit=arm.hits)
        return True

    def check(self, site: str, measurements=None) -> None:
        """Raise the armed exception if ``site`` fires on this hit."""
        if not self.fires(site, measurements):
            return
        arm = self._arms[site]
        exc = arm.exc or InjectedFault
        if isinstance(exc, type) and issubclass(exc, InjectedFault):
            raise exc(site, arm.hits)
        raise exc(f"injected fault at {site!r} (hit {arm.hits})")

    def hits(self, site: str) -> int:
        arm = self._arms.get(site)
        return arm.hits if arm else 0

    def fired(self, site: str) -> int:
        arm = self._arms.get(site)
        return arm.fired if arm else 0

    def site_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-armed-site ``{"hits": n, "fired": n}`` — the accounting that
        lands in ``JoinResult.diagnostics["fault_sites"]`` and the
        ``print_results`` FaultSites aggregate."""
        return {site: {"hits": arm.hits, "fired": arm.fired}
                for site, arm in self._arms.items()}

    # ------------------------------------------------------------- stacking
    def __enter__(self) -> "FaultInjector":
        _STACK.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _STACK.remove(self)


_STACK: List[FaultInjector] = []


def active() -> Optional[FaultInjector]:
    """The innermost active injector, or None (production: always None)."""
    return _STACK[-1] if _STACK else None


def fires(site: str, measurements=None) -> bool:
    """Module-level probe: False when no injector is active (zero-cost in
    production beyond a list check)."""
    inj = active()
    return inj.fires(site, measurements) if inj is not None else False


def check(site: str, measurements=None) -> None:
    """Module-level raise-if-armed probe (no-op without an injector)."""
    inj = active()
    if inj is not None:
        inj.check(site, measurements)

"""Phase timers, counters, and .perf-compatible reporting.

Replaces ``performance/Measurements.{h,cpp}`` (SURVEY.md §5.1): the
reference's ~60 static start/stop functions around `gettimeofday` + PAPI
cycles, compile-gated sub-timers (``MEASUREMENT_DETAILS_*``), per-rank
``<rank>.perf`` tag files gathered to rank 0 over MPI_Send/Recv
(Measurements.cpp:548-590), the printed per-phase table (:592-702), and the
``/proc/self/status`` memory probe (:825-851).

TPU design: a timer registry keyed by the reference's own tag vocabulary
(JTOTAL, JHIST, JMPI, JPROC, SWINALLOC, ...) so baseline comparison is
mechanical; fences are ``jax.block_until_ready`` (device work is async);
hardware-counter analogs come from ``jax.profiler`` traces rather than PAPI.
Everything under one jit cannot be phase-timed from the host, so phase timing
is honest at the granularity the driver actually executes (histogram program /
join program), with the jit-internal split available via profiler traces
(:meth:`Measurements.trace`).  The fine-grained *counter* details the
reference accumulates in its hot loops (tuple sums, per-Put byte/call counts,
Measurements.cpp:272-349) are exact here without instrumenting the hot path —
block geometry is static, so the driver derives them from config + results
(:meth:`Measurements.record_exchange`).
"""

from __future__ import annotations

import json
import os
import socket
import time
from collections import defaultdict
from typing import Dict, Iterable, List, Optional

import jax

# every timer and span also opens a profiler TraceMe named SPAN_PREFIX + its
# tag (``trj.JTOTAL``), on the device trace's clock
from tpu_radix_join.observability.stages import SPAN_PREFIX, stage_of

# Reference tag vocabulary (Measurements.cpp:136-142,176-178,351-368,533-542)
JTOTAL = "JTOTAL"          # end-to-end join wall time
JHIST = "JHIST"            # histogram phase
JMPI = "JMPI"              # network partitioning phase
JPROC = "JPROC"            # local processing phase
SWINALLOC = "SWINALLOC"    # window allocation (capacity measurement + compile)
SNETCOMPL = "SNETCOMPL"    # network completion wait
SLOCPREP = "SLOCPREP"      # local preparation

MWINWAIT = "MWINWAIT"      # time spent on retried (undersized-window) attempts
JCOMPILE = "JCOMPILE"      # XLA compilation (no reference analog: it has none
                           # at runtime; kept out of every phase column)
SDISPATCH = "SDISPATCH"    # per-program dispatch round-trip floor (not a
                           # cumulative phase: the amortized cost of ONE
                           # empty-program dispatch through the host
                           # attachment, measured once per run)

_GATHER_BUF_BYTES = 1 << 16   # fixed allgather slot per process (gather_all)


# Detail tags (MEASUREMENT_DETAILS_* analogs).  Counters carry the exact
# quantities the reference sums per call site; rates are derived on report.
RTUPLES = "RTUPLES"        # inner tuples joined (counter)
STUPLES = "STUPLES"        # outer tuples joined (counter)
RESULTS = "RESULTS"        # global match count (RESULT_COUNTER analog)
BPBUILD = "BPBUILD"        # bucket-path build phase timer (hash_join)
BPPROBE = "BPPROBE"        # bucket-path probe phase timer
BPBUILDTUPLES = "BPBUILDTUPLES"  # tuples hashed into build buckets
BPPROBETUPLES = "BPPROBETUPLES"  # tuples probed against the buckets
RETRIES = "RETRIES"        # engine capacity-regrow attempts superseded
                           # (hash_join rollback; distinct from the
                           # robustness layer's RETRYN policy attempts)
MWINPUTCNT = "MWINPUTCNT"  # logical block transfers shuffled (MPI_Put count analog)
MWINBYTES = "MWINBYTES"    # shuffle wire bytes incl. padding (8B/tuple slots)
WIREBYTES = "WIREBYTES"    # actual wire bytes shipped per exchange under the
                           # active codec (== MWINBYTES when codec="off";
                           # smaller under the bit-packed format)
PACKRATIO = "PACKRATIO"    # gauge: packed wire bytes as a percent of the raw
                           # two/three-lane format (100 = no compression)
XSTAGES = "XSTAGES"        # gauge: column groups per staged exchange (1 = fused)
WINCAPR = "WINCAPR"        # per-(sender,dest) block capacity, inner window
WINCAPS = "WINCAPS"        # per-(sender,dest) block capacity, outer window
FINJECT = "FINJECT"        # injected faults fired (robustness/faults.py)
RETRYN = "RETRYN"          # robustness-layer retry attempts (robustness/retry.py)
BACKOFFMS = "BACKOFFMS"    # total retry backoff slept, milliseconds
CKPTSAVE = "CKPTSAVE"      # checkpoints written (robustness/checkpoint.py)
CKPTLOAD = "CKPTLOAD"      # checkpoints resumed from
GRIDPAIRS = "GRIDPAIRS"    # chunk pairs actually probed by chunked_join_grid
                           # (resume skips completed pairs — see ops/chunked.py)
PREFETCH = "PREFETCH"      # chunks staged by the grid prefetch thread before
                           # the consuming pair asked for them (ops/chunked.py
                           # pipelined mode; each carries a "prefetch" span)
SORTREUSE = "SORTREUSE"    # grid pair probes that reused the row's presorted
                           # inner chunk instead of re-sorting the packed
                           # union — rows x (cols - 1) on a full grid
VCHK = "VCHK"              # integrity-verification timing tag (times_us ONLY:
                           # summary() merges counters over times on a shared
                           # key, so the check count lives under VCHKN)
VCHKN = "VCHKN"            # integrity checksum comparisons performed
VFAIL = "VFAIL"            # checksum mismatches detected (robustness/verify.py)
VREPAIR = "VREPAIR"        # damaged partitions recomputed under --verify repair
QADMIT = "QADMIT"          # queries admitted by the service queue
QREJECT = "QREJECT"        # queries rejected at admission (depth / quota)
QDEADLINE = "QDEADLINE"    # queries cancelled by their deadline
QWARM = "QWARM"            # warm queries (capacity-cache hit: no sizing pass)
QDEGRADED = "QDEGRADED"    # queries served by the degraded fallback engine
BRKTRIP = "BRKTRIP"        # circuit-breaker trips (closed/half-open -> open)
BRKPROBE = "BRKPROBE"      # half-open health probes dispatched
PLANDRIFT = "PLANDRIFT"    # gauge: |actual - predicted| JTOTAL as a percent of
                           # the planner's prediction (planner/audit.py) — the
                           # plan-vs-actual closed-loop signal; lower is better
WDOGTRIP = "WDOGTRIP"      # hang-watchdog trips (observability/watchdog.py)
PMBUNDLE = "PMBUNDLE"      # forensics bundles written (observability/postmortem)
MEPOCH = "MEPOCH"          # gauge: current membership epoch (robustness/
                           # membership.py) — bumps fence out stale collectives
RANKLOST = "RANKLOST"      # ranks declared lost on lease lapse (membership.py)
RECOVERN = "RECOVERN"      # partitions recomputed during elastic recovery
                           # (robustness/recovery.py); < the total partition
                           # count means resume was partition-granular
RECOVERMS = "RECOVERMS"    # total elastic-recovery wall milliseconds (detect ->
                           # re-plan -> recompute -> splice)
RANKJOIN = "RANKJOIN"      # ranks admitted from a `joining` lease — the growth
                           # mirror of RANKLOST (robustness/membership.py)
HEDGED = "HEDGED"          # straggler hedges launched: speculative out-of-band
                           # recomputes of a slow-but-alive rank's unfinished
                           # partitions (robustness/straggler.py)
HEDGEWIN = "HEDGEWIN"      # hedged partitions whose speculative recompute won
                           # the manifest's first-writer-wins fence — the
                           # original never double-counts past these
SPECWASTE = "SPECWASTE"    # hedged partitions whose claim LOST (the original
                           # owner's realized line landed first): wasted
                           # speculative work, the hedging overhead gauge
JXAUDIT = "JXAUDIT"        # gauge: live graftcheck (jaxpr IR audit) findings
                           # on the traced entry points — the static twin of
                           # the lint gate; lower is better, clean repo holds 0
STATICMEM = "STATICMEM"    # gauge: static live-set peak bytes of the traced
                           # fused pipeline (analysis/jaxpr/memory.py) — plan
                           # geometry descriptor feeding the feasibility gate
NCOMPILE = "NCOMPILE"      # backend compiles observed via jax.monitoring
                           # (observability/compilemon.py); a resident serve
                           # session recompiling after warmup is a storm
COMPILEMS = "COMPILEMS"    # total backend-compile wall milliseconds (the
                           # counter twin of the JCOMPILE bracket: hears
                           # every compile, not just the bracketed one)
PARTPASS = "PARTPASS"      # fused (pallas) radix-partition passes selected at
                           # trace time (ops/radix.py); one per traced scatter/
                           # reorder site, so a recompiling session ticks it
                           # per program build, not per execution
PARTFALLBACK = "PARTFALLBACK"  # partition/histogram auto-select fell back to
                           # the XLA sort path (Pallas unavailable or fanout
                           # past MAX_PARTITIONS) — the silent-degrade signal;
                           # more of these on a TPU backend is a regression
SORTPASS = "SORTPASS"      # Pallas LSD radix sorts selected at trace time
                           # (ops/sorting.py resolve_sort_impl); one per
                           # traced sort site, like PARTPASS
SORTFALLBACK = "SORTFALLBACK"  # nothing ticks it: the sort's ``auto`` is
                           # lax.sort, with no arm to fall back from; it
                           # reads 0 and stays because the benchmark's
                           # ``fallbacks`` check and chip_smoke.py read it
FAILOVER = "FAILOVER"      # fleet queries failed over to another worker after
                           # the routed worker died mid-query (service/fleet.py)
REPLAYN = "REPLAYN"        # journal intents replayed (failover retries plus
                           # restart-time unacknowledged-intent replay)
WINCARN = "WINCARN"        # fleet worker incarnations spawned (boot + restarts)
WRESTART = "WRESTART"      # dead-worker restarts (WINCARN minus the boot pool)
JDEPTH = "JDEPTH"          # gauge: peak unacknowledged query-journal depth
DOUBLEEXEC = "DOUBLEEXEC"  # fingerprints with >1 journaled outcome — the
                           # exactly-once invariant; any nonzero is a bug
RCHIT = "RCHIT"            # result-cache hits: queries short-circuited by a
                           # content-fingerprint match before admission
                           # (service/resultcache.py); the whole-result
                           # amortization win — fewer at the same traffic
                           # means repeated work stopped deduping
RCMISS = "RCMISS"          # result-cache misses (cold content, TTL expiry,
                           # or a digest/epoch check dropping a stale entry)
BATCHN = "BATCHN"          # fused micro-batches dispatched as ONE device
                           # program (service/microbatch.py); scenario-
                           # shaped — the fuse ratio BATCHQ/BATCHN is the
                           # gated observable, not the raw count
BATCHQ = "BATCHQ"          # queries served through fused micro-batches
                           # (each batch of k ticks this k times)
DELTAMERGE = "DELTAMERGE"  # queries served O(N+Δ): delta sorted + merged
                           # into the device-resident sorted union instead
                           # of re-sorting the full relation
                           # (service/resident.py + ops/merge_delta.py)
RESBYTES = "RESBYTES"      # gauge: device-resident sorted-union bytes held
                           # by the resident-state manager (bounded by
                           # ServiceConfig.resident_budget_bytes)
JRATE = "JRATE"            # derived: (R+S) tuples / JTOTAL second
JPROCRATE = "JPROCRATE"    # derived: (R+S) tuples / JPROC second
HILOCRATE = "HILOCRATE"    # derived: inner tuples / JHIST second
HOLOCRATE = "HOLOCRATE"    # derived: outer tuples / JHIST second


class Measurements:
    """Per-process measurement registry.

    ``init`` -> ``Measurements::init`` (Measurements.cpp:707-749) minus the
    MPI_Bcast of the experiment id (single-process drivers name their own).
    """

    def __init__(self, node_id: int = 0, num_nodes: int = 1,
                 tag: str = "experiment"):
        self.node_id = node_id
        self.num_nodes = num_nodes
        self.tag = tag
        self._starts: Dict[str, float] = {}
        # open profiler spans by tag: keyed, so timers may overlap
        self._annotations: Dict[str, object] = {}
        self.times_us: Dict[str, float] = defaultdict(float)
        self.counters: Dict[str, int] = defaultdict(int)
        self._tracer = None
        # paired wall/monotonic anchors, taken back to back: perf_counter is
        # not comparable across processes, so every timestamp this registry
        # emits carries an epoch-relative twin — the alignment key merged
        # multi-rank timelines sort by (observability/timeline.py)
        self._mono0 = time.perf_counter()
        self.meta: Dict[str, object] = {
            "host": socket.gethostname(),
            "node": node_id,
            "nodes": num_nodes,
            "epoch_s": time.time(),
        }
        # always-on flight recorder (observability/flightrec.py): every
        # start/stop/incr/event below mirrors into this bounded ring with
        # no opt-in flag — the black box a post-mortem bundle freezes and
        # the idle clock the hang watchdog polls.  Deliberately NOT gated
        # on a tracer/config: a hung run leaves nothing behind when
        # recording is opt-in.
        from tpu_radix_join.observability.flightrec import FlightRecorder
        self.flightrec = FlightRecorder(epoch_s=self.meta["epoch_s"],
                                        mono_s=self._mono0)

    # ------------------------------------------------------------ span tracer
    def attach_tracer(self, tracer=None, trace_id=None, **tags):
        """Attach (or build) an observability.SpanTracer sharing this
        registry's clock anchors: every ``start``/``stop`` pair then mirrors
        into a timeline span and every :meth:`event` into an instant event.
        Returns the tracer.

        ``trace_id`` is the join-level trace identity (rank 0 mints one,
        peers adopt it over the lease-dir channel) — it lands in the span
        file metadata, ``meta["trace_id"]``, and the flight-recorder
        context, so span files, ledger rows, and forensics bundles all
        join on the same key."""
        if tracer is None:
            from tpu_radix_join.observability.spans import SpanTracer
            tracer = SpanTracer(rank=self.node_id, trace_id=trace_id,
                                tags=tags,
                                epoch_s=self.meta["epoch_s"],
                                mono_s=self._mono0)
        self.meta["trace_id"] = tracer.trace_id
        self.flightrec.set_context(trace_id=tracer.trace_id)
        self._tracer = tracer
        return tracer

    @property
    def tracer(self):
        return self._tracer

    def set_trace_tags(self, **tags) -> None:
        """Stamp tags (plan strategy, engine, ...) onto future spans; a
        no-op without an attached tracer."""
        if self._tracer is not None:
            self._tracer.set_tags(**tags)

    def span(self, name: str, **args):
        """Timeline-only span context (grid pairs, checkpoint writes):
        shows on the trace without minting a ``times_us`` tag per instance
        — per-pair tags would make .perf files unbounded.  Always mirrors
        into the flight-recorder ring and the profiler (``trj.<name>``);
        the tracer remains opt-in."""
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            self.flightrec.record("span", name, **args)
            try:
                with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                    if self._tracer is not None:
                        with self._tracer.span(name, **args):
                            yield
                    else:
                        yield
            finally:
                self.flightrec.record("span_end", name)

        return _ctx()

    # ----------------------------------------------------------------- timers
    def start(self, key: str) -> None:
        self._starts[key] = time.perf_counter()
        self.flightrec.record("begin", key)
        self._close_annotation(key)   # a restarted timer's span ends here
        annotation = jax.profiler.TraceAnnotation(SPAN_PREFIX + key)
        annotation.__enter__()
        self._annotations[key] = annotation
        if self._tracer is not None:
            self._tracer.begin(key)

    def _close_annotation(self, key: str) -> None:
        annotation = self._annotations.pop(key, None)
        if annotation is not None:
            annotation.__exit__(None, None, None)

    def stop(self, key: str, fence=None) -> float:
        """Stop a timer; ``fence`` (any pytree of jax arrays) is
        block_until_ready'd first so async device work is included — the
        equivalent of the reference's MPI barrier + gettimeofday pairing
        (Measurements.cpp:90-134)."""
        if fence is not None:
            jax.block_until_ready(fence)
        dt = (time.perf_counter() - self._starts.pop(key)) * 1e6
        self._close_annotation(key)
        self.times_us[key] += dt
        self.flightrec.record("end", key, us=round(dt, 1))
        if self._tracer is not None:
            # the span records the real wall interval; exclude_from_running
            # shifts only the accumulated column (a compile excluded from
            # JTOTAL still happened on the timeline, under its own span)
            self._tracer.end(key)
        return dt

    def add_time_us(self, key: str, us: float) -> None:
        self.times_us[key] += us

    def exclude_from_running(self, us: float) -> None:
        """Shift every currently-running timer's start forward by ``us`` so an
        interval that must not land in their columns (XLA compilation — the
        reference's phase timers contain no compile because none exists at
        runtime, Measurements.cpp:137-141) is excluded from whatever spans it
        (JTOTAL, SWINALLOC).  JCOMPILE keeps the time under its own tag."""
        for k in self._starts:
            self._starts[k] += us / 1e6

    def incr(self, key: str, by: int = 1) -> None:
        self.counters[key] += by
        self.flightrec.record("incr", key, by=by, total=self.counters[key])

    def event(self, name: str, **data) -> None:
        """Append a trace event to ``meta["events"]`` (lands in the
        ``<rank>.info`` JSON).  The robustness layer records faults fired,
        retries taken, and checkpoints written here so a post-mortem can
        reconstruct the failure/recovery timeline without logs; values must
        be JSON-serializable.

        Timestamps: ``t_s`` is this process's raw monotonic clock (kept for
        artifact compatibility, NOT comparable across processes) and
        ``t_epoch_s`` its wall-clock twin via the init-time anchor pair —
        the field merged multi-rank timelines align on."""
        now = time.perf_counter()
        events = self.meta.setdefault("events", [])
        events.append({"event": name,
                       "t_s": round(now, 6),
                       "t_epoch_s": round(
                           self.meta["epoch_s"] + (now - self._mono0), 6),
                       **data})
        self.flightrec.record("event", name, **data)
        if self._tracer is not None:
            self._tracer.instant(name, **data)

    # ----------------------------------------------------- detail accumulators
    def record_exchange(self, num_nodes: int, cap_r: int, cap_s: int,
                        tuple_bytes: int = 8,
                        wire_bytes: Optional[int] = None,
                        pack_ratio_pct: Optional[float] = None,
                        stages: Optional[int] = None) -> None:
        """Shuffle-detail counters (MEASUREMENT_DETAILS_NETWORK analog,
        Measurements.cpp:272-349): the reference counts every 64KB ``MPI_Put``
        and its bytes in the hot loop; here block geometry is static so the
        equivalent quantities are derived — per relation, each node ships N
        blocks of ``capacity`` wire tuples (window.block_all_to_all).
        ``tuple_bytes``: 8 for two uint32 lanes (the reference's
        CompressedTuple size), 12 when the key_hi lane travels too.

        ``wire_bytes``: actual bytes shipped per node per exchange under the
        active codec (packed block words x 4; defaults to the raw lane
        bytes when the codec is off).  ``pack_ratio_pct`` and ``stages`` are
        gauges describing the exchange plan (100 / 1 = codec off, fused)."""
        self.incr(MWINPUTCNT, 2 * num_nodes)
        raw_bytes = tuple_bytes * num_nodes * (cap_r + cap_s)
        self.incr(MWINBYTES, raw_bytes)
        self.incr(WIREBYTES,
                  raw_bytes if wire_bytes is None else int(wire_bytes))
        if pack_ratio_pct is not None:
            self.counters[PACKRATIO] = int(round(pack_ratio_pct))
        if stages is not None:
            self.counters[XSTAGES] = int(stages)
        self.counters[WINCAPR] = cap_r
        self.counters[WINCAPS] = cap_s
        # gauge assignments above bypass incr(); one ring record keeps the
        # exchange geometry visible in the flight recorder too
        self.flightrec.record(
            "gauge", "exchange", wirebytes=self.counters[WIREBYTES],
            pack_ratio_pct=self.counters.get(PACKRATIO),
            stages=self.counters.get(XSTAGES))

    def derive_rates(self) -> None:
        """Derived throughput tags (the HILOCRATE/HOLOCRATE pattern,
        Measurements.cpp:251-260: quantity / sub-phase time)."""
        tuples = self.counters.get(RTUPLES, 0) + self.counters.get(STUPLES, 0)
        for rate_key, time_key in ((JRATE, JTOTAL), (JPROCRATE, JPROC)):
            us = self.times_us.get(time_key, 0.0)
            if tuples and us > 0:
                self.counters[rate_key] = int(tuples / (us / 1e6))
        # histogram scan rates, tuples/s per side (the reference reports MB/s
        # over the same quantities, Measurements.cpp:251-260)
        jh = self.times_us.get(JHIST, 0.0)
        if jh > 0:
            for rate_key, cnt_key in ((HILOCRATE, RTUPLES),
                                      (HOLOCRATE, STUPLES)):
                cnt = self.counters.get(cnt_key, 0)
                if cnt:
                    self.counters[rate_key] = int(cnt / (jh / 1e6))

    def measure_dispatch_floor(self, iters: int = 20) -> float:
        """Record SDISPATCH: the amortized round-trip of dispatching one
        trivial program and fencing it — the floor every split-phase column
        (JMPI/JHIST/SLOCPREP/JPROC) pays per program between host and
        device; readers subtract it to see work net of dispatch.  The reference keeps comparable
        "special" timers for accounting honesty (Measurements.cpp:176-178).
        Stored as a floor (assignment, not +=); returns microseconds."""
        import jax.numpy as jnp
        fn = jax.jit(lambda x: x + jnp.uint32(1))
        x = jnp.zeros((8,), jnp.uint32)
        jax.block_until_ready(fn(x))   # compile outside the timed loop
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(x))
        us = (time.perf_counter() - t0) / iters * 1e6
        self.times_us[SDISPATCH] = us
        return us

    # ------------------------------------------------------- memory / tracing
    def memory_utilization(self) -> Dict[str, int]:
        """Host VmSize/VmRSS (printMemoryUtilization parity,
        Measurements.cpp:825-851) plus per-device HBM stats where the backend
        exposes them.  Values in bytes; also merged into ``meta``."""
        out: Dict[str, int] = {}
        try:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith(("VmSize:", "VmRSS:")):
                        k, v = line.split(":", 1)
                        out[k] = int(v.split()[0]) * 1024
        except OSError:   # non-Linux host
            pass
        for i, dev in enumerate(jax.local_devices()):
            stats = getattr(dev, "memory_stats", lambda: None)()
            if stats and "bytes_in_use" in stats:
                out[f"device{i}_bytes_in_use"] = int(stats["bytes_in_use"])
        self.meta["memory"] = out
        return out

    def trace(self, trace_dir: str, record: bool = True):
        """Profiler context (PAPI/CUDA-event analog, Measurements.cpp:90-107 /
        eth.cu:179-222): wraps ``jax.profiler.trace`` AND, on exit, reads
        the written trace (:func:`op_table`) so the jit-internal split
        becomes registry data, not just a TensorBoard file:

          * ``CTOTAL`` (times_us) — device busy time, the analog of the
            reference's PAPI total-cycles bracket (CTOTAL,
            Measurements.cpp:90-107), from a device plane only;
          * ``meta["trace"]`` — the per-op table ({op: {us, count}},
            heaviest first) and its sum per ``trj.*`` stage.

        ``record=False`` restores the bare passthrough."""
        if not record:
            return jax.profiler.trace(trace_dir)

        import contextlib

        @contextlib.contextmanager
        def _ctx():
            with jax.profiler.trace(trace_dir):
                yield self
            table = op_table(trace_dir)
            if table is not None:
                self.meta["trace"] = table
                # a host plane's XLA threads are no cycles-analog
                if table["plane"].startswith(DEVICE_PLANE):
                    self.times_us["CTOTAL"] = table["busy_us"]

        return _ctx()

    # ---------------------------------------------------------------- output
    def lines(self):
        """Tagged key/value/unit lines in the reference's .perf format
        (Measurements.cpp:136-142)."""
        for k in sorted(self.times_us):
            yield f"{k}\t{self.times_us[k]:.0f}\tus"
        for k in sorted(self.counters):
            yield f"{k}\t{self.counters[k]}\tcount"

    def store(self, out_dir: str) -> str:
        """Write ``<rank>.perf`` and ``<rank>.info`` (Measurements.cpp:707-770)."""
        self.derive_rates()
        os.makedirs(out_dir, exist_ok=True)
        perf = os.path.join(out_dir, f"{self.node_id}.perf")
        with open(perf, "w") as f:
            for line in self.lines():
                f.write(line + "\n")
        with open(os.path.join(out_dir, f"{self.node_id}.info"), "w") as f:
            json.dump(self.meta, f, indent=2)
        return perf

    def summary(self) -> Dict[str, float]:
        self.derive_rates()
        return {**{k: v for k, v in self.times_us.items()},
                **{k: float(v) for k, v in self.counters.items()}}

    # ----------------------------------------------------------- aggregation
    def _slim_meta(self) -> Dict[str, object]:
        """Truncated stand-in for an oversized meta in :meth:`gather_all`:
        never fail the report of an already-successful join over big
        metadata — drop the bulk but preserve the fields the aggregate
        report and timeline merge read (a truncated rank must not silently
        vanish from the [RESULTS] FailureClasses line)."""
        slim: Dict[str, object] = {"truncated": True}
        for k in ("failure_class", "epoch_s"):
            if k in self.meta:
                slim[k] = self.meta[k]
        if isinstance(self.meta.get("events"), list):
            slim["events_count"] = len(self.meta["events"])
        return slim

    def gather_all(self) -> List["Measurements"]:
        """Network gather of every process's registry — the analog of the
        reference's rank-0 result gather over MPI_Send/Recv
        (serializeResults/receiveAllMeasurements, Measurements.cpp:548-590).
        Replaces the shared-directory assumption of :meth:`load` for
        multi-process worlds: each process JSON-serializes its registry into
        a fixed-size byte buffer and an allgather hands every process all of
        them (rank 0 reports; the others get the same data for free, which
        the reference's point-to-point gather cannot do).  Single-process
        worlds return ``[self]`` without touching the runtime."""
        import jax as _jax
        if _jax.process_count() == 1:
            return [self]
        import numpy as np
        from jax.experimental import multihost_utils
        rec = {
            "node": self.node_id,
            "num_nodes": self.num_nodes,
            "times_us": self.times_us,
            "counters": self.counters,
            "meta": self.meta,
        }
        payload = json.dumps(rec, default=str).encode()
        cap = _GATHER_BUF_BYTES - 4
        if len(payload) > cap:
            rec["meta"] = self._slim_meta()
            payload = json.dumps(rec, default=str).encode()
        if len(payload) > cap:
            raise ValueError(
                f"measurement payload ({len(payload)}B) exceeds the "
                f"{cap}B gather buffer even without meta")
        buf = np.zeros(_GATHER_BUF_BYTES, np.uint8)
        buf[:4] = np.frombuffer(
            np.uint32(len(payload)).tobytes(), dtype=np.uint8)
        buf[4:4 + len(payload)] = np.frombuffer(payload, dtype=np.uint8)
        rows = np.asarray(multihost_utils.process_allgather(buf))
        out = []
        for row in rows:
            n = int(np.frombuffer(row[:4].tobytes(), dtype=np.uint32)[0])
            rec = json.loads(row[4:4 + n].tobytes().decode())
            m = Measurements(node_id=int(rec["node"]),
                             num_nodes=int(rec["num_nodes"]))
            m.times_us.update({k: float(v)
                               for k, v in rec["times_us"].items()})
            m.counters.update({k: int(v)
                               for k, v in rec["counters"].items()})
            m.meta = rec["meta"]
            out.append(m)
        return out

    @classmethod
    def load(cls, out_dir: str) -> List["Measurements"]:
        """Read every ``<rank>.perf`` in a directory back into registries —
        the file-based analog of the rank-0 result gather
        (serializeResults/receiveAllMeasurements, Measurements.cpp:548-590)."""
        out = []
        for name in sorted(os.listdir(out_dir)):
            if not name.endswith(".perf"):
                continue
            try:
                node_id = int(name[:-5])
            except ValueError:
                continue   # stray non-rank .perf file (e.g. notes.perf)
            m = cls(node_id=node_id)
            with open(os.path.join(out_dir, name)) as f:
                for line in f:
                    key, value, unit = line.rstrip("\n").split("\t")
                    if unit == "us":
                        m.times_us[key] = float(value)
                    else:
                        m.counters[key] = int(value)
            out.append(m)
        return out


DEVICE_PLANE = "/device:"
#: the op line of a device plane in ``jax.profiler.ProfileData``
OPS_LINE = "XLA Ops"
#: host threads that run XLA's CPU programs (a backend with no device plane)
HOST_XLA_LINE = "tf_XLA"


def op_table(trace_dir: str) -> Optional[dict]:
    """The per-op table of the profiler trace under ``trace_dir``, read
    with ``jax.profiler.ProfileData``: the busiest device plane's
    ``XLA Ops`` line or, on a backend with no device plane (the CPU), the
    host's XLA threads.  ``{"plane", "busy_us", "ops": {op: {"us",
    "count"}}, "stages": {stage: us}}``, heaviest first; an op's stage is
    the ``trj.*`` scope that owns it (observability/stages.py), or
    ``"none"``.  None where no trace or no op is there."""
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return None
    best = None
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        ops: Dict[str, List[float]] = {}
        for line in plane.lines:
            if not (line.name == OPS_LINE if device
                    else line.name.startswith(HOST_XLA_LINE)):
                continue
            for e in line.events:
                # "%fusion.3 = u32[...] fusion(...)" on a device plane
                name = e.name.partition(" = ")[0].lstrip("%")
                if "::" in name or name.startswith("end: "):
                    continue   # thread-pool and thunk-end markers
                acc = ops.setdefault(name, [0.0, 0])
                acc[0] += e.duration_ns / 1e3
                acc[1] += 1
        busy = sum(us for us, _ in ops.values())
        if busy > 0 and (best is None or (device, busy) > best[0]):
            best = ((device, busy), plane.name, ops)
    if best is None:
        return None
    (_, busy), plane, ops = best
    by_stage: Dict[str, float] = defaultdict(float)
    for name, (us, _) in ops.items():
        by_stage[stage_of(name) or "none"] += us
    return {"plane": plane, "busy_us": busy,
            "ops": {name: {"us": us, "count": n} for name, (us, n) in
                    sorted(ops.items(), key=lambda kv: -kv[1][0])},
            "stages": dict(sorted(by_stage.items(), key=lambda kv: -kv[1]))}


def print_results(measurements: Iterable[Measurements],
                  file=None) -> Dict[str, Dict[str, float]]:
    """Rank-0 report: per-tag max/avg across nodes plus the ``[RESULTS]``
    line (printMeasurements, Measurements.cpp:592-702 — the reference prints
    per-rank phase columns and the total tuple count; max-over-ranks is the
    number that bounds the critical path in an SPMD phase).  Returns the
    aggregate dict it printed."""
    ms = list(measurements)
    agg: Dict[str, Dict[str, float]] = {}
    keys = sorted({k for m in ms for k in (*m.times_us, *m.counters)})
    for k in keys:
        vals = [m.times_us.get(k, m.counters.get(k, 0)) for m in ms]
        agg[k] = {"max": float(max(vals)), "avg": float(sum(vals) / len(vals))}
    print(f"[RESULTS] Nodes: {len(ms)}", file=file)
    total = sum(m.counters.get(RESULTS, 0) for m in ms) // max(1, len(ms))
    print(f"[RESULTS] Tuples: {total}", file=file)
    # per-rank failure classes (robustness/retry.py taxonomy, stamped into
    # meta by main.py): one degraded rank must be visible in the aggregate
    # summary, not only in that rank's own .info file.  "ok" ranks are
    # summarized; anything else is named rank by rank.
    classes = {m.node_id: str(m.meta.get("failure_class"))
               for m in ms if m.meta.get("failure_class") is not None}
    if classes:
        bad = {rank: c for rank, c in sorted(classes.items()) if c != "ok"}
        if bad:
            per_rank = " ".join(f"rank{rank}={c}" for rank, c in bad.items())
            print(f"[RESULTS] FailureClasses: {len(bad)}/{len(classes)} "
                  f"ranks not ok — {per_rank}", file=file)
        else:
            print(f"[RESULTS] FailureClasses: ok x{len(classes)}", file=file)
    # per-site fault-injection accounting (faults.FaultInjector.site_stats,
    # stamped into meta as "fault_sites" by main.py / the chaos runner): a
    # soak report must show which sites were exercised, not just that
    # FINJECT ticked.  Summed across ranks.
    sites: Dict[str, Dict[str, int]] = {}
    for m in ms:
        for site, st in (m.meta.get("fault_sites") or {}).items():
            acc = sites.setdefault(site, {"hits": 0, "fired": 0})
            acc["hits"] += int(st.get("hits", 0))
            acc["fired"] += int(st.get("fired", 0))
    if sites:
        per_site = " ".join(
            f"{site}={st['fired']}/{st['hits']}"
            for site, st in sorted(sites.items()))
        print(f"[RESULTS] FaultSites (fired/hits): {per_site}", file=file)
    for k in keys:
        unit = "us" if any(k in m.times_us for m in ms) else "count"
        print(f"[RESULTS] {k}: max {agg[k]['max']:.0f} {unit}, "
              f"avg {agg[k]['avg']:.0f} {unit}", file=file)
    return agg

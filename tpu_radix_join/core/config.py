"""Typed runtime configuration.

TPU-native replacement for the reference's compile-time constant header
(``core/Configuration.h:15-40``) plus its CMake ``-D`` switches
(``CMakeLists.txt:10-15``): one frozen dataclass whose derived quantities
(partition counts, packing layout, padded shuffle capacities) are computed
properties, so the relationships the reference spreads across four files
(``NetworkPartitioning.cpp:128-129``, ``LocalPartitioning.cpp:147-153``,
``BuildProbe.cpp:55-61``, ``GPUWrapper.cu:39-41``) live in one place.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class JoinConfig:
    """All knobs of the join pipeline.

    The reference equivalents:
      * ``network_fanout_bits``  -> ``NETWORK_PARTITIONING_FANOUT`` (Configuration.h:30)
      * ``local_fanout_bits``    -> ``LOCAL_PARTITIONING_FANOUT`` (Configuration.h:31)
      * ``payload_bits``         -> ``PAYLOAD_BITS`` (Configuration.h:38)
      * ``two_level``            -> ``ENABLE_TWO_LEVEL_PARTITIONING`` (Configuration.h:28)
      * ``allocation_factor``    -> ``ALLOCATION_FACTOR`` (Configuration.h:36); here it is
        the slack on the statically-shaped per-destination shuffle blocks rather than on
        a malloc'd pool, because XLA requires static shapes (SURVEY.md 7.2).
      * ``result_aggregation_node`` -> ``RESULT_AGGREGATION_NODE`` (Configuration.h:19)
      * ``assignment_policy``    -> AssignmentMap policy (AssignmentMap.cpp:41-43 is
        round-robin; "load_aware" realises the skew-aware API shape its ctor promises).
      * ``probe_algorithm``      -> selects among the BuildProbe / GPU probe-kernel
        families (BuildProbe.cpp chained table; kernels.cu probe / probe_count).
    """

    # --- partitioning geometry -------------------------------------------------
    network_fanout_bits: int = 5
    local_fanout_bits: int = 5
    two_level: bool = False

    # --- tuple layout ----------------------------------------------------------
    key_bits: int = 32           # 32 -> single uint32 key lane; 64 -> hi/lo lanes
    payload_bits: int = 27       # rid width contract (Configuration.h:38)
    # 32-bit count-path key-range discipline (the sort probe packs key+side
    # into one uint32, capping real keys at 2**31-3 = MAX_MERGE_KEY;
    # ops/merge_count.py):
    #   "narrow" — always the packed fast path; keys above the cap flip
    #              key_contract_violations (loud, never silent).
    #   "full"   — always the full-range 2-key lexicographic discipline
    #              (merge_count_per_partition_full): every sub-sentinel
    #              uint32 key (<= 0xFFFFFFFD) joins exactly, ~1.7x the
    #              packed sort cost.
    #   "auto"   — per join: Relation-driven entry points decide statically
    #              from the relations' key bounds (Relation.key_bound);
    #              join_arrays probes the device max key once (~2 HBM
    #              scans) — set narrow/full explicitly to skip the probe.
    # Irrelevant to key_bits=64 (always the wide 3-lane path), the bucket/
    # two-level, chunked, and materializing disciplines (never packed).
    key_range: str = "auto"

    # --- distribution ----------------------------------------------------------
    num_nodes: int = 1           # total mesh size (all devices, all hosts)
    num_hosts: int = 1           # >1 selects the hierarchical (dcn, ici) mesh
    mesh_axis: str = "nodes"
    result_aggregation_node: int = 0

    # --- shuffle data plane (Window) ------------------------------------------
    # "measured": run the histogram phase as its own program and compile the
    #   shuffle at the exact (pow2-rounded) worst-case block demand — the
    #   analog of the reference's runtime-sized windows (Window.cpp:168-177).
    # "static": skip the sizing pre-pass; capacity = local_size / N *
    #   allocation_factor (cheaper, can overflow under skew; overflow flips ok).
    window_sizing: str = "measured"
    allocation_factor: float = 1.5   # slack multiplier on padded blocks (static
                                     # window sizing + local bucket capacities)
    # Wire codec for the shuffle exchange (data/tuples.make_wire_spec):
    #   "off"  — two/three uint32 lanes per tuple on the wire (8/12 B), plus a
    #            separate per-sender count collective (the pre-codec format).
    #   "pack" — bounds-aware bit-packed blocks: fanout bits dropped from
    #            keys (restored positionally from per-partition header
    #            counts), key remainder and rid packed to the minimum lane
    #            budget implied by the key bound / relation sizes; the count
    #            side channel folds into the header, eliminating one
    #            collective per relation per exchange.
    #   "auto" — the engine (or the planner) packs only when the packed
    #            block is actually smaller than the raw lanes.
    # Note: packing masks key bits above the measured bound, so injected
    # corruption in those high bits (chaos exchange.corrupt_lane) is healed
    # rather than detected — keep "off" when chaos-testing lane corruption.
    exchange_codec: str = "off"
    # Staged exchange (parallel/window.block_all_to_all): split the [N, C]
    # block buffer into k column groups exchanged via k smaller sequenced
    # collectives, bounding live exchange-buffer memory to ~1/k.
    # 1 = fused single collective; 0 = auto (engine/planner picks by block
    # size); k > 1 = exactly k stages.
    exchange_stages: int = 1
    # Partition/reorder implementation (ops/radix scatter_to_blocks &
    # friends):
    #   "auto"   — fused Pallas partition kernel when the backend compiles
    #              Mosaic and the fanout fits MAX_PARTITIONS, else the
    #              XLA sort path (the fallback ticks PARTFALLBACK).
    #   "sort"   — force the XLA sort-based scatter (the pre-kernel path).
    #   "pallas" / "pallas_interpret" — force the fused kernel (interpret
    #              runs it through the Pallas interpreter: CPU tier-1
    #              parity tests and host-mesh benches).
    partition_impl: str = "auto"
    # "auto" and "xla" both mean lax.sort, the only sort (ops/sorting.py).
    sort_impl: str = "auto"

    # --- policies --------------------------------------------------------------
    assignment_policy: str = "round_robin"   # or "load_aware"
    probe_algorithm: str = "sort"            # "sort" | "bucket"
    match_rate_cap: int = 8                  # max materialized matches per outer tuple
    chunk_size: Optional[int] = None         # out-of-core probe chunking (LD kernels)
    max_retries: int = 0                     # capacity-shortfall retries with doubled
                                             # static shapes (0 = detect only, the
                                             # reference's abort-on-failure parity)

    # --- resilience (robustness/) ----------------------------------------------
    # Terminal behavior once max_retries capacity doublings are exhausted:
    #   "none"    — return ok=False with diagnostics (detect-and-report).
    #   "chunked" — degrade to the out-of-core chunked count (ops/chunked.py),
    #               whose only capacity is the caller-chosen slab size; the
    #               result carries diagnostics["degraded"] = "chunked".
    fallback: str = "none"
    # Out-of-core grid engine (ops/chunked.chunked_join_grid) used by the
    # chunked fallback and verify="repair":
    #   "off"  — synchronous loop (one probe, one readback, one checkpoint
    #            fsync per pair, in program order).
    #   "on"   — pipelined engine: once-per-row inner sorts probed by
    #            binary search, double-buffered chunk prefetch, deferred
    #            readbacks, write-behind checkpoints.
    #   "auto" — pipelined for any grid larger than a single chunk pair.
    grid_pipeline: str = "auto"
    # Pause between capacity-grow retry attempts (0 = immediate, the
    # pre-robustness behavior).  Exponential with deterministic jitter
    # (robustness/retry.RetryPolicy): attempt k sleeps
    # min(retry_backoff_s * retry_backoff_mult**k, retry_backoff_max_s).
    retry_backoff_s: float = 0.0
    retry_backoff_mult: float = 2.0
    retry_backoff_max_s: float = 30.0
    retry_jitter: float = 0.0

    # --- skew handling ---------------------------------------------------------
    # Probe-level hot-partition splitting (operators/skew.py; the reference's
    # dormant SD::OPT skew machinery, kernels_optimized.cu:301-344,864-943):
    # partitions whose global OUTER weight exceeds skew_threshold x the mean
    # total weight (and whose inner side is cheap enough to replicate) are
    # split — inner side replicated via all_gather, outer side spread by a
    # rid hash — instead of owned by one node.  None disables.  Composes
    # with the sort probe AND the two-level/bucket discipline (the
    # reference's own skew locus is its partitioned probe kernels,
    # kernels_optimized.cu:301-943: replicated hot R simply joins the local
    # radix pass); only the chunked out-of-core probe is excluded (see
    # __post_init__).  Requires network fanout <= 5 (the hot set is a
    # uint32 bit mask) and measured window sizing.
    skew_threshold: Optional[float] = None

    # --- data placement --------------------------------------------------------
    # How Relation-driven entry points materialize shards (SURVEY.md §7.4
    # item 5): "auto" generates on device when the relation kind supports it
    # — since r4 that is every kind (unique/modulo: Feistel walk / residues;
    # zipf: integer-table sampler), all bit-identical to the host twins —
    # with host generation + device_put as the fallback for future kinds;
    # "host" forces the host path (useful for debugging); "device" requires
    # on-device generation.
    generation: str = "auto"

    # --- integrity verification (robustness/verify.py) -------------------------
    # End-to-end per-partition integrity checksums (count + sum + xor-fold of
    # key lanes), computed over the pristine inputs before the exchange and
    # re-derived from the pipeline after exchange / after local sort:
    #   "off"    — no checksums (production default; zero overhead).
    #   "check"  — mismatch => ok=False, failure_class="data_corruption"
    #              (VFAIL counter + a data_corruption event).
    #   "repair" — mismatch => recompute only the damaged network partitions
    #              from the retained pristine inputs via the chunked grid
    #              machinery (VREPAIR counter + grid_pair spans), then return
    #              a corrected ok=True result.
    verify: str = "off"

    # --- instrumentation -------------------------------------------------------
    debug_checks: bool = False   # runtime conservation invariants (JOIN_ASSERT analog)
    # Phase-split timing (Measurements.cpp:139-141 JMPI/JPROC columns): run
    # the shuffle and the local probe as two programs so host timers see each
    # phase, instead of one fused program (which XLA may overlap/fuse across
    # the phase boundary — faster, but host-opaque).  Costs the fusion.
    measure_phases: bool = False

    def __post_init__(self):
        if self.network_fanout_bits < 0 or self.local_fanout_bits < 0:
            raise ValueError("fanout bits must be non-negative")
        if self.key_bits not in (32, 64):
            raise ValueError("key_bits must be 32 or 64")
        if self.num_nodes < 1:
            raise ValueError("num_nodes must be >= 1")
        if self.num_hosts < 1 or self.num_nodes % self.num_hosts:
            raise ValueError("num_nodes must divide evenly over num_hosts")
        if self.assignment_policy not in ("round_robin", "load_aware"):
            raise ValueError(f"unknown assignment policy {self.assignment_policy!r}")
        if self.probe_algorithm not in ("sort", "bucket"):
            raise ValueError(f"unknown probe algorithm {self.probe_algorithm!r}")
        if self.allocation_factor < 1.0:
            raise ValueError("allocation_factor must be >= 1.0")
        if self.window_sizing not in ("measured", "static"):
            raise ValueError(f"unknown window sizing mode {self.window_sizing!r}")
        if self.exchange_codec not in ("off", "pack", "auto"):
            raise ValueError(
                f"unknown exchange codec {self.exchange_codec!r} "
                "(expected 'off', 'pack', or 'auto')")
        if self.exchange_stages < 0:
            raise ValueError(
                "exchange_stages must be >= 0 (0 = auto, 1 = fused, "
                "k > 1 = staged)")
        if self.partition_impl not in (
                "auto", "sort", "pallas", "pallas_interpret"):
            raise ValueError(
                f"unknown partition impl {self.partition_impl!r} (expected "
                "'auto', 'sort', 'pallas', or 'pallas_interpret')")
        if self.sort_impl not in ("auto", "xla"):
            raise ValueError(
                f"unknown sort impl {self.sort_impl!r} (expected "
                "'auto' or 'xla')")
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if self.fallback not in ("none", "chunked"):
            raise ValueError(f"unknown fallback mode {self.fallback!r}")
        if self.grid_pipeline not in ("off", "on", "auto"):
            raise ValueError(
                f"unknown grid pipeline mode {self.grid_pipeline!r}")
        if self.retry_backoff_s < 0 or self.retry_backoff_max_s < 0:
            raise ValueError("retry backoff delays must be >= 0")
        if self.retry_backoff_mult < 1.0:
            raise ValueError("retry_backoff_mult must be >= 1.0")
        if not 0.0 <= self.retry_jitter <= 1.0:
            raise ValueError("retry_jitter must be in [0, 1]")
        if self.generation not in ("auto", "host", "device"):
            raise ValueError(f"unknown generation mode {self.generation!r}")
        if self.key_range not in ("auto", "narrow", "full"):
            raise ValueError(f"unknown key range mode {self.key_range!r}")
        if self.key_range != "auto" and self.key_bits == 64:
            raise ValueError(
                "key_range selects among 32-bit count disciplines; "
                "key_bits=64 always takes the wide hi/lo path")
        if self.skew_threshold is not None:
            if self.skew_threshold <= 0:
                raise ValueError("skew_threshold must be positive")
            if self.chunk_size:
                raise ValueError(
                    "skew splitting does not compose with the chunked "
                    "out-of-core probe: the split replicates the hot inner "
                    "side onto every device (operators/skew.py), growing "
                    "exactly the resident working set chunking exists to "
                    "bound — for skewed out-of-core joins run the grid join "
                    "(ops/chunked.chunked_join_grid), whose per-pair probes "
                    "need no hot-side replication")
            if self.network_fanout_bits > 5:
                raise ValueError(
                    "skew splitting supports network fanout <= 5 "
                    "(hot set is a uint32 bit mask)")
            if self.window_sizing != "measured":
                raise ValueError(
                    "skew splitting requires window_sizing='measured' "
                    "(hot detection reads the sizing program's histograms)")
        if self.chunk_size is not None and (
                self.chunk_size < 1
                or self.two_level or self.probe_algorithm == "bucket"):
            raise ValueError(
                "chunk_size requires the sort probe (chunking bounds the "
                "probe working set; the bucketized path is already blocked)")
        if self.verify not in ("off", "check", "repair"):
            raise ValueError(f"unknown verify mode {self.verify!r}")
        if self.verify != "off" and self.measure_phases:
            raise ValueError(
                "verify does not compose with measure_phases: the split "
                "driver consumes the shuffle program's outputs positionally "
                "(operators/hash_join._run_split) and cannot carry the "
                "checksum outputs through the phase boundary — use the "
                "fused pipeline (measure_phases=False) for verified runs")

    # --- derived geometry ------------------------------------------------------
    @property
    def sort_probe(self) -> bool:
        """True when the (chunk-free) flat sort-merge probe discipline is
        active — the predicate that selects the 31-bit merge-count packing
        (ops/merge_count.MAX_MERGE_KEY) as the key-range contract."""
        return (not self.two_level and self.probe_algorithm != "bucket"
                and not self.chunk_size)

    @property
    def bucket_path(self) -> bool:
        """True when local processing goes through the second radix pass +
        bucketized probe (two-level discipline)."""
        return self.two_level or self.probe_algorithm == "bucket"

    @property
    def mesh_axes(self):
        """Axis name(s) the pipeline's collectives run over: the flat
        ``mesh_axis`` string, or the ``("dcn", "ici")`` pair when the mesh is
        hierarchical (num_hosts > 1) so the shuffle aggregates cross-host
        traffic (parallel/window.py)."""
        return self.mesh_axis if self.num_hosts == 1 else ("dcn", "ici")

    @property
    def network_partition_count(self) -> int:
        """NETWORK_PARTITIONING_COUNT = 1 << FANOUT (Configuration.h:33)."""
        return 1 << self.network_fanout_bits

    @property
    def local_partition_count(self) -> int:
        """LOCAL_PARTITIONING_COUNT = 1 << FANOUT (Configuration.h:34)."""
        return 1 << self.local_fanout_bits

    @property
    def total_fanout_bits(self) -> int:
        return self.network_fanout_bits + (self.local_fanout_bits if self.two_level else 0)

    @property
    def total_partition_count(self) -> int:
        return 1 << self.total_fanout_bits

    def shuffle_block_capacity(self, local_size: int) -> int:
        """Static per-destination block size for the all_to_all shuffle.

        The reference sizes each rank's RMA window exactly from the global
        histogram (Window.cpp:168-177); XLA needs the shape before the data
        exists, so we take the expected per-destination share with
        ``allocation_factor`` slack, rounded up to a multiple of 8 lanes.
        Overflow is detected at runtime (Window.assert_all_tuples_written).
        """
        n = max(1, self.num_nodes)
        cap = int(math.ceil(local_size / n * self.allocation_factor))
        return max(8, -(-cap // 8) * 8)

    def bucket_capacity(self, total_slots: int, num_buckets: int) -> int:
        """Static per-bucket capacity for the local partitioning pass: expected
        share of ``total_slots`` with ``allocation_factor`` slack (the analog
        of LocalPartitioning's cacheline-padded sub-partition sizing,
        LocalPartitioning.cpp:178-181)."""
        cap = int(math.ceil(total_slots / max(1, num_buckets) * self.allocation_factor))
        return max(8, -(-cap // 8) * 8)

    # --- key/rid packing contract ---------------------------------------------
    @property
    def key_remainder_bits(self) -> int:
        """Key bits that survive compression (partition bits are implied by
        partition membership — NetworkPartitioning.cpp:128-129)."""
        return self.key_bits - self.network_fanout_bits

    @property
    def probe_shift_bits(self) -> int:
        """Bits below the probe-comparison key remainder: the analog of
        ``shiftBits = 5 + 27 (+5)`` in BuildProbe.cpp:55-61 / GPUWrapper.cu:39-41.
        In the SoA layout the rid lives in its own lane, so only fanout bits
        shift out of the key lane."""
        return self.total_fanout_bits

    def bucket_count_for(self, inner_size: int) -> int:
        """N = next power of two >= inner partition size (BuildProbe.cpp:59-61)."""
        return _next_pow2(max(1, inner_size))

    def replace(self, **kw) -> "JoinConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Knobs of the resident join service (tpu_radix_join/service/).

    Lives beside :class:`JoinConfig` because the pair travels together —
    a session is (how to join) x (how to serve) — but stays a separate
    dataclass: none of these fields changes the compiled program, so they
    must never enter plan-cache or checkpoint fingerprints.
    """

    # --- admission (service/admission.py) --------------------------------
    max_queue_depth: int = 64        # pending queries across all tenants
    tenant_quota: int = 8            # in-flight queries per tenant

    # --- deadlines (service/deadline.py) ---------------------------------
    default_deadline_s: Optional[float] = None   # per-query override wins;
                                                 # None = unlimited

    # --- circuit breaker (service/breaker.py) ----------------------------
    breaker_threshold: int = 3       # consecutive backend failures to trip
    breaker_cooldown_s: float = 30.0  # open -> half-open promotion delay
    #: serve from the CPU engine while the breaker is open; off by default
    #: so that a device outage fails queries loudly instead of answering
    #: them on the host (main.py --cpu-fallback)
    cpu_fallback: bool = False

    # --- outcome retention (service/session.py) --------------------------
    outcomes_keep: int = 512         # recent QueryOutcomes kept in memory;
                                     # the SLO recorder owns the aggregates,
                                     # so a week-long worker must not grow
                                     # this list with every query served

    # --- placed-relation LRU (service/session.py) ------------------------
    place_cache_max: int = 8         # device-resident placed-batch entries;
                                     # the HBM bound on input reuse (was the
                                     # hard-coded _PLACE_CACHE_MAX)

    # --- result cache (service/resultcache.py) ---------------------------
    # Content-fingerprint result cache: a repeated query on unchanged
    # inputs short-circuits before admission.  0 disables (the default —
    # turning whole-result reuse on is an operator decision, not a silent
    # behavior change); entries expire after result_cache_ttl_s (None =
    # no TTL) and invalidate on spec/epoch/config change via the content
    # fingerprint itself.
    result_cache_max: int = 0
    result_cache_ttl_s: Optional[float] = None

    # --- inter-query micro-batching (service/microbatch.py) --------------
    # Bounded window coalescer: small same-shape joins arriving within
    # batch_window_ms fuse into ONE device program (composite-key batched
    # count).  0.0 disables; batch_max_queries bounds one fused batch.
    batch_window_ms: float = 0.0
    batch_max_queries: int = 8

    # --- incremental delta-merge joins (service/resident.py) -------------
    # Explicit HBM budget for device-resident sorted unions kept across
    # queries (O(N+Δ) serving: sort only the per-query delta, merge into
    # the resident state, binary-search probe).  0 disables.
    resident_budget_bytes: int = 0

    def __post_init__(self):
        if self.max_queue_depth < 1:
            raise ValueError("max_queue_depth must be >= 1")
        if self.tenant_quota < 1:
            raise ValueError("tenant_quota must be >= 1")
        if (self.default_deadline_s is not None
                and self.default_deadline_s < 0):
            raise ValueError("default_deadline_s must be >= 0 (or None)")
        if self.breaker_threshold < 1:
            raise ValueError("breaker_threshold must be >= 1")
        if self.breaker_cooldown_s < 0:
            raise ValueError("breaker_cooldown_s must be >= 0")
        if self.outcomes_keep < 1:
            raise ValueError("outcomes_keep must be >= 1")
        if self.place_cache_max < 0:
            raise ValueError("place_cache_max must be >= 0 (0 = no reuse)")
        if self.result_cache_max < 0:
            raise ValueError("result_cache_max must be >= 0 (0 = disabled)")
        if (self.result_cache_ttl_s is not None
                and self.result_cache_ttl_s <= 0):
            raise ValueError("result_cache_ttl_s must be > 0 (or None)")
        if self.batch_window_ms < 0:
            raise ValueError("batch_window_ms must be >= 0 (0 = disabled)")
        if self.batch_max_queries < 2:
            raise ValueError("batch_max_queries must be >= 2 (a batch of "
                             "one is the serial path)")
        if self.resident_budget_bytes < 0:
            raise ValueError(
                "resident_budget_bytes must be >= 0 (0 = disabled)")

    def replace(self, **kw) -> "ServiceConfig":
        return dataclasses.replace(self, **kw)

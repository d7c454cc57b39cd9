"""Perf-regression gate CLI: fresh BENCH json vs a committed baseline.

    python tools_check_regress.py BENCH_fused.json --baseline BASELINE.json
    python tools_check_regress.py BENCH_fused.json --baseline BASELINE.json \
        --threshold 0.25 --tag-threshold JTOTAL=0.10 --allow SWINALLOC

Prints the per-tag delta table (worse% > 0 means the fresh run regressed:
a cost tag grew or a rate tag dropped) and exits

    0  no tag past its threshold (or the baseline has no numeric tags),
    1  at least one regression,
    2  usage / IO errors (unreadable files, bad --tag-threshold spec).

``--strict`` also fails tags present in the baseline but missing from the
fresh result — a silently vanished measurement is itself a signal.  The
comparison logic lives in tpu_radix_join.observability.regress; bench.py
runs the same check in-process via ``--check-regress BASELINE.json``.

Direction is per-tag and automatic: serve-mode SLO tags are pinned
lower-is-better (``slo_p99_ms`` and friends are latencies;
``admission_rejection_rate`` / ``deadline_miss_rate`` / ``degraded_rate``
regress when they GROW, even though "rate" normally marks a throughput),
so a ``--serve-bench`` BENCH json gates correctly with no extra flags.

The ``--exchange-bench`` footprint tags are pinned the same way:
``wirebytes`` (total bytes the all_to_all actually shipped under the
active codec), ``peak_exchange_bytes`` (largest live allocation of one
staged collective), and ``bytes_per_tuple`` are lower-is-better — a codec
or staging change that inflates the wire regresses even when the join
stays correct and the wall time holds.  The BENCH headline ``value`` is
the wire *reduction* ratio (raw 8 B per tuple over packed bytes per
tuple), which keeps the headline higher-is-better like every other bench.

The observability counters are pinned lower-is-better too: ``PLANDRIFT``
(planner/audit.py — |actual - predicted| join time as a percent of the
cost model's prediction) regresses when it GROWS, catching stale device
profiles in CI before they surface as mispredicted plans; ``PMBUNDLE``
(forensics bundles written) and ``WDOGTRIP`` (hang-watchdog trips) count
deaths per round, so a bench round that starts emitting bundles fails
the gate even if the surviving joins kept their speed.

The calibration-loop tags are pinned lower-is-better as well:
``NCOMPILE`` / ``COMPILEMS`` (backend compiles seen via jax.monitoring —
observability/compilemon.py) regress when a round starts recompiling
warm shapes; ``fit_residual`` and ``stale_constants``
(tools_profile_fit.py) regress when the fitted profile's spread grows or
more constants drift away from the clock.

A ``--partition-bench`` BENCH json gates the destination-grouping A/B
(ops/pallas/partition.py fused kernel vs the sort-based scatter):

    {"metric": "partition_fused_speedup", "value": 1.71, "size": 16777216,
     "num_blocks": 8, "partition_ms": 4757.0, "partition_kernel_ms": 2873.0,
     "partition_sort_ms": 8121.0, "partition_unit_ms": 0.0856}

The headline ``value`` is the wall speedup (sort arm over fused arm,
higher is better); ``partition_ms`` / ``partition_kernel_ms`` /
``partition_sort_ms`` are walls and ``partition_unit_ms`` is the reduced
ms/Mtuple/pass constant the profile fitter recovers — all pinned
lower-is-better, alongside the ``PARTFALLBACK`` counter (silent degrades
to the XLA sort path; on a TPU backend more of them means the fused
kernel stopped being auto-selected).

A ``--recovery-bench`` BENCH json gates the elastic-recovery A/B
(robustness/membership.py + recovery.py — kill-1-of-8 partition-level
recovery vs the cold full restart it replaces):

    {"metric": "elastic_recovery_speedup", "value": 2.34, "size": 262144,
     "num_partitions": 16, "recover_ms": 334.9, "cold_restart_ms": 784.3,
     "recovern": 2, "resumed_partitions": 14, "ranklost": 1, "mepoch": 1}

The headline ``value`` is the wall ratio (cold restart over recovery,
higher is better).  ``recover_ms``/``cold_restart_ms`` are walls;
``recovern`` (partitions recomputed — the bench refuses to bless a run
where it reaches the partition count, i.e. a veiled restart),
``ranklost``, and ``mepoch`` (membership epochs burned per round) are
pinned lower-is-better: a fleet that starts losing more ranks or
fencing more epochs per round regresses even when each individual
recovery still lands oracle-exact.

The ``--recovery-bench --straggle f`` arm gates the straggler-hedging
tail A/B (robustness/straggler.py — speculative recompute of a slow
rank's unfinished partitions through the manifest fence):

    {"metric": "straggler_hedge_tail_speedup", "value": 2.62,
     "size": 131072, "num_partitions": 32, "straggle_factor": 4.0,
     "hedged_ms": 526.3, "unhedged_ms": 1379.9, "hedgewin": 4,
     "specwaste": 0, "recovern": 4, "manifest_total": 131072}

The headline ``value`` is the tail ratio (unhedged wall over hedged
wall, higher is better).  ``hedged_ms``/``unhedged_ms`` are walls and
``specwaste`` counts speculative recomputes the original won anyway —
all lower-is-better — while ``hedgewin`` (fence wins per hedge round)
is pinned higher-is-better: fewer wins at the same hedge count means
the detector started hedging partitions that were about to finish.

A ``--fleet-bench`` BENCH json gates the crash-only fleet failover A/B
(service/fleet.py + journal.py — SIGKILL one of four supervised serve
workers mid-query vs the cold supervisor restart it replaces):

    {"metric": "fleet_failover_speedup", "value": 7.72, "workers": 4,
     "queries": 5, "failover_ms": 518.1, "cold_restart_ms": 3998.7,
     "failover": 1, "replayn": 1, "jdepth": 1, "wincarn": 4,
     "worker_restarts": 0, "double_exec": 0}

The headline ``value`` is the wall ratio (cold restart over failover,
higher is better).  ``failover_ms``/``cold_restart_ms`` are walls;
``failover`` (mid-query deaths failed over), ``replayn`` (journal
intents replayed), ``jdepth`` (peak unacknowledged journal depth),
``wincarn`` (worker incarnations spawned), and ``worker_restarts`` are
pinned lower-is-better: a fleet that starts burning more incarnations
or replays per round regresses even when each query still lands
oracle-exact.  ``double_exec`` is pinned to ZERO — it counts
fingerprints with more than one journaled outcome, the exactly-once
invariant, and because its baseline is 0 any growth is an infinite
relative delta: a single double execution fails this gate at every
threshold, no ``--allow`` precedent.

A ``--serve-throughput-bench`` BENCH json gates the serving fast-path
A/B (service/resultcache.py result cache, service/microbatch.py +
ops/merge_delta.py fused micro-batches, service/resident.py delta
merges):

    {"metric": "serve_fastpath_speedup", "value": 5.92,
     "unit": "serial_over_fused_wall_q4",
     "cache_cold_latency_ms": 441.1, "cache_hit_latency_ms": 0.14,
     "cache_speedup": 3088.0, "cache_hit_rate": 0.33,
     "batch_speedup_2": 4.1, "batch_speedup_4": 5.9,
     "batch_speedup_8": 6.6, "batch_fuse_ratio": 4.67,
     "delta_speedup_16": 6.9, "delta_speedup_64": 8.3,
     "delta_speedup_256": 8.2, "delta_speedup": 8.3,
     "rchit": 1, "rcmiss": 2, "batchn": 6, "batchq": 28,
     "deltamerge": 9, "resbytes": 1179648, "statusz_polls": 5,
     "double_exec": 0}

The headline ``value`` is the Q=4 fused-over-serial wall speedup
(higher is better), and every ``*_speedup`` plus ``cache_hit_rate`` and
``batch_fuse_ratio`` gate higher-is-better — a fast path that stops
firing shows up as a collapsed ratio before it shows up as latency.
``rchit`` / ``deltamerge`` are pinned higher-is-better (fewer
whole-query amortization wins at the same traffic means a tier went
dark) while ``rcmiss`` is a cost; ``batchn`` / ``batchq`` /
``resbytes`` / ``statusz_polls`` are declared neutral (traffic- and
budget-shaped descriptors whose gated observables are the ratios).
``double_exec`` rides the --fleet-bench zero pin: the bench's
mid-batch ``fleet.worker_kill`` leg must keep the journal exactly-once
even while a fused group dies on a worker's pipe.

The ``--recovery-bench --grow`` arm gates mid-run admission vs fixed
survivors (rank admission re-expanding the assignment map):

    {"metric": "elastic_grow_speedup", "value": 1.18, "size": 524288,
     "num_partitions": 32, "grown_ms": 20.5, "fixed_ms": 24.2,
     "recovern": 18, "resumed_partitions": 14, "rankjoin": 1,
     "survivors_fixed": 8, "survivors_grown": 9}

``grown_ms``/``fixed_ms`` are the critical-path recompute walls (the
slowest single survivor's share — what decides when a data-parallel
epoch completes) and gate lower-is-better; ``rankjoin`` is declared
neutral (a grow arm admits by design — losses regress, joins don't).

The static-analysis counters gate the same way: ``lint_findings`` and
``stale_baseline`` (``tools_lint.py --json`` — live graftlint findings
and baseline suppressions whose finding was already fixed) are pinned
lower-is-better, so a convention regression (a stray direct sort, an
unpinned counter tag, an implicit hot-path host sync) fails this gate
exactly like a perf regression.  The lint rules themselves, their
baseline discipline, and the ``--transfer-guard`` runtime twin are
documented in tools_lint.py.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tpu_radix_join.observability.regress import (DEFAULT_THRESHOLD,
                                                  check_files,
                                                  parse_tag_thresholds)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tools_check_regress.py",
        description="Compare a fresh result JSON against a perf baseline.")
    p.add_argument("fresh", help="fresh result (BENCH_*.json or any flat "
                                 "JSON of numeric tags)")
    p.add_argument("--baseline", required=True,
                   help="baseline JSON (e.g. BASELINE.json)")
    p.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                   help="default relative worsening allowed per tag "
                        "(default %(default)s)")
    p.add_argument("--tag-threshold", action="append", default=[],
                   metavar="TAG=REL",
                   help="per-tag override, repeatable (e.g. JTOTAL=0.10)")
    p.add_argument("--allow", action="append", default=[], metavar="TAG",
                   help="tag allowed to regress this round, repeatable")
    p.add_argument("--strict", action="store_true",
                   help="also fail baseline tags missing from the fresh "
                        "result")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        tag_thr = parse_tag_thresholds(args.tag_threshold)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        code, report = check_files(
            args.fresh, args.baseline, threshold=args.threshold,
            tag_thresholds=tag_thr, allow=args.allow, strict=args.strict)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(report)
    return code


if __name__ == "__main__":
    sys.exit(main())

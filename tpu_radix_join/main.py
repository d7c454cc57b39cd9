"""Driver CLI: the ``main.cpp`` analog, with runtime flags.

The reference hard-codes its workload (20M tuples/node, seed 1234+rank,
main.cpp:70-71,94) and parses no arguments (main.cpp:28); every knob is a
compile-time constant.  Here the same driver flow — init measurements, size
the pool, generate relations, run the join, aggregate + store results
(main.cpp:28-149) — is a proper CLI over the typed JoinConfig.

Usage:
    python -m tpu_radix_join.main --tuples-per-node 1048576 --nodes 1
    python -m tpu_radix_join.main --nodes 8 --outer-kind zipf --zipf-theta 0.75 \
        --assignment load_aware
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tpu_radix_join",
        description="Distributed radix hash join on a TPU mesh")
    p.add_argument("--tuples-per-node", type=int, default=1 << 20,
                   help="tuples per node per relation (reference: 20M, main.cpp:70)")
    p.add_argument("--nodes", type=int, default=0,
                   help="mesh size (0 = all visible devices)")
    p.add_argument("--hosts", type=int, default=1,
                   help="hosts in the mesh; >1 builds the hierarchical "
                        "(dcn, ici) mesh with the two-stage shuffle")
    p.add_argument("--network-fanout", type=int, default=5,
                   help="network radix bits (Configuration.h:30)")
    p.add_argument("--local-fanout", type=int, default=5)
    p.add_argument("--two-level", action="store_true",
                   help="enable second-level partitioning (Configuration.h:28)")
    p.add_argument("--probe", choices=["sort", "bucket"], default="sort")
    p.add_argument("--assignment", choices=["round_robin", "load_aware"],
                   default="round_robin")
    p.add_argument("--window-sizing", choices=["measured", "static"],
                   default="measured")
    p.add_argument("--chunk-size", type=int, default=None,
                   help="stream the probe in slabs of this many tuples "
                        "(out-of-core LD mode)")
    p.add_argument("--max-retries", type=int, default=0,
                   help="capacity-shortfall retries with doubled shapes")
    p.add_argument("--retry-backoff", type=float, default=0.0,
                   help="seconds to pause before the first capacity retry "
                        "(doubles each attempt, robustness/retry.py); 0 = "
                        "immediate")
    p.add_argument("--fallback", choices=["none", "chunked"], default="none",
                   help="after max-retries capacity doublings still "
                        "overflow: 'chunked' degrades to the out-of-core "
                        "count instead of returning ok=False")
    p.add_argument("--verify", choices=["off", "check", "repair"],
                   default="off",
                   help="end-to-end integrity verification (robustness/"
                        "verify.py): per-partition count/sum/xor checksums "
                        "of the key lanes, computed before the exchange and "
                        "re-derived after it (and after the local radix "
                        "pass on the bucket path).  'check' fails a "
                        "mismatched join with failure_class="
                        "data_corruption; 'repair' recomputes only the "
                        "damaged partitions out-of-core and returns the "
                        "corrected count (VREPAIR counter)")
    p.add_argument("--exchange-codec", choices=["off", "pack", "auto"],
                   default="off",
                   help="shuffle wire codec (data/tuples.make_wire_spec): "
                        "'pack' bit-packs key remainders + rids to the "
                        "bounds measured by the sizing pre-pass and folds "
                        "the count side channel into the packed header "
                        "(one collective per relation per exchange); "
                        "'auto' packs only when the packed block beats the "
                        "raw 8/12 B lanes")
    p.add_argument("--exchange-stages", type=int, default=1, metavar="K",
                   help="staged exchange (parallel/window.py): split each "
                        "[N, C] block buffer into K column groups exchanged "
                        "by K sequenced collectives, bounding live exchange "
                        "memory to ~1/K.  1 = fused single collective, "
                        "0 = auto (stage 4-ways once blocks are >= 4096 "
                        "slots)")
    p.add_argument("--partition-impl",
                   choices=["auto", "sort", "pallas", "pallas_interpret"],
                   default="auto",
                   help="partition/reorder implementation (ops/radix.py): "
                        "'auto' takes the fused Pallas histogram-scan-"
                        "scatter kernel when the backend compiles Mosaic "
                        "and the fanout fits, else the XLA sort path "
                        "(fallback ticks PARTFALLBACK and logs once); "
                        "'sort' forces the sort-based scatter; 'pallas"
                        "_interpret' runs the kernel interpreted (CPU "
                        "parity/bench)")
    p.add_argument("--sort-impl",
                   choices=["auto", "xla", "pallas", "pallas_interpret"],
                   default="auto",
                   help="sort implementation behind every hot reorder "
                        "(ops/sorting.py): 'auto' and 'xla' are lax.sort, "
                        "which beats the radix sort at every size measured "
                        "on a TPU v5e (a 20M x 20M join: 75 ms against "
                        "2647 ms); 'pallas' forces the Pallas LSD radix "
                        "sort (ops/pallas/radix_sort.py) for 1-D uint32 "
                        "sorts; 'pallas_interpret' runs that kernel "
                        "interpreted (CPU parity/bench)")
    p.add_argument("--cpu-fallback", action="store_true",
                   help="if device/mesh init fails, rebuild the engine over "
                        "host CPU devices (loud [DEGRADE] warning) instead "
                        "of aborting; in serve mode, answer from the CPU "
                        "engine while the circuit breaker is open instead "
                        "of failing those queries")
    p.add_argument("--grid-chunk-tuples", type=int, default=None,
                   help="run the out-of-core grid join (ops/chunked.py) "
                        "streaming both relations in chunks of this many "
                        "tuples; single-node only")
    p.add_argument("--grid-pipeline", choices=["off", "on", "auto"],
                   default="auto",
                   help="out-of-core grid engine: 'on' overlaps chunk "
                        "prefetch, probe compute, host readbacks, and "
                        "checkpoint flushes (inner chunks sorted once per "
                        "grid row); 'off' keeps the synchronous "
                        "one-pair-at-a-time loop (the A/B lever); 'auto' "
                        "pipelines any grid larger than one chunk pair "
                        "(planner plans may override auto)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="grid mode: directory for the slab-boundary "
                        "checkpoint file (atomic save after every chunk "
                        "pair; see --resume)")
    p.add_argument("--resume", action="store_true",
                   help="grid mode: resume from the checkpoint in "
                        "--checkpoint-dir (default: a fresh run removes any "
                        "stale checkpoint first)")
    p.add_argument("--skew-threshold", type=float, default=None,
                   help="split partitions heavier than this multiple of the "
                        "mean (replicate inner / spread outer); off by default")
    p.add_argument("--debug-checks", action="store_true",
                   help="per-partition conservation invariants "
                        "(JOIN_ASSERT analog; extra passes)")
    p.add_argument("--transfer-guard", choices=["off", "log", "disallow"],
                   default="off",
                   help="arm jax.transfer_guard around the join: 'log' "
                        "prints every implicit device<->host transfer, "
                        "'disallow' raises on one — the runtime twin of "
                        "tools_lint.py's static sync-point rule (explicit "
                        "utils.hostsync.host_readback stays legal under "
                        "both; data generation/placement is outside the "
                        "guard, matching the reference timing bracket)")
    p.add_argument("--measure-phases", action="store_true",
                   help="run shuffle and probe as separate programs so "
                        ".perf carries JMPI and JPROC columns (costs the "
                        "cross-phase fusion)")
    p.add_argument("--generation", choices=["auto", "host", "device"],
                   default="auto",
                   help="relation materialization: on-device sharded "
                        "generation when supported (auto/device) or host "
                        "numpy + transfer (host)")
    p.add_argument("--key-range", choices=["auto", "narrow", "full"],
                   default="auto",
                   help="32-bit count-path key discipline: 'narrow' packs "
                        "key+side into one uint32 (keys < 2^31-2, fastest), "
                        "'full' takes every sub-sentinel uint32 key via the "
                        "2-key lexicographic sort (~1.7x), 'auto' decides "
                        "from the generated relations' static key bounds")
    p.add_argument("--outer-kind", choices=["unique", "modulo", "zipf"],
                   default="unique")
    p.add_argument("--modulo", type=int, default=None)
    p.add_argument("--zipf-theta", type=float, default=0.75)
    p.add_argument("--seed", type=int, default=1234,
                   help="base seed (reference: srand(1234+nodeId), main.cpp:94)")
    p.add_argument("--output-dir", default=None,
                   help="experiment dir for .perf/.info files (default: none)")
    p.add_argument("--timeline-dir", default=None,
                   help="export this rank's phase spans + robustness/planner "
                        "instant events as Chrome trace-event JSON "
                        "(<rank>.spans.json; merge ranks with "
                        "tools_make_report.py --emit-timeline, load in "
                        "Perfetto)")
    p.add_argument("--metrics-interval", type=float, default=0.0,
                   metavar="SEC",
                   help="sample host RSS, device HBM bytes_in_use, and the "
                        "counter registry every SEC seconds into "
                        "<rank>.metrics.jsonl under --timeline-dir (or "
                        "--output-dir); 0 = off")
    p.add_argument("--trace", action="store_true",
                   help="bracket the joins with the profiler (the PAPI "
                        "total-cycles analog, Measurements.cpp:90-107,137): "
                        "CTOTAL lands in .perf and the per-op device table "
                        "in .info; requires --output-dir")
    def positive_int(v):
        iv = int(v)
        if iv < 1:
            raise argparse.ArgumentTypeError("must be >= 1")
        return iv

    p.add_argument("--repeat", type=positive_int, default=1)
    p.add_argument("--plan", default=None, metavar="auto|explain|FILE",
                   help="planner mode (tpu_radix_join.planner): 'auto' "
                        "costs every execution discipline against the "
                        "--profile constants and applies the cheapest "
                        "feasible one; 'explain' prints the per-strategy "
                        "predicted-cost table and exits; a path loads a "
                        "previously saved JoinPlan JSON verbatim")
    p.add_argument("--plan-cache-dir", default=None,
                   help="persist chosen plans AND the engine's converged "
                        "window capacities here (atomic, fingerprinted): a "
                        "warm second run skips planning and the sizing "
                        "pre-pass; invalidated when the profile, shapes, or "
                        "config change")
    p.add_argument("--profile", default="v5e_lite",
                   help="device profile for the planner: a packaged name "
                        "(profiles/*.json), a JSON path (e.g. from "
                        "tools_make_report.py --emit-profile or "
                        "tools_profile_fit.py), or 'auto' — prefer the "
                        "ledger's fitted profile_fitted.json while fresh, "
                        "else the committed snapshot")
    p.add_argument("--ledger-dir", default=None,
                   help="append this run's distilled telemetry (phase "
                        "times, counters, plan-vs-actual, fingerprint) to "
                        "the cross-run ledger here at exit "
                        "(observability/ledger.py; default: "
                        "$TPU_RADIX_LEDGER_DIR, else off).  The ledger "
                        "feeds tools_profile_fit.py and --profile auto")
    p.add_argument("--serve", default=None, metavar="FILE",
                   help="resident service mode (tpu_radix_join.service): "
                        "read one JSON query request per line from FILE "
                        "('-' = stdin), run them all through ONE JoinSession "
                        "(mesh, compiled programs, and converged capacities "
                        "stay warm across queries), and print one outcome "
                        "JSON line per query plus a final summary line with "
                        "the SLO percentiles")
    p.add_argument("--serve-batch", type=int, default=1, metavar="N",
                   help="serve mode: submit N requests before draining "
                        "(default 1 = closed loop; larger batches exercise "
                        "queue depth and tenant quotas)")
    p.add_argument("--serve-queue-depth", type=int, default=64,
                   help="serve mode: admission queue depth bound "
                        "(exceeded -> admission_rejected/queue_full)")
    p.add_argument("--serve-tenant-quota", type=int, default=8,
                   help="serve mode: max in-flight queries per tenant "
                        "(exceeded -> admission_rejected/tenant_quota)")
    p.add_argument("--serve-deadline-s", type=float, default=None,
                   metavar="SEC",
                   help="serve mode: default per-query latency budget "
                        "(requests may override with their own deadline_s; "
                        "expiry -> deadline_exceeded)")
    p.add_argument("--result-cache", type=int, default=0, metavar="N",
                   help="serve/fleet mode: relation-fingerprint result "
                        "cache of N entries (service/resultcache.py) — "
                        "repeated queries over unchanged relation content "
                        "short-circuit before admission, stamped "
                        "served_by=cache_hit (default 0 = off)")
    p.add_argument("--result-cache-ttl-s", type=float, default=None,
                   metavar="SEC",
                   help="serve/fleet mode: expire result-cache entries "
                        "older than SEC (default: no TTL)")
    p.add_argument("--batch-window-ms", type=float, default=0.0,
                   metavar="MS",
                   help="serve/fleet mode: coalesce co-batchable queries "
                        "arriving within MS into ONE fused device program "
                        "(service/microbatch.py + ops/merge_delta.py); the "
                        "fleet router additionally keys on the batch "
                        "signature so co-batchable tenants share a worker "
                        "(default 0 = off)")
    p.add_argument("--batch-max", type=int, default=8, metavar="N",
                   help="serve/fleet mode: max queries fused into one "
                        "micro-batch (default 8)")
    p.add_argument("--place-cache-max", type=int, default=8, metavar="N",
                   help="serve mode: placed-relation LRU entries kept "
                        "device-resident per session (default 8; placed "
                        "bytes surface in heartbeats and --statusz)")
    p.add_argument("--resident-budget-mb", type=float, default=0.0,
                   metavar="MB",
                   help="serve mode: HBM budget for device-resident sorted "
                        "inner lanes (service/resident.py) — incremental "
                        "requests (delta_tuples_per_node > 0) then sort "
                        "only their delta and merge in O(N+Δ), stamped "
                        "served_by=delta_merge (default 0 = off)")
    p.add_argument("--fleet", type=int, default=None, metavar="N",
                   help="crash-only fleet serving (service/fleet.py): "
                        "supervise N --serve worker subprocesses, route "
                        "queries by consistent hash on tenant, health-check "
                        "workers by lease heartbeat (two missed beats = "
                        "lapse, the rank-lapse rule), restart dead workers "
                        "with exponential backoff + a crash-loop breaker, "
                        "and guarantee exactly-once outcomes through the "
                        "durable query journal (intent before dispatch, "
                        "outcome before reply, replay on death); SIGTERM "
                        "drains gracefully.  Requires --serve FILE|-; "
                        "--statusz gains a fleet section and a readiness-"
                        "aware /healthz")
    p.add_argument("--fleet-dir", default=None,
                   help="fleet work dir: the query journal plus per-worker "
                        "lease/timeline artifacts live here (default: "
                        "fleet/ under --output-dir or --timeline-dir, else "
                        "a private tempdir — restart the supervisor over "
                        "the SAME dir to replay unacknowledged intents)")
    p.add_argument("--fleet-kill-at", type=int, default=None, metavar="N",
                   help="arm the fleet.worker_kill chaos site at the N-th "
                        "dispatched query (1-based): the routed worker is "
                        "SIGKILLed right after the request hits its pipe, "
                        "and the supervisor must journal-replay it on a "
                        "healthy worker (seeded from --seed, mirrors "
                        "--rank-death-at)")
    p.add_argument("--statusz", type=int, default=None, metavar="PORT",
                   help="serve mode: expose a read-only live-introspection "
                        "HTTP endpoint on 127.0.0.1:PORT "
                        "(observability/statusz.py): GET /statusz returns a "
                        "JSON snapshot of the current phase + open spans, "
                        "counter registry, SLO/breaker/queue state, lease "
                        "board + membership epoch, straggler/hedge posture, "
                        "and the last few per-query critical paths; "
                        "/statusz/<section> returns one section, /healthz "
                        "liveness; 0 = pick an ephemeral port (printed on "
                        "stderr)")
    p.add_argument("--breaker-threshold", type=int, default=3,
                   help="serve mode: consecutive backend failures that trip "
                        "the circuit breaker (queries fail fast while it is "
                        "open, or go to the CPU engine with --cpu-fallback)")
    p.add_argument("--breaker-cooldown-s", type=float, default=30.0,
                   help="serve mode: seconds the breaker stays open before "
                        "half-opening for a primary health probe")
    p.add_argument("--watchdog-timeout", type=float, default=0.0,
                   metavar="SEC",
                   help="hang watchdog (observability/watchdog.py): when "
                        "the flight recorder sees no progress for SEC "
                        "seconds while a phase timer is open, dump every "
                        "thread's stack + the ring into a forensics bundle "
                        "and kill the join through the engine cancel hook "
                        "(classified backend_unavailable); 0 = off")
    p.add_argument("--forensics-dir", default=None,
                   help="directory for post-mortem forensics bundles "
                        "(observability/postmortem.py): any terminal "
                        "classified failure or watchdog trip writes a "
                        "self-contained bundle_*.json here (default: "
                        "$TPU_RADIX_FORENSICS_DIR, else forensics/ under "
                        "--output-dir or --timeline-dir when one is set)")
    p.add_argument("--elastic", choices=["on", "off"], default="off",
                   help="elastic mesh recovery (robustness/membership.py + "
                        "recovery.py): heartbeat an epoch-stamped lease per "
                        "rank, detect peer loss at phase boundaries, fence "
                        "the membership epoch, and finish the join on the "
                        "survivor mesh by recomputing only the lost "
                        "partitions host-side — a rank death becomes a "
                        "recovered, oracle-exact run instead of a hang")
    p.add_argument("--rank-lease-s", type=float, default=5.0, metavar="SEC",
                   help="membership lease window: a rank whose lease file "
                        "is older than SEC seconds is declared lost and the "
                        "membership epoch fences (default 5.0)")
    p.add_argument("--lease-dir", default=None,
                   help="shared directory for membership lease files "
                        "(default: $TPU_RADIX_LEASE_DIR, else leases/ under "
                        "--output-dir or --timeline-dir, else a private "
                        "tempdir — multi-process runs must share one)")
    p.add_argument("--rank-death-at", type=int, default=None, metavar="N",
                   help="arm the membership.rank_death chaos site at the "
                        "N-th phase boundary (1-based): with "
                        "TPU_RJ_RANK_DEATH_SUICIDE set this process dies "
                        "for real (SIGKILL, the multi-rank recovery test's "
                        "victim); otherwise the highest node rank's death "
                        "is simulated and --elastic on recovers it")
    p.add_argument("--rank-missed-beats", type=int, default=2, metavar="N",
                   help="lapse threshold in missed heartbeats: a lease is "
                        "declared lost only after N full lease windows of "
                        "silence (lapse window = N x --rank-lease-s; "
                        "default 2 — one missed beat never kills a rank)")
    p.add_argument("--elastic-grow", action="store_true",
                   help="admit joining ranks mid-run (rank admission, "
                        "robustness/membership.py): a newcomer's 'joining' "
                        "lease is admitted at the next phase boundary with "
                        "a fenced epoch bump, and the next epoch's recovery "
                        "plan re-expands partition assignment onto it; "
                        "requires --elastic on")
    p.add_argument("--elastic-join", type=int, default=None, metavar="N",
                   help="run as a JOINING process against an N-rank "
                        "incumbent world (the growth half's newcomer): "
                        "write a joining lease under the shared "
                        "--lease-dir, wait for admission (an incumbent "
                        "epoch bump), then recompute this rank's share of "
                        "unfinished partitions through the shared "
                        "--checkpoint-dir manifest; mutually exclusive "
                        "with driving a join")
    p.add_argument("--hedge", choices=["on", "off", "auto"], default="off",
                   help="straggler hedging (robustness/straggler.py): when "
                        "a live rank's manifest progress falls below "
                        "--hedge-threshold x the median for two "
                        "consecutive boundary checks, speculatively "
                        "recompute its unfinished partitions; the manifest "
                        "fence (first writer wins) keeps speculation from "
                        "double-counting; 'auto' backs off while "
                        "SPECWASTE > HEDGEWIN")
    p.add_argument("--hedge-threshold", type=float, default=0.5,
                   metavar="F",
                   help="relative-progress straggler threshold: hedge when "
                        "slowest < F x median partitions done (default "
                        "0.5; must be in (0, 1))")
    p.add_argument("--straggle-factor", type=float, default=0.0,
                   metavar="F",
                   help="arm the compute.straggle chaos site: the highest "
                        "node rank stalls for F x TPU_RJ_STRAGGLE_UNIT_S "
                        "at the first phase boundary — the hedging "
                        "benchmark's slow-rank model (0 = off)")
    p.add_argument("--rank-join-at", type=int, default=None, metavar="N",
                   help="arm the membership.rank_join chaos site at the "
                        "N-th phase boundary (1-based): a synthetic "
                        "joining lease appears beyond the boot world and "
                        "--elastic-grow admits it — the single-process "
                        "growth test's newcomer")
    p.add_argument("--pipeline-repeats", action="store_true",
                   help="dispatch the --repeat joins asynchronously and "
                        "fence once (amortized-throughput methodology, "
                        "bench.py): removes the ~100ms/join host dispatch "
                        "round-trip from the reported rate; no per-join "
                        "retry loop")
    return p


def _local_tpu_chips() -> int:
    """TPU chips on this host's PCI bus, found without opening the backend
    (the fleet supervisor must not hold a chip); 0 when JAX_PLATFORMS
    keeps JAX off the TPU."""
    import os
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms:
        return 0
    from jax._src.hardware_utils import num_available_tpu_chips_and_device_id
    return num_available_tpu_chips_and_device_id()[0]


def _forensics_dir(args):
    """Resolve where forensics bundles land: explicit flag, then the
    environment, then a ``forensics/`` subdir of whichever artifact dir
    the run already writes — None (no bundles) only when the run has no
    artifact dir at all."""
    import os

    d = (args.forensics_dir
         or os.environ.get("TPU_RADIX_FORENSICS_DIR")
         or (os.path.join(args.output_dir, "forensics")
             if args.output_dir else None)
         or (os.path.join(args.timeline_dir, "forensics")
             if args.timeline_dir else None))
    return d


def _lease_dir(args):
    """Where membership lease files live: explicit flag, then the
    environment, then ``leases/`` under whichever artifact dir the run
    already writes, else a private tempdir (fine single-process; a
    multi-process world must share one via the flag or env)."""
    import os
    import tempfile

    return (args.lease_dir
            or os.environ.get("TPU_RADIX_LEASE_DIR")
            or (os.path.join(args.output_dir, "leases")
                if args.output_dir else None)
            or (os.path.join(args.timeline_dir, "leases")
                if args.timeline_dir else None)
            or tempfile.mkdtemp(prefix="tpu_rj_leases_"))


def _trace_identity(args, rank):
    """Join-level trace id shared by every rank of one distributed run.

    Rank 0 mints the id and publishes it through the shared lease dir —
    the only cross-rank side channel that exists before the mesh does;
    peers adopt it by polling for the file (with a freshness fence so a
    previous run's stale file is never adopted).  Every rank's span
    export, ledger row, and forensics bundle then carries ONE
    correlation key, which is what lets tools_critical_path.py group a
    directory of span files into a single join.  A peer that never sees
    the file falls back to minting locally with a warning — correlation
    degrades, the run does not."""
    import os
    import tempfile
    import time

    from tpu_radix_join.observability.spans import _new_trace_id

    lease_dir = _lease_dir(args)
    path = os.path.join(lease_dir, "trace_id")
    if rank == 0:
        tid = _new_trace_id()
        os.makedirs(lease_dir, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=lease_dir, prefix=".trace_id.")
        with os.fdopen(fd, "w") as f:
            f.write(tid)
        os.replace(tmp, path)
        return tid
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        try:
            st = os.stat(path)
            # freshness fence: only adopt a file written for THIS run
            # (peers launch within the lease window of rank 0; anything
            # older is a leftover from an earlier run in the same dir)
            if time.time() - st.st_mtime <= 120.0:
                with open(path) as f:
                    tid = f.read().strip()
                if tid:
                    return tid
        except OSError:
            pass
        time.sleep(0.05)
    tid = _new_trace_id()
    print(f"[OBS] rank {rank}: no shared trace_id under {lease_dir} "
          f"after 10s; minted {tid} locally — cross-rank correlation "
          "degraded", file=sys.stderr)
    return tid


def _ledger_dir(args):
    """The cross-run ledger location: explicit flag, then the environment
    — None means this run keeps no ledger (the pre-ledger default)."""
    import os

    return args.ledger_dir or os.environ.get("TPU_RADIX_LEDGER_DIR")


def _ledger_flush(args, meas):
    """Append this run's distilled registry to the cross-run ledger at
    exit.  Runs that measured nothing (--plan explain, argparse errors)
    skip silently; a ledger write failure must never change the run's
    exit code — the ledger is memory, not a dependency."""
    d = _ledger_dir(args)
    if not d or (not meas.times_us and not meas.counters):
        return
    try:
        from tpu_radix_join.observability.ledger import Ledger, run_payload
        led = Ledger(d)
        row = led.append("run", run_payload(meas))
        print(f"[OBS] ledger row {row['run_id']} -> {led.path}",
              file=sys.stderr)
    except Exception as e:   # noqa: BLE001 — telemetry must not fail the run
        print(f"[OBS] ledger append failed: {e!r}", file=sys.stderr)


def _emit_failure_bundle(meas, exc, args, reason="failure"):
    """Write a forensics bundle for a terminal classified failure.

    A watchdog trip already wrote its bundle (the exception carries the
    path); everything else gets one here.  Bundle emission must never
    turn a classified failure into an unclassified crash — errors land
    on stderr and the original failure proceeds."""
    path = getattr(exc, "bundle", None)
    if path:
        return path
    out_dir = _forensics_dir(args)
    if not out_dir:
        print("[FORENSICS] no bundle dir (--forensics-dir / --output-dir / "
              "--timeline-dir all unset); skipping bundle", file=sys.stderr)
        return None
    try:
        from tpu_radix_join.observability.postmortem import write_bundle
        # exceptions may carry structured forensics of their own (e.g.
        # CoordinatorTimeout's attempts + cumulative backoff, RankLost's
        # epoch) — fold them into the bundle next to the repr
        extra = {"error": repr(exc)}
        extra.update(getattr(exc, "bundle_extra", None) or {})
        return write_bundle(
            out_dir, meas, reason=reason,
            failure_class=getattr(exc, "failure_class", None),
            config=vars(args), extra=extra)
    except Exception as e:   # noqa: BLE001 - forensics must not mask
        print(f"[FORENSICS] bundle write failed: {e!r}", file=sys.stderr)
        return None


def _run_grid(args, inner, outer, expected, meas, plan=None) -> int:
    """Out-of-core grid mode: both relations streamed in device-generated
    chunks, every (inner, outer) chunk pair probed exactly once, with an
    atomic checkpoint after each pair (--checkpoint-dir) so a killed run
    resumes from its last completed pair (--resume) instead of restarting
    — the capability the single-shot reference lacks (SURVEY.md §5.4)."""
    import os

    from tpu_radix_join.data.streaming import stream_chunks_device
    from tpu_radix_join.ops.chunked import chunked_join_grid
    from tpu_radix_join.robustness.retry import RetryPolicy

    chunk = args.grid_chunk_tuples
    ckpt_path = None
    if args.checkpoint_dir:
        os.makedirs(args.checkpoint_dir, exist_ok=True)
        ckpt_path = os.path.join(args.checkpoint_dir, "grid.ckpt")
        if not args.resume and os.path.exists(ckpt_path):
            # a fresh run must never silently resume from a stale file
            os.remove(ckpt_path)
    # fingerprint tag: everything that changes the grid's total
    tag = (f"{args.outer_kind}:{inner.global_size}:{args.seed}:{chunk}:"
           f"{args.key_range}")
    policy = (RetryPolicy(max_attempts=args.max_retries + 1,
                          base_delay_s=args.retry_backoff or 0.5,
                          jitter=0.1)
              if args.max_retries else None)
    # --grid-pipeline "auto" defers to a planner plan's decision (the cost
    # model priced both grid rows); an explicit off/on flag wins the A/B
    pipeline = args.grid_pipeline
    if pipeline == "auto" and plan is not None and plan.engine == "chunked":
        pipeline = plan.grid_pipeline
    from tpu_radix_join.planner.audit import audit_plan, phase_snapshot

    meas.set_trace_tags(strategy="chunked_grid", engine="chunked")
    times0 = phase_snapshot(meas)
    meas.start("JTOTAL")
    try:
        total = chunked_join_grid(
            stream_chunks_device(inner, 0, chunk),
            lambda: stream_chunks_device(outer, 0, chunk),
            min(chunk, 1 << 20),
            checkpoint_path=ckpt_path, checkpoint_tag=tag,
            progress=True, key_range=args.key_range, measurements=meas,
            retry_policy=policy, plan=plan, pipeline=pipeline)
    except Exception as e:
        # a classified failure (e.g. DataCorruption from a key lane in the
        # sentinel range — the streamed-lane corruption signature) exits
        # with the machine-readable class instead of a bare traceback
        cls = getattr(e, "failure_class", None)
        if cls is None:
            raise
        meas.stop("JTOTAL")
        meas.meta["failure_class"] = cls
        print(f"[RESULTS] failure/failure_class: {cls}")
        print(f"[RESULTS] failure/error: {e}", file=sys.stderr)
        bundle = _emit_failure_bundle(meas, e, args)
        if bundle:
            print(f"[FORENSICS] bundle {bundle}", file=sys.stderr)
        if args.output_dir:
            path = meas.store(args.output_dir)
            print(f"[PERF] stored {path}")
        return 1
    meas.stop("JTOTAL")
    cp = None
    if meas.tracer is not None:
        from tpu_radix_join.observability.critpath import (
            critical_path_from_tracer, format_summary)
        cp = critical_path_from_tracer(meas.tracer)
        meas.meta["critical_path"] = cp
        print(f"[CRITPATH] {format_summary(cp)}")
    # plan-vs-actual: the grid engine's measured JTOTAL against the cost
    # model's prediction for the chunked strategy (planner/audit.py)
    audit = audit_plan(plan, meas, times0=times0, critical_path=cp)
    if audit is not None:
        print(f"[PLAN] actual_ms={audit['actual_ms']:.1f} "
              f"predicted_ms={audit['predicted_ms']:.1f} "
              f"drift={audit['drift_pct']:.1f}%")
    print(f"[RESULTS] Tuples: {total}")
    if expected is not None:
        status = "OK" if total == expected else "MISMATCH"
        print(f"[RESULTS] Expected: {expected} ({status})")
    for line in meas.lines():
        print(f"[PERF] {line}")
    if args.output_dir:
        path = meas.store(args.output_dir)
        print(f"[PERF] stored {path}")
    return 1 if (expected is not None and total != expected) else 0


def _run_serve(args, cfg, meas, nodes, sampler=None, membership=None) -> int:
    """Resident service mode: every request in the file flows through ONE
    :class:`~tpu_radix_join.service.JoinSession` — warm plan/capacity
    reuse across queries, admission control at the door, per-query
    deadlines, and a circuit breaker that fails queries fast while the
    backend is down (or, with --cpu-fallback, answers them on the CPU).
    One outcome JSON line per query on stdout, then a summary line
    carrying the SLO snapshot."""
    import json as _json
    import os
    import time as _time

    import jax

    from tpu_radix_join.core.config import ServiceConfig
    from tpu_radix_join.service import (AdmissionRejected, JoinSession,
                                        MicroBatcher, QueryRequest)

    plan_cache = None
    if args.plan_cache_dir:
        from tpu_radix_join.planner import PlanCache, load_profile
        from tpu_radix_join.planner.cache import ManifestMismatch

        plan_cache = PlanCache(args.plan_cache_dir,
                               load_profile(args.profile),
                               measurements=meas)
        try:
            plan_cache.check_manifest(jax.process_count())
        except ManifestMismatch as e:
            print(f"[PLAN] {e}", file=sys.stderr)
            return 2
        plan_cache.write_manifest(jax.process_count(),
                                  rank=jax.process_index())

    svc = ServiceConfig(
        max_queue_depth=args.serve_queue_depth,
        tenant_quota=args.serve_tenant_quota,
        default_deadline_s=args.serve_deadline_s,
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown_s=args.breaker_cooldown_s,
        cpu_fallback=args.cpu_fallback,
        place_cache_max=args.place_cache_max,
        result_cache_max=args.result_cache,
        result_cache_ttl_s=args.result_cache_ttl_s,
        batch_window_ms=args.batch_window_ms,
        batch_max_queries=args.batch_max,
        resident_budget_bytes=int(args.resident_budget_mb * (1 << 20)))
    ledger = None
    ld = _ledger_dir(args)
    if ld:
        from tpu_radix_join.observability.ledger import Ledger
        ledger = Ledger(ld)
    session = JoinSession(cfg, svc, measurements=meas,
                          plan_cache=plan_cache, profile=args.profile,
                          forensics_dir=_forensics_dir(args),
                          ledger=ledger, membership=membership,
                          elastic=args.elastic == "on",
                          elastic_grow=args.elastic_grow,
                          hedge=args.hedge,
                          hedge_threshold=args.hedge_threshold)
    # the coalescer is owned by the serve loop (no threads of its own):
    # offer() as requests arrive, due() before blocking, flush() at EOF
    batcher = MicroBatcher(svc.batch_window_ms, svc.batch_max_queries)
    # fleet workers are spawned with an incarnation id (w<slot>i<n>,
    # service/fleet.py); stamping it into the flight-recorder context
    # makes every forensics bundle this worker writes group per
    # incarnation under tools_postmortem.py --merge
    incarnation = os.environ.get("TPU_RJ_WORKER_INCARNATION")
    if incarnation:
        meas.flightrec.set_context(worker_incarnation=incarnation)
    if sampler is not None:
        # heartbeat ticks carry the live SLO/breaker snapshot in serve mode;
        # with membership attached the lease write rides the same tick
        if membership is not None:
            lease_extra = membership.board.sampler_extra(
                epoch_of=membership.epoch_of)
            sampler.extra = (lambda hb=session._heartbeat_extra:
                             {**hb(), **lease_extra()})
        else:
            sampler.extra = session._heartbeat_extra

    statusz = None
    if args.statusz is not None:
        # live introspection plane: read-only JSON over loopback, priced
        # per request (no background sampling thread) — polling it costs
        # the poller, not the join
        from tpu_radix_join.observability.statusz import (
            StatuszServer, measurements_sections)
        from tpu_radix_join.performance.measurements import (HEDGED,
                                                             HEDGEWIN,
                                                             SPECWASTE)
        sections = dict(measurements_sections(meas))
        sections["service"] = session._heartbeat_extra
        if membership is not None:
            sections["leases"] = membership.board.sampler_extra(
                epoch_of=membership.epoch_of)
        sections["hedge"] = (lambda: {
            "mode": session.hedge,
            "threshold": session.hedge_threshold,
            "elastic_grow": session.elastic_grow,
            "hedged": int(meas.counters.get(HEDGED, 0)),
            "wins": int(meas.counters.get(HEDGEWIN, 0)),
            "wasted": int(meas.counters.get(SPECWASTE, 0))})
        sections["critical_paths"] = (
            lambda: list(session.recent_critical_paths))
        if svc.result_cache_max or svc.resident_budget_bytes:
            sections["cache"] = (lambda: {
                "result_cache": session.result_cache.stats(),
                "resident": session.resident.stats(),
                "placed_bytes": session.placed_bytes()})
        if svc.batch_window_ms > 0:
            sections["batch"] = (lambda: {
                **batcher.stats(),
                "session_fused_batches": session.batches_fused,
                "session_fused_queries": session.batch_queries_fused})

        def _readiness():
            # /healthz readiness: closed session, open breaker, or a
            # stale own-lease heartbeat all mean "do not route here" —
            # 503 with the reason, so the fleet supervisor or an
            # external LB can act on the status code alone
            from tpu_radix_join.service.breaker import OPEN as _BRK_OPEN
            if session._closed:
                return {"ok": False, "reason": "session_closed"}
            if session.breaker.state == _BRK_OPEN:
                return {"ok": False, "reason": "breaker_open"}
            if membership is not None:
                lease = membership.board.read(membership.board.rank)
                if lease is not None:
                    age = _time.time() - lease.t_epoch_s
                    if age > membership.board.lapse_window_s:
                        return {"ok": False,
                                "reason": f"heartbeat_stale_{age:.1f}s"}
            return {"ok": True}

        statusz = StatuszServer(port=args.statusz, sections=sections,
                                readiness=_readiness)
        statusz.start()
        print(f"[STATUSZ] serving http://127.0.0.1:{statusz.port}"
              "/statusz", file=sys.stderr)

    errors = 0
    fuse = svc.batch_window_ms > 0

    def emit(out):
        print(_json.dumps({"event": "outcome", **out.to_json()}), flush=True)

    def flush_groups(groups):
        # submit every member of every due group back-to-back, then drain:
        # contiguous co-signature queries fuse inside run_next_batch
        submitted = 0
        for group in groups:
            for request in group:
                try:
                    session.submit(request)
                    submitted += 1
                except AdmissionRejected as e:
                    emit(session.rejection_outcome(request, e))
        if submitted:
            session.drain(on_outcome=emit)

    if args.serve == "-":
        # stream, don't slurp: a resident session answers requests as
        # they arrive on the pipe (an operator can hold stdin open and
        # poll --statusz between queries); EOF still ends the session
        if fuse:
            # reader thread + timed queue: a parked micro-batch group
            # must flush when its window expires even if stdin goes
            # quiet — a blocking readline would strand it forever (the
            # fleet supervisor's dispatch_batch awaits those outcomes)
            import queue as _queue
            import threading as _threading

            lineq: "_queue.Queue" = _queue.Queue()

            def _read_lines():
                try:
                    for raw in sys.stdin:
                        lineq.put(raw)
                finally:
                    lineq.put(None)

            _threading.Thread(target=_read_lines, name="serve-stdin",
                              daemon=True).start()

            def _timed_lines():
                while True:
                    nd = batcher.next_deadline_s()
                    wait = 0.2 if nd is None else max(0.001, min(0.2, nd))
                    try:
                        raw = lineq.get(timeout=wait)
                    except _queue.Empty:
                        flush_groups(batcher.due())
                        continue
                    if raw is None:
                        return
                    yield raw

            lines = _timed_lines()
        else:
            lines = iter(sys.stdin)
    else:
        with open(args.serve) as f:
            lines = f.read().splitlines()

    batch = max(1, args.serve_batch)
    try:
        pending = 0
        for lineno, line in enumerate(lines, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            qid = None
            try:
                obj = _json.loads(line)
                if not isinstance(obj, dict):
                    raise ValueError("request must be a JSON object")
                obj.setdefault("query_id", f"line{lineno}")
                qid = obj.get("query_id")
                request = QueryRequest.from_json(obj)
            except (ValueError, TypeError) as e:
                # a malformed line is the CLIENT's bug: report it and keep
                # serving — one bad request must not kill the session
                errors += 1
                print(_json.dumps({"event": "request_error",
                                   "line": lineno, "query_id": qid,
                                   "error": str(e)}),
                      flush=True)
                continue
            # tier 0: a result-cache hit answers before admission — it
            # never occupies a queue slot or a tenant quota
            hit = session.try_cache(request)
            if hit is not None:
                emit(hit)
                continue
            if fuse and request.delta_tuples_per_node == 0:
                # park in the signature window; key bound = the widest
                # key any generated lane can carry for this request
                key_bound = max(request.tuples_per_node * cfg.num_nodes,
                                request.modulo or 0)
                group = batcher.offer(request, key_bound)
                if group is not None:
                    flush_groups([group])
                flush_groups(batcher.due())
                continue
            try:
                session.submit(request)
                pending += 1
            except AdmissionRejected as e:
                emit(session.rejection_outcome(request, e))
            if pending >= batch:
                session.drain(on_outcome=emit)
                pending = 0
        if fuse:
            flush_groups(batcher.flush())
        session.drain(on_outcome=emit)
        summary = session.summary()
        print(_json.dumps({"event": "summary", **summary}), flush=True)
        # admission rejections are backpressure working as designed; only
        # executed-and-failed queries (or unparseable requests) fail the run
        return 1 if (errors or summary.get("queries_failed", 0)) else 0
    finally:
        if statusz is not None:
            statusz.stop()
        session.close()


def _run_fleet(args) -> int:
    """Crash-only fleet supervision (``--fleet N``): own N ``--serve -``
    worker subprocesses behind the journal's exactly-once discipline.

    The supervisor reads the same JSONL request stream serve mode does,
    but each query is intent-journaled, routed by tenant hash to a live
    worker, and outcome-journaled before the client sees the reply; a
    worker SIGKILLed mid-query fails over (replay on a healthy worker),
    and a SIGTERM to the supervisor drains gracefully — admission stops,
    in-flight queries finish, workers exit cleanly (withdrawing their own
    leases), and the journal ends with zero unacknowledged intents.
    Exit 0 = every accepted query got exactly one outcome."""
    import contextlib
    import json as _json
    import os
    import queue as _queue
    import signal as _signal
    import tempfile
    import threading

    from tpu_radix_join.performance.measurements import Measurements
    from tpu_radix_join.robustness import faults
    from tpu_radix_join.service.fleet import FleetSupervisor

    work_dir = (args.fleet_dir
                or (os.path.join(args.output_dir, "fleet")
                    if args.output_dir else None)
                or (os.path.join(args.timeline_dir, "fleet")
                    if args.timeline_dir else None)
                or tempfile.mkdtemp(prefix="tpu_rj_fleet_"))

    # the workers inherit the supervisor's join/serve shape; requests
    # carry the per-query knobs (tuples_per_node, seed, deadline_s, ...)
    worker_args = []
    if args.nodes:
        worker_args += ["--nodes", str(args.nodes)]
    if args.verify != "off":
        worker_args += ["--verify", args.verify]
    worker_args += ["--profile", args.profile,
                    "--max-retries", str(args.max_retries),
                    "--fallback", args.fallback,
                    "--breaker-threshold", str(args.breaker_threshold),
                    "--breaker-cooldown-s", str(args.breaker_cooldown_s),
                    "--serve-queue-depth", str(args.serve_queue_depth),
                    "--serve-tenant-quota", str(args.serve_tenant_quota),
                    "--place-cache-max", str(args.place_cache_max)]
    if args.serve_deadline_s is not None:
        worker_args += ["--serve-deadline-s", str(args.serve_deadline_s)]
    if args.result_cache:
        worker_args += ["--result-cache", str(args.result_cache)]
        if args.result_cache_ttl_s is not None:
            worker_args += ["--result-cache-ttl-s",
                            str(args.result_cache_ttl_s)]
    if args.batch_window_ms > 0:
        # the workers MUST share the batch window: dispatch_batch writes a
        # fused group's request lines back-to-back, and it is the worker's
        # own coalescer that turns them into one device program
        worker_args += ["--batch-window-ms", str(args.batch_window_ms),
                        "--batch-max", str(args.batch_max)]
    if args.resident_budget_mb:
        worker_args += ["--resident-budget-mb", str(args.resident_budget_mb)]

    meas = Measurements()
    sup = FleetSupervisor(args.fleet, worker_args, work_dir,
                          measurements=meas,
                          lease_s=args.rank_lease_s,
                          missed_beats=args.rank_missed_beats,
                          result_cache_max=args.result_cache,
                          result_cache_ttl_s=args.result_cache_ttl_s,
                          batch_window_ms=args.batch_window_ms)

    statusz = None
    if args.statusz is not None:
        from tpu_radix_join.observability.statusz import (
            StatuszServer, measurements_sections)
        sections = dict(measurements_sections(meas))
        sections["fleet"] = sup.statusz_section
        statusz = StatuszServer(port=args.statusz, sections=sections,
                                readiness=sup.readiness)
        statusz.start()
        print(f"[STATUSZ] serving http://127.0.0.1:{statusz.port}"
              "/statusz", file=sys.stderr)

    # SIGTERM = graceful drain: the handler only flips a flag — the
    # in-flight dispatch (the supervisor is single-threaded by design)
    # finishes its query, then the loop sees the flag and drains
    stop = threading.Event()

    def _on_term(signum, frame):
        stop.set()

    prev_term = _signal.signal(_signal.SIGTERM, _on_term)

    # requests arrive through a reader thread + queue so the serve loop
    # can poll the stop flag: a blocking readline would ride out SIGTERM
    # (PEP 475 retries it) and strand the drain until the next line
    lineq: "_queue.Queue" = _queue.Queue()

    def _read_lines(src):
        try:
            for line in src:
                lineq.put(line)
        finally:
            lineq.put(None)

    if args.serve == "-":
        src = sys.stdin
    else:
        src = open(args.serve)
    reader = threading.Thread(target=_read_lines, args=(src,),
                              name="fleet-stdin", daemon=True)

    def emit(out):
        print(_json.dumps({"event": "outcome", **out}, default=str),
              flush=True)

    errors = 0
    rc = 0
    try:
        with contextlib.ExitStack() as stack:
            if args.fleet_kill_at is not None:
                inj = faults.FaultInjector(seed=args.seed,
                                           measurements=meas)
                inj.arm(faults.FLEET_WORKER_KILL, at=args.fleet_kill_at)
                stack.enter_context(inj)
            sup.start()
            # a previous incarnation's accepted-but-unanswered queries
            # replay before any new admission — the restart half of
            # exactly-once (each replayed outcome is emitted too, marked
            # replayed, so the client is made whole)
            replayed = sup.replay_unacknowledged(emit)
            if replayed:
                print(f"[FLEET] replayed {len(replayed)} unacknowledged "
                      f"intent(s) from {sup.journal.path}",
                      file=sys.stderr)
            reader.start()
            # supervisor-side micro-batch windows: co-signature requests
            # arriving within --batch-window-ms dispatch together via
            # dispatch_batch (one signature-routed worker, back-to-back
            # lines, the worker fuses them into one device program)
            import time as _time
            window_s = args.batch_window_ms / 1000.0
            parked: dict = {}          # sig -> (opened_monotonic, [obj])

            def _flush_sig(sig):
                _, group = parked.pop(sig)
                for out in sup.dispatch_batch(group):
                    emit(out)

            def _flush_due():
                now = _time.monotonic()
                for sig in sorted(parked, key=lambda s: parked[s][0]):
                    if now - parked[sig][0] >= window_s:
                        _flush_sig(sig)

            poll_s = min(0.2, window_s) if window_s > 0 else 0.2
            lineno = 0
            while not stop.is_set():
                try:
                    line = lineq.get(timeout=poll_s if parked else 0.2)
                except _queue.Empty:
                    _flush_due()
                    continue
                if line is None:
                    break
                lineno += 1
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                try:
                    obj = _json.loads(line)
                    if not isinstance(obj, dict):
                        raise ValueError("request must be a JSON object")
                    obj.setdefault("query_id", f"line{lineno}")
                except (ValueError, TypeError) as e:
                    errors += 1
                    print(_json.dumps({"event": "request_error",
                                       "line": lineno, "error": str(e)}),
                          flush=True)
                    continue
                sig = sup._batch_signature(obj)
                if sig is None or obj.get("delta_tuples_per_node"):
                    emit(sup.dispatch(obj))
                else:
                    opened, group = parked.get(sig,
                                               (_time.monotonic(), []))
                    group.append(obj)
                    parked[sig] = (opened, group)
                    if len(group) >= args.batch_max:
                        _flush_sig(sig)
                _flush_due()
            # EOF / SIGTERM: no parked query is ever lost to the drain
            for sig in list(parked):
                _flush_sig(sig)
        report = sup.drain()
        summary = {**sup.summary(), "drain": report}
        print(_json.dumps({"event": "summary", **summary}, default=str),
              flush=True)
        if report["unacked"] or report["double_exec"]:
            # a stranded or doubled query is the one failure this mode
            # exists to rule out — fail loud
            print(f"[FLEET] exactly-once violated at drain: "
                  f"unacked={report['unacked']} "
                  f"double_exec={report['double_exec']}", file=sys.stderr)
            rc = 1
        if errors:
            rc = 1
        return rc
    finally:
        sup.close()
        if statusz is not None:
            statusz.stop()
        if src is not sys.stdin:
            src.close()
        _signal.signal(_signal.SIGTERM, prev_term)
        _ledger_flush(args, meas)


def _run_joiner(args, cfg, meas, nodes, *, membership) -> int:
    """The newcomer's half of elastic growth (``--elastic-join N``).

    Mirror image of the incumbents' admission path
    (membership.MembershipView._admit): this process wrote a ``joining``
    lease before any work; here it (1) waits for an incumbent epoch bump
    — the fenced admission signal, readable from the shared lease dir
    with no coordinator — then (2) regenerates the deterministic
    relations host-side and recomputes ITS share of unfinished
    partitions through the shared manifest, exactly the
    ``execute_recovery(only_rank=...)`` multi-survivor discipline the
    incumbents' regrow uses.  Divergent plan timing across processes is
    safe: the manifest fence (first writer wins within an epoch) makes
    double-computation waste, never double-counting.
    """
    import os
    import time as _time

    from tpu_radix_join import Relation
    from tpu_radix_join.robustness.checkpoint import PartitionManifest
    from tpu_radix_join.robustness.recovery import (execute_recovery,
                                                    host_keys,
                                                    partition_weights,
                                                    plan_recovery)

    board = membership.board
    num_ranks = board.num_ranks            # incumbent world size (= N)
    if nodes % num_ranks:
        print(f"[RESULTS] failure/joiner: {nodes} nodes do not divide "
              f"over {num_ranks} incumbent ranks", file=sys.stderr)
        return 1
    npp = nodes // num_ranks
    my_nodes = list(range(board.rank * npp, (board.rank + 1) * npp))
    print(f"[ELASTIC] joiner rank={board.rank} nodes={my_nodes} "
          f"waiting for admission under {board.run_dir}", file=sys.stderr)

    # -- wait for the fenced admission: any incumbent member lease at
    # epoch >= 1 means the board admitted someone (us — we are the only
    # joining lease we wrote) and the next plan prices us in
    deadline = _time.monotonic() + max(120.0, 6.0 * board.lapse_window_s)
    admitted_epoch = 0
    while _time.monotonic() < deadline:
        for r in board.discover():
            if r == board.rank:
                continue
            lease = board.read(r)
            if (lease is not None and lease.status == "member"
                    and lease.epoch > admitted_epoch):
                admitted_epoch = lease.epoch
        if admitted_epoch >= 1:
            break
        board.heartbeat(membership.epoch, status="joining")
        _time.sleep(min(0.2, board.lease_s / 4.0))
    if admitted_epoch < 1:
        print("[RESULTS] failure/joiner: no admission epoch bump before "
              "deadline — incumbents never saw the joining lease "
              "(dead world, or --elastic-grow not set there)",
              file=sys.stderr)
        return 1
    membership.epoch = admitted_epoch
    membership.joined.add(board.rank)
    board.heartbeat(admitted_epoch, status="member")
    print(f"[ELASTIC] joiner admitted epoch={admitted_epoch}",
          file=sys.stderr)

    # -- regenerate the deterministic inputs host-side (the property
    # that makes coordinator-free growth possible: a newcomer computes
    # the same host_keys every incumbent does)
    global_size = args.tuples_per_node * nodes
    inner = Relation(global_size, nodes, "unique", seed=args.seed)
    outer_kw = {}
    if args.outer_kind == "modulo":
        outer_kw["modulo"] = args.modulo or max(1, global_size // 4)
    elif args.outer_kind == "zipf":
        outer_kw["zipf_theta"] = args.zipf_theta
        outer_kw["key_domain"] = global_size
    outer = Relation(global_size, nodes, args.outer_kind,
                     seed=args.seed + 1, **outer_kw)
    rk, rhi = host_keys(inner)
    sk, shi = host_keys(outer)
    num_p = cfg.network_partition_count
    fp = (f"elastic:{args.outer_kind}:{global_size}:"
          f"{args.seed}:{num_p}")
    os.makedirs(args.checkpoint_dir, exist_ok=True)
    manifest = PartitionManifest(
        os.path.join(args.checkpoint_dir, "partitions.manifest"),
        fingerprint=fp, measurements=meas)

    plan = plan_recovery(
        num_nodes=nodes, num_partitions=num_p, lost_ranks=[],
        epoch=admitted_epoch, manifest=manifest,
        weights=partition_weights(rk, sk, num_p),
        joined_ranks=my_nodes)
    board.heartbeat(admitted_epoch, status="member")
    matches, counts = execute_recovery(
        plan, rk, sk, rhi, shi, only_rank=set(my_nodes),
        manifest=manifest, measurements=meas)

    # -- report once the shared manifest is complete: our share is done
    # (post-realization lines above), the rest arrives as incumbents
    # finish theirs — completeness, not a barrier, is the exit signal
    deadline = _time.monotonic() + max(120.0, 6.0 * board.lapse_window_s)
    while _time.monotonic() < deadline:
        if len(manifest.completed()) >= num_p:
            break
        board.heartbeat(admitted_epoch, status="member")
        _time.sleep(0.1)
    done = manifest.completed()
    matches = int(sum(rec["count"] for rec in done.values()))
    mine = sum(1 for rec in done.values()
               if rec.get("owner") in set(my_nodes))
    expected = inner.expected_matches(outer)
    print(f"[RESULTS] joiner: rank={board.rank} epoch={admitted_epoch} "
          f"owned_partitions={mine} "
          f"manifest_partitions={len(done)}/{num_p}")
    print(f"[RESULTS] Tuples: {matches}")
    if expected is not None:
        status = "OK" if matches == expected else "MISMATCH"
        print(f"[RESULTS] Expected: {expected} ({status})")
        if matches != expected:
            return 1
    if len(done) < num_p:
        print("[RESULTS] failure/joiner: manifest incomplete at "
              "deadline", file=sys.stderr)
        return 1
    aud = manifest.audit()
    print(f"[ELASTIC] joiner manifest audit total={aud['total']} "
          f"fenced_duplicates={aud['fenced_duplicates']}",
          file=sys.stderr)
    if args.output_dir:
        path = meas.store(args.output_dir)
        print(f"[PERF] stored {path}")
    return 0


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.trace and not args.output_dir:
        parser.error("--trace writes its artifacts under --output-dir")
    if args.pipeline_repeats and args.measure_phases:
        parser.error("--pipeline-repeats dispatches without intermediate "
                     "fences; the --measure-phases split timers need a "
                     "fence per program — drop one of the two")
    if args.serve is not None and args.grid_chunk_tuples is not None:
        parser.error("--serve runs the in-core resident engine; the "
                     "out-of-core grid is a one-shot mode")
    if args.elastic_grow and args.elastic != "on":
        parser.error("--elastic-grow admits ranks into the elastic "
                     "recovery protocol — it needs --elastic on")
    if args.hedge != "off" and args.elastic != "on":
        parser.error("--hedge speculates through the elastic recovery "
                     "machinery — it needs --elastic on")
    if not 0.0 < args.hedge_threshold < 1.0:
        parser.error("--hedge-threshold must be in (0, 1): it is the "
                     "slowest/median progress ratio below which hedging "
                     "arms")
    if args.rank_missed_beats < 1:
        parser.error("--rank-missed-beats must be >= 1")
    if args.fleet is not None:
        if args.fleet < 1:
            parser.error("--fleet needs at least one worker")
        if args.serve is None:
            parser.error("--fleet supervises --serve workers — pass "
                         "--serve FILE (or '-' for stdin)")
        if args.elastic_join is not None:
            parser.error("--fleet is a serving supervisor, not a mesh "
                         "rank; it cannot run as --elastic-join")
    if args.elastic_join is not None:
        if not args.checkpoint_dir:
            parser.error("--elastic-join recomputes through the shared "
                         "partition manifest — pass the incumbents' "
                         "--checkpoint-dir")
        if args.elastic != "on":
            parser.error("--elastic-join is the growth half of elastic "
                         "recovery — it needs --elastic on")
        if not args.nodes:
            parser.error("--elastic-join cannot infer the incumbent "
                         "world's node count from its own devices — "
                         "pass the incumbents' --nodes")

    import contextlib
    import os

    if args.profile == "auto":
        # resolve BEFORE jax init: the decision reads only the ledger dir
        from tpu_radix_join.planner.profile import resolve_profile
        args.profile = resolve_profile("auto", ledger_dir=_ledger_dir(args))
        print(f"[PROFILE] auto -> {args.profile}", file=sys.stderr)

    if args.fleet is not None:
        if args.fleet > 1 and _local_tpu_chips():
            parser.error(
                f"--fleet {args.fleet} on a TPU host: every --serve worker "
                f"opens all local chips and a chip serves one process, so "
                f"worker 2 could not start; run --fleet 1")
        # the supervisor never initializes devices — the workers own the
        # mesh; dispatch before the driver's jax/device bring-up
        return _run_fleet(args)

    import jax

    from tpu_radix_join import HashJoin, JoinConfig, Relation
    from tpu_radix_join.parallel.multihost import initialize as init_multihost
    from tpu_radix_join.performance import Measurements
    from tpu_radix_join.utils.platform import enable_compile_cache

    distributed = init_multihost()   # no-op unless a world is configured
    enable_compile_cache()
    nodes = args.nodes or jax.device_count()
    if args.grid_chunk_tuples is not None and nodes != 1:
        parser.error("--grid-chunk-tuples runs the single-node out-of-core "
                     "grid; use --nodes 1")
    if args.resume and not args.checkpoint_dir:
        parser.error("--resume reads the checkpoint under --checkpoint-dir")
    cfg = JoinConfig(
        num_nodes=nodes,
        num_hosts=args.hosts,
        network_fanout_bits=args.network_fanout,
        local_fanout_bits=args.local_fanout,
        two_level=args.two_level,
        probe_algorithm=args.probe,
        assignment_policy=args.assignment,
        window_sizing=args.window_sizing,
        chunk_size=args.chunk_size,
        max_retries=args.max_retries,
        retry_backoff_s=args.retry_backoff,
        fallback=args.fallback,
        skew_threshold=args.skew_threshold,
        key_range=args.key_range,
        generation=args.generation,
        debug_checks=args.debug_checks,
        measure_phases=args.measure_phases,
        verify=args.verify,
        exchange_codec=args.exchange_codec,
        exchange_stages=args.exchange_stages,
        partition_impl=args.partition_impl,
        sort_impl=args.sort_impl,
    )

    meas = Measurements(node_id=jax.process_index(), num_nodes=nodes)

    # compile telemetry: every backend compile lands in NCOMPILE/COMPILEMS
    # via jax.monitoring (observability/compilemon.py) — heartbeat ticks,
    # the ledger row, and the regress gate all see compile churn
    from tpu_radix_join.observability.compilemon import (
        install_compile_monitor, uninstall_compile_monitor)
    install_compile_monitor(meas)

    # ---------------------------------------------------- observability
    # (tpu_radix_join.observability): opt-in span timeline + live metrics
    # heartbeat; without the flags the driver behaves exactly as before.
    tracer = None
    if args.timeline_dir:
        os.makedirs(args.timeline_dir, exist_ok=True)
        # distributed runs share ONE join-level trace id (rank 0 mints,
        # peers adopt through the lease dir) so the exported span files
        # correlate as a single join; solo runs mint locally
        trace_id = (_trace_identity(args, jax.process_index())
                    if jax.process_count() > 1 else None)
        tracer = meas.attach_tracer(trace_id=trace_id, nodes=nodes)
    sampler = None
    if args.metrics_interval:
        mdir = args.timeline_dir or args.output_dir
        if not mdir:
            parser.error("--metrics-interval writes <rank>.metrics.jsonl "
                         "under --timeline-dir or --output-dir — pass one")
        from tpu_radix_join.observability import MetricsSampler
        sampler = MetricsSampler(
            os.path.join(mdir, f"{meas.node_id}.metrics.jsonl"),
            args.metrics_interval, measurements=meas)
        sampler.start()

    # ------------------------------------------------- elastic membership
    # (tpu_radix_join.robustness.membership): epoch-stamped leases in a
    # shared dir.  Always on for multi-process worlds (loss DETECTION and
    # classification are free safety); recovery itself is --elastic on.
    membership = None
    board = None
    if args.elastic == "on" or distributed:
        from tpu_radix_join.robustness.membership import (LeaseBoard,
                                                          MembershipView)
        if args.elastic_join is not None:
            # joiner mode: rank comes from the shared lease dir (first
            # free id at or above the incumbent world size), and the
            # first lease is a JOINING lease — admission is the
            # incumbents' move, not ours
            lease_dir = _lease_dir(args)
            rank = LeaseBoard.next_rank(lease_dir,
                                        floor=args.elastic_join)
            board = LeaseBoard(lease_dir, rank=rank,
                               num_ranks=args.elastic_join,
                               lease_s=args.rank_lease_s,
                               missed_beats=args.rank_missed_beats,
                               measurements=meas)
            membership = MembershipView(board, measurements=meas)
            board.heartbeat(0, status="joining")
        else:
            board = LeaseBoard(_lease_dir(args), rank=jax.process_index(),
                               num_ranks=jax.process_count(),
                               lease_s=args.rank_lease_s,
                               missed_beats=args.rank_missed_beats,
                               measurements=meas)
            membership = MembershipView(board, measurements=meas)
            board.heartbeat(0)       # first lease before any join work
        if sampler is not None:
            # liveness rides the telemetry cadence: every sampler tick
            # heartbeats the lease and reports the membership epoch +
            # lease status (a joiner's tick says "joining" until its
            # own view admits it)
            sampler.extra = board.sampler_extra(
                epoch_of=membership.epoch_of,
                status_of=membership.my_status)
    try:
        if args.elastic_join is not None:
            rc = _run_joiner(args, cfg, meas, nodes,
                             membership=membership)
        elif args.serve is not None:
            rc = _run_serve(args, cfg, meas, nodes, sampler=sampler,
                            membership=membership)
        else:
            rc = _run_driver(args, cfg, meas, distributed, nodes,
                             membership=membership)
    finally:
        if board is not None:
            # a clean exit withdraws the lease: peers see an absent (not
            # stale) lease and a deliberate departure, not a silent death
            board.withdraw(board.rank)
        uninstall_compile_monitor(meas)
        if sampler is not None:
            sampler.stop()
        _ledger_flush(args, meas)
        if tracer is not None:
            # save in the finally: a failed/degraded run's timeline is the
            # one a post-mortem needs most
            path = tracer.save(args.timeline_dir)
            print(f"[OBS] timeline spans stored {path}", file=sys.stderr)
    if distributed and membership is not None and membership.lost:
        # a survivor of a rank loss must NOT walk jax.distributed's atexit
        # shutdown: the coordination service's shutdown barrier can never
        # complete with a dead peer and LOG(FATAL)s the process (observed
        # rc -6 after a fully recovered run).  Every artifact is already
        # flushed above — exit hard with the honest code.
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)
    return rc


def _plan_static_payload(profile, workload, plan, meas):
    """graftcheck cross-validation for ``--plan explain``: trace the
    fused pipeline at this workload's geometry and diff its all_to_all
    bytes against the cost model (STATIC-DRIFT column), recording the
    STATICMEM / JXAUDIT gauges.  Best-effort: tracing needs
    ``num_nodes`` host devices — on any failure explain simply omits
    the column rather than failing the driver."""
    if plan is None or plan.engine != "incore":
        return None
    try:
        from tpu_radix_join.analysis.jaxpr import run_audit
        from tpu_radix_join.analysis.jaxpr.crossval import static_for_explain
        from tpu_radix_join.analysis.jaxpr.trace import build_entries
        from tpu_radix_join.performance.measurements import (JXAUDIT,
                                                             STATICMEM)
        from tpu_radix_join.planner.cost_model import plan_exchange

        n = max(1, workload.num_nodes)
        per_node = max(8, -(-max(workload.r_tuples, workload.s_tuples)
                            // n))
        cap = max(8, 1 << (-(-per_node // n) - 1).bit_length())
        views = build_entries(num_nodes=n, per_node=per_node, cap=cap,
                              entries=("pipeline",))
        res = run_audit(views)
        xplan = plan_exchange(profile, workload,
                              fanout_bits=plan.network_fanout_bits)
        payload = static_for_explain(views[0], xplan)
        meas.counters[JXAUDIT] = len(res.findings)
        peak = res.stats.get("pipeline", {}).get("peak_live_bytes")
        if peak is not None:
            meas.counters[STATICMEM] = int(peak)
        return payload
    except Exception as e:       # noqa: BLE001 — advisory column only
        print(f"[PLAN] static cross-validation unavailable: {e}",
              file=sys.stderr)
        return None


def _run_driver(args, cfg, meas, distributed, nodes, membership=None) -> int:
    """Driver body after flag/observability setup (main() wraps this in the
    tracer/sampler lifecycle so every exit path exports its timeline)."""
    import contextlib
    import os

    import jax

    from tpu_radix_join import HashJoin, Relation

    # ---------------------------------------------------------- planner
    # (tpu_radix_join.planner): optional — without --plan/--plan-cache-dir
    # the driver behaves exactly as before.
    plan = None
    plan_cache = None
    plan_costs = None
    explain_tbl = None
    plan_static = None
    if args.plan is not None or args.plan_cache_dir:
        import dataclasses as _dc

        from tpu_radix_join.planner import (JoinPlan, PlanCache, Workload,
                                            explain_table, load_profile,
                                            plan_join)
        from tpu_radix_join.planner.cache import ManifestMismatch

        profile = load_profile(args.profile)
        global_size = args.tuples_per_node * nodes
        if args.plan_cache_dir:
            plan_cache = PlanCache(args.plan_cache_dir, profile,
                                   measurements=meas)
            try:
                # multi-host guard: a cache dir written by a different
                # topology or profile must fail fast, not desynchronize
                plan_cache.check_manifest(jax.process_count())
            except ManifestMismatch as e:
                print(f"[PLAN] {e}", file=sys.stderr)
                return 2
            plan_cache.write_manifest(jax.process_count(),
                                      rank=jax.process_index())
        if args.plan in ("auto", "explain"):
            workload = Workload(
                r_tuples=global_size, s_tuples=global_size,
                key_bound=global_size,   # generated keys live in [0, N)
                num_nodes=nodes, repeats=args.repeat)
            wl_fp = {"workload": _dc.asdict(workload)}
            if plan_cache is not None and args.plan == "auto":
                plan, _ = plan_cache.lookup(global_size, global_size, wl_fp)
            if plan is None:
                plan, costs = plan_join(profile, workload)
                plan_costs, explain_tbl = costs, explain_table
                plan_static = _plan_static_payload(profile, workload,
                                                   plan, meas)
                if args.plan == "explain":
                    cp_col = None
                    if args.timeline_dir:
                        # measured critical path from the span exports a
                        # prior run left under --timeline-dir: the table
                        # prices the winning strategy against the rank
                        # that actually bounded the wall clock, not the
                        # local mean
                        from tpu_radix_join.observability.critpath import \
                            critical_path_for_dir
                        cp = critical_path_for_dir(args.timeline_dir)
                        if not cp.get("error"):
                            # compile wall comes off the measured bound
                            # (audit_plan's exclude-from-running twin):
                            # the table prices steady-state joins
                            jc = float((cp.get("phase_ms") or {})
                                       .get("JCOMPILE", 0.0))
                            cp_col = {
                                "strategy": plan.strategy,
                                "bound_ms": max(
                                    0.0, cp.get("path_ms", 0.0) - jc),
                                "bound_rank": cp.get("bounding_rank"),
                                "wait_fraction": cp.get("wait_fraction")}
                    print(explain_table(costs, plan, static=plan_static,
                                        critpath=cp_col))
                    # constants half of explain: where each profile
                    # constant came from (fit provenance vs committed
                    # citation) and which ones the ledger's accumulated
                    # PLANDRIFT says have gone stale
                    from tpu_radix_join.observability.ledger import (
                        default_ledger_dir, load_rows)
                    from tpu_radix_join.planner.calibrate import detect_stale
                    from tpu_radix_join.planner.profile import \
                        format_provenance
                    ld = _ledger_dir(args) or default_ledger_dir()
                    print(format_provenance(
                        profile, stale=detect_stale(load_rows(ld))))
                    return 0
                if plan_cache is not None:
                    plan_cache.store(global_size, global_size, wl_fp,
                                     plan=plan)
        elif args.plan is not None:
            plan = JoinPlan.load(args.plan)
        if plan is not None:
            print(f"[PLAN] strategy={plan.strategy} engine={plan.engine} "
                  f"predicted_ms={plan.predicted_ms:.1f} "
                  f"profile={plan.profile_name or profile.name}")
            meas.meta["plan"] = plan.to_dict()
            # the planner's decision is a timeline instant event + span tag:
            # a merged multi-rank trace shows WHICH discipline each rank ran
            # next to the phases it produced (ISSUE 3 tentpole)
            meas.event("plan_decision", strategy=plan.strategy,
                       engine=plan.engine,
                       predicted_ms=round(plan.predicted_ms, 3))
            meas.set_trace_tags(strategy=plan.strategy, engine=plan.engine)
            if plan.engine == "chunked" and nodes == 1:
                if args.grid_chunk_tuples is None:
                    args.grid_chunk_tuples = plan.chunk_tuples or (1 << 20)
            elif plan.engine == "chunked":
                print("[PLAN] chunked engine is single-node; keeping the "
                      "in-core engine at this mesh size", file=sys.stderr)
            if plan.engine == "incore" and args.grid_chunk_tuples is None:
                cfg = cfg.replace(**plan.config_kwargs())
                if (plan.pipeline_repeats and args.repeat > 1
                        and not cfg.measure_phases):
                    args.pipeline_repeats = True

    engine = None
    if args.grid_chunk_tuples is None:
        if args.cpu_fallback:
            from tpu_radix_join.robustness.degrade import \
                engine_with_cpu_fallback
            engine, dinfo = engine_with_cpu_fallback(cfg, measurements=meas)
            if dinfo["degraded"]:
                # structured, parseable: key=value pairs after the marker
                print(f"[DEGRADE] failure_class={dinfo['failure_class']} "
                      f"backend=cpu nodes={dinfo['num_nodes']} "
                      f"error={dinfo['error']}", file=sys.stderr)
                cfg = engine.config
                nodes = cfg.num_nodes
        else:
            engine = HashJoin(cfg, measurements=meas, plan_cache=plan_cache)

    # elastic wiring: membership view (loss detection + epoch fencing) and,
    # with a checkpoint dir, the partition manifest (partition-level resume)
    elastic = args.elastic == "on"
    if engine is not None and (elastic or membership is not None):
        manifest = None
        if elastic and args.checkpoint_dir:
            from tpu_radix_join.robustness.checkpoint import PartitionManifest
            os.makedirs(args.checkpoint_dir, exist_ok=True)
            fp = (f"elastic:{args.outer_kind}:{args.tuples_per_node * nodes}:"
                  f"{args.seed}:{cfg.network_partition_count}")
            manifest = PartitionManifest(
                os.path.join(args.checkpoint_dir, "partitions.manifest"),
                fingerprint=fp, measurements=meas)
        engine.membership = membership
        engine.elastic = elastic
        engine.partition_manifest = manifest
        engine.elastic_grow = args.elastic_grow
        engine.hedge = args.hedge
        engine.hedge_threshold = args.hedge_threshold
        engine.straggle_factor = args.straggle_factor

    global_size = args.tuples_per_node * nodes
    meas.meta.update(tuples_per_node=args.tuples_per_node,
                     global_size=global_size, config=vars(args))
    inner = Relation(global_size, nodes, "unique", seed=args.seed)
    outer_kw = {}
    if args.outer_kind == "modulo":
        outer_kw["modulo"] = args.modulo or max(1, global_size // 4)
    elif args.outer_kind == "zipf":
        outer_kw["zipf_theta"] = args.zipf_theta
        outer_kw["key_domain"] = global_size
    outer = Relation(global_size, nodes, args.outer_kind,
                     seed=args.seed + 1, **outer_kw)

    expected = inner.expected_matches(outer)

    if args.grid_chunk_tuples is not None:
        return _run_grid(args, inner, outer, expected, meas, plan=plan)
    # Generate + place once, join --repeat times: the reference generates
    # before its join timers start (main.cpp:94-116), so repeats must not
    # re-pay generation/transfer — with host generation the device_put
    # completes lazily inside the first join's fence, silently inflating
    # JPROC by the host-to-device transfer time.
    r_batch, s_batch = engine.place(inner), engine.place(outer)
    result = None
    # --trace: the reference brackets exactly the join with PAPI and writes
    # CTOTAL into every rank's perf file (Measurements.cpp:90-107,137); here
    # the profiler bracket wraps the same span and the xplane decoder turns
    # it into CTOTAL + the per-op table on exit (Measurements.trace).
    trace_ctx = (meas.trace(os.path.join(args.output_dir, "trace"))
                 if args.trace else contextlib.nullcontext())
    # hang watchdog (--watchdog-timeout): evidence first (stacks + bundle),
    # then the kill through the engine cancel hook — a hung collective
    # becomes a classified backend_unavailable exit, not a silent stall
    from tpu_radix_join.observability.watchdog import Watchdog, engine_killer
    from tpu_radix_join.planner.audit import (actuals_for_explain,
                                              audit_plan,
                                              critpath_for_explain,
                                              phase_snapshot)

    wd_ctx = (Watchdog(meas, timeout_s=args.watchdog_timeout,
                       kill=engine_killer(engine),
                       bundle_dir=_forensics_dir(args), config=vars(args),
                       membership=membership)
              if args.watchdog_timeout > 0 else contextlib.nullcontext())
    if elastic and engine is not None:
        # host-side regeneration source for recovery: the deterministic
        # Relation specs, never the distributed arrays (hash_join.join()
        # records the same pair on the Relations API path)
        engine._elastic_rel = (inner, outer)
    # Membership chaos sites arm on ONE injector: only the innermost
    # installed injector is consulted (faults.py stacking), so a driver
    # mixing --rank-death-at / --rank-join-at / --straggle-factor must
    # register every site on the same instance.  The victim of the
    # multi-rank recovery test additionally sets the suicide env var.
    from tpu_radix_join.robustness import faults as _faults
    death_ctx = contextlib.nullcontext()
    if args.rank_death_at or args.rank_join_at or args.straggle_factor > 0:
        inj = _faults.FaultInjector(seed=args.seed, measurements=meas)
        if args.rank_death_at:
            inj.arm(_faults.RANK_DEATH, at=args.rank_death_at)
        if args.rank_join_at:
            inj.arm(_faults.RANK_JOIN, at=args.rank_join_at)
        if args.straggle_factor > 0:
            inj.arm(_faults.COMPUTE_STRAGGLE, at=1)
        death_ctx = inj
    # --transfer-guard: the runtime half of the sync-point discipline —
    # the static rule (tools_lint.py) forbids implicit readback spellings;
    # this guard proves at run time that none slipped through a dynamic
    # path.  Armed around the join only: generation + placement transfer
    # by design (the reference pays them outside its timers too).
    tg_ctx = (jax.transfer_guard(args.transfer_guard)
              if args.transfer_guard != "off" else contextlib.nullcontext())
    times0 = phase_snapshot(meas)
    try:
        with trace_ctx, wd_ctx, death_ctx, tg_ctx:
            if args.pipeline_repeats and args.repeat > 1:
                result = engine.join_arrays_pipelined(r_batch, s_batch,
                                                      args.repeat)
            else:
                for i in range(args.repeat):
                    result = engine.join_arrays(r_batch, s_batch)
    except Exception as e:
        # terminal classified failure (watchdog trip, injected fault,
        # corruption): exit with the machine-readable class + a forensics
        # bundle; an unclassified exception stays a loud traceback
        cls = getattr(e, "failure_class", None)
        if cls is None:
            raise
        if "JTOTAL" in meas._starts:
            meas.stop("JTOTAL")
        meas.meta["failure_class"] = cls
        print(f"[RESULTS] failure/failure_class: {cls}")
        print(f"[RESULTS] failure/error: {e}", file=sys.stderr)
        bundle = _emit_failure_bundle(meas, e, args)
        if bundle:
            print(f"[FORENSICS] bundle {bundle}", file=sys.stderr)
        if args.output_dir:
            path = meas.store(args.output_dir)
            print(f"[PERF] stored {path}")
        return 1
    # plan-vs-actual audit (planner/audit.py): every planned join closes
    # the loop on the PR 2 cost model — measured JTOTAL vs predicted_ms,
    # PLANDRIFT gauge for the regress gate, and the explain table grows
    # its actuals column for the strategy that actually ran
    # critical-path attribution (observability/critpath.py): reconstruct
    # the path over this rank's live tracer stream (the cross-rank file
    # merge is tools_critical_path.py's post-run job), stamp it into the
    # registry meta so bundles and the ledger carry it, print the
    # [CRITPATH] line, and re-price the plan audit against the measured
    # bounding rank instead of the local mean
    cp = None
    if meas.tracer is not None:
        from tpu_radix_join.observability.critpath import (
            critical_path_from_tracer, format_summary)
        cp = critical_path_from_tracer(meas.tracer)
        meas.meta["critical_path"] = cp
        if jax.process_index() == 0:
            print(f"[CRITPATH] {format_summary(cp)}")
    audit = audit_plan(plan, meas, repeats=args.repeat, times0=times0,
                       critical_path=cp)
    if audit is not None and jax.process_index() == 0:
        print(f"[PLAN] actual_ms={audit['actual_ms']:.1f} "
              f"predicted_ms={audit['predicted_ms']:.1f} "
              f"drift={audit['drift_pct']:.1f}%")
        if plan_costs is not None and explain_tbl is not None:
            print(explain_tbl(plan_costs, plan,
                              actuals=actuals_for_explain(audit),
                              static=plan_static,
                              critpath=critpath_for_explain(audit)))
    # per-rank failure class rides the registry meta into the rank-0
    # aggregate report (performance.print_results): a multi-rank run where
    # one rank degraded must say so in the summary, not only in that
    # rank's own .info file
    meas.meta["failure_class"] = (result.diagnostics or {}).get(
        "failure_class", "ok" if result.ok else "unknown")
    # per-site fault-injection accounting (hits/fired, faults.site_stats):
    # rides into the rank-0 FaultSites aggregate next to FailureClasses
    if (result.diagnostics or {}).get("fault_sites"):
        meas.meta["fault_sites"] = result.diagnostics["fault_sites"]
    if args.repeat > 1:
        # RESULTS accumulates per join; the report's "Tuples" line means THE
        # join's result count.  Times/tuple counters stay cumulative (JRATE
        # divides cumulative tuples by cumulative time — consistent).
        meas.counters["RESULTS"] = result.matches
    if args.measure_phases or args.output_dir:
        # dispatch-floor tag: lets readers subtract the per-program host
        # round trip from the split phase columns (VERDICT r3 weak #6)
        meas.measure_dispatch_floor()

    if (result.diagnostics or {}).get("recovered"):
        d = result.diagnostics
        print(f"[RESULTS] recovered: epoch={d.get('membership_epoch')} "
              f"lost_ranks={d.get('lost_ranks')} "
              f"resumed={len(d.get('resumed_partitions') or [])} "
              f"recomputed={len(d.get('recovered_partitions') or [])}")
        if d.get("regrown"):
            print(f"[RESULTS] regrown: "
                  f"joined_ranks={d.get('joined_ranks_admitted')} "
                  f"survivors={d.get('survivors')}")
        if d.get("hedged"):
            print(f"[RESULTS] hedged: straggler={d.get('straggler')} "
                  f"partitions={d.get('hedged_partitions')} "
                  f"hedgewin={d.get('hedgewin')} "
                  f"specwaste={d.get('specwaste')}")
    # The reference's rank-0 aggregate report (Measurements.cpp:592-702):
    # multi-process worlds gather every rank's registry over the network
    # first (Measurements.gather_all); rank 0 alone prints.  After a rank
    # loss the gather itself is a collective on the dead mesh — skip it
    # and let the lowest SURVIVOR report from its own registry.
    lost = sorted(membership.lost) if membership is not None else []
    all_meas = (meas.gather_all() if distributed and not lost else [meas])
    if lost and membership.board.num_ranks > 1:
        reporter = membership.board.rank == min(membership.survivors)
    else:
        reporter = jax.process_index() == 0
    if reporter:
        if len(all_meas) == 1:
            # multi-rank runs get this line from print_results below
            print(f"[RESULTS] Tuples: {result.matches}")
        if expected is not None:
            status = "OK" if result.matches == expected else "MISMATCH"
            print(f"[RESULTS] Expected: {expected} ({status})")
        print(f"[RESULTS] Conservation: {'OK' if result.ok else 'VIOLATED'}")
        out_devs = meas.meta.get("output_devices")
        if out_devs:
            print(f"[RESULTS] Output devices: {len(out_devs)} "
                  f"({'; '.join(out_devs)})")
        if not result.ok and result.diagnostics:
            for k, v in result.diagnostics.items():
                print(f"[RESULTS] failure/{k}: {v}")
        total_us = meas.times_us.get("JTOTAL", 0.0)
        if total_us:
            rate = (2 * global_size * args.repeat) / (total_us / 1e6)
            print(f"[RESULTS] Throughput: {rate / 1e6:.1f} M tuples/sec")
        if len(all_meas) > 1:
            from tpu_radix_join.performance import print_results
            print_results(all_meas)
        else:
            for line in meas.lines():
                print(f"[PERF] {line}")
    if args.output_dir:
        # the post-join memory checkpoint (JOIN_MEM_DEBUG analog,
        # main.cpp:32,68,92): lands in <rank>.info under "memory"
        meas.memory_utilization()
        path = meas.store(args.output_dir)
        if jax.process_index() == 0:
            print(f"[PERF] stored {path}")

    bad = (expected is not None and result.matches != expected) or not result.ok
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

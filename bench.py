"""Benchmark driver: single-chip radix join throughput on real TPU.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Workload: the reference's canonical per-node join scaled to one chip —
16M ⋈ 16M dense unique uint32 keys (BASELINE.md config #2; the reference runs
20M ⋈ 20M per node, main.cpp:70-71).  Correctness is asserted against the
unique-key oracle before timing.

Timing methodology: each candidate is jitted end-to-end and timed over
enough async dispatches that compute dominates; the clock stops on a host
readback (np.asarray) of the final result.  The default path measures the
chip: it refuses to run when JAX finds no TPU, and a kernel that fails to
compile or miscounts fails the run instead of handing the measurement to
another implementation.

vs_baseline: the reference publishes no numbers (BASELINE.md — published {}),
so the denominator is 1e9 tuples/sec/accelerator, a nominal figure for the
reference-era GPU build/probe kernels (sm_60-class, eth.cu) on this workload;
vs_baseline >= 1.0 therefore means beating reference-class per-accelerator
throughput.

``--check-regress BASELINE.json`` runs the observability regression gate
as a post-step: the fresh result's numeric tags are compared against the
baseline (tools_check_regress.py semantics), the delta table goes to
stderr, and the process exits 1 on any regression — the JSON line above
is printed either way.
"""

import contextlib
import json
import math
import os
import sys
import time

import numpy as np


def _time_amortized(fn, args, iters=20):
    """Seconds/iteration: ``iters`` async dispatches closed by one host
    readback."""
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    np.asarray(out)
    return (time.perf_counter() - t0) / iters


#: cross-run ledger destination (--ledger-dir / $TPU_RADIX_LEDGER_DIR);
#: set by main(), consumed by _ledger_append after every BENCH JSON line
_LEDGER_DIR = None


def _ledger_append(result):
    """Mirror the BENCH result line into the cross-run telemetry ledger
    (observability/ledger.py) so tools_profile_fit.py can fit constants
    from live rounds without the report-time backfill.  Off unless a
    ledger dir is configured; a ledger failure never fails the bench."""
    if not _LEDGER_DIR:
        return
    try:
        from tpu_radix_join.observability.ledger import Ledger, bench_payload
        payload = bench_payload(result)
        if payload is not None:
            led = Ledger(_LEDGER_DIR)
            led.append("bench", payload)
            print(f"note: ledger row -> {led.path}", file=sys.stderr)
    except Exception as e:   # noqa: BLE001 — telemetry must not sink a bench
        print(f"note: ledger append failed: {e!r}", file=sys.stderr)


def _planned_strategy(size, iters):
    """What the planner would run for the bench workload (pure host math),
    stamped into the BENCH json beside the measured discipline."""
    try:
        from tpu_radix_join.planner import Workload, load_profile, plan_join
        plan, _ = plan_join(load_profile(), Workload(
            r_tuples=size, s_tuples=size, key_bound=size,
            num_nodes=1, repeats=iters))
        return {"strategy": plan.strategy,
                "predicted_ms": plan.predicted_ms,
                "profile": plan.profile_name}
    except Exception as e:       # a planner bug must not sink the bench
        return {"strategy": "unknown", "error": repr(e)}


#: published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (16 GB of HBM at
#: 819 GB/s per chip).  A device missing here is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "hbm_bytes": 16 * 10**9,
                    "source": "Google Cloud 'TPU v5e' page"},
}


def device_peaks(device_kind):
    """The peak row for ``device_kind``; raises on a device not in the
    table rather than scoring it against another chip's roofline."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"error: no published peaks for device_kind "
                         f"{device_kind!r}; add it to bench.DEVICE_PEAKS "
                         f"with its source") from None


def _sort_bandwidth_gbps(sort_s, size):
    """Achieved HBM GB/s of the sort stage against the external-sort traffic
    lower bound (PERF_NOTES "sort floor": ``1 + ceil(log2(union/V))`` passes
    of read+write over the packed union, V = 4M VMEM-resident elements).
    ``sort_s`` is the measured probe time per iteration, an upper bound on
    the sort, so the result is a lower bound on the sort's GB/s."""
    union = 2 * size
    vmem_elems = 4 << 20
    passes = 1 + max(0, math.ceil(math.log2(union / vmem_elems)))
    min_traffic_bytes = passes * 2 * union * 4       # r+w, 4 B/element
    return min_traffic_bytes / sort_s / 1e9


def _run_chaos(runs, base_seed=0, forensics_dir=None):
    """``--chaos N``: CPU soak of N seeded fault schedules with verification
    on.  Prints one outcome line per run and a JSON summary; a violating
    schedule is shrunk to a minimal repro written under artifacts/chaos/,
    with a forensics bundle (observability/postmortem.py) named in the
    repro.  Exit 0 iff no violations."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)
    from tpu_radix_join.robustness import chaos

    def show(out):
        cls = f" class={out.failure_class}" if out.failure_class else ""
        print(f"[CHAOS] seed={out.schedule.seed} {out.status}{cls} "
              f"arms={[s for s, _ in out.schedule.arms]}")

    here = os.path.dirname(os.path.abspath(__file__))
    bundle_dir = forensics_dir or os.path.join(here, "artifacts", "chaos",
                                               "forensics")
    runner = chaos.ChaosRunner(verify="check", bundle_dir=bundle_dir)
    outcomes, summary = chaos.soak(runs, base_seed=base_seed, runner=runner,
                                   on_outcome=show)
    for out in outcomes:
        if out.status != chaos.VIOLATION:
            continue
        shrunk = chaos.shrink(
            out.schedule,
            lambda s: runner.run(s).status == chaos.VIOLATION)
        repro = runner.run(shrunk)
        here = os.path.dirname(os.path.abspath(__file__))
        rdir = os.path.join(here, "artifacts", "chaos")
        os.makedirs(rdir, exist_ok=True)
        path = os.path.join(rdir, f"repro_seed{shrunk.seed}.json")
        print("[CHAOS] repro " + chaos.write_repro(repro, path))
        print(f"[CHAOS] repro written to {path}")
        if repro.bundle:
            print(f"[CHAOS] forensics bundle {repro.bundle}")
    print("[CHAOS] " + json.dumps(summary, sort_keys=True))
    return 0 if summary["violations"] == 0 else 1


def _run_grid_bench(check_baseline=None):
    """``--grid-bench``: A/B of the out-of-core grid engines (ops/chunked.py
    ``--grid-pipeline off`` vs ``on``) on a 4x4 chunk grid, CPU-sized like
    ``--chaos`` — it validates the pipeline's overlap win and work counters
    (GRIDPAIRS/PREFETCH/SORTREUSE), not chip throughput.  Prints one BENCH
    JSON line whose headline ``value`` is pipelined pairs/sec and whose
    ``vs_baseline``/``speedup`` is pipelined-over-synchronous, so
    tools_check_regress.py fails loudly when the pipeline regresses."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)

    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.data.streaming import stream_chunks_device
    from tpu_radix_join.ops.chunked import chunked_join_grid
    from tpu_radix_join.performance import Measurements
    from tpu_radix_join.performance.measurements import (GRIDPAIRS, PREFETCH,
                                                         SORTREUSE)

    chunk = 1 << 15                  # 32K-tuple chunks -> 4x4 grid
    size = chunk * 4
    inner = Relation(size, 1, "unique", seed=11)
    outer = Relation(size, 1, "unique", seed=12)
    expected = inner.expected_matches(outer)

    def run(mode, meas=None):
        # inner streamed once, outer regenerated per row (the out-of-core
        # shape): generation overlap is part of what the pipeline hides
        t0 = time.perf_counter()
        total = chunked_join_grid(
            stream_chunks_device(inner, 0, chunk),
            lambda: stream_chunks_device(outer, 0, chunk),
            chunk, measurements=meas, pipeline=mode)
        return total, time.perf_counter() - t0

    stats = {}
    for mode in ("off", "on"):
        run(mode)                    # warmup: compiles + thread spinup
        meas = Measurements(node_id=0, num_nodes=1)
        total, wall = run(mode, meas)
        if expected is not None and total != expected:
            print(f"ERROR: grid total {total} != oracle {expected} "
                  f"(pipeline={mode})", file=sys.stderr)
            sys.exit(3)
        pairs = meas.counters.get(GRIDPAIRS, 0)
        stats[mode] = {"wall_s": wall, "pairs": pairs,
                       "pairs_per_sec": pairs / wall if wall > 0 else 0.0,
                       "prefetch": meas.counters.get(PREFETCH, 0),
                       "sortreuse": meas.counters.get(SORTREUSE, 0)}
        print(f"note: pipeline={mode}: {wall*1e3:.1f} ms, "
              f"{stats[mode]['pairs_per_sec']:.2f} pairs/s, "
              f"PREFETCH={stats[mode]['prefetch']} "
              f"SORTREUSE={stats[mode]['sortreuse']}", file=sys.stderr)
    speedup = (stats["on"]["pairs_per_sec"]
               / max(stats["off"]["pairs_per_sec"], 1e-9))
    result = {
        "metric": "grid_join_pipeline",
        "value": round(stats["on"]["pairs_per_sec"], 3),
        "unit": "pairs/sec",
        "vs_baseline": round(speedup, 4),
        "speedup": round(speedup, 4),
        "pairs_per_sec_sync": round(stats["off"]["pairs_per_sec"], 3),
        "pairs_per_sec_pipelined": round(stats["on"]["pairs_per_sec"], 3),
        "gridpairs": stats["on"]["pairs"],
        "prefetch": stats["on"]["prefetch"],
        "sortreuse": stats["on"]["sortreuse"],
    }
    print(json.dumps(result))
    _ledger_append(result)
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        return code
    return 0


def _run_exchange_bench(check_baseline=None):
    """``--exchange-bench``: A/B of the shuffle wire format — raw 8 B/tuple
    lanes over a fused all_to_all versus the bit-packed codec
    (data/tuples.py WireSpec) over a 4-group staged exchange
    (parallel/window.py) — on an 8-way host-CPU mesh with full verification
    on.  Both arms must be oracle-exact (exit 3 otherwise); the BENCH
    headline ``value`` is the wire *reduction* ratio (raw bytes/tuple over
    packed bytes/tuple, higher is better), and the footprint tags
    (``bytes_per_tuple``, ``peak_exchange_bytes``, ``wirebytes``) gate
    lower-is-better under tools_check_regress.py."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)

    from tpu_radix_join.core.config import JoinConfig
    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.operators.hash_join import HashJoin
    from tpu_radix_join.performance import Measurements

    nodes, per_node = 8, 1 << 17
    inner = Relation(per_node * nodes, nodes, "unique", seed=21)
    outer = Relation(per_node * nodes, nodes, "unique", seed=22)
    expected = inner.expected_matches(outer)

    arms = (("off", dict(exchange_codec="off", exchange_stages=1)),
            ("pack", dict(exchange_codec="pack", exchange_stages=4)))
    stats = {}
    for name, kw in arms:
        meas = Measurements(node_id=0, num_nodes=nodes)
        eng = HashJoin(JoinConfig(num_nodes=nodes, verify="check", **kw),
                       measurements=meas)
        eng.join(inner, outer)              # warmup: mesh + compile
        t0 = time.perf_counter()
        res = eng.join(inner, outer)
        wall = time.perf_counter() - t0
        if not res.ok:
            print(f"ERROR: verification failed (codec={name}): "
                  f"{res.failure}", file=sys.stderr)
            sys.exit(3)
        if expected is not None and res.matches != expected:
            print(f"ERROR: matches {res.matches} != oracle {expected} "
                  f"(codec={name})", file=sys.stderr)
            sys.exit(3)
        xs = meas.meta.get("exchange_plan")
        if not xs:
            print(f"ERROR: no exchange_plan stamped (codec={name})",
                  file=sys.stderr)
            sys.exit(3)
        stats[name] = dict(xs, wall_s=wall)
        print(f"note: codec={name}: {xs['bytes_per_tuple']:.3f} B/tuple, "
              f"peak {xs['peak_exchange_bytes']} B/collective, "
              f"wire {xs['wire_bytes']} B, stages={xs['stages']}, "
              f"{wall*1e3:.1f} ms wall", file=sys.stderr)

    off, pack = stats["off"], stats["pack"]
    reduction = off["bytes_per_tuple"] / max(pack["bytes_per_tuple"], 1e-9)
    peak_speedup = (off["peak_exchange_bytes"]
                    / max(pack["peak_exchange_bytes"], 1))
    result = {
        "metric": "exchange_wire_reduction",
        "value": round(reduction, 4),
        "unit": "raw_over_packed_bytes",
        "vs_baseline": round(reduction, 4),
        "bytes_per_tuple": round(pack["bytes_per_tuple"], 4),
        "bytes_per_tuple_raw": round(off["bytes_per_tuple"], 4),
        "peak_exchange_bytes": pack["peak_exchange_bytes"],
        "peak_exchange_bytes_raw": off["peak_exchange_bytes"],
        "peak_speedup": round(peak_speedup, 2),
        "wirebytes": pack["wire_bytes"],
        "wirebytes_raw": off["wire_bytes"],
        "pack_ratio_pct": pack["pack_ratio_pct"],
        "stages": pack["stages"],
        "wall_off_ms": round(off["wall_s"] * 1e3, 1),
        "wall_pack_ms": round(pack["wall_s"] * 1e3, 1),
    }
    print(json.dumps(result))
    _ledger_append(result)
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        return code
    return 0


def _run_partition_bench(check_baseline=None, size=1 << 24):
    """``--partition-bench``: A/B of the destination-grouping engine —
    the sort-based block scatter (``sort_kv_unstable`` over every lane)
    versus the fused Pallas histogram→scan→scatter partition kernel
    (ops/pallas/partition.py, interpreted on this host mesh) — at ``size``
    keys over 8 destination blocks.

    Correctness first: two full 8-way host-CPU joins (one per impl) with
    ``verify=check`` must be oracle-exact (exit 3 otherwise) so the timing
    legs can never bless a wrong kernel.  The BENCH headline ``value`` is
    the wall speedup (sort over fused, higher is better); the per-arm
    walls land as lower-is-better tags and ``partition_unit_ms`` is the
    reduced ms/Mtuple/pass constant the profile fitter recovers
    (planner/calibrate.py BENCH_PARTITION_METRIC)."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)

    import jax
    import jax.numpy as jnp
    from tpu_radix_join.core.config import JoinConfig
    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.data.tuples import TupleBatch
    from tpu_radix_join.operators.hash_join import HashJoin
    from tpu_radix_join.ops.pallas.partition import partition_slots_pallas
    from tpu_radix_join.ops.radix import scatter_to_blocks
    from tpu_radix_join.performance import Measurements

    nodes, per_node = 8, 1 << 15
    inner = Relation(per_node * nodes, nodes, "unique", seed=31)
    outer = Relation(per_node * nodes, nodes, "unique", seed=32)
    expected = inner.expected_matches(outer)
    for impl in ("sort", "pallas_interpret"):
        meas = Measurements(node_id=0, num_nodes=nodes)
        eng = HashJoin(JoinConfig(num_nodes=nodes, verify="check",
                                  partition_impl=impl), measurements=meas)
        res = eng.join(inner, outer)
        if not res.ok:
            print(f"ERROR: verification failed (partition_impl={impl}): "
                  f"{res.failure}", file=sys.stderr)
            sys.exit(3)
        if expected is not None and res.matches != expected:
            print(f"ERROR: matches {res.matches} != oracle {expected} "
                  f"(partition_impl={impl})", file=sys.stderr)
            sys.exit(3)
        print(f"note: join oracle-exact (partition_impl={impl}, "
              f"{per_node * nodes} tuples/side)", file=sys.stderr)

    # timing legs: the isolated scatter at bench scale — the same
    # (batch, dest) -> blocks transform both engines run inside shard_map,
    # jitted standalone so the A/B measures the grouping discipline alone
    n = size
    cap = (n // nodes) * 3 // 2          # uniform dest + 1.5x slack
    rng = np.random.default_rng(7)
    dest = jnp.asarray(rng.integers(0, nodes, n, dtype=np.uint32))
    batch = TupleBatch(key=jnp.asarray(
        rng.integers(0, 1 << 31, n, dtype=np.uint32)),
        rid=jnp.arange(n, dtype=jnp.uint32))

    def arm(impl):
        fn = jax.jit(lambda b, d: scatter_to_blocks(
            b, d, nodes, cap, "inner", impl=impl)[0].key)
        return _time_amortized(fn, (batch, dest), iters=2) * 1e3

    sort_wall = arm("sort")
    fused_wall = arm("pallas_interpret")
    kernel_fn = jax.jit(lambda d: partition_slots_pallas(
        d, num_groups=nodes, capacity=cap, interpret=True)[0])
    kernel_wall = _time_amortized(kernel_fn, (dest,), iters=2) * 1e3
    unit = kernel_wall / (2.0 * n / 1e6)
    speedup = sort_wall / max(fused_wall, 1e-9)
    print(f"note: {n} keys -> {nodes} blocks: sort {sort_wall:.0f} ms, "
          f"fused {fused_wall:.0f} ms (kernel {kernel_wall:.0f} ms), "
          f"speedup {speedup:.2f}x, unit {unit:.4f} ms/Mtuple/pass",
          file=sys.stderr)

    result = {
        "metric": "partition_fused_speedup",
        "value": round(speedup, 3),
        "unit": "sort_over_fused_wall",
        "vs_baseline": round(speedup, 3),
        "size": n,
        "num_blocks": nodes,
        "partition_ms": round(fused_wall, 1),
        "partition_kernel_ms": round(kernel_wall, 1),
        "partition_sort_ms": round(sort_wall, 1),
        "partition_unit_ms": round(unit, 4),
    }
    print(json.dumps(result))
    _ledger_append(result)
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        return code
    return 0


def _run_sort_bench(check_baseline=None, size=1 << 18):
    """``--sort-bench``: A/B of the flat-sort engine — ``lax.sort`` (the
    XLA emitter) versus the Pallas LSD radix sort
    (ops/pallas/radix_sort.py, interpreted on this host) — across
    key-bound widths and 1/2/3-lane tuples.

    Correctness first, twice over: (1) every (lanes, bound) cell of a
    small sweep must be oracle-exact against NumPy on BOTH arms — keys
    non-decreasing and the row multiset preserved (exit 3 otherwise);
    (2) two full 8-way host-CPU joins, one per forced ``sort_impl``
    ("xla", "pallas_interpret"), must verify oracle-exact — so the
    timing legs can never bless a wrong kernel.  The BENCH headline
    ``value`` is the wall speedup (xla over pallas, higher is better —
    expected < 1 in interpret mode on host CPU, and below 1 on a TPU
    v5e too, where lax.sort wins at every size measured), the per-arm
    walls land as lower-is-better tags, and ``sort_pass_unit_ms`` is the
    reduced ms/Mtuple/pass constant the profile fitter recovers
    (planner/calibrate.py BENCH_RADIX_SORT_METRIC).  The bounded-key leg
    must run FEWER passes and land a lower wall than the unbounded leg
    (the pass-skip is the whole point of carrying key bounds), also exit
    3 on violation."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)

    import jax
    import jax.numpy as jnp
    from tpu_radix_join.core.config import JoinConfig
    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.operators.hash_join import HashJoin
    from tpu_radix_join.ops.pallas.radix_sort import (num_radix_passes,
                                                      radix_pass_slots_pallas)
    from tpu_radix_join.ops.sorting import (set_default_sort_impl,
                                            sort_kv_unstable, sort_unstable)
    from tpu_radix_join.performance import Measurements

    # -- oracle sweep: both arms vs NumPy at every (lanes, bound) cell --
    rng = np.random.default_rng(13)
    n_small = 1 << 12
    for bound in (None, 1 << 16):
        hi = bound if bound is not None else 1 << 32
        keys = rng.integers(0, hi, n_small, dtype=np.uint32)
        vals = [rng.integers(0, 1 << 32, n_small, dtype=np.uint32)
                for _ in range(2)]
        for lanes in (1, 2, 3):
            ops = [jnp.asarray(keys)] + [jnp.asarray(v)
                                         for v in vals[:lanes - 1]]
            for impl in ("xla", "pallas_interpret"):
                if lanes == 1:
                    out = [sort_unstable(ops[0], impl=impl,
                                         key_bound=bound)]
                else:
                    out = list(sort_kv_unstable(*ops, impl=impl,
                                                key_bound=bound))
                got = [np.asarray(o) for o in out]
                ok = bool(np.all(got[0] == np.sort(keys)))
                # row-multiset preservation: canonicalize both sides by
                # lexicographic row order (equal keys may order their
                # value lanes differently per arm — both are unstable)
                raw = [keys] + vals[:lanes - 1]
                perm_in = np.lexsort(tuple(reversed(raw)))
                perm_out = np.lexsort(tuple(reversed(got)))
                ok = ok and all(
                    bool(np.all(r[perm_in] == g[perm_out]))
                    for r, g in zip(raw, got))
                if not ok:
                    print(f"ERROR: sort oracle mismatch (impl={impl}, "
                          f"lanes={lanes}, bound={bound})", file=sys.stderr)
                    sys.exit(3)
    print(f"note: sort oracle-exact on both arms "
          f"({n_small} keys x bounds (None, 1<<16) x 1/2/3 lanes)",
          file=sys.stderr)

    # -- end-to-end: one full join per forced sort engine --
    nodes, per_node = 8, 1 << 15
    inner = Relation(per_node * nodes, nodes, "unique", seed=31)
    outer = Relation(per_node * nodes, nodes, "unique", seed=32)
    expected = inner.expected_matches(outer)
    fallbacks = 0
    for impl in ("xla", "pallas_interpret"):
        meas = Measurements(node_id=0, num_nodes=nodes)
        eng = HashJoin(JoinConfig(num_nodes=nodes, verify="check",
                                  sort_impl=impl), measurements=meas)
        res = eng.join(inner, outer)
        if not res.ok:
            print(f"ERROR: verification failed (sort_impl={impl}): "
                  f"{res.failure}", file=sys.stderr)
            sys.exit(3)
        if expected is not None and res.matches != expected:
            print(f"ERROR: matches {res.matches} != oracle {expected} "
                  f"(sort_impl={impl})", file=sys.stderr)
            sys.exit(3)
        fallbacks = max(fallbacks, meas.counters.get("SORTFALLBACK", 0))
        print(f"note: join oracle-exact (sort_impl={impl}, "
              f"{per_node * nodes} tuples/side)", file=sys.stderr)
    set_default_sort_impl("auto")        # don't leak the forced engine

    # -- timing legs: flat 2-lane kv sort at bench scale --
    n = size
    keys = jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint32))
    rids = jnp.arange(n, dtype=jnp.uint32)
    bounded = jnp.asarray(rng.integers(0, 1 << 16, n, dtype=np.uint32))

    def arm(impl, k, key_bound=None):
        fn = jax.jit(lambda a, b: sort_kv_unstable(
            a, b, impl=impl, key_bound=key_bound)[0])
        return _time_amortized(fn, (k, rids), iters=2) * 1e3

    xla_wall = arm("xla", keys)
    pallas_wall = arm("pallas_interpret", keys)
    bounded_wall = arm("pallas_interpret", bounded, key_bound=1 << 16)
    passes = num_radix_passes(None)
    bounded_passes = num_radix_passes(1 << 16)
    if not (bounded_passes < passes and bounded_wall < pallas_wall):
        print(f"ERROR: bounded keys must run fewer passes at lower wall: "
              f"{bounded_passes}/{passes} passes, "
              f"{bounded_wall:.0f}/{pallas_wall:.0f} ms", file=sys.stderr)
        sys.exit(3)
    # the slot kernel alone (one digit pass; passes are cost-identical,
    # so the per-row kernel wall is one pass times the row's pass count)
    kernel_fn = jax.jit(lambda k: radix_pass_slots_pallas(
        k, shift=0, interpret=True))
    kernel_wall = _time_amortized(kernel_fn, (keys,), iters=2) * 1e3 * passes
    unit = kernel_wall / (passes * n / 1e6)
    speedup = xla_wall / max(pallas_wall, 1e-9)
    print(f"note: {n} keys kv-sorted: xla {xla_wall:.0f} ms, radix "
          f"{pallas_wall:.0f} ms/{passes}p (kernel {kernel_wall:.0f} ms), "
          f"bounded {bounded_wall:.0f} ms/{bounded_passes}p, "
          f"speedup {speedup:.2f}x, unit {unit:.4f} ms/Mtuple/pass",
          file=sys.stderr)

    result = {
        "metric": "radix_sort_speedup",
        "value": round(speedup, 3),
        "unit": "xla_over_pallas_wall",
        "vs_baseline": round(speedup, 3),
        "size": n,
        "sort_ms": round(pallas_wall, 1),
        "sort_xla_ms": round(xla_wall, 1),
        "sort_kernel_ms": round(kernel_wall, 1),
        "sort_pass_unit_ms": round(unit, 4),
        "sort_passes": passes,
        "sort_bounded_ms": round(bounded_wall, 1),
        "sort_bounded_passes": bounded_passes,
        "sortfallback": int(fallbacks),
    }
    print(json.dumps(result))
    _ledger_append(result)
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        return code
    return 0


def _run_serve_bench(check_baseline=None, queries=20, chaos=False):
    """``--serve-bench [N]``: the resident-service amortization bench.  N
    queries stream through ONE JoinSession on host CPU; query 0 pays mesh
    bring-up + compilation + the JHIST sizing pre-pass, every later
    same-shape query warm-starts from the session's hot capacity cache.
    Prints one BENCH JSON line whose headline ``value`` is warm
    queries/sec and whose SLO tags (slo_p99_ms, admission_rejection_rate,
    ...) gate direction-aware under tools_check_regress.py.

    ``--serve-chaos`` arms a mid-stream burst of 3 consecutive
    ``backend.dispatch`` outages: the breaker must trip, serve the next
    queries degraded on the CPU-fallback engine, recover through a
    half-open probe, and END CLOSED — with every outcome classified.
    Exit 3 on any unclassified outcome, silent wrong count, or a chaos
    run that fails to trip+recover."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)

    from tpu_radix_join.core.config import JoinConfig, ServiceConfig
    from tpu_radix_join.performance import Measurements
    from tpu_radix_join.robustness import faults
    from tpu_radix_join.robustness.faults import TransientFault
    from tpu_radix_join.service import UNCLASSIFIED, JoinSession, QueryRequest

    cfg = JoinConfig(num_nodes=8)
    svc = ServiceConfig(breaker_threshold=3, breaker_cooldown_s=0.05,
                        cpu_fallback=True)
    meas = Measurements(node_id=0, num_nodes=8)
    session = JoinSession(cfg, svc, measurements=meas)

    burst_at = queries // 2
    inj = faults.FaultInjector(seed=7, measurements=meas)
    if chaos:
        # three consecutive primary-dispatch outages mid-stream: exactly
        # the breaker threshold, so the trip happens ON the burst
        inj.arm(faults.BACKEND_DISPATCH,
                at=tuple(range(burst_at, burst_at + 3)),
                exc=TransientFault)

    outcomes = []
    ctx = inj if chaos else contextlib.nullcontext()
    with ctx:
        for i in range(queries):
            session.submit(QueryRequest(query_id=f"q{i}",
                                        tuples_per_node=1 << 13, seed=17))
            out = session.run_next()
            outcomes.append(out)
            if chaos and out.latency_ms < 50:
                time.sleep(0.02)     # let the open-state cooldown elapse
    summary = session.summary()
    session.close()

    bad = []
    for o in outcomes:
        if o.failure_class == UNCLASSIFIED:
            bad.append(f"{o.query_id}: unclassified outcome")
        if (o.status == "ok" and o.expected is not None
                and o.matches != o.expected):
            bad.append(f"{o.query_id}: silent wrong count {o.matches} != "
                       f"{o.expected}")
    if chaos:
        if summary["breaker_trips"] < 1:
            bad.append("chaos burst did not trip the breaker")
        if summary["breaker_probes"] < 1:
            bad.append("breaker never dispatched a half-open probe")
        if summary["breaker_state"] != "closed":
            bad.append(f"breaker ended {summary['breaker_state']}, "
                       f"not closed")
        if summary["degraded_queries"] < 1:
            bad.append("no query served degraded while open")
    if bad:
        for b in bad:
            print(f"ERROR: {b}", file=sys.stderr)
        return 3

    cold_ms = outcomes[0].latency_ms
    warm = sorted(o.latency_ms for o in outcomes if o.warm)
    warm_p50 = warm[len(warm) // 2] if warm else float("nan")
    warm_qps = (len(warm) / (sum(warm) / 1e3)) if warm else 0.0
    for o in outcomes:
        print(f"note: {o.query_id} {o.status}/{o.failure_class} "
              f"{o.latency_ms:.1f} ms engine={o.engine}"
              f"{' warm' if o.warm else ''} breaker={o.breaker_state}",
              file=sys.stderr)
    result = {
        "metric": "resident_join_service",
        "value": round(warm_qps, 3),
        "unit": "queries/sec",
        "queries": queries,
        "cold_latency_ms": round(cold_ms, 3),
        "warm_latency_p50_ms": round(warm_p50, 3),
        "warm_speedup": round(cold_ms / warm_p50, 2) if warm else 0.0,
        "warm_queries": summary["warm_queries"],
        "degraded_queries": summary["degraded_queries"],
        "breaker_trips": summary["breaker_trips"],
        "breaker_probes": summary["breaker_probes"],
        "admission_rejection_rate": summary["admission_rejection_rate"],
        "deadline_miss_rate": summary["deadline_miss_rate"],
        "degraded_rate": summary["degraded_rate"],
        "slo_p50_ms": summary.get("slo_p50_ms"),
        "slo_p95_ms": summary.get("slo_p95_ms"),
        "slo_p99_ms": summary.get("slo_p99_ms"),
        "chaos": chaos,
    }
    print(json.dumps(result))
    _ledger_append(result)
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        return code
    return 0


def _run_serve_throughput_bench(check_baseline=None):
    """``--serve-throughput-bench``: the serving fast-path A/B for the
    three gated tiers (ROADMAP serving throughput: result cache,
    micro-batching, delta-merge), all on host CPU.

    Four legs, each oracle-exact or exit 3:

      * **cache** — one session with the fingerprint result cache on: a
        timed cold execution vs the timed repeat of the SAME content.
        The repeat must come back ``served_by=cache_hit`` with the cold
        answer and >= 10x faster (the tier exists to skip admission and
        execution entirely, so anything less means it executed).
      * **batch** — per batch size Q in {2, 4, 8}: a serial drain of Q
        co-signature queries vs the SAME queries drained through ONE
        fused device program (``served_by=batched``).  Warm pass first
        so both arms price steady-state serving, not compilation; the
        fused arm must beat serial by >= 1.5x at Q=4.
      * **delta** — per Δ/N in {1/16, 1/64, 1/256}: a resident session
        absorbing three deltas O(N+Δ) (``served_by=delta_merge``, the
        unchanged-outer incremental probe) vs the budget-0 posture
        re-sorting and re-probing from scratch every query; >= 2x at
        Δ/N = 1/64.
      * **fleet chaos** — a 2-worker fleet coalescing a 4-query
        co-batchable group through one worker, SIGKILLed mid-batch
        (``fleet.worker_kill``): every query must still end oracle-exact
        through journaled failover and the drain audit must report
        ``unacked == 0`` and ``double_exec == 0``.

    The statusz leg polls a live ``/statusz`` 5 times plus ``/healthz``
    against the cache/batch sections while the session serves — the
    introspection plane must answer every poll mid-serving.

    The BENCH headline ``value`` is the Q=4 fused-over-serial speedup;
    cache_speedup / delta_speedup / batch_fuse_ratio and the six serving
    counters ride as tags, direction-gated under tools_check_regress.py
    (double_exec pins to zero)."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)

    import statistics
    import tempfile
    import urllib.request

    from tpu_radix_join.core.config import JoinConfig, ServiceConfig
    from tpu_radix_join.observability.statusz import StatuszServer
    from tpu_radix_join.performance import Measurements
    from tpu_radix_join.performance.measurements import (BATCHN, BATCHQ,
                                                         DELTAMERGE, FAILOVER,
                                                         RCHIT, RCMISS,
                                                         RESBYTES)
    from tpu_radix_join.robustness import faults
    from tpu_radix_join.service import JoinSession, QueryRequest
    from tpu_radix_join.service.fleet import FleetSupervisor

    cfg = JoinConfig(num_nodes=8)
    bad = []

    def exact(out, leg):
        if not (out is not None and out.status == "ok"
                and out.expected is not None
                and out.matches == out.expected):
            bad.append(
                f"{leg}: {getattr(out, 'query_id', None)} not oracle-exact "
                f"({getattr(out, 'status', 'missing')} "
                f"matches={getattr(out, 'matches', None)} "
                f"expected={getattr(out, 'expected', None)} "
                f"{getattr(out, 'detail', '')})")
            return False
        return True

    # ---- leg 1: result cache + the statusz/healthz liveness poll
    svc = ServiceConfig(result_cache_max=8, batch_window_ms=25.0,
                        batch_max_queries=8, default_deadline_s=300.0)
    meas = Measurements(node_id=0, num_nodes=8)
    session = JoinSession(cfg, svc, measurements=meas)
    statusz = StatuszServer(port=0, sections={
        "cache": lambda: session.result_cache.stats(),
        "batch": lambda: {"fused_batches": session.batches_fused,
                          "fused_queries": session.batch_queries_fused},
    })
    statusz.start()
    url = f"http://127.0.0.1:{statusz.port}"
    polls = 0
    try:
        session.submit(QueryRequest(query_id="warm",
                                    tuples_per_node=1 << 13, seed=3))
        session.run_next()          # engine + compile warm-up, seed 3
        t0 = time.perf_counter()
        session.submit(QueryRequest(query_id="cold",
                                    tuples_per_node=1 << 13, seed=5))
        cold = session.run_next()
        cold_ms = (time.perf_counter() - t0) * 1e3
        hit = session.try_cache(QueryRequest(query_id="hit",
                                             tuples_per_node=1 << 13,
                                             seed=5))
        # 5-poll liveness against the serving session: every poll must
        # answer with the cache/batch sections present, plus /healthz
        for _ in range(5):
            with urllib.request.urlopen(f"{url}/statusz",
                                        timeout=5) as resp:
                page = json.loads(resp.read())
            if "cache" not in page.get("sections", page):
                bad.append("statusz poll lost the cache section")
            polls += 1
        with urllib.request.urlopen(f"{url}/healthz", timeout=5) as resp:
            if resp.status != 200:
                bad.append(f"/healthz answered {resp.status}")
        cache_stats = session.result_cache.stats()
    finally:
        statusz.stop()
        session.close()
    exact(cold, "cache-cold")
    if hit is None or hit.served_by != "cache_hit":
        bad.append(f"repeat content did not cache-serve "
                   f"(served_by={getattr(hit, 'served_by', None)})")
        cache_speedup = 0.0
        hit_ms = float("nan")
    else:
        exact(hit, "cache-hit")
        if hit.matches != cold.matches:
            bad.append(f"cache hit answer drifted: {hit.matches} != "
                       f"{cold.matches}")
        hit_ms = hit.latency_ms
        cache_speedup = cold_ms / max(hit_ms, 1e-9)
        if cache_speedup < 10.0:
            bad.append(f"cache hit only {cache_speedup:.1f}x over cold "
                       f"({hit_ms:.3f} vs {cold_ms:.1f} ms); gate is 10x")
    if polls < 5:
        bad.append(f"only {polls}/5 statusz polls answered")

    # ---- leg 2: micro-batch fuse A/B at Q = 2, 4, 8
    def batch_arm(q, fuse, tag):
        svc = ServiceConfig(batch_window_ms=50.0 if fuse else 0.0,
                            batch_max_queries=8, default_deadline_s=300.0)
        m2 = Measurements(node_id=0, num_nodes=8)
        s2 = JoinSession(cfg, svc, measurements=m2)
        try:
            walls = []
            outs = []
            for rnd in ("w", "t"):          # warm pass, then timed pass
                for i in range(q):
                    s2.submit(QueryRequest(query_id=f"{tag}{rnd}{i}",
                                           tuples_per_node=1 << 10,
                                           seed=23))
                t0 = time.perf_counter()
                outs = s2.drain(batched=fuse)
                walls.append((time.perf_counter() - t0) * 1e3)
            for o in outs:
                exact(o, f"batch-q{q}-{'fused' if fuse else 'serial'}")
                want = "batched" if fuse else "execute"
                if o.served_by != want:
                    bad.append(f"{o.query_id}: served_by={o.served_by}, "
                               f"want {want}")
            return walls[-1], m2
        finally:
            s2.close()

    batch_speedups = {}
    batchn = batchq = 0
    for q in (2, 4, 8):
        serial_ms, _ = batch_arm(q, fuse=False, tag=f"s{q}")
        fused_ms, mf = batch_arm(q, fuse=True, tag=f"f{q}")
        batchn += int(mf.counters.get(BATCHN, 0))
        batchq += int(mf.counters.get(BATCHQ, 0))
        batch_speedups[q] = serial_ms / max(fused_ms, 1e-9)
        print(f"note: batch q={q}: serial {serial_ms:.1f} ms vs fused "
              f"{fused_ms:.1f} ms -> {batch_speedups[q]:.2f}x",
              file=sys.stderr)
    if batch_speedups[4] < 1.5:
        bad.append(f"fused batch of 4 only {batch_speedups[4]:.2f}x over "
                   f"serial; gate is 1.5x")
    fuse_ratio = batchq / batchn if batchn else 0.0

    # ---- leg 3: delta-merge A/B at Δ/N = 1/16, 1/64, 1/256
    def delta_arm(budget, ratio, tag):
        svc = ServiceConfig(resident_budget_bytes=budget,
                            default_deadline_s=300.0)
        m3 = Measurements(node_id=0, num_nodes=8)
        s3 = JoinSession(cfg, svc, measurements=m3)
        nt = 1 << 14
        try:
            lats, outs = [], []
            for i in range(4):
                s3.submit(QueryRequest(
                    query_id=f"{tag}{i}", tuples_per_node=nt,
                    delta_tuples_per_node=max(1, nt // ratio), seed=11))
                out = s3.run_next()
                outs.append(out)
                lats.append(out.latency_ms)
            for o in outs:
                exact(o, f"delta-1/{ratio}-"
                         f"{'resident' if budget else 'full'}")
            if budget:
                hot = [o.served_by for o in outs[1:]]
                if hot != ["delta_merge"] * 3:
                    bad.append(f"resident arm 1/{ratio} not on the delta "
                               f"path: {hot}")
            # query 0 is the cold seed in BOTH arms; steady state is q1..3
            return statistics.mean(lats[1:]), m3
        finally:
            s3.close()

    delta_speedups = {}
    deltamerge = resbytes = 0
    for ratio in (16, 64, 256):
        # warm pass compiles the per-shape programs (process-global
        # lru_cache in ops/merge_delta.py), so the timed pass prices
        # serving, not tracing
        delta_arm(1 << 27, ratio, f"dwr{ratio}_")
        delta_arm(0, ratio, f"dwf{ratio}_")
        hot_ms, mr = delta_arm(1 << 27, ratio, f"dr{ratio}_")
        cold_ms_d, _ = delta_arm(0, ratio, f"df{ratio}_")
        deltamerge += int(mr.counters.get(DELTAMERGE, 0))
        resbytes = max(resbytes, int(mr.counters.get(RESBYTES, 0)))
        delta_speedups[ratio] = cold_ms_d / max(hot_ms, 1e-9)
        print(f"note: delta 1/{ratio}: resident {hot_ms:.1f} ms vs full "
              f"re-sort {cold_ms_d:.1f} ms -> "
              f"{delta_speedups[ratio]:.2f}x", file=sys.stderr)
    if delta_speedups[64] < 2.0:
        bad.append(f"delta merge at 1/64 only {delta_speedups[64]:.2f}x "
                   f"over the full re-sort; gate is 2x")

    # ---- leg 4: mid-batch worker kill must not break exactly-once
    tmp = tempfile.mkdtemp(prefix="serve_tp_bench_")
    tpn_c = 1 << 10
    worker_args = ["--nodes", "1", "--verify", "check",
                   "--batch-window-ms", "25", "--batch-max", "8"]
    mF = Measurements()
    sup = FleetSupervisor(2, worker_args, os.path.join(tmp, "chaos"),
                          measurements=mF, lease_s=1.0,
                          batch_window_ms=25.0)
    double_exec = -1
    try:
        sup.start()
        warm = sup.dispatch({"query_id": "cw", "tenant": "t0",
                             "tuples_per_node": tpn_c, "seed": 7})
        if not (warm.get("status") == "ok"
                and warm.get("matches") == tpn_c):
            bad.append(f"fleet warm-up not oracle-exact: {warm.get('status')} "
                       f"matches={warm.get('matches')}")
        group = [{"query_id": f"c{i}", "tenant": "t0",
                  "tuples_per_node": tpn_c, "seed": 7 + i}
                 for i in range(4)]
        # the kill site fires per written query (1-based): the routed
        # worker dies right after the first write, mid-group — the
        # unanswered remainder must fail over under its journaled
        # fingerprints
        with faults.FaultInjector(seed=13, measurements=mF).arm(
                faults.FLEET_WORKER_KILL, at=1):
            outs = sup.dispatch_batch(group)
        for o in outs:
            if not (o.get("status") == "ok"
                    and o.get("matches") == tpn_c):
                bad.append(f"mid-batch kill lost {o.get('query_id')}: "
                           f"{o.get('status')} "
                           f"matches={o.get('matches')} != {tpn_c} "
                           f"({o.get('detail')})")
        report = sup.drain()
        double_exec = report["double_exec"]
        if report["unacked"] or report["double_exec"]:
            bad.append(f"mid-batch kill broke exactly-once at drain: "
                       f"{report}")
        if int(mF.counters.get(FAILOVER, 0)) < 1:
            bad.append("mid-batch kill never failed over — the chaos "
                       "site did not fire (armed at write 1)")
        print(f"note: mid-batch kill: 4/4 exact through failover, "
              f"restarts={sup.restarts}, drain={report}", file=sys.stderr)
    finally:
        sup.close()

    if bad:
        for b in bad:
            print(f"ERROR: {b}", file=sys.stderr)
        return 3

    print(f"note: cache {cache_speedup:.0f}x (cold {cold_ms:.1f} ms -> "
          f"hit {hit_ms:.3f} ms), batch {batch_speedups[4]:.2f}x at q=4, "
          f"delta {delta_speedups[64]:.2f}x at 1/64", file=sys.stderr)
    result = {
        "metric": "serve_fastpath_speedup",
        "value": round(batch_speedups[4], 3),
        "unit": "serial_over_fused_wall_q4",
        "cache_cold_latency_ms": round(cold_ms, 3),
        "cache_hit_latency_ms": round(hit_ms, 4),
        "cache_speedup": round(cache_speedup, 1),
        "cache_hit_rate": cache_stats["hit_rate"],
        "batch_speedup_2": round(batch_speedups[2], 3),
        "batch_speedup_4": round(batch_speedups[4], 3),
        "batch_speedup_8": round(batch_speedups[8], 3),
        "batch_fuse_ratio": round(fuse_ratio, 3),
        "delta_speedup_16": round(delta_speedups[16], 3),
        "delta_speedup_64": round(delta_speedups[64], 3),
        "delta_speedup_256": round(delta_speedups[256], 3),
        "delta_speedup": round(delta_speedups[64], 3),
        "rchit": int(meas.counters.get(RCHIT, 0)),
        "rcmiss": int(meas.counters.get(RCMISS, 0)),
        "batchn": batchn,
        "batchq": batchq,
        "deltamerge": deltamerge,
        "resbytes": resbytes,
        "statusz_polls": polls,
        "double_exec": double_exec,
    }
    print(json.dumps(result))
    _ledger_append(result)
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        return code
    return 0


def _run_critpath_bench(check_baseline=None, size=1 << 20, iters=5):
    """``--critpath-bench``: instrumentation-overhead A/B for the
    critical-path attribution plane (observability/critpath.py +
    statusz.py).

    Two arms of the same 1M x 1M 8-way host-CPU join: the BARE arm runs
    with the registry alone (the pre-observability posture); the
    INSTRUMENTED arm attaches the span tracer, keeps a live ``/statusz``
    endpoint up and polls it once per join (the operator's heartbeat
    query), and reconstructs the critical path after every join — the
    full cost of the introspection plane under load.  Per-arm walls are
    per-iteration medians, so one scheduler hiccup cannot fake a
    regression.  The headline ``value`` is instrumented throughput;
    ``critpath_overhead_pct`` and the path's ``wait_fraction`` gate
    lower-is-better under tools_check_regress.py.  Exit 3 when either
    arm misses the oracle or the overhead exceeds the 1%% acceptance
    bar."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)

    import urllib.request

    import jax.numpy as jnp
    from tpu_radix_join.core.config import JoinConfig
    from tpu_radix_join.data.tuples import TupleBatch
    from tpu_radix_join.observability.critpath import (
        critical_path_from_tracer)
    from tpu_radix_join.observability.statusz import (StatuszServer,
                                                      measurements_sections)
    from tpu_radix_join.operators.hash_join import HashJoin
    from tpu_radix_join.performance import Measurements

    nodes, n = 8, size
    cfg = JoinConfig(num_nodes=nodes)
    rng = np.random.default_rng(29)
    rk = (rng.permutation(n) + 1).astype(np.uint32)
    sk = rng.integers(1, n + 1, size=n).astype(np.uint32)
    rid = np.arange(n, dtype=np.uint32)
    r = TupleBatch(key=jnp.asarray(rk), rid=jnp.asarray(rid))
    s = TupleBatch(key=jnp.asarray(sk), rid=jnp.asarray(rid))

    def median(vals):
        vs = sorted(vals)
        return vs[len(vs) // 2]

    def bare_arm():
        meas = Measurements(node_id=0, num_nodes=nodes)
        eng = HashJoin(cfg, measurements=meas)
        res = eng.join_arrays(r, s)              # compile warm-up
        walls = []
        for _ in range(iters):
            t0 = time.perf_counter()
            res = eng.join_arrays(r, s)
            walls.append((time.perf_counter() - t0) * 1e3)
        return res, median(walls)

    def instrumented_arm():
        meas = Measurements(node_id=0, num_nodes=nodes)
        meas.attach_tracer(nodes=nodes)
        eng = HashJoin(cfg, measurements=meas)
        statusz = StatuszServer(port=0,
                                sections=measurements_sections(meas))
        statusz.start()
        url = f"http://127.0.0.1:{statusz.port}/statusz"
        cp = None
        try:
            res = eng.join_arrays(r, s)          # compile warm-up
            walls = []
            for _ in range(iters):
                t0 = time.perf_counter()
                res = eng.join_arrays(r, s)
                with urllib.request.urlopen(url, timeout=5) as resp:
                    json.loads(resp.read())
                cp = critical_path_from_tracer(meas.tracer)
                walls.append((time.perf_counter() - t0) * 1e3)
            polls = statusz.requests_served
        finally:
            statusz.stop()
        return res, median(walls), cp, polls

    res_bare, bare_ms = bare_arm()
    res_inst, inst_ms, cp, polls = instrumented_arm()
    for arm, res in (("bare", res_bare), ("instrumented", res_inst)):
        if not (res.ok and res.matches == n):
            print(f"ERROR: {arm} arm missed the oracle: {res.matches} "
                  f"!= {n}", file=sys.stderr)
            return 3
    if cp is None or cp.get("error"):
        print(f"ERROR: no critical path reconstructed: "
              f"{(cp or {}).get('error')}", file=sys.stderr)
        return 3
    overhead_pct = 100.0 * (inst_ms - bare_ms) / max(bare_ms, 1e-9)
    mtps = (2 * n / 1e6) / (inst_ms / 1e3)
    print(f"note: {n}x{n} join: bare {bare_ms:.1f} ms vs instrumented "
          f"{inst_ms:.1f} ms (tracer + {polls} statusz polls + per-join "
          f"critpath) -> overhead {overhead_pct:+.2f}%, path bound by "
          f"rank {cp['bounding_rank']}, wait fraction "
          f"{cp['wait_fraction']:.3f}", file=sys.stderr)
    result = {
        "metric": "critpath_overhead",
        "value": round(mtps, 3),
        "unit": "Mtuples/sec_instrumented",
        "size": n,
        "critpath_overhead_pct": round(max(0.0, overhead_pct), 3),
        "wait_fraction": cp["wait_fraction"],
        "bare_wall_ms": round(bare_ms, 2),
        "instrumented_wall_ms": round(inst_ms, 2),
        "statusz_polls": polls,
        "critpath_path_ms": cp["path_ms"],
        "critpath_barriers": len(cp.get("barriers", [])),
    }
    print(json.dumps(result))
    _ledger_append(result)
    if overhead_pct > 1.0:
        print(f"ERROR: introspection overhead {overhead_pct:.2f}% exceeds "
              "the 1% acceptance bar", file=sys.stderr)
        return 3
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        return code
    return 0


def _run_recovery_bench(check_baseline=None, size=1 << 18):
    """``--recovery-bench``: the elastic-recovery A/B — kill-1-of-8
    partition-level recovery versus the cold full restart it replaces.

    Both arms run an 8-way host-CPU mesh at ``size`` tuples per side with
    the oracle-friendly chaos inputs (R a permutation of 1..n, S uniform,
    true count exactly n).  The **restart arm** times a full warm join —
    what a non-elastic job pays after ANY rank death.  The **recovery
    arm** models the kill: a partition manifest holds the true counts of
    every partition the dead rank did NOT own (realized pre-death), the
    ``membership.rank_death`` site fires mid-join, and the elastic engine
    resumes the manifest + recomputes only the dead rank's partitions
    host-side.  Both arms are compile-warmed before timing.

    Exit 3 unless the recovered count is oracle-exact AND the recompute
    stayed partition-granular (``RECOVERN`` strictly below the partition
    count).  The BENCH headline ``value`` is the wall ratio (cold restart
    over recovery, higher is better); ``recover_ms``/``cold_restart_ms``/
    ``recovern``/``ranklost``/``mepoch`` gate lower-is-better under
    tools_check_regress.py."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)

    import tempfile

    import jax.numpy as jnp
    from tpu_radix_join.core.config import JoinConfig
    from tpu_radix_join.data.tuples import TupleBatch
    from tpu_radix_join.operators.hash_join import HashJoin
    from tpu_radix_join.performance import Measurements
    from tpu_radix_join.performance.measurements import (MEPOCH, RANKLOST,
                                                         RECOVERN)
    from tpu_radix_join.robustness import faults
    from tpu_radix_join.robustness.checkpoint import PartitionManifest

    nodes, n = 8, size
    cfg = JoinConfig(num_nodes=nodes, network_fanout_bits=4, verify="check")
    num_p = cfg.network_partition_count
    dead = nodes - 1                       # _rank_death's simulated victim
    rng = np.random.default_rng(23)
    rk = (rng.permutation(n) + 1).astype(np.uint32)
    sk = rng.integers(1, n + 1, size=n).astype(np.uint32)
    rid = np.arange(n, dtype=np.uint32)
    r = TupleBatch(key=jnp.asarray(rk), rid=jnp.asarray(rid))
    s = TupleBatch(key=jnp.asarray(sk), rid=jnp.asarray(rid))
    # every S key matches exactly one R key, so a partition's true count
    # is its S-key population — what the manifest would hold post-realize
    true = np.bincount(sk & (num_p - 1), minlength=num_p)

    # ---- restart arm: the full warm join a non-elastic job re-pays
    eng = HashJoin(cfg, measurements=Measurements(num_nodes=nodes))
    res = eng.join_arrays(r, s)            # compile warm-up
    if not (res.ok and res.matches == n):
        print(f"ERROR: baseline join missed the oracle: {res.matches} "
              f"!= {n}", file=sys.stderr)
        return 3
    t0 = time.perf_counter()
    eng.join_arrays(r, s)
    cold_ms = (time.perf_counter() - t0) * 1e3

    # ---- recovery arm: manifest resumes all but the dead rank's share
    tmp = tempfile.mkdtemp(prefix="recovery_bench_")
    eng.elastic = True

    def one_recovery(tag):
        man = PartitionManifest(os.path.join(tmp, f"m_{tag}.manifest"),
                                fingerprint={"bench": "recovery"})
        man.mark_many({p: int(true[p]) for p in range(num_p)
                       if p % nodes != dead}, owner_of=lambda p: p % nodes)
        m = Measurements(num_nodes=nodes)
        eng.measurements = m
        eng.partition_manifest = man
        try:
            with faults.FaultInjector(seed=5, measurements=m).arm(
                    faults.RANK_DEATH, at=2):
                t0 = time.perf_counter()
                out = eng.join_arrays(r, s)
                wall_ms = (time.perf_counter() - t0) * 1e3
        finally:
            eng.partition_manifest = None
        return out, wall_ms, m

    one_recovery("warm")                   # compile-warm the masked grids
    out, recover_ms, m = one_recovery("timed")
    recovern = int(m.counters.get(RECOVERN, 0))
    if not (out.ok and out.matches == n):
        print(f"ERROR: recovered join missed the oracle: "
              f"{out.matches} != {n}", file=sys.stderr)
        return 3
    if not 0 < recovern < num_p:
        print(f"ERROR: recompute was not partition-granular: RECOVERN="
              f"{recovern} of {num_p} partitions", file=sys.stderr)
        return 3
    resumed = len(out.diagnostics.get("resumed_partitions") or [])
    speedup = cold_ms / max(recover_ms, 1e-9)
    print(f"note: kill-1-of-{nodes}: recovery {recover_ms:.0f} ms "
          f"({recovern}/{num_p} partitions recomputed, {resumed} resumed) "
          f"vs cold restart {cold_ms:.0f} ms -> {speedup:.2f}x",
          file=sys.stderr)

    result = {
        "metric": "elastic_recovery_speedup",
        "value": round(speedup, 3),
        "unit": "cold_restart_over_recovery_wall",
        "size": n,
        "num_partitions": num_p,
        "recover_ms": round(recover_ms, 1),
        "cold_restart_ms": round(cold_ms, 1),
        "recovern": recovern,
        "resumed_partitions": resumed,
        "ranklost": int(m.counters.get(RANKLOST, 0)),
        "mepoch": int(m.counters.get(MEPOCH, 0)),
    }
    print(json.dumps(result))
    _ledger_append(result)
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        return code
    return 0


def _run_recovery_straggle_bench(check_baseline=None, factor=4.0,
                                 size=1 << 17):
    """``--recovery-bench --straggle f``: the hedged-vs-unhedged tail A/B.

    One rank of the 8-way host mesh stalls for ``f x straggle_unit_s``
    mid-join (the ``compute.straggle`` site).  The **unhedged arm** eats
    the stall in full — the whole join stretches by the slowest rank,
    the reference's RMA-window failure mode.  The **hedged arm** lets the
    relative-progress detector (robustness/straggler.py) flag the victim
    off manifest progress and speculatively recomputes its unfinished
    stripe through the manifest fence — first writer wins, so even a
    late-finishing original could not double-count.  The manifest
    pre-realizes every partition OUTSIDE the victim's stripe (the counts
    a healthy rank would have posted pre-stall), so the hedge recompute
    must stay partition-granular — a hedge that recomputes everything is
    a veiled restart and exits 3 exactly like the shrink bench's gate.

    Exit 3 unless both arms are oracle-exact, HEDGEWIN >= 1, the hedge
    stayed partition-granular, the manifest audit sums to the oracle,
    and the hedged tail beats the unhedged tail.  ``hedged_ms``/
    ``unhedged_ms``/``specwaste`` gate lower-is-better, the headline
    ``value`` (unhedged over hedged wall) higher-is-better."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)

    import tempfile

    import jax.numpy as jnp
    from tpu_radix_join.core.config import JoinConfig
    from tpu_radix_join.data.tuples import TupleBatch
    from tpu_radix_join.operators.hash_join import HashJoin
    from tpu_radix_join.performance import Measurements
    from tpu_radix_join.performance.measurements import (HEDGED, HEDGEWIN,
                                                         RECOVERN, SPECWASTE)
    from tpu_radix_join.robustness import faults
    from tpu_radix_join.robustness.checkpoint import PartitionManifest
    from tpu_radix_join.robustness.membership import (LeaseBoard,
                                                      MembershipView)

    nodes, n = 8, size
    cfg = JoinConfig(num_nodes=nodes, network_fanout_bits=5, verify="check")
    num_p = cfg.network_partition_count
    victim = nodes - 1                 # _compute_straggle's simulated victim
    rng = np.random.default_rng(29)
    rk = (rng.permutation(n) + 1).astype(np.uint32)
    sk = rng.integers(1, n + 1, size=n).astype(np.uint32)
    rid = np.arange(n, dtype=np.uint32)
    r = TupleBatch(key=jnp.asarray(rk), rid=jnp.asarray(rid))
    s = TupleBatch(key=jnp.asarray(sk), rid=jnp.asarray(rid))
    true = np.bincount(sk & (num_p - 1), minlength=num_p)

    tmp = tempfile.mkdtemp(prefix="straggle_bench_")
    eng = HashJoin(cfg, measurements=Measurements(num_nodes=nodes))
    eng.elastic = True
    eng.straggle_factor = float(factor)
    eng.straggle_unit_s = 0.25         # the stall the unhedged arm eats

    def one_arm(tag, hedge):
        man = PartitionManifest(os.path.join(tmp, f"m_{tag}.manifest"),
                                fingerprint={"bench": "straggle"})
        man.mark_many({p: int(true[p]) for p in range(num_p)
                       if p % nodes != victim}, owner_of=lambda p: p % nodes)
        m = Measurements(num_nodes=nodes)
        board = LeaseBoard(os.path.join(tmp, f"leases_{tag}"), rank=0,
                           num_ranks=1, lease_s=300.0, measurements=m)
        membership = MembershipView(board, measurements=m)
        board.heartbeat(0)
        eng.measurements = m
        eng.partition_manifest = man
        eng.membership = membership
        eng.hedge = hedge
        try:
            with faults.FaultInjector(seed=7, measurements=m).arm(
                    faults.COMPUTE_STRAGGLE, at=1):
                t0 = time.perf_counter()
                out = eng.join_arrays(r, s)
                wall_ms = (time.perf_counter() - t0) * 1e3
        finally:
            eng.partition_manifest = None
            eng.membership = None
            eng.hedge = "off"
        return out, wall_ms, m, man

    one_arm("warm_off", "off")         # compile-warm the plain join
    one_arm("warm_on", "on")           # compile-warm the masked grids
    out_u, unhedged_ms, _, _ = one_arm("timed_off", "off")
    out_h, hedged_ms, mh, man_h = one_arm("timed_on", "on")
    recovern = int(mh.counters.get(RECOVERN, 0))
    hedgewin = int(mh.counters.get(HEDGEWIN, 0))
    aud = man_h.audit()
    for tag, out in (("unhedged", out_u), ("hedged", out_h)):
        if not (out.ok and out.matches == n):
            print(f"ERROR: {tag} arm missed the oracle: {out.matches} "
                  f"!= {n}", file=sys.stderr)
            return 3
    if int(mh.counters.get(HEDGED, 0)) < 1 or hedgewin < 1:
        print(f"ERROR: the hedge never engaged or never won a fence: "
              f"HEDGED={int(mh.counters.get(HEDGED, 0))} "
              f"HEDGEWIN={hedgewin}", file=sys.stderr)
        return 3
    if not 0 < recovern < num_p:
        print(f"ERROR: hedge recompute was not partition-granular (a "
              f"veiled restart): RECOVERN={recovern} of {num_p} "
              f"partitions", file=sys.stderr)
        return 3
    if aud["total"] != n:
        print(f"ERROR: manifest audit does not sum to the oracle: "
              f"{aud['total']} != {n} "
              f"(fenced_duplicates={aud['fenced_duplicates']})",
              file=sys.stderr)
        return 3
    speedup = unhedged_ms / max(hedged_ms, 1e-9)
    if speedup <= 1.0:
        print(f"ERROR: hedged arm was not faster: {hedged_ms:.0f} ms "
              f"hedged vs {unhedged_ms:.0f} ms unhedged", file=sys.stderr)
        return 3
    print(f"note: straggle x{factor}: hedged {hedged_ms:.0f} ms "
          f"({recovern}/{num_p} partitions speculated, {hedgewin} fence "
          f"wins) vs unhedged {unhedged_ms:.0f} ms -> {speedup:.2f}x",
          file=sys.stderr)

    result = {
        "metric": "straggler_hedge_tail_speedup",
        "value": round(speedup, 3),
        "unit": "unhedged_tail_over_hedged_tail",
        "size": n,
        "num_partitions": num_p,
        "straggle_factor": float(factor),
        "hedged_ms": round(hedged_ms, 1),
        "unhedged_ms": round(unhedged_ms, 1),
        "hedgewin": hedgewin,
        "specwaste": int(mh.counters.get(SPECWASTE, 0)),
        "recovern": recovern,
        "manifest_total": int(aud["total"]),
    }
    print(json.dumps(result))
    _ledger_append(result)
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        return code
    return 0


def _run_recovery_grow_bench(check_baseline=None, size=1 << 19):
    """``--recovery-bench --grow``: mid-run admission speedup vs fixed
    survivors.

    Scenario: a join is mid-flight with 14 of the 32 partitions realized
    in the manifest when a ninth process writes a ``joining`` lease; the
    board admits it with a fenced epoch bump (the REAL admission path —
    MembershipView.check over a lease dir, RANKJOIN ticks) and the
    recovery plan re-expands `load_aware_assignment` over the enlarged
    membership.  Both arms recompute the same 18 unfinished partitions
    through `execute_recovery(only_rank=...)` per survivor; the reported
    wall is the **critical path** — the slowest single survivor's share,
    which is what decides when a data-parallel epoch completes.  The
    fixed arm spreads 18 partitions over 8 survivors (max share 3), the
    grown arm over 9 (max share 2).

    Exit 3 unless the merged count is oracle-exact on both arms, the
    recompute stayed partition-granular (the veiled-restart refusal the
    shrink bench pioneered: resumed > 0 and recomputed < num_p), and the
    grown critical path beats the fixed one.  ``grown_ms``/``fixed_ms``
    gate lower-is-better; ``value`` (fixed over grown) higher-is-better."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)

    import tempfile

    from tpu_radix_join.core.config import JoinConfig
    from tpu_radix_join.performance import Measurements
    from tpu_radix_join.performance.measurements import RANKJOIN, RECOVERN
    from tpu_radix_join.robustness.checkpoint import PartitionManifest
    from tpu_radix_join.robustness.membership import (LeaseBoard,
                                                      MembershipView)
    from tpu_radix_join.robustness.recovery import (execute_recovery,
                                                    partition_weights,
                                                    plan_recovery)

    nodes, n = 8, size
    cfg = JoinConfig(num_nodes=nodes, network_fanout_bits=5, verify="check")
    num_p = cfg.network_partition_count
    rng = np.random.default_rng(31)
    rk = (rng.permutation(n) + 1).astype(np.uint32)
    sk = rng.integers(1, n + 1, size=n).astype(np.uint32)
    true = np.bincount(sk & (num_p - 1), minlength=num_p)
    realized = list(range(14))             # partitions done pre-admission
    weights = partition_weights(rk, sk, num_p)

    # -- the admission itself rides the real lease protocol: incumbents
    # hold member leases, the newcomer writes a joining lease, one
    # check() batch admits it with the fenced epoch bump
    tmp = tempfile.mkdtemp(prefix="grow_bench_")
    m = Measurements(num_nodes=nodes)
    lease_dir = os.path.join(tmp, "leases")
    for incumbent in range(nodes):
        LeaseBoard(lease_dir, rank=incumbent, num_ranks=nodes,
                   lease_s=300.0).heartbeat(0)
    board = LeaseBoard(lease_dir, rank=0, num_ranks=nodes, lease_s=300.0,
                       measurements=m)
    joiner_rank = LeaseBoard.next_rank(lease_dir, floor=nodes)
    LeaseBoard(lease_dir, rank=joiner_rank, num_ranks=nodes,
               lease_s=300.0).heartbeat(0, status="joining")
    mv = MembershipView(board, measurements=m)
    mv.check()
    if joiner_rank not in mv.joined or mv.epoch != 1:
        print(f"ERROR: admission did not land: joined={sorted(mv.joined)} "
              f"epoch={mv.epoch}", file=sys.stderr)
        return 3
    rankjoin = int(m.counters.get(RANKJOIN, 0))

    def one_arm(tag, joined_ranks):
        man = PartitionManifest(os.path.join(tmp, f"m_{tag}.manifest"),
                                fingerprint={"bench": "grow"})
        man.mark_many({p: int(true[p]) for p in realized},
                      owner_of=lambda p: p % nodes)
        plan = plan_recovery(num_nodes=nodes, num_partitions=num_p,
                             lost_ranks=[], epoch=mv.epoch, manifest=man,
                             weights=weights, joined_ranks=joined_ranks)
        am = Measurements(num_nodes=nodes)
        critical_ms, matches = 0.0, 0
        for survivor in plan.survivors:
            t0 = time.perf_counter()
            matches, _ = execute_recovery(plan, rk, sk,
                                          only_rank={survivor},
                                          manifest=man, measurements=am)
            critical_ms = max(critical_ms,
                              (time.perf_counter() - t0) * 1e3)
        return plan, critical_ms, matches, int(
            am.counters.get(RECOVERN, 0)), man

    one_arm("warm", ())                    # compile-warm the masked grids
    plan_f, fixed_ms, matches_f, recovern_f, _ = one_arm("fixed", ())
    plan_g, grown_ms, matches_g, recovern_g, man_g = one_arm(
        "grown", sorted(mv.joined))
    for tag, matches in (("fixed", matches_f), ("grown", matches_g)):
        if matches != n:
            print(f"ERROR: {tag} arm missed the oracle: {matches} != {n}",
                  file=sys.stderr)
            return 3
    for tag, recovern in (("fixed", recovern_f), ("grown", recovern_g)):
        if not (len(realized) > 0 and 0 < recovern < num_p):
            print(f"ERROR: {tag} arm recompute was not partition-granular "
                  f"(a veiled restart): RECOVERN={recovern} of {num_p} "
                  f"partitions, {len(realized)} resumed", file=sys.stderr)
            return 3
    if joiner_rank not in set(plan_g.reassignment.values()):
        print(f"ERROR: the grown plan never assigned the newcomer "
              f"(rank {joiner_rank}) a partition: "
              f"{plan_g.reassignment}", file=sys.stderr)
        return 3
    speedup = fixed_ms / max(grown_ms, 1e-9)
    if speedup <= 1.0:
        print(f"ERROR: grown arm was not faster: {grown_ms:.0f} ms grown "
              f"vs {fixed_ms:.0f} ms fixed", file=sys.stderr)
        return 3
    print(f"note: join-mid-run: grown critical path {grown_ms:.0f} ms "
          f"({len(plan_g.survivors)} survivors) vs fixed {fixed_ms:.0f} ms "
          f"({len(plan_f.survivors)}) -> {speedup:.2f}x",
          file=sys.stderr)

    result = {
        "metric": "elastic_grow_speedup",
        "value": round(speedup, 3),
        "unit": "fixed_critical_path_over_grown",
        "size": n,
        "num_partitions": num_p,
        "grown_ms": round(grown_ms, 1),
        "fixed_ms": round(fixed_ms, 1),
        "recovern": recovern_g,
        "resumed_partitions": len(realized),
        "rankjoin": rankjoin,
        "survivors_fixed": len(plan_f.survivors),
        "survivors_grown": len(plan_g.survivors),
        "manifest_total": int(man_g.audit()["total"]),
    }
    print(json.dumps(result))
    _ledger_append(result)
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        return code
    return 0


def _run_fleet_bench(check_baseline=None, workers=4, tpn=1 << 10):
    """``--fleet-bench``: the crash-only fleet failover A/B — kill-1-of-4
    mid-query failover versus the cold supervisor restart it replaces.

    The **failover arm** boots a 4-worker supervised fleet
    (service/fleet.py), compile-warms every slot through its ring tenant,
    then arms ``fleet.worker_kill``: the timed query's routed worker is
    SIGKILLed with the request on its pipe, and the wall runs until a
    *survivor* serves the journal-replayed attempt.  The **cold arm** is
    what a non-supervised serve deployment pays for the same death: a
    fresh supervisor restarted over a journal holding that unacknowledged
    intent, with the wall covering worker boot + replay + cold compile.

    Exit 3 unless both arms are oracle-exact, the failover attempt count
    proves a real mid-query death (attempts >= 2), both drains report the
    journal fully acknowledged with ``double_exec == 0`` (the
    exactly-once invariant), and failover beats the cold restart.  The
    BENCH headline ``value`` is the wall ratio (cold restart over
    failover, higher is better); ``failover_ms`` / ``cold_restart_ms`` /
    ``failover`` / ``replayn`` / ``jdepth`` / ``wincarn`` /
    ``worker_restarts`` / ``double_exec`` gate lower-is-better under
    tools_check_regress.py (``double_exec`` pins to zero: any growth from
    a zero base is an infinite delta)."""
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(8, respect_existing=True)

    import tempfile

    from tpu_radix_join.performance import Measurements
    from tpu_radix_join.performance.measurements import (FAILOVER, JDEPTH,
                                                         REPLAYN, WINCARN)
    from tpu_radix_join.robustness import faults
    from tpu_radix_join.service.fleet import FleetSupervisor, route_tenant
    from tpu_radix_join.service.journal import QueryJournal

    nodes = 1                   # single-device workers: boot cost is the
    expect = tpn * nodes        # jax import + one compile, not the mesh
    worker_args = ["--nodes", str(nodes), "--verify", "check"]

    def req(qid, tenant):
        return {"query_id": qid, "tenant": tenant,
                "tuples_per_node": tpn, "seed": 7}

    tmp = tempfile.mkdtemp(prefix="fleet_bench_")

    # ---- failover arm: warm fleet, SIGKILL the routed worker mid-query
    m = Measurements()
    sup = FleetSupervisor(workers, worker_args,
                          os.path.join(tmp, "failover"),
                          measurements=m, lease_s=1.0)
    try:
        sup.start()
        # one tenant per ring slot so every worker compile-warms before
        # the timed kill — the failover lands on a warm survivor, which
        # is the steady-state a supervised fleet actually runs in
        slots = list(range(workers))
        tenant_for = {}
        i = 0
        while len(tenant_for) < workers and i < 10000:
            t = f"t{i}"
            tenant_for.setdefault(route_tenant(t, slots), t)
            i += 1
        if len(tenant_for) < workers:
            print(f"ERROR: ring left slots tenant-less: {sorted(tenant_for)}",
                  file=sys.stderr)
            return 3
        for s in sorted(tenant_for):
            out = sup.dispatch(req(f"warm_w{s}", tenant_for[s]))
            if not (out.get("status") == "ok"
                    and out.get("matches") == expect):
                print(f"ERROR: warm-up on worker {s} not oracle-exact: "
                      f"{out.get('status')} matches={out.get('matches')} "
                      f"!= {expect}", file=sys.stderr)
                return 3
        victim = sorted(tenant_for)[0]
        with faults.FaultInjector(seed=11, measurements=m).arm(
                faults.FLEET_WORKER_KILL, at=1):
            t0 = time.perf_counter()
            out = sup.dispatch(req("kill", tenant_for[victim]))
            failover_ms = (time.perf_counter() - t0) * 1e3
        fleet = out.get("fleet") or {}
        if not (out.get("status") == "ok" and out.get("matches") == expect):
            print(f"ERROR: failover outcome not oracle-exact: "
                  f"{out.get('status')} matches={out.get('matches')} "
                  f"!= {expect} ({out.get('detail')})", file=sys.stderr)
            return 3
        if fleet.get("attempts", 1) < 2 or fleet.get("worker") == victim:
            print(f"ERROR: no real failover happened: served by worker "
                  f"{fleet.get('worker')} in {fleet.get('attempts')} "
                  f"attempt(s) (victim was {victim})", file=sys.stderr)
            return 3
        report = sup.drain()
    finally:
        sup.close()
    if report["unacked"] or report["double_exec"]:
        print(f"ERROR: failover arm broke exactly-once at drain: "
              f"{report}", file=sys.stderr)
        return 3

    # ---- cold arm: supervisor restart over a journal with the same
    # death's unacknowledged intent — boot + replay + cold compile
    cold_dir = os.path.join(tmp, "cold")
    QueryJournal(cold_dir).append_intent(req("cold_kill", "t0"))
    m2 = Measurements()
    sup2 = FleetSupervisor(workers, worker_args, cold_dir,
                           measurements=m2, lease_s=1.0)
    try:
        t0 = time.perf_counter()
        sup2.start()
        replayed = sup2.replay_unacknowledged()
        cold_ms = (time.perf_counter() - t0) * 1e3
        report2 = sup2.drain()
    finally:
        sup2.close()
    if not (len(replayed) == 1 and replayed[0].get("status") == "ok"
            and replayed[0].get("matches") == expect):
        print(f"ERROR: cold-restart replay not oracle-exact: {replayed}",
              file=sys.stderr)
        return 3
    if report2["unacked"] or report2["double_exec"]:
        print(f"ERROR: cold arm broke exactly-once at drain: {report2}",
              file=sys.stderr)
        return 3

    speedup = cold_ms / max(failover_ms, 1e-9)
    if speedup <= 1.0:
        print(f"ERROR: failover was not faster than the cold restart: "
              f"{failover_ms:.0f} ms vs {cold_ms:.0f} ms", file=sys.stderr)
        return 3
    print(f"note: kill-1-of-{workers}: failover {failover_ms:.0f} ms "
          f"(survivor, attempt {fleet.get('attempts')}) vs cold "
          f"supervisor restart {cold_ms:.0f} ms -> {speedup:.2f}x",
          file=sys.stderr)

    result = {
        "metric": "fleet_failover_speedup",
        "value": round(speedup, 3),
        "unit": "cold_restart_over_failover_wall",
        "workers": workers,
        "queries": sup.queries,
        "failover_ms": round(failover_ms, 1),
        "cold_restart_ms": round(cold_ms, 1),
        "failover": int(m.counters.get(FAILOVER, 0)),
        "replayn": int(m.counters.get(REPLAYN, 0)),
        "jdepth": int(m.counters.get(JDEPTH, 0)),
        "wincarn": int(m.counters.get(WINCARN, 0)),
        "worker_restarts": sup.restarts,
        "double_exec": report["double_exec"] + report2["double_exec"],
    }
    print(json.dumps(result))
    _ledger_append(result)
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        return code
    return 0


def main():
    # regression-gate post-step: parsed before any backend work so a typo'd
    # flag fails fast instead of after a multi-minute timed run
    check_baseline = None
    argv = sys.argv[1:]
    # forensics bundles (observability/postmortem.py): a chaos violation
    # drops one here
    global _LEDGER_DIR
    _LEDGER_DIR = os.environ.get("TPU_RADIX_LEDGER_DIR")
    if "--ledger-dir" in argv:
        i = argv.index("--ledger-dir")
        if i + 1 >= len(argv):
            print("error: --ledger-dir needs a directory path",
                  file=sys.stderr)
            sys.exit(2)
        _LEDGER_DIR = argv[i + 1]
    forensics_dir = os.environ.get("TPU_RADIX_FORENSICS_DIR")
    if "--forensics-dir" in argv:
        i = argv.index("--forensics-dir")
        if i + 1 >= len(argv):
            print("error: --forensics-dir needs a directory path",
                  file=sys.stderr)
            sys.exit(2)
        forensics_dir = argv[i + 1]
    if "--chaos" in argv:
        # chaos soak mode (robustness/chaos.py): N seeded fault schedules
        # with verification always on, every run must pass or classify;
        # a violating schedule is ddmin-shrunk to a minimal (seed, arms)
        # repro.  CPU-sized — it validates failure semantics, not
        # throughput.
        i = argv.index("--chaos")
        try:
            runs = int(argv[i + 1])
        except (IndexError, ValueError):
            print("error: --chaos needs an integer run count",
                  file=sys.stderr)
            sys.exit(2)
        base_seed = (int(argv[argv.index("--chaos-seed") + 1])
                     if "--chaos-seed" in argv else 0)
        sys.exit(_run_chaos(runs, base_seed=base_seed,
                            forensics_dir=forensics_dir))
    if "--check-regress" in argv:
        i = argv.index("--check-regress")
        if i + 1 >= len(argv):
            print("error: --check-regress needs a baseline path",
                  file=sys.stderr)
            sys.exit(2)
        check_baseline = argv[i + 1]
        if not os.path.exists(check_baseline):
            print(f"error: baseline {check_baseline} not found",
                  file=sys.stderr)
            sys.exit(2)
    if "--static-gate" in argv:
        # merged static-analysis gate (tools_static_gate.py): graftlint
        # AST conventions + graftcheck jaxpr IR audit, both strict,
        # device-free — gates program invariants, not throughput.  Rides
        # bench so CI rigs that only know bench entry points can run it.
        import tools_static_gate
        gate_args = []
        if "--static-gate-json" in argv:
            i = argv.index("--static-gate-json")
            if i + 1 >= len(argv):
                print("error: --static-gate-json needs a file path",
                      file=sys.stderr)
                sys.exit(2)
            gate_args = ["--json", argv[i + 1]]
        sys.exit(tools_static_gate.main(gate_args))
    if "--grid-bench" in argv:
        # like --chaos: CPU-sized — it gates the pipelined grid engine,
        # not the chip
        sys.exit(_run_grid_bench(check_baseline))
    if "--exchange-bench" in argv:
        # wire-format A/B (data/tuples.py codec + parallel/window.py
        # staging): CPU-sized like --grid-bench — it gates exchange bytes
        # and the live exchange footprint, not chip throughput
        sys.exit(_run_exchange_bench(check_baseline))
    if "--partition-bench" in argv:
        # destination-grouping A/B (ops/pallas/partition.py vs the sort
        # scatter): CPU-sized like --grid-bench — it gates the fused
        # partition kernel's speedup and unit constant, not chip throughput
        sys.exit(_run_partition_bench(check_baseline))
    if "--sort-bench" in argv:
        # flat-sort A/B (ops/pallas/radix_sort.py vs lax.sort): CPU-sized
        # like --grid-bench — it gates the LSD radix kernel's correctness,
        # pass-skipping, and unit constant, not chip throughput
        sys.exit(_run_sort_bench(check_baseline))
    if "--recovery-bench" in argv:
        # elastic-recovery A/B (robustness/recovery.py): CPU-sized like
        # --chaos/--grid-bench — it gates kill-1-of-8 partition-level
        # recovery against the cold restart, not chip throughput.
        # --grow switches to the mid-run-admission-vs-fixed-survivors
        # arm; --straggle f to the hedged-vs-unhedged tail arm at
        # slowdown factor f (robustness/straggler.py)
        if "--grow" in argv:
            sys.exit(_run_recovery_grow_bench(check_baseline))
        if "--straggle" in argv:
            i = argv.index("--straggle")
            try:
                factor = float(argv[i + 1])
            except (IndexError, ValueError):
                print("error: --straggle needs a numeric slowdown factor",
                      file=sys.stderr)
                sys.exit(2)
            sys.exit(_run_recovery_straggle_bench(check_baseline, factor))
        sys.exit(_run_recovery_bench(check_baseline))
    if "--critpath-bench" in argv:
        # critical-path attribution overhead A/B (observability/critpath
        # + statusz): CPU-sized like --grid-bench — it gates the
        # introspection plane's <1% overhead bar, not chip throughput
        sys.exit(_run_critpath_bench(check_baseline))
    if "--fleet-bench" in argv:
        # crash-only fleet failover A/B (service/fleet.py + journal.py):
        # CPU-sized like --chaos/--serve-bench — it gates kill-1-of-4
        # mid-query failover against the cold supervisor restart and the
        # journal's exactly-once drain audit, not chip throughput
        sys.exit(_run_fleet_bench(check_baseline))
    if "--serve-throughput-bench" in argv:
        # serving fast-path A/B (service/resultcache.py + microbatch.py +
        # resident.py + ops/merge_delta.py): CPU-sized like
        # --chaos/--serve-bench — it gates the cache/batch/delta speedups,
        # the mid-batch-kill exactly-once audit, and statusz liveness,
        # not chip throughput
        sys.exit(_run_serve_throughput_bench(check_baseline))
    if "--serve-bench" in argv:
        # resident-service amortization bench (service/session.py):
        # CPU-sized like --chaos/--grid-bench — it gates warm-query reuse
        # and breaker recovery semantics, not chip throughput
        i = argv.index("--serve-bench")
        queries = 20
        if i + 1 < len(argv) and argv[i + 1].isdigit():
            queries = int(argv[i + 1])
        if queries < 2:
            print("error: --serve-bench needs at least 2 queries "
                  "(one cold, one warm)", file=sys.stderr)
            sys.exit(2)
        sys.exit(_run_serve_bench(check_baseline, queries=queries,
                                  chaos="--serve-chaos" in argv))

    size = 1 << 24               # 16M tuples per side
    planned = _planned_strategy(size, iters=20)

    import jax
    import jax.numpy as jnp

    from tpu_radix_join.utils.platform import enable_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"error: bench.py measures the chip; JAX found "
              f"{device.platform!r} (the CPU-sized A/Bs are the --*-bench "
              f"modes)", file=sys.stderr)
        sys.exit(2)
    peaks = device_peaks(device.device_kind)
    enable_compile_cache()

    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.ops.merge_count import merge_count_chunks, merge_count_pallas

    r_rel = Relation(size, 1, "unique", seed=1)
    s_rel = Relation(size, 1, "unique", seed=2)
    r = jax.block_until_ready(r_rel.shard(0))
    s = jax.block_until_ready(s_rel.shard(0))

    def fail(msg):
        print(f"error: {msg}", file=sys.stderr)
        sys.exit(1)

    best = None
    probes = [("xla", jax.jit(merge_count_chunks)),
              ("pallas", jax.jit(merge_count_pallas))]
    for name, fn in probes:
        matches = int(np.asarray(fn(r.key, s.key)).astype(np.uint64).sum())
        if matches != size:
            fail(f"{name} probe miscounts ({matches} != {size})")
        dt = _time_amortized(fn, (r.key, s.key))
        print(f"note: {name}: {dt*1e3:.1f} ms/iter", file=sys.stderr)
        if best is None or dt < best[1]:
            best = (name, dt)
    dt = best[1]

    # Full HashJoin pipeline at nodes=1 (compiled executable, amortized):
    # the driver-visible rate, not just the probe op.  Reported as a note —
    # the headline metric stays the probe for round-over-round comparability.
    from tpu_radix_join import HashJoin, JoinConfig
    eng = HashJoin(JoinConfig(num_nodes=1))
    rb = eng._place(r_rel)
    sb = eng._place(s_rel)
    jax.block_until_ready((rb, sb))
    cap_r, cap_s, _ = eng._measure_capacities(
        rb, sb, shuffles=not eng._single_node_sort_probe())
    fn = eng._get_compiled(rb, sb, cap_r, cap_s)
    counts, flags = fn(rb, sb)
    flags = np.asarray(flags)
    pipe_matches = int(np.asarray(counts).astype(np.uint64).sum())
    if pipe_matches != size:
        fail(f"pipeline miscounts ({pipe_matches} != {size})")
    if flags.any():
        fail(f"pipeline failure flags {flags.tolist()}")
    pdt = _time_amortized(lambda a, b: fn(a, b)[0], (rb, sb))
    print(f"note: full_pipeline: {pdt*1e3:.1f} ms/iter "
          f"({2*size/pdt/1e9:.3f} G tuples/s)", file=sys.stderr)

    # Wide-key (64-bit) fused Pallas kernel against its XLA twin.  Hi lanes
    # derived the same way Relation(key_bits=64) derives them.
    from tpu_radix_join.data.relation import key_hi_lane
    from tpu_radix_join.ops.merge_count import merge_count_wide_per_partition
    r_hi = key_hi_lane(r.key)
    s_hi = key_hi_lane(s.key)

    def wide(impl):
        return jax.jit(lambda a, b, c, d: merge_count_wide_per_partition(
            a, b, c, d, 5, impl=impl))

    args = (r.key, r_hi, s.key, s_hi)
    fp, fx = wide("pallas"), wide("xla")
    # validation calls double as compile warmup for the timed fn objects
    cp = np.asarray(fp(*args)).astype(np.uint64)
    cx = np.asarray(fx(*args)).astype(np.uint64)
    if not np.array_equal(cp, cx):
        fail(f"wide pallas != xla ({cp.sum()} vs {cx.sum()})")
    if cp.sum() != size:
        fail(f"wide kernels miscount ({cp.sum()} != {size})")
    dtp = _time_amortized(fp, args)
    dtx = _time_amortized(fx, args)
    print(f"note: wide_pallas: {dtp*1e3:.1f} ms/iter (== xla counts); "
          f"wide_xla: {dtx*1e3:.1f} ms/iter", file=sys.stderr)

    # Weighted (masked) Pallas histogram: backs the skew spread-demand pass
    from tpu_radix_join.ops.radix import local_histogram
    pid = r.key & jnp.uint32(31)
    mask = (r.key & jnp.uint32(1)).astype(bool)

    def hist(impl):
        return jax.jit(lambda p, w: local_histogram(p, 32, valid=w,
                                                    impl=impl))

    hfp, hfx = hist("pallas"), hist("xla")
    if not np.array_equal(np.asarray(hfp(pid, mask)),
                          np.asarray(hfx(pid, mask))):
        fail("weighted histogram pallas != xla")
    dth = _time_amortized(hfp, (pid, mask))
    print(f"note: weighted_histogram_pallas: {dth*1e3:.1f} ms/iter "
          f"(== xla)", file=sys.stderr)

    tuples_per_sec = (2 * size) / dt   # both relations processed
    # Bandwidth of the dominant stage against the chip's published peak:
    # the headline ratio carries the number that justifies or indicts it.
    sort_gbps = _sort_bandwidth_gbps(dt, size)
    print(f"note: sort stage >= {sort_gbps:.1f} GB/s vs {peaks['hbm_gbps']} "
          f"GB/s peak ({device.device_kind}, {peaks['source']})",
          file=sys.stderr)
    result = {
        "metric": "single_chip_join_throughput",
        "value": round(tuples_per_sec, 1),
        "unit": "tuples/sec",
        "vs_baseline": round(tuples_per_sec / 1e9, 4),
        "size": size,
        "device_kind": device.device_kind,
        "probe_impl": best[0],
        "sort_gbps": round(sort_gbps, 1),
        "hbm_peak_gbps": peaks["hbm_gbps"],
        "planned_strategy": planned.get("strategy", "unknown"),
        "planned": planned,
    }
    print(json.dumps(result))
    _ledger_append(result)
    if check_baseline:
        from tpu_radix_join.observability.regress import check_result
        code, report = check_result(result, check_baseline)
        print(report, file=sys.stderr)
        if code:
            sys.exit(code)


if __name__ == "__main__":
    main()

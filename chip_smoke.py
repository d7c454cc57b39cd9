#!/usr/bin/env python3
"""Chip smoke: the radix join's main path once on a TPU, oracle-checked.

    python chip_smoke.py               # one chip: batch, skewed and serving
    python chip_smoke.py --four-chips  # 2x2 mesh: the --nodes 4 joins only

Every phase runs in this one process, through the entry point a user calls
(``tpu_radix_join.main.main``), at the reference's canonical 20M ⋈ 20M
tuples per node (``main.cpp:70-71``, SURVEY §0) with dense unique keys,
on-device generation and the default ``auto`` kernel choices:

* batch:   unique ⋈ unique; the oracle is exactly ``tuples x nodes``
  matches with every conservation flag clean;
* skewed:  the outer relation drawn Zipf(θ=0.75) with load-aware
  assignment; the count must equal a NumPy join of the same seeds;
* serving: the ``--serve`` loop answers four requests (two distinct
  joins, one exact repeat, one skewed join); every answer must be ``ok``,
  come from the primary (device) engine and match its oracle.

Earlier lines report the device, the compile-cache directory, per-phase
compile and wall times, the kernel-choice counters and peak device
memory.  The last line is the result, ``{"ok": true, "device": {...}}``,
printed only when every check passed.  Any failure exits non-zero with
the reason on stderr; so does a process that finds no TPU.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import io
import json
import os
import re
import sys
import time

TUPLES_PER_NODE = 20_000_000
SEED = 1234
ZIPF_THETA = 0.75
REPO = os.path.dirname(os.path.abspath(__file__))
#: counters that record which partition and sort implementation ``auto``
#: resolved to at trace time (ops/radix.py, ops/sorting.py)
IMPL_COUNTERS = ("PARTPASS", "PARTFALLBACK", "SORTPASS", "SORTFALLBACK")


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class _Tee(io.TextIOBase):
    """Forward writes to the real stdout while keeping a copy to parse."""

    def __init__(self, out):
        self.out, self.buf = out, io.StringIO()

    def write(self, s):
        self.out.write(s)
        self.buf.write(s)
        return len(s)

    def flush(self):
        self.out.flush()


def run_main(argv):
    """``main.main(argv)`` in-process; returns (rc, its stdout, wall s)."""
    from tpu_radix_join.main import main

    tee = _Tee(sys.stdout)
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(tee):
        rc = main(argv)
    return rc, tee.buf.getvalue(), time.perf_counter() - t0


def perf_values(text: str) -> dict:
    """The ``[PERF] TAG<tab>value<tab>unit`` lines of a driver run."""
    return {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^\[PERF\] (\w+)\t([-\d.]+)\t", text, re.M)}


def numpy_join_count(global_size: int, seed: int, outer_kind: str) -> int:
    """Plain NumPy reference: the inner relation is the seeded Feistel
    permutation of [0, N) (seed), the outer the unique or Zipf draw of the
    next seed; the count is a per-key histogram lookup on the host."""
    import numpy as np

    from tpu_radix_join.data.relation import (feistel_permutation_np,
                                              zipf_keys_np, zipf_tables)

    bits = max(2, (global_size - 1).bit_length())

    def unique(s):
        k = feistel_permutation_np(np.arange(global_size, dtype=np.uint64),
                                   bits, s)
        # cycle-walk the keys that left [0, N), shrinking the walked set
        walk = np.flatnonzero(k >= global_size)
        while walk.size:
            k[walk] = feistel_permutation_np(k[walk], bits, s)
            walk = walk[k[walk] >= global_size]
        return k.astype(np.int64)

    r = unique(seed)
    if outer_kind == "zipf":
        head, tail = zipf_tables(ZIPF_THETA, global_size)
        s = zipf_keys_np(0, global_size, head, tail, global_size, seed + 1)
    else:
        s = unique(seed + 1)
    per_key = np.bincount(r, minlength=global_size)
    return int(per_key[s.astype(np.int64)].sum())


def peak_bytes() -> int:
    import jax

    return int((jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", -1))


def batch_phase(name: str, nodes: int, outer_kind: str, expected: int,
                report: dict) -> None:
    """One ``main.main`` join at 20M tuples per node, checked exactly."""
    argv = ["--tuples-per-node", str(TUPLES_PER_NODE), "--nodes", str(nodes),
            "--generation", "device", "--seed", str(SEED)]
    if outer_kind == "zipf":
        argv += ["--outer-kind", "zipf", "--zipf-theta", str(ZIPF_THETA),
                 "--assignment", "load_aware"]
    print(f"[SMOKE] phase {name}: main {' '.join(argv)}", flush=True)
    rc, out, wall = run_main(argv)
    perf = perf_values(out)
    m = re.search(r"^\[RESULTS\] Tuples: (\d+)", out, re.M)
    got = int(m.group(1)) if m else None
    # the chips the join's own output (its per-node counts) sits on
    m = re.search(r"^\[RESULTS\] Output devices: (\d+)", out, re.M)
    out_devices = int(m.group(1)) if m else None
    counters = {c: int(perf.get(c, 0)) for c in IMPL_COUNTERS}
    report[name] = {"wall_s": wall, "compile_s": perf.get("JCOMPILE", 0) / 1e6,
                    "join_s": perf.get("JPROC", 0) / 1e6, "matches": got,
                    "expected": expected, "output_devices": out_devices,
                    **counters,
                    "peak_bytes_in_use": peak_bytes()}
    print(f"[SMOKE] {name}: {json.dumps(report[name])}", flush=True)
    check(rc == 0, f"{name}: main returned {rc}")
    check(got == expected, f"{name}: {got} matches, oracle {expected}")
    check("[RESULTS] Conservation: OK" in out,
          f"{name}: conservation flags not clean")
    check(out_devices == nodes,
          f"{name}: the {nodes}-node join's output sits on {out_devices} "
          f"distinct devices")
    check(counters["PARTFALLBACK"] == 0 and counters["SORTFALLBACK"] == 0,
          f"{name}: auto fell back off the Pallas kernels: {counters}")


def serve_phase(skew_ref: int, report: dict) -> None:
    """Four requests through the ``--serve`` loop of one session."""
    n = TUPLES_PER_NODE
    requests = [
        {"query_id": "join_a", "tuples_per_node": n, "seed": SEED},
        {"query_id": "join_b", "tuples_per_node": n, "seed": SEED + 100},
        {"query_id": "join_a_repeat", "tuples_per_node": n, "seed": SEED},
        {"query_id": "join_skew", "tuples_per_node": n, "seed": SEED,
         "outer_kind": "zipf", "zipf_theta": ZIPF_THETA},
    ]
    oracle = {"join_a": n, "join_b": n, "join_a_repeat": n,
              "join_skew": skew_ref}
    out_dir = os.path.join(REPO, "chiprun_out", "smoke")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "serve_requests.jsonl")
    with open(path, "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in requests)
    argv = ["--serve", path, "--nodes", "1", "--generation", "device"]
    print(f"[SMOKE] phase serve: main {' '.join(argv)}", flush=True)
    rc, out, wall = run_main(argv)
    outcomes = {}
    for line in out.splitlines():
        if line.startswith("{"):
            obj = json.loads(line)
            if obj.get("event") == "outcome":
                outcomes[obj["query_id"]] = obj
    report["serve"] = {
        "wall_s": wall, "peak_bytes_in_use": peak_bytes(),
        "latency_ms": {q: o.get("latency_ms") for q, o in outcomes.items()}}
    print(f"[SMOKE] serve: {json.dumps(report['serve'])}", flush=True)
    check(rc == 0, f"serve: main returned {rc}")
    check(set(outcomes) == set(oracle),
          f"serve: outcomes for {sorted(outcomes)}, sent {sorted(oracle)}")
    for q, o in outcomes.items():
        check(o["status"] == "ok", f"serve {q}: status {o['status']}")
        check(o["engine"] == "primary", f"serve {q}: engine {o['engine']}")
        check(o["matches"] == oracle[q] == o["expected"],
              f"serve {q}: {o['matches']} matches, oracle {oracle[q]}, "
              f"engine's expected {o['expected']}")


def run(four_chips: bool) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {dev.platform!r}")
    nodes = 4 if four_chips else 1
    check(len(devices) >= nodes,
          f"--four-chips needs 4 devices, JAX sees {len(devices)}")

    from tpu_radix_join.ops.sorting import resolve_sort_impl
    from tpu_radix_join.utils.platform import enable_compile_cache

    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if cache and os.path.isdir(cache) else 0
    print(f"[SMOKE] device_kind={dev.device_kind} devices={len(devices)}",
          flush=True)
    print(f"[SMOKE] compile cache {cache} ({entries} entries before run)",
          flush=True)
    print(f"[SMOKE] auto sort impl: {resolve_sort_impl('auto')}",
          flush=True)

    gs = TUPLES_PER_NODE * nodes
    report: dict = {}
    # the host reference runs beside the first device phase: NumPy drops
    # the interpreter lock in its array loops
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        ref = pool.submit(numpy_join_count, gs, SEED, "zipf")
        batch_phase("batch", nodes, "unique", gs, report)
        skew_ref = ref.result()
    print(f"[SMOKE] numpy reference (zipf outer, {gs} tuples): {skew_ref} "
          f"matches", flush=True)
    batch_phase("skewed", nodes, "zipf", skew_ref, report)
    if not four_chips:
        serve_phase(skew_ref, report)
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(devices)}}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the --nodes 4 batch and skewed joins "
                        "(80M x 80M) over a 2x2 mesh; each join's output "
                        "must sit on 4 distinct chips")
    args = p.parse_args(argv)
    try:
        result = run(args.four_chips)
    except SmokeFailure as e:
        print(f"[SMOKE] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Profiler-trace breakdown of the fused single-chip pipeline.

Produces the round-3 verdict's missing evidence (weak #2's last link): a
real-chip ``jax.profiler`` trace of the fused 16M ⋈ 16M pipeline parsed into
a per-op time breakdown (``Measurements.trace``), answering directly what
fraction of the pipeline is the sort — PERF_NOTES' sort-floor argument
predicts >= ~95%.

    python experiments/exp_trace_pipeline.py [log2_size=24] [out_dir]

Writes the raw trace plus ``breakdown.json`` (CTOTAL, per-op table, sort
share) under ``out_dir`` (default artifacts/chip_r4/trace_16m) and prints
the table.  The CTOTAL tag is the reference's PAPI total-cycles analog
(performance/Measurements.cpp:90-107).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import numpy as np

from tpu_radix_join import HashJoin, JoinConfig, Relation
from tpu_radix_join.performance import Measurements

ITERS = 8


def main() -> int:
    args = [a for a in sys.argv[1:] if a != "--two-level"]
    two_level = "--two-level" in sys.argv[1:]
    log2 = int(args[0]) if args else 24
    out_dir = args[1] if len(args) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "artifacts", "chip_r4", f"trace_{1 << log2 >> 20}m")
    size = 1 << log2
    print(f"device: {jax.devices()[0]}, size: {size:,}, out: {out_dir}, "
          f"two_level: {two_level}", flush=True)
    # --two-level: trace the bucket discipline's fused program instead — the
    # per-op table answers how its device time splits between the second
    # radix pass and the per-bucket probe (VERDICT r4 weak #3's "real work
    # vs round-trips" question, net of any dispatch entirely by design:
    # the trace sees only device ops).  Geometry stays at the JoinConfig
    # defaults so the traced executable is the SAME program as the
    # cli_16m_twolevel_fused timing run it explains.
    eng = HashJoin(JoinConfig(num_nodes=1, two_level=two_level))
    r = eng.place(Relation(size, 1, "unique", seed=1))
    s = eng.place(Relation(size, 1, "unique", seed=2))
    cap_r, cap_s, _ = eng._measure_capacities(
        r, s, shuffles=not eng._single_node_sort_probe())
    fn = eng._get_compiled(r, s, cap_r, cap_s)
    counts, flags = fn(r, s)                       # warm (compile cached)
    matches = int(np.asarray(counts).astype(np.uint64).sum())
    assert matches == size and not np.asarray(flags).any(), (matches, flags)

    m = Measurements()
    t0 = time.perf_counter()
    with m.trace(out_dir):
        for _ in range(ITERS):
            counts, flags = fn(r, s)
        np.asarray(counts)                         # host readback fence
    wall = time.perf_counter() - t0
    tr = m.meta.get("trace")
    if tr is None:
        print("ERROR: no parsable xplane artifact", flush=True)
        return 1

    busy = tr["busy_us"]
    sort_us = sum(v["us"] for name, v in tr["ops"].items()
                  if "sort" in name.lower())
    rows = [(name, v["us"], v["count"]) for name, v in tr["ops"].items()]
    print(f"plane: {tr['plane']}")
    print(f"CTOTAL (busy): {busy / 1e3:.1f} ms over {ITERS} iters "
          f"({busy / ITERS / 1e3:.1f} ms/iter; wall {wall * 1e3:.0f} ms)")
    print(f"sort share: {100.0 * sort_us / busy:.1f}% "
          f"({sort_us / ITERS / 1e3:.1f} ms/iter)")
    for name, us, cnt in rows[:15]:
        print(f"  {us / ITERS / 1e3:9.3f} ms/iter x{cnt:<4d} {name[:90]}")

    with open(os.path.join(out_dir, "breakdown.json"), "w") as f:
        json.dump({"size": size, "iters": ITERS, "plane": tr["plane"],
                   # discipline marker: bench._sort_bandwidth_gbps must only
                   # consume sort-path traces (absent key = legacy sort-path)
                   "discipline": "two_level" if two_level else "sort",
                   "busy_us": busy, "sort_share": sort_us / busy,
                   "ops": tr["ops"]}, f, indent=1)
    print(f"wrote {out_dir}/breakdown.json", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

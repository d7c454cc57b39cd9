"""At-scale out-of-core grid join on the real chip (the LD capability,
kernels.cu:563-858 / data.hpp iterCount, exercised at reference-exceeding
scale on ONE device).

128M ⋈ 128M unique tuples (8x the 16M bench config; 2 GB of key+rid lanes
per side at full residency — the grid join holds only O(chunk) instead),
both sides **device-generated** per chunk (data/streaming.stream_chunks_device)
so the run measures the join engine, not the host attachment.  Exact oracle:
unique ⋈ unique over the same range must count exactly GLOBAL matches.

    python experiments/exp_out_of_core.py [global_log2=27] [chunk_log2=24] [key_bits=32]

``global_log2 >= 31`` requires ``key_bits=64`` (the BASELINE config #5 shape:
1B ⋈ 1B wide keys — ``python ... 30 26 64`` runs the full billion-scale grid
on one chip, out of core).

Checkpointed (VERDICT r3 weak #1): every completed (inner, outer) chunk pair
is persisted under artifacts/oo_ckpt/, so a run that dies mid-grid resumes
at the next pair on rerun instead of restarting.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from tpu_radix_join.data.relation import Relation
from tpu_radix_join.data.streaming import stream_chunks_device
from tpu_radix_join.ops.chunked import chunked_join_grid


def main() -> int:
    glog = int(sys.argv[1]) if len(sys.argv) > 1 else 27
    clog = int(sys.argv[2]) if len(sys.argv) > 2 else 24
    key_bits = int(sys.argv[3]) if len(sys.argv) > 3 else 32
    size, chunk = 1 << glog, 1 << clog
    print(f"device: {jax.devices()[0]}, global: {size:,} x {size:,}, "
          f"chunk: {chunk:,} ({(size // chunk) ** 2} grid pairs), "
          f"key_bits: {key_bits}", flush=True)
    r = Relation(size, 1, "unique", seed=1, key_bits=key_bits)
    s = Relation(size, 1, "unique", seed=2, key_bits=key_bits)

    ckpt_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "artifacts", "oo_ckpt")
    os.makedirs(ckpt_dir, exist_ok=True)
    tag = f"oo_g{glog}_c{clog}_k{key_bits}_seeds12"
    ckpt = os.path.join(ckpt_dir, tag + ".json")
    if os.path.exists(ckpt):
        print(f"resuming from checkpoint {ckpt}", flush=True)

    t0 = time.perf_counter()
    # both sides as generators: chunked_join_grid consumes the inner side
    # exactly once and re-streams the outer per inner chunk, so device
    # residency stays O(chunk) — required at the billion-scale config
    total = chunked_join_grid(
        stream_chunks_device(r, 0, chunk),
        lambda: stream_chunks_device(s, 0, chunk),
        slab_size=chunk,
        checkpoint_path=ckpt, checkpoint_tag=tag, progress=True,
        # unique Relations cap keys below 2**31 (relation.py size guard):
        # the narrow hint skips the per-pair max-key probe on 32-bit grids
        key_range="narrow" if key_bits == 32 else "auto")
    dt = time.perf_counter() - t0
    ok = total == size
    print(f"matches: {total:,} expected: {size:,} "
          f"({'OK' if ok else 'MISMATCH'})")
    print(f"wall: {dt:.1f} s  ({2 * size / dt / 1e6:.1f} M tuples/s "
          f"end-to-end; the grid probes {(size // chunk)} x the outer side, "
          f"so probe work is {(size // chunk)}x a resident join's; resumed "
          f"runs report only the remaining pairs' wall time)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Round-5 sort-floor attack (VERDICT r4 #5): the combined discipline —
radix-scatter the packed union into 64 pid blocks, batched row sorts of
n/64, fused per-block merge scan — measured end-to-end against the flat
champion (``merge_count_pallas``: one flat unstable sort + one Pallas pass).

Why this is THE remaining candidate: PERF_NOTES' round-2 primitive table
shows batched sorts at [64, 524288] cost 30.7 ms vs 47.7 ms flat at 33.5M,
i.e. bucketization wins IF it costs < ~17 ms.  Every binning engine was
priced individually (scatter-add 98 ms/16M, counting-sort DMA >= 361
stage-units, in-VMEM redistribution ~60 ms); this experiment runs the one
composition the verdict asked for, with the cheapest grouping engine the
hardware offers (the dest kv-sort + contiguous per-run DMA discipline of
``ops/radix.scatter_to_blocks``), and validates the count exactly.

The reference's counterpart shape is its two-pass partition-then-probe
(operators/gpu/kernels_optimized.cu:19-246): partition first, then many
small per-partition probes — on TPU the open question is only whether any
grouping pass undercuts the flat sort's 325 stage-units.

    python experiments/exp_radix_batched.py [log2_half=24]

Prints ms/iter for: flat champion, combined end-to-end, and the combined
path's stage decomposition (dest kv-sort / block DMA+mask / batched row
sort / scan), then an explicit WIN/DEAD-END verdict line for PERF_NOTES.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np

from tpu_radix_join.ops.merge_count import (
    _S_PACK_PAD, _pack_pm, merge_count_chunks, merge_count_pallas)
from tpu_radix_join.ops.pallas.merge_scan import (
    TILE, merge_scan_chunks, pallas_available)
from tpu_radix_join.ops.sorting import sort_kv_unstable

FANOUT_BITS = 6                      # 64 blocks, the measured DMA sweet spot


def _time(fn, args, iters=10):
    out = fn(*args)                  # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    np.asarray(jax.tree.leaves(out)[0])   # readback closes the async window
    return (time.perf_counter() - t0) / iters


def _scan_count(flat: jnp.ndarray) -> jnp.ndarray:
    """Per-tile partial counts of a blockwise-sorted packed array.  Valid
    because pid occupies the top bits (_pack_pm), so equal packed keys never
    span block rows and pads carry zero weight wherever they sit."""
    pad = (-flat.shape[0]) % TILE
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), _S_PACK_PAD, jnp.uint32)])
    if pallas_available():
        return merge_scan_chunks(flat)
    from tpu_radix_join.ops.merge_count import _weights
    w, _ = _weights(flat)
    return jnp.sum(w.reshape(4096, -1), axis=1, dtype=jnp.uint32)


def _group_blocks(packed: jnp.ndarray, capacity: int):
    """Dest-grouping permutation + per-run DMA into [nb, capacity] rows
    (the scatter_to_blocks loop discipline, single lane)."""
    nb = 1 << FANOUT_BITS
    dest = packed >> jnp.uint32(32 - FANOUT_BITS)
    sdest, svals = sort_kv_unstable(dest, packed)
    bounds = jnp.searchsorted(
        sdest, jnp.arange(nb + 1, dtype=jnp.uint32)).astype(jnp.uint32)
    starts, counts = bounds[:-1], bounds[1:] - bounds[:-1]
    padded = jnp.concatenate(
        [svals, jnp.full((capacity,), _S_PACK_PAD, jnp.uint32)])

    def copy(d, out):
        return jax.lax.dynamic_update_slice(
            out, jax.lax.dynamic_slice(padded, (starts[d],), (capacity,)),
            (d * capacity,))

    out = jax.lax.fori_loop(0, nb, copy,
                            jnp.zeros((nb * capacity,), jnp.uint32))
    col = jnp.arange(capacity, dtype=jnp.uint32)[None, :]
    ok = (col < counts[:, None]).reshape(-1)
    rows = jnp.where(ok, out, jnp.uint32(_S_PACK_PAD)).reshape(nb, capacity)
    overflow = jnp.sum(jnp.maximum(counts, jnp.uint32(capacity))
                       - jnp.uint32(capacity))
    return rows, overflow


def combined_count(r_keys, s_keys, capacity):
    packed = _pack_pm(r_keys, s_keys, FANOUT_BITS)
    rows, overflow = _group_blocks(packed, capacity)
    rows = jax.lax.sort((rows,), dimension=1, is_stable=False)[0]
    return _scan_count(rows.reshape(-1)), overflow


def main():
    log2 = int(sys.argv[1]) if len(sys.argv) > 1 else 24
    half = 1 << log2
    n = 2 * half
    nb = 1 << FANOUT_BITS
    capacity = 2 * (n // nb)          # 2x mean slack; overflow-checked
    rng = np.random.default_rng(0)
    perm = rng.permutation(half).astype(np.uint32)
    r = jax.device_put(jnp.asarray(perm))
    s = jax.device_put(jnp.asarray(rng.permutation(half).astype(np.uint32)))
    jax.block_until_ready((r, s))
    print(f"device: {jax.devices()[0]}, union: {n:,}, "
          f"blocks: {nb} x {capacity}", flush=True)

    champion = jax.jit(merge_count_pallas if pallas_available()
                       else merge_count_chunks)
    cc = np.asarray(champion(r, s)).astype(np.uint64).sum()
    assert cc == half, (cc, half)
    t_flat = _time(champion, (r, s))
    print(f"flat champion (sort+scan):     {t_flat*1e3:8.2f} ms/iter")

    comb = jax.jit(lambda a, b: combined_count(a, b, capacity))
    counts, overflow = comb(r, s)
    ov = int(np.asarray(overflow))
    total = np.asarray(counts).astype(np.uint64).sum()
    assert ov == 0, f"block overflow: {ov}"
    assert total == half, (total, half)
    t_comb = _time(lambda a, b: comb(a, b)[0], (r, s))
    print(f"combined (scatter+batched+scan): {t_comb*1e3:6.2f} ms/iter")

    # stage decomposition
    pm = jax.jit(lambda a, b: _pack_pm(a, b, FANOUT_BITS))
    packed = jax.block_until_ready(pm(r, s))
    grp = jax.jit(lambda p: _group_blocks(p, capacity)[0])
    rows = jax.block_until_ready(grp(packed))
    t_grp = _time(grp, (packed,))
    rsort = jax.jit(
        lambda x: jax.lax.sort((x,), dimension=1, is_stable=False)[0])
    rows_sorted = jax.block_until_ready(rsort(rows))
    t_rsort = _time(rsort, (rows,))
    t_scan = _time(jax.jit(lambda x: _scan_count(x.reshape(-1))),
                   (rows_sorted,))
    print(f"  stage: group into blocks      {t_grp*1e3:8.2f} ms "
          f"(dest kv-sort + {nb} DMA runs)")
    print(f"  stage: batched row sort       {t_rsort*1e3:8.2f} ms")
    print(f"  stage: fused merge scan       {t_scan*1e3:8.2f} ms")

    delta = (t_flat - t_comb) / t_flat * 100.0
    verdict = ("WIN" if t_comb < t_flat * 0.85 else
               "no-win" if t_comb < t_flat else "DEAD-END")
    print(f"verdict: {verdict} — combined is {delta:+.1f}% vs flat "
          f"({t_comb*1e3:.2f} vs {t_flat*1e3:.2f} ms/iter)", flush=True)


if __name__ == "__main__":
    main()

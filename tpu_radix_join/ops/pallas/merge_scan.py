"""Pallas TPU kernel: fused merge-count scan.

After the combined sort (ops/merge_count.py), XLA computes the match weights
with ~5 separate passes over the 2n array (cumsum, shift-compare, cummax,
elementwise, chunk reduction) — each a full HBM round trip.  This kernel fuses
them into ONE pass: a sequential grid walks the sorted packed keys tile by
tile, carrying the running R-count, run base, and previous key in SMEM
scratch, and emits one uint32 partial match count per tile.

This is the hand-written counterpart of the reference's fused GPU probe
kernels (probe_count, kernels.cu:423-463): where the GPU kernel chases hash
buckets per thread, the TPU kernel turns the probe into a carried scan at HBM
bandwidth.

In-tile layout: tiles are [ROWS, 128] uint32 in VMEM (row-major order of the
flat sorted array); full-tile scans decompose into a lane scan (axis=1) plus
an exclusive row-offset scan, all on the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 256          # tile = ROWS x 128 uint32 = 128KB VMEM
LANES = 128
TILE = ROWS * LANES


def pallas_available() -> bool:
    """True on a TPU backend: the one rule by which every ``auto`` site
    (ops/radix.py, ops/sorting.py, merge_count.py) picks a compiled
    Pallas kernel; elsewhere they take interpret=True or the XLA path."""
    return jax.default_backend() == "tpu"


def out_struct(shape, dtype, like) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct whose varying-manual-axes (vma) annotation is
    inherited from ``like``: inside a ``shard_map`` with check_vma=True,
    pallas_call outputs must declare how they vary over the mesh axes — a
    per-device kernel output varies exactly like its per-device input."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _iscan(x: jnp.ndarray, op, ident, axis: int) -> jnp.ndarray:
    """Inclusive Hillis-Steele scan along ``axis`` built from circular roll +
    iota mask (Mosaic lowers neither the cumsum/cummax primitives nor
    lane-offset slices, but pltpu.roll is native)."""
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    sh = 1
    while sh < n:
        rolled = pltpu.roll(x, sh, axis=axis)
        x = op(x, jnp.where(idx >= sh, rolled, ident))
        sh *= 2
    return x


def _tile_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumsum over a [ROWS, 128] int32 tile in flat row-major order."""
    lane = _iscan(x, jnp.add, 0, axis=1)
    row_tot = jnp.sum(x, axis=1, keepdims=True)
    row_off = _iscan(row_tot, jnp.add, 0, axis=0) - row_tot   # exclusive
    return lane + row_off


def _tile_cummax(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cummax over a [ROWS, 128] int32 tile in flat row-major order."""
    lane = _iscan(x, jnp.maximum, 0, axis=1)
    row_max = jnp.max(x, axis=1, keepdims=True)
    row_carry = _iscan(row_max, jnp.maximum, 0, axis=0)
    # exclusive over rows: shift down one row
    row_idx = jax.lax.broadcasted_iota(jnp.int32, row_carry.shape, 0)
    prev = jnp.where(row_idx >= 1, pltpu.roll(row_carry, 1, axis=0), 0)
    return jnp.maximum(lane, prev)


def _tile_scan(packed, carry_c_r, carry_base, carry_prev):
    """Shared per-tile merge-weight scan.  All arithmetic is int32: Mosaic
    does not legalize unsigned max or reductions, and every quantity here
    fits — keys are packed>>1 < 2^31, counts <= n < 2^31.  The prev-key
    sentinel is -1 (no valid key < 0).

    Returns (weight, key, new_c_r, new_base, new_prev_key); the carries'
    "last flat element" is expressed as a reduction (Mosaic cannot extract a
    VMEM scalar): c_r and base_run are nondecreasing in flat order and keys
    are sorted, so last == max (or carry + tile sum)."""
    key = (packed >> jnp.uint32(1)).astype(jnp.int32)
    is_s = (packed & jnp.uint32(1)).astype(jnp.int32)
    is_r = 1 - is_s

    c_r = _tile_cumsum(is_r) + carry_c_r

    # previous key in flat row-major order via circular rolls: lane roll
    # brings key[r, j-1] (and key[r, 127] into lane 0); a row roll on top
    # fixes lane 0 to key[r-1, 127]; element (0, 0) takes the carry.
    lane_idx = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
    row_idx = jax.lax.broadcasted_iota(jnp.int32, key.shape, 0)
    rl = pltpu.roll(key, 1, axis=1)
    prev_key = jnp.where(lane_idx == 0, pltpu.roll(rl, 1, axis=0), rl)
    prev_key = jnp.where((lane_idx == 0) & (row_idx == 0), carry_prev,
                         prev_key)
    run_start = key != prev_key

    base_at_start = jnp.where(run_start, c_r - is_r, 0)
    base_run = jnp.maximum(_tile_cummax(base_at_start), carry_base)

    weight = is_s * (c_r - base_run)
    return (weight, key, carry_c_r + jnp.sum(is_r), jnp.max(base_run),
            jnp.max(key))


def _kernel(packed_ref, out_ref, c_r_ref, base_ref, prev_key_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        c_r_ref[0] = jnp.int32(0)
        base_ref[0] = jnp.int32(0)
        prev_key_ref[0] = jnp.int32(-1)   # never equals a real key

    weight, _, c_r, base, prev = _tile_scan(
        packed_ref[:], c_r_ref[0], base_ref[0], prev_key_ref[0])
    out_ref[t, 0] = jnp.sum(weight).astype(jnp.uint32)
    c_r_ref[0] = c_r
    base_ref[0] = base
    prev_key_ref[0] = prev


def _kernel_partitions(packed_ref, out_ref, maxw_ref, c_r_ref, base_ref,
                       prev_key_ref, *, num_partitions: int, pid_shift: int):
    """Merge-weight scan fused with per-partition accumulation.

    Input is sorted in PARTITION-MAJOR packing (pid in the top bits, see
    merge_count._pack_pm), so each tile intersects only a narrow contiguous
    pid range; the per-partition masked reductions are ``pl.when``-guarded on
    that range, so only ~2 of them execute per tile regardless of the fanout.
    Accumulation is int32 (wraps identically to the uint32 contract); the
    caller bitcasts.  ``maxw_ref`` carries the max single-tuple match weight
    (max inner multiplicity among matched outer tuples) — the quantity the
    driver's uint32-overflow risk bound needs (hash_join._count_risk).
    """
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        for p in range(num_partitions):
            out_ref[p] = jnp.int32(0)
        maxw_ref[0] = jnp.int32(0)
        c_r_ref[0] = jnp.int32(0)
        base_ref[0] = jnp.int32(0)
        prev_key_ref[0] = jnp.int32(-1)

    packed = packed_ref[:]
    weight, _, c_r, base, prev = _tile_scan(
        packed, c_r_ref[0], base_ref[0], prev_key_ref[0])
    maxw_ref[0] = jnp.maximum(maxw_ref[0], jnp.max(jnp.max(weight, axis=0)))
    if num_partitions == 1:
        out_ref[0] = out_ref[0] + jnp.sum(jnp.sum(weight, axis=0))
    else:
        pid = (packed >> jnp.uint32(pid_shift)).astype(jnp.int32)
        pid_min = jnp.min(pid)
        pid_max = jnp.max(pid)
        for p in range(num_partitions):
            @pl.when((pid_min <= p) & (p <= pid_max))
            def _acc(p=p):
                c = jnp.sum(jnp.sum(jnp.where(pid == p, weight, 0), axis=0))
                out_ref[p] = out_ref[p] + c

    c_r_ref[0] = c_r
    base_ref[0] = base
    prev_key_ref[0] = prev


@functools.partial(jax.jit, static_argnames=("num_partitions", "interpret"))
def merge_scan_partitions(packed_sorted: jnp.ndarray, *, num_partitions: int,
                          interpret: bool = False):
    """Per-partition match counts (uint32 [num_partitions]) in ONE pass over
    a partition-major sorted packed array (merge_count._pack_pm layout:
    pid in the top log2(num_partitions) bits, then key remainder, then the
    side tag in bit 0).

    Replaces sort + ~5 XLA scan passes + a 33.5M-weight ``jnp.bincount``
    scatter-add (measured 375.7 ms at 16M⋈16M on the round-2 chip; this
    kernel's whole post-sort phase is ~one HBM pass).  Length must be a tile
    multiple (pad post-sort with 0xFFFFFFFF = the S pad, which sorts last and
    carries zero weight).

    Returns ``(counts, max_weight)``: the second output is the max
    single-outer-tuple match count (uint32 scalar), accumulated in the same
    pass — the driver's uint32-overflow risk bound consumes it
    (hash_join._count_risk).
    """
    n = packed_sorted.shape[0]
    if n % TILE:
        raise ValueError(f"length {n} must be a multiple of {TILE}")
    if num_partitions & (num_partitions - 1):
        raise ValueError("num_partitions must be a power of two")
    num_tiles = n // TILE
    pid_shift = 32 - (num_partitions.bit_length() - 1)
    kernel = functools.partial(_kernel_partitions,
                               num_partitions=num_partitions,
                               pid_shift=pid_shift)
    out, maxw = pl.pallas_call(
        kernel,
        grid=(num_tiles,),
        in_specs=[pl.BlockSpec((ROWS, LANES), lambda t: (t, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((num_partitions,), lambda t: (0,),
                                memory_space=pltpu.SMEM),
                   pl.BlockSpec((1,), lambda t: (0,),
                                memory_space=pltpu.SMEM)),
        out_shape=(out_struct((num_partitions,), jnp.int32, packed_sorted),
                   out_struct((1,), jnp.int32, packed_sorted)),
        scratch_shapes=[
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
    )(packed_sorted.reshape(num_tiles * ROWS, LANES))
    return (jax.lax.bitcast_convert_type(out, jnp.uint32),
            maxw[0].astype(jnp.uint32))


def _kernel_partitions_wide(lo_ref, hi_ref, tag_ref, out_ref, maxw_ref,
                            c_r_ref, base_ref, prev_lo_ref, prev_hi_ref,
                            *, num_partitions: int, pid_shift: int):
    """Wide-key (hi/lo lane) variant of :func:`_kernel_partitions`.

    Input is the three-lane partition-major sort order (lo_rot, hi, tag)
    where ``lo_rot`` is the low key lane rotated so the pid sits in its top
    bits (merge_count._rotate_pid).  Both 32-bit key lanes use all 32 bits,
    and Mosaic legalizes neither unsigned max nor uint->int converts of
    values >= 2^31, so comparisons ride an order-preserving bitcast:
    ``x ^ 0x8000_0000`` reinterpreted as int32 (run equality and max-based
    carry extraction are both preserved).  A tile's first element losing its
    run_start against the initial carry is harmless: its run base is 0,
    exactly what the carry init encodes.
    """
    t = pl.program_id(0)
    int32_min = jnp.int32(-2147483648)

    @pl.when(t == 0)
    def _init():
        for p in range(num_partitions):
            out_ref[p] = jnp.int32(0)
        maxw_ref[0] = jnp.int32(0)
        c_r_ref[0] = jnp.int32(0)
        base_ref[0] = jnp.int32(0)
        prev_lo_ref[0] = int32_min
        prev_hi_ref[0] = int32_min

    flip = jnp.uint32(0x80000000)
    lo = jax.lax.bitcast_convert_type(lo_ref[:] ^ flip, jnp.int32)
    hi = jax.lax.bitcast_convert_type(hi_ref[:] ^ flip, jnp.int32)
    is_s = tag_ref[:].astype(jnp.int32)
    is_r = 1 - is_s

    carry_c_r = c_r_ref[0]
    carry_base = base_ref[0]
    c_r = _tile_cumsum(is_r) + carry_c_r

    lane_idx = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 1)
    row_idx = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 0)

    def shift_prev(x, carry):
        rl = pltpu.roll(x, 1, axis=1)
        prev = jnp.where(lane_idx == 0, pltpu.roll(rl, 1, axis=0), rl)
        return jnp.where((lane_idx == 0) & (row_idx == 0), carry, prev)

    run_start = ((lo != shift_prev(lo, prev_lo_ref[0]))
                 | (hi != shift_prev(hi, prev_hi_ref[0])))
    base_at_start = jnp.where(run_start, c_r - is_r, 0)
    base_run = jnp.maximum(_tile_cummax(base_at_start), carry_base)
    weight = is_s * (c_r - base_run)
    maxw_ref[0] = jnp.maximum(maxw_ref[0], jnp.max(jnp.max(weight, axis=0)))

    if num_partitions == 1:
        out_ref[0] = out_ref[0] + jnp.sum(jnp.sum(weight, axis=0))
    else:
        pid = (lo_ref[:] >> jnp.uint32(pid_shift)).astype(jnp.int32)
        pid_min = jnp.min(pid)
        pid_max = jnp.max(pid)
        for p in range(num_partitions):
            @pl.when((pid_min <= p) & (p <= pid_max))
            def _acc(p=p):
                c = jnp.sum(jnp.sum(jnp.where(pid == p, weight, 0), axis=0))
                out_ref[p] = out_ref[p] + c

    c_r_ref[0] = carry_c_r + jnp.sum(is_r)
    base_ref[0] = jnp.max(base_run)
    # last flat element of (lo, hi): lo is sorted so last lo == max; the
    # last hi is the max over the final lo run (hi sorted within equal lo)
    last_lo = jnp.max(lo)
    c_r_dummy = jnp.where(lo == last_lo, hi, int32_min)
    prev_lo_ref[0] = last_lo
    prev_hi_ref[0] = jnp.max(c_r_dummy)


@functools.partial(jax.jit, static_argnames=("num_partitions", "interpret"))
def merge_scan_partitions_wide(lo_rot_sorted: jnp.ndarray,
                               hi_sorted: jnp.ndarray,
                               tag_sorted: jnp.ndarray, *,
                               num_partitions: int,
                               interpret: bool = False):
    """Per-partition match counts for 64-bit keys in one pass over the
    three-lane partition-major sort order (see merge_count's wide Pallas
    path).  Lengths must be a tile multiple (pad post-sort with the all-ones
    triple (0xFFFFFFFF, 0xFFFFFFFF, 1) — the wide S pad image, lexicographic
    maximum, zero weight).  Returns ``(counts, max_weight)`` as
    :func:`merge_scan_partitions` does."""
    n = lo_rot_sorted.shape[0]
    if n % TILE:
        raise ValueError(f"length {n} must be a multiple of {TILE}")
    if num_partitions & (num_partitions - 1):
        raise ValueError("num_partitions must be a power of two")
    num_tiles = n // TILE
    pid_shift = 32 - (num_partitions.bit_length() - 1)
    kernel = functools.partial(_kernel_partitions_wide,
                               num_partitions=num_partitions,
                               pid_shift=pid_shift)
    spec = pl.BlockSpec((ROWS, LANES), lambda t: (t, 0),
                        memory_space=pltpu.VMEM)
    out, maxw = pl.pallas_call(
        kernel,
        grid=(num_tiles,),
        in_specs=[spec, spec, spec],
        out_specs=(pl.BlockSpec((num_partitions,), lambda t: (0,),
                                memory_space=pltpu.SMEM),
                   pl.BlockSpec((1,), lambda t: (0,),
                                memory_space=pltpu.SMEM)),
        out_shape=(out_struct((num_partitions,), jnp.int32, lo_rot_sorted),
                   out_struct((1,), jnp.int32, lo_rot_sorted)),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32) for _ in range(4)],
        interpret=interpret,
    )(lo_rot_sorted.reshape(num_tiles * ROWS, LANES),
      hi_sorted.reshape(num_tiles * ROWS, LANES),
      tag_sorted.reshape(num_tiles * ROWS, LANES))
    return (jax.lax.bitcast_convert_type(out, jnp.uint32),
            maxw[0].astype(jnp.uint32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_scan_chunks(packed_sorted: jnp.ndarray,
                      interpret: bool = False) -> jnp.ndarray:
    """Per-tile match counts (uint32 [n / TILE]) for a sorted packed array.

    ``packed_sorted`` must be sorted uint32 with length a multiple of TILE
    (callers pad with the S pack-pad value 0xFFFFFFFF, which sorts last and
    contributes zero weight)."""
    n = packed_sorted.shape[0]
    if n % TILE:
        raise ValueError(f"length {n} must be a multiple of {TILE}")
    num_tiles = n // TILE
    return pl.pallas_call(
        _kernel,
        grid=(num_tiles,),
        in_specs=[pl.BlockSpec((ROWS, LANES), lambda t: (t, 0),
                               memory_space=pltpu.VMEM)],
        # full-array SMEM block (one uint32 per tile): the TPU lowering
        # rejects sub-(8,128) blocks unless they span the whole array, so
        # every grid step maps the same block and writes its own row.
        out_specs=pl.BlockSpec((num_tiles, 1), lambda t: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=out_struct((num_tiles, 1), jnp.uint32, packed_sorted),
        scratch_shapes=[
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
    )(packed_sorted.reshape(num_tiles * ROWS, LANES)).reshape(num_tiles)

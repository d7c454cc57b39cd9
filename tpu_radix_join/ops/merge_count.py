"""Sort-merge match counting: the fast TPU probe discipline.

Replaces the searchsorted-based probe where profiling on v5e shows
``jnp.searchsorted(method='sort')`` costs ~470ms at 16M keys (it re-sorts per
side) while a single combined sort costs ~80ms.  This is the TPU-idiomatic
realisation of BuildProbe (tasks/BuildProbe.cpp:47-121): where the reference
chases hash-bucket chains per tuple, we sort the *union* of both key sets once
and recover every outer tuple's duplicate-aware match count with cumulative
scans — no random gathers, no per-tuple loops, everything a sort or a scan.

Scheme (keys must fit 31 bits; the pipeline's key-range check enforces it):

  packed = key << 1 | side_tag        (R tag 0 sorts before S within a key)
  sort packed;  runs of equal key are contiguous, R-part first.
  c_r[i]        = inclusive cumsum of "is R"
  base_run[i]   = c_r just before this run's start (cummax propagation)
  weight[i]     = is_S[i] ? c_r[i] - base_run[i] : 0     # |R with equal key|
  matches       = sum(weight)   (chunked uint32 partial sums, host uint64 total)

Padding slots (side sentinels, tuples.py) map to two reserved top key values
with no cross-side partner, so they contribute zero without any masking pass.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from tpu_radix_join.observability import stages

# Largest valid key for the merge path (inclusive): 31-bit packing with two
# reserved pad key slots (0x7FFFFFFE, 0x7FFFFFFF) above it.  The pipeline's
# keys_ok check enforces key <= MAX_MERGE_KEY; violations are routed to the
# pad values here (no match) and flagged there.
MAX_MERGE_KEY = 0x7FFFFFFD
# Plain ints, not jnp scalars: module import must never initialize a backend.
_R_PACK_PAD = 0xFFFFFFFC   # key slot 0x7FFFFFFE, tag 0
_S_PACK_PAD = 0xFFFFFFFF   # key slot 0x7FFFFFFF, tag 1

# The packed value carries the side tag, so equal values are fully
# interchangeable and an unstable sort loses nothing (ops/sorting.py).
from tpu_radix_join.ops.sorting import (
    sort_lex_unstable as _sort_lex_unstable,
    sort_unstable as _sort_unstable,
)


def _resolve_impl(impl: str | None, fanout_bits: int) -> str:
    """Shared impl auto-routing for every count discipline: the fused Pallas
    kernels on TPU (their SMEM accumulators cap the partition count at 128),
    the portable XLA scans elsewhere."""
    if impl is not None:
        return impl
    from tpu_radix_join.ops.pallas.merge_scan import pallas_available
    return ("pallas" if (pallas_available() and (1 << fanout_bits) <= 128)
            else "xla")


def _pack(r_keys: jnp.ndarray, s_keys: jnp.ndarray) -> jnp.ndarray:
    one = jnp.uint32(1)
    r_ok = r_keys <= jnp.uint32(MAX_MERGE_KEY)
    s_ok = s_keys <= jnp.uint32(MAX_MERGE_KEY)
    pr = jnp.where(r_ok, r_keys << one, jnp.uint32(_R_PACK_PAD))
    ps = jnp.where(s_ok, (s_keys << one) | one, jnp.uint32(_S_PACK_PAD))
    return jnp.concatenate([pr, ps])


def _run_weights(is_s: jnp.ndarray, run_start: jnp.ndarray) -> jnp.ndarray:
    """Per-position match weights for a sorted sequence: at every S position,
    the number of R tuples in its equal-key run (the module docstring's
    cumsum/cummax scheme).  ``is_s``: uint32 0/1 side tags in sort order
    (R before S within a run); ``run_start``: bool, True where a new
    equal-key run begins."""
    is_r = jnp.uint32(1) - is_s
    c_r = jnp.cumsum(is_r, dtype=jnp.uint32)
    # c_r *before* the run start, propagated across the run via cummax
    # (c_r is monotone non-decreasing, so cummax of the starts is exact).
    base_at_start = jnp.where(run_start, c_r - is_r, jnp.uint32(0))
    base_run = jax.lax.cummax(base_at_start)
    return is_s * (c_r - base_run)


def _weights(packed_sorted: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(weight per position, key per position) for the sorted packed array."""
    one = jnp.uint32(1)
    key = packed_sorted >> one
    is_s = (packed_sorted & one).astype(jnp.uint32)
    prev_key = jnp.concatenate([jnp.full((1,), 0xFFFFFFFF, jnp.uint32), key[:-1]])
    return _run_weights(is_s, key != prev_key), key


@jax.jit
def presort_keys(keys: jnp.ndarray) -> jnp.ndarray:
    """Sort a raw key lane once for reuse across many probes.

    The sorted array is the "inner side" input of
    :func:`merge_count_presorted`: the out-of-core grid sorts each inner
    chunk once per grid *row* and probes every outer chunk of the row
    against it, eliminating the ``(n_outer_chunks - 1)`` redundant sorts
    the packed-union discipline pays per row (ops/chunked.py pipeline).
    No packing, no side tag: the raw uint32 keys sort as-is, so the full
    sub-sentinel key range is supported without the 31-bit
    :data:`MAX_MERGE_KEY` ceiling."""
    return _sort_unstable(keys)


@jax.named_scope(stages.MERGE_SCAN)
def merge_count_presorted(r_sorted: jnp.ndarray, s_keys: jnp.ndarray,
                          return_max_weight: bool = False):
    """Duplicate-aware match count of ``s_keys`` against an ALREADY-SORTED
    inner key lane (:func:`presort_keys` output): two binary searches per
    outer key — ``upper_bound - lower_bound`` over the sorted inner is
    exactly the per-outer-tuple match weight — instead of re-sorting the
    packed union per probe.  O(m log n) gathers against the resident
    sorted inner; on the sort-bound grid engine this converts the per-pair
    sort into a once-per-row sort.

    Key-range discipline: none needed — raw uint32 comparisons cover every
    sub-sentinel key, so there is no narrow/full split on this path.  The
    caller must keep real keys out of the reserved sentinel range
    (``<= 0xFFFFFFFD``, tuples.py): an outer S pad (0xFFFFFFFF) can then
    never equal an inner key and contributes zero weight, and an inner
    sentinel would silently pad-match — the grid's per-chunk key-bound
    check (ops/chunked.py) enforces this loudly.

    Returns the uint32 total (overflow-safe iff ``max_weight * len(s_keys)
    < 2**32``, the same window guard as ``merge_count_chunks``);
    ``return_max_weight`` also returns the max per-outer-tuple weight."""
    lb = jnp.searchsorted(r_sorted, s_keys, side="left").astype(jnp.uint32)
    ub = jnp.searchsorted(r_sorted, s_keys, side="right").astype(jnp.uint32)
    weight = ub - lb
    total = jnp.sum(weight, dtype=jnp.uint32)
    if return_max_weight:
        return total, jnp.max(weight)
    return total


@jax.named_scope(stages.MERGE_SCAN)
def merge_count_chunks(r_keys: jnp.ndarray, s_keys: jnp.ndarray,
                       num_chunks: int = 4096,
                       return_max_weight: bool = False):
    """Match count as uint32 partial sums over fixed position chunks
    (sum on host in uint64).  Safe against uint32 overflow as long as any
    ``(n/num_chunks)``-position window's weights stay < 2**32 — guaranteed
    when per-key inner multiplicity * chunk width < 2**32 (canonical
    workloads: inner multiplicity ~1).  ``return_max_weight`` also returns
    the max single-outer-tuple match count (uint32 scalar), from which the
    caller checks that guarantee at runtime (``max_weight * chunk_width <
    2**32``, see ops/chunked.chunked_join_count)."""
    packed = _sort_unstable(_pack(r_keys, s_keys))
    weight, _ = _weights(packed)
    n = weight.shape[0]
    c = max(1, num_chunks)
    pad = (-n) % c
    weight = jnp.concatenate([weight, jnp.zeros((pad,), jnp.uint32)])
    counts = jnp.sum(weight.reshape(c, -1), axis=1, dtype=jnp.uint32)
    if return_max_weight:
        return counts, jnp.max(weight)
    return counts


@jax.named_scope(stages.MERGE_SCAN)
def merge_count_pallas(r_keys: jnp.ndarray, s_keys: jnp.ndarray,
                       interpret: bool = False) -> jnp.ndarray:
    """Match counting with the fused Pallas scan kernel for the post-sort
    phase (ops/pallas/merge_scan.py): sort + ONE pass instead of sort + ~5
    XLA scan passes.  Returns uint32 per-tile partial counts (host uint64
    sum).  Pads to the kernel tile size with the S pack-pad (sorts last,
    weight 0)."""
    from tpu_radix_join.ops.pallas.merge_scan import TILE, merge_scan_chunks
    packed = _pack(r_keys, s_keys)
    n = packed.shape[0]
    pad = (-n) % TILE
    if pad:
        packed = jnp.concatenate(
            [packed, jnp.full((pad,), _S_PACK_PAD, jnp.uint32)])
    return merge_scan_chunks(_sort_unstable(packed), interpret=interpret)


def _pack_pm(r_keys: jnp.ndarray, s_keys: jnp.ndarray,
             fanout_bits: int) -> jnp.ndarray:
    """Partition-major packing: ``pid | key_remainder | side_tag`` from top to
    bottom bits, so a single sort groups tuples by network partition first and
    by full key within it (equal full keys stay adjacent: same pid + same
    remainder).  This is what lets the fused Pallas kernel accumulate
    per-partition counts with ~2 active reductions per tile
    (merge_scan._kernel_partitions).

    Pad handling mirrors ``_pack``: out-of-range keys map to the reserved key
    slots 0x7FFFFFFE (R) / 0x7FFFFFFF (S), which land at the TOP of the
    remainder range of partitions (P-2) and (P-1) — interior to the array,
    not at its end, but in runs no cross-side real key can share (real keys
    <= MAX_MERGE_KEY exclude exactly those two (pid, remainder) pairs), so
    they carry zero weight wherever they sort."""
    one = jnp.uint32(1)
    f = jnp.uint32(fanout_bits)
    mask = jnp.uint32((1 << fanout_bits) - 1)

    def pm(keys, ok, pad_key, tag):
        k = jnp.where(ok, keys, jnp.uint32(pad_key))
        pid = k & mask
        rem = k >> f
        if fanout_bits:
            top = pid << jnp.uint32(32 - fanout_bits)
        else:
            top = jnp.uint32(0)
        return top | (rem << one) | jnp.uint32(tag)

    r_ok = r_keys <= jnp.uint32(MAX_MERGE_KEY)
    s_ok = s_keys <= jnp.uint32(MAX_MERGE_KEY)
    return jnp.concatenate([
        pm(r_keys, r_ok, 0x7FFFFFFE, 0),
        pm(s_keys, s_ok, 0x7FFFFFFF, 1),
    ])


@jax.named_scope(stages.MERGE_SCAN)
def merge_count_per_partition(r_keys: jnp.ndarray, s_keys: jnp.ndarray,
                              fanout_bits: int,
                              impl: str | None = None,
                              return_max_weight: bool = False):
    """Per-network-partition match counts, uint32 [1 << fanout_bits].

    Each partition's count must stay < 2**32 (SURVEY.md §7.4 item 2
    contract).  ``impl``: None = auto (fused Pallas kernel on TPU, XLA
    elsewhere), or one of "xla", "pallas", "pallas_interpret".

    The Pallas path sorts in partition-major packing and fuses the weight
    scan + per-partition accumulation into one pass
    (merge_scan.merge_scan_partitions); the XLA path is the portable
    fallback: low-bit packing + a weights bincount (a scatter-add XLA
    serializes on TPU — measured 375.7 ms vs ~55 ms total for the Pallas
    path at 16M⋈16M, round 2).

    ``return_max_weight`` also returns the max single-outer-tuple match
    count (uint32 scalar; free in the Pallas pass, one extra reduction in
    XLA) — the driver's overflow-risk bound input (hash_join._count_risk):
    a partition's count is <= max_weight x its outer tuple count, so the
    guard needs no wider accumulators (the reference is immune via its
    uint64 RESULT_COUNTER, HashJoin.h:26; uint32 counts + this bound are
    the no-device-int64 equivalent).
    """
    impl = _resolve_impl(impl, fanout_bits)
    if impl == "xla":
        packed = _sort_unstable(_pack(r_keys, s_keys))
        weight, key = _weights(packed)
        pid = (key & jnp.uint32((1 << fanout_bits) - 1)).astype(jnp.int32)
        counts = jnp.bincount(pid, weights=weight,
                              length=1 << fanout_bits).astype(jnp.uint32)
        if return_max_weight:
            return counts, jnp.max(weight)
        return counts
    from tpu_radix_join.ops.pallas.merge_scan import TILE, merge_scan_partitions
    packed = _sort_unstable(_pack_pm(r_keys, s_keys, fanout_bits))
    pad = (-packed.shape[0]) % TILE
    if pad:
        # post-sort padding: 0xFFFFFFFF is the partition-major S pad (all-ones
        # pid and remainder), >= every packed value, so sortedness holds
        packed = jnp.concatenate(
            [packed, jnp.full((pad,), _S_PACK_PAD, jnp.uint32)])
    counts, maxw = merge_scan_partitions(
        packed, num_partitions=1 << fanout_bits,
        interpret=(impl == "pallas_interpret"))
    if return_max_weight:
        return counts, maxw
    return counts


@jax.named_scope(stages.MERGE_SCAN)
def merge_count_per_partition_full(r_keys: jnp.ndarray, s_keys: jnp.ndarray,
                                   fanout_bits: int,
                                   impl: str | None = None,
                                   return_max_weight: bool = False):
    """Full-range uint32 merge count: accepts every sub-sentinel key
    (``key <= 0xFFFFFFFD`` — the R/S pad values stay reserved, tuples.py),
    removing the 31-bit :data:`MAX_MERGE_KEY` ceiling of the packed path.

    Discipline: a 2-key lexicographic unstable sort on (pid-rotated key,
    side tag) — the explicit tag lane keeps every equal-key run's R tuples
    ahead of its S tuples, doing the job of the packing's stolen bit — then
    the usual cumsum/cummax weight pass.  Per-partition counts come from
    prefix-sum differences at the P+1 partition boundary positions of the
    pid-major order (``searchsorted``, P scalar binary searches) instead of
    a weights bincount: a scatter-add XLA serializes on TPU (measured ~98ms
    per 16M pass) while the boundary gather is O(P log n).  The uint32
    prefix sums may wrap; boundary differences stay exact modulo 2**32, so
    each partition's count is exact under the pipeline's "partition count
    < 2**32" contract (guarded by ``max_weight`` at the call sites).

    Cost: a 2-lane sort, ~1.7x the packed single-lane path — the engine
    routes here only when keys exceed the packing (config.key_range) and it
    beats the 3-lane ``key_bits=64`` escape (~2.6x).  The reference needs no
    analog: its hash-bucket chains never pack key bits (BuildProbe.cpp:81-106).

    ``impl`` as in :func:`merge_count_per_partition`: on TPU the post-sort
    scan fuses into one Pallas pass by feeding the wide kernel a zero hi
    lane — run equality on (rot, 0) degenerates to run equality on rot, so
    ``merge_scan_partitions_wide`` computes exactly these counts; "xla" is
    the portable scan-passes + boundary-differences fallback.
    """
    impl = _resolve_impl(impl, fanout_bits)
    rot = jnp.concatenate([_rotate_pid(r_keys, fanout_bits),
                           _rotate_pid(s_keys, fanout_bits)])
    tag = jnp.concatenate([
        jnp.zeros(r_keys.shape, jnp.uint32), jnp.ones(s_keys.shape, jnp.uint32)])
    rot, tag = _sort_lex_unstable(rot, tag, num_keys=2)
    if impl != "xla":
        from tpu_radix_join.ops.pallas.merge_scan import (
            TILE, merge_scan_partitions_wide)
        n = rot.shape[0]
        pad = (-n) % TILE
        if pad:
            # post-sort padding with the (all-ones rot, tag 1) S-pad image:
            # the lexicographic maximum (real keys stay below the sentinels,
            # so their rotations never reach all-ones), zero weight
            ones = jnp.full((pad,), 0xFFFFFFFF, jnp.uint32)
            rot = jnp.concatenate([rot, ones])
            tag = jnp.concatenate([tag, jnp.ones((pad,), jnp.uint32)])
        # hi derived FROM rot — not a fresh constant — so it inherits rot's
        # varying-manual-axes annotation inside shard_map-traced pipelines
        # (a fresh zero lane fails pallas_call's vma consistency check):
        # zero for real keys, all-ones on the pad image (rot == all-ones is
        # unreachable for real keys by the sentinel contract)
        hi = jnp.where(rot == jnp.uint32(0xFFFFFFFF), rot,
                       rot & jnp.uint32(0))
        counts, maxw = merge_scan_partitions_wide(
            rot, hi, tag, num_partitions=1 << fanout_bits,
            interpret=(impl == "pallas_interpret"))
        if return_max_weight:
            return counts, maxw
        return counts
    prev = jnp.concatenate(
        [jnp.full((1,), 0xFFFFFFFF, jnp.uint32), rot[:-1]])
    # position 0: the synthetic prev (all-ones) can only suppress a run
    # start when rot[0] is itself the global-max value — i.e. every element
    # is an S pad, whose weights are zero regardless
    weight = _run_weights(tag, rot != prev)
    cw = jnp.concatenate([jnp.zeros((1,), jnp.uint32),
                          jnp.cumsum(weight, dtype=jnp.uint32)])
    if fanout_bits:
        bnd_vals = (jnp.arange(1 << fanout_bits, dtype=jnp.uint32)
                    << jnp.uint32(32 - fanout_bits))
        idx = jnp.searchsorted(rot, bnd_vals)
        idx = jnp.concatenate(
            [idx, jnp.full((1,), rot.shape[0], idx.dtype)])
        counts = cw[idx[1:]] - cw[idx[:-1]]
    else:
        counts = cw[-1:]
    if return_max_weight:
        return counts, jnp.max(weight)
    return counts


def _rotate_pid(lo: jnp.ndarray, fanout_bits: int) -> jnp.ndarray:
    """Rotate the low key lane right by ``fanout_bits`` so the partition id
    occupies the top bits: sorting by (lo_rot, hi) groups by partition first,
    then by (key remainder, hi) — equal (hi, lo) keys stay adjacent, which is
    all the weight scan needs (run equality, not numeric order)."""
    if not fanout_bits:
        return lo
    f = jnp.uint32(fanout_bits)
    return (lo << jnp.uint32(32 - fanout_bits)) | (lo >> f)


@jax.named_scope(stages.MERGE_SCAN)
def merge_count_wide_per_partition(
    r_lo: jnp.ndarray, r_hi: jnp.ndarray,
    s_lo: jnp.ndarray, s_hi: jnp.ndarray,
    fanout_bits: int,
    impl: str | None = None,
    return_max_weight: bool = False,
):
    """64-bit-key match counting without 64-bit arithmetic.

    TPU int64 is limited/slow (SURVEY.md §7.4 item 3), so wide keys ride as
    two uint32 lanes and the combined sort is a three-key lexicographic
    ``lax.sort`` — the tag key keeps every equal-key run's R tuples ahead of
    its S tuples, exactly what the 31-bit packing achieves in the single-lane
    path.  The weight scheme is the module's usual cumsum/cummax pass with
    run boundaries on (hi, lo).  No jax x64 needed.

    ``impl`` as in :func:`merge_count_per_partition`: the TPU path sorts by
    (pid-rotated lo, hi, tag) and fuses the scan + per-partition histogram
    into one Pallas pass (merge_scan_partitions_wide); the XLA fallback
    sorts (hi, lo, tag) and bincounts the weights.

    Pad sentinels sit in BOTH lanes (make_padding wide=True), and R/S pads
    differ in the hi lane, so padding contributes zero weight on either path.
    ``return_max_weight`` as in :func:`merge_count_per_partition`.
    """
    impl = _resolve_impl(impl, fanout_bits)
    hi = jnp.concatenate([r_hi, s_hi])
    lo = jnp.concatenate([r_lo, s_lo])
    tag = jnp.concatenate([
        jnp.zeros(r_lo.shape, jnp.uint32), jnp.ones(s_lo.shape, jnp.uint32)])
    if impl != "xla":
        from tpu_radix_join.ops.pallas.merge_scan import (
            TILE, merge_scan_partitions_wide)
        lo_rot, hi, tag = _sort_lex_unstable(
            _rotate_pid(lo, fanout_bits), hi, tag, num_keys=3)
        pad = (-lo_rot.shape[0]) % TILE
        if pad:
            # the wide S pad's image (all-ones lanes, tag 1) is the
            # lexicographic maximum, so post-sort padding keeps sortedness
            ones = jnp.full((pad,), 0xFFFFFFFF, jnp.uint32)
            lo_rot = jnp.concatenate([lo_rot, ones])
            hi = jnp.concatenate([hi, ones])
            tag = jnp.concatenate([tag, jnp.ones((pad,), jnp.uint32)])
        counts, maxw = merge_scan_partitions_wide(
            lo_rot, hi, tag, num_partitions=1 << fanout_bits,
            interpret=(impl == "pallas_interpret"))
        if return_max_weight:
            return counts, maxw
        return counts

    hi, lo, tag = _sort_lex_unstable(hi, lo, tag, num_keys=3)
    prev_hi = jnp.concatenate([jnp.full((1,), 0xFFFFFFFF, jnp.uint32), hi[:-1]])
    prev_lo = jnp.concatenate([jnp.full((1,), 0xFFFFFFFF, jnp.uint32), lo[:-1]])
    # position 0 is always a run start: (prev_hi, prev_lo) = the S pad pair,
    # which real keys can't equal (hi < 0xFFFFFFFE contract) — and if x[0] IS
    # an S pad, its weight is 0 anyway (no R pad shares the run).
    run_start = (hi != prev_hi) | (lo != prev_lo)
    weight = _run_weights(tag, run_start)
    pid = (lo & jnp.uint32((1 << fanout_bits) - 1)).astype(jnp.int32)
    counts = jnp.bincount(pid, weights=weight,
                          length=1 << fanout_bits).astype(jnp.uint32)
    if return_max_weight:
        return counts, jnp.max(weight)
    return counts

"""Radix partitioning primitives, TPU-style.

The reference's hot partitioning loops are per-tuple scattered writes made
cache-friendly with software write-combining buffers and AVX non-temporal
streams (``NetworkPartitioning.cpp:116-173,224-260``;
``LocalPartitioning.cpp:194-250``).  SWWC has no TPU analog — the idiomatic
equivalent (SURVEY.md §7.2) is *sort by partition id + offsets from a cumsum of
the histogram*: one vectorized, statically-shaped reorder instead of per-tuple
scatter.  These primitives are the shared core under both NetworkPartitioning
(partition-to-destination-node routing) and LocalPartitioning (second radix
pass), i.e. the TPU equivalents of the GPU ``histogram_build_L1/L2`` +
``reorder_L1/L2`` kernel families (operators/gpu/kernels.cu:19-185).
"""

from __future__ import annotations

import sys
from typing import Tuple

import jax
import jax.numpy as jnp

from tpu_radix_join.data.tuples import CompressedBatch, make_padding_like
from tpu_radix_join.observability import stages
from tpu_radix_join.ops.sorting import sort_kv_unstable
from tpu_radix_join.performance.measurements import PARTFALLBACK, PARTPASS


# ------------------------------------------------------------- impl selection
#
# Partition-impl auto-selection happens at TRACE time (these functions run
# inside jit/shard_map bodies, where no host counter can tick per
# execution), so the observability hook lives at module level: the engine
# registers its Measurements once (HashJoin.__init__) and every traced
# scatter/reorder site records which path it took — PARTPASS for the fused
# Pallas kernel, PARTFALLBACK when auto degrades to the XLA sort path.
_partition_observer: dict = {"meas": None}
_fallback_logged = False


def install_partition_observer(measurements) -> None:
    """Register a performance.Measurements (or None) to receive PARTPASS /
    PARTFALLBACK ticks from trace-time impl selection.  Process-global: the
    most recent engine wins, which is the engine whose programs are being
    traced."""
    _partition_observer["meas"] = measurements


def _partition_pass():
    """Tick PARTPASS for one fused partition op as it is traced, and
    return the ``trj.partition`` scope that names its device work."""
    m = _partition_observer["meas"]
    if m is not None:
        m.incr(PARTPASS)
    return jax.named_scope(stages.PARTITION)


def _note_fallback(site: str, num_partitions: int, why: str) -> None:
    """Auto-select degraded to the XLA sort path: tick the counter and log
    once per process instead of staying silent (a TPU run quietly paying
    the sort where the fused kernel was expected is a perf bug)."""
    global _fallback_logged
    m = _partition_observer["meas"]
    if m is not None:
        m.incr(PARTFALLBACK)
    if not _fallback_logged:
        _fallback_logged = True
        print(f"[radix] partition auto-select fell back to the XLA sort "
              f"path at {site} (num_partitions={num_partitions}: {why}); "
              f"further fallbacks tick PARTFALLBACK silently",
              file=sys.stderr)


def resolve_partition_impl(impl: str | None, num_partitions: int,
                           site: str) -> str:
    """Resolve a partition ``impl`` request to a concrete path.

    ``None``/"auto" prefers the fused Pallas kernel when the backend has
    one and the fanout fits its unrolled loop, else falls back to the
    sort-based path ("loop") with PARTFALLBACK visibility.  "sort" is an
    explicit alias for the default sort discipline; "loop"/"gather" name
    its two fill disciplines; "pallas"/"pallas_interpret" force the fused
    kernel (interpret = traced JAX ops, the tier-1 CPU parity path)."""
    from tpu_radix_join.ops.pallas.merge_scan import pallas_available
    from tpu_radix_join.ops.pallas.partition import MAX_PARTITIONS
    if impl in (None, "auto"):
        if not pallas_available():
            _note_fallback(site, num_partitions, "Pallas unavailable")
            return "loop"
        if num_partitions > MAX_PARTITIONS:
            _note_fallback(site, num_partitions,
                           f"> MAX_PARTITIONS {MAX_PARTITIONS}")
            return "loop"
        return "pallas"
    if impl == "sort":
        return "loop"
    return impl


@jax.named_scope(stages.PARTITION)
def local_histogram(pid: jnp.ndarray, num_partitions: int,
                    valid: jnp.ndarray | None = None,
                    impl: str | None = None) -> jnp.ndarray:
    """Count tuples per partition (LocalHistogram.cpp:44-47).

    ``pid`` uint32 [n]; returns uint32 [num_partitions].  ``valid`` masks out
    padding slots (the reference never needs this because MPI buffers are
    exactly sized; statically-shaped TPU blocks do).

    ``impl``: None = auto — the Pallas streaming histogram on TPU (one HBM
    pass, masked VPU reductions; 7.5-10 ms at 16M, round-2 chip) vs the XLA
    ``bincount`` scatter-add elsewhere (XLA serializes it on TPU: 154 ms at
    16M).  "xla" / "pallas" / "pallas_interpret" force a path.
    """
    from tpu_radix_join.ops.pallas.histogram import (MAX_PARTITIONS,
                                                     histogram_pallas)
    from tpu_radix_join.ops.pallas.merge_scan import pallas_available
    if impl is None:
        if (pallas_available()
                and num_partitions <= MAX_PARTITIONS):
            impl = "pallas"
        else:
            impl = "xla"
            _note_fallback("local_histogram", num_partitions,
                           f"> MAX_PARTITIONS {MAX_PARTITIONS}"
                           if pallas_available()
                           else "Pallas unavailable")
    weights = None if valid is None else valid.astype(jnp.uint32)
    if impl == "xla":
        # bincount stages two scalar () device_put eqns (weak-typed
        # bounds, ALIAS semantics — free on every backend); the jaxpr
        # transfer rule's byte threshold (analysis/jaxpr/rules_ir.py)
        # keeps them out of the audit while still catching bulk traffic
        hist = jnp.bincount(pid.astype(jnp.int32), weights=weights,
                            length=num_partitions)
        return hist.astype(jnp.uint32)
    return histogram_pallas(pid, weights, num_partitions=num_partitions,
                            interpret=(impl == "pallas_interpret"))


def exclusive_cumsum(hist: jnp.ndarray) -> jnp.ndarray:
    """Partition base offsets = exclusive prefix sum of the histogram
    (LocalPartitioning.cpp:165-192, minus the cacheline padding which has no
    meaning for a dense reorder)."""
    return jnp.concatenate([jnp.zeros((1,), hist.dtype), jnp.cumsum(hist)[:-1]])


@jax.named_scope(stages.PARTITION)
def reorder_by_partition(
    batch: CompressedBatch, pid: jnp.ndarray, num_partitions: int,
    valid: jnp.ndarray | None = None,
    impl: str | None = None,
) -> Tuple[CompressedBatch, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Reorder so each partition's tuples are contiguous (order *within* a
    partition is unspecified — every consumer re-sorts or is order-free).

    Returns (reordered batch, reordered pid, histogram, base offsets).  Invalid
    (padding) slots are routed to a virtual partition after all real ones so
    they land at the tail.

    ``impl`` (resolve_partition_impl): the fused Pallas kernel assigns every
    tuple its dense destination in two streaming passes over the ids and the
    lanes move in one unique-index scatter; the sort fallback is ``argsort``
    on the partition id — XLA lowers this to a parallel sort, the TPU
    replacement for the SWWC scatter loop (see module docstring) — with the
    histogram derived from ``searchsorted`` run bounds over the sorted keys
    (one fewer HBM pass than a separate ``local_histogram``).
    """
    sort_key = pid.astype(jnp.uint32)
    if valid is not None:
        sort_key = jnp.where(valid, sort_key, jnp.uint32(num_partitions))
    impl = resolve_partition_impl(impl, num_partitions, "reorder_by_partition")
    if impl in ("pallas", "pallas_interpret"):
        from tpu_radix_join.ops.pallas.partition import partition_slots_pallas
        with _partition_pass():
            # num_partitions + 1 dense groups: the virtual invalid partition
            # is a REAL group here so every tuple lands (a permutation), with
            # invalid rows contiguous at the tail exactly like the sort path
            slots, hist_x = partition_slots_pallas(
                sort_key, num_groups=num_partitions + 1, group_size=1,
                capacity=None, interpret=(impl == "pallas_interpret"))
        scatter = lambda x: (jnp.zeros_like(x) + x[0] * x.dtype.type(0)
                             ).at[slots].set(x, mode="drop")
        out = jax.tree.map(scatter, batch)
        hist = hist_x[:num_partitions]
        return out, scatter(pid), hist, exclusive_cumsum(hist)
    # kv-sort instead of argsort + gather: the payload lanes travel with
    # their key in one fused sort (a profiled 3x win over argsort+gather
    # on v5e — see scatter_to_blocks)
    leaves, treedef = jax.tree.flatten(batch)
    sorted_lanes = sort_kv_unstable(sort_key, *leaves, pid)
    key_s = sorted_lanes[0]
    out = jax.tree.unflatten(treedef, sorted_lanes[1:-1])
    # run bounds over the already-sorted keys replace the separate
    # local_histogram pass: bounds[p] = #keys < p, so adjacent differences
    # are exactly the per-partition counts with invalid rows (key ==
    # num_partitions) excluded — byte-identical to the bincount, one fewer
    # pass over the ids
    bounds = jnp.searchsorted(
        key_s,
        jnp.arange(num_partitions + 1, dtype=jnp.uint32)).astype(jnp.uint32)
    hist = bounds[1:] - bounds[:-1]
    return out, sorted_lanes[-1], hist, exclusive_cumsum(hist)


@jax.named_scope(stages.PARTITION)
def scatter_to_blocks(
    batch,
    dest: jnp.ndarray,
    num_blocks: int,
    capacity: int,
    side: str,
    valid: jnp.ndarray | None = None,
    impl: str | None = None,
):
    """Route tuples into ``num_blocks`` statically-sized blocks of ``capacity``
    slots, padding unused slots with the side's sentinel.

    This is the send half of the Window data plane: where the reference
    ``MPI_Put``s exactly-sized slices computed by OffsetMap
    (``Window.cpp:86-144``), XLA needs static shapes, so each destination gets
    a fixed-capacity block and a valid count (SURVEY.md §7.2).

    ``impl`` (resolve_partition_impl; None = auto):
      * "pallas" / "pallas_interpret": the fused histogram→scan→scatter
        kernel (ops/pallas/partition.py) — slot assignment in two streaming
        passes over the ids, then ONE unique-index scatter per lane; no sort.
      * "sort"/"loop"/"gather": sort by destination, then place each run;
        "loop" is a ``fori_loop`` of per-destination dynamic-slice copies
        (one contiguous DMA per destination), "gather" ONE vectorized row
        gather over the [num_blocks, capacity] grid
        (experiments/exp_block_scatter.py holds the on-chip measurements —
        the reference has the same obsession with this inner loop's
        discipline, NetworkPartitioning.cpp:224-260).

    Returns (blocks batch with arrays shaped [num_blocks * capacity],
    counts uint32 [num_blocks] — the *unclipped* per-destination demand, and
    overflow uint32 — how many tuples did not fit; 0 in correct runs, checked
    by Window.assert_all_tuples_written).
    """
    impl = resolve_partition_impl(impl, num_blocks, "scatter_to_blocks")
    if impl in ("pallas", "pallas_interpret"):
        blocks, counts, _, overflow = _scatter_blocks_fused(
            batch, dest, None, num_blocks, 1, capacity, side, valid, impl)
        return blocks, counts, overflow
    sort_key = dest.astype(jnp.uint32)
    if valid is not None:
        sort_key = jnp.where(valid, sort_key, jnp.uint32(num_blocks))

    # One key-value sort carries every lane along (no random gathers — a
    # profiled 3x win over argsort+gather on v5e), then each destination's
    # run is a *contiguous* slice of the sorted lanes.  Unstable: tuple
    # order within a destination block is free (the local probe re-sorts).
    lanes, treedef = jax.tree.flatten(batch)
    sorted_all = sort_kv_unstable(sort_key, *lanes)
    sorted_dest, sorted_lanes = sorted_all[0], sorted_all[1:]

    # Run boundaries via binary search over the sorted keys (num_blocks+1
    # queries) instead of a 16M-wide scatter-add histogram.
    bounds = jnp.searchsorted(
        sorted_dest, jnp.arange(num_blocks + 1, dtype=jnp.uint32)).astype(jnp.uint32)
    counts = bounds[1:] - bounds[:-1]
    starts = bounds[:-1]

    blocks, overflow = _fill_blocks(batch, lanes, treedef, sorted_lanes,
                                    starts, counts, num_blocks, capacity,
                                    side, impl)
    return blocks, counts, overflow


@jax.named_scope(stages.PARTITION)
def scatter_to_blocks_grouped(
    batch,
    dest: jnp.ndarray,
    sub: jnp.ndarray,
    num_blocks: int,
    num_sub: int,
    capacity: int,
    side: str,
    valid: jnp.ndarray | None = None,
    impl: str | None = None,
):
    """:func:`scatter_to_blocks` with a secondary ordering key: tuples within
    each destination block land sorted by ``sub`` (the partition id on the
    wire-codec path), and the per-(block, sub) occupancy comes back as an
    extra ``[num_blocks, num_sub]`` array.

    That pair — pid-sorted blocks + per-pid counts — is exactly what the
    packed exchange needs to drop the fanout bits from keys and reconstruct
    them positionally on receipt (data/tuples.pack_blocks).  ``sub`` may be
    ANY value in [0, num_sub) regardless of ``dest`` (skew spreading routes
    hot tuples to destinations that don't own their partition; the header
    records the truth).

    Returns ``(blocks, counts, group_counts, overflow)`` where ``counts`` is
    the unclipped per-destination demand (same contract as
    ``scatter_to_blocks``) and ``group_counts`` is uint32
    [num_blocks, num_sub], *clipped* to capacity so it sums to the tuples
    actually present in each block."""
    impl = resolve_partition_impl(impl, num_blocks * num_sub,
                                  "scatter_to_blocks_grouped")
    if impl in ("pallas", "pallas_interpret"):
        return _scatter_blocks_fused(batch, dest, sub, num_blocks, num_sub,
                                     capacity, side, valid, impl)
    comp = dest.astype(jnp.uint32) * jnp.uint32(num_sub) + sub.astype(
        jnp.uint32)
    sort_key = comp
    if valid is not None:
        sort_key = jnp.where(valid, sort_key,
                             jnp.uint32(num_blocks * num_sub))

    lanes, treedef = jax.tree.flatten(batch)
    sorted_all = sort_kv_unstable(sort_key, *lanes)
    sorted_comp, sorted_lanes = sorted_all[0], sorted_all[1:]

    group_bounds = jnp.searchsorted(
        sorted_comp,
        jnp.arange(num_blocks * num_sub + 1, dtype=jnp.uint32)
    ).astype(jnp.uint32)
    # destination run bounds are every num_sub-th group bound
    bounds = group_bounds[::num_sub]
    counts = bounds[1:] - bounds[:-1]
    starts = bounds[:-1]
    group_raw = (group_bounds[1:] - group_bounds[:-1]).reshape(
        num_blocks, num_sub)
    # clip to capacity the way the block fill does: the first ``capacity``
    # slots of each destination run survive, i.e. the lowest pids keep their
    # tuples and the clip eats the tail
    cum = jnp.minimum(jnp.cumsum(group_raw, axis=1),
                      jnp.uint32(capacity))
    group_counts = jnp.concatenate([cum[:, :1], cum[:, 1:] - cum[:, :-1]],
                                   axis=1)

    blocks, overflow = _fill_blocks(batch, lanes, treedef, sorted_lanes,
                                    starts, counts, num_blocks, capacity,
                                    side, impl)
    return blocks, counts, group_counts, overflow


def _fill_blocks(batch, lanes, treedef, sorted_lanes, starts, counts,
                 num_blocks, capacity, side, impl):
    """Shared block-fill core: place each destination's sorted run into its
    fixed-capacity block, pad the rest with the side sentinel."""
    pad_leaves = jax.tree.leaves(make_padding_like(batch, 1, side))
    col = jnp.arange(capacity, dtype=jnp.uint32)[None, :]
    col_ok = (col < jnp.minimum(counts, jnp.uint32(capacity))[:, None]
              ).reshape(-1)

    if impl == "gather":
        n = sorted_lanes[0].shape[0]
        idx = jnp.minimum((starts[:, None] + col).reshape(-1),
                          jnp.uint32(n - 1))
        masked = [
            jnp.where(col_ok, lane[idx], pad[0])
            for lane, pad in zip(sorted_lanes, pad_leaves)
        ]
    else:
        padded_lanes = [
            jnp.concatenate([lane, jnp.full((capacity,), pad[0], lane.dtype)])
            for lane, pad in zip(sorted_lanes, pad_leaves)
        ]

        def copy_block(d, outs):
            return tuple(
                jax.lax.dynamic_update_slice(
                    out,
                    jax.lax.dynamic_slice(lane, (starts[d],), (capacity,)),
                    (d * capacity,))
                for out, lane in zip(outs, padded_lanes)
            )

        # Derive the init buffers from the input lanes (not fresh zeros) so
        # their varying-manual-axes type matches inside shard_map bodies.
        init = tuple(
            jnp.zeros((num_blocks * capacity,), l.dtype) + l[0] * l.dtype.type(0)
            for l in lanes)
        outs = jax.lax.fori_loop(0, num_blocks, copy_block, init)
        # Mask slots past each destination's count back to the pad value
        # (covers both partial blocks and slice overread into the next run).
        masked = [
            jnp.where(col_ok, out, pad[0])
            for out, pad in zip(outs, pad_leaves)
        ]
    blocks = jax.tree.unflatten(treedef, masked)
    overflow = jnp.sum(
        jnp.maximum(counts, jnp.uint32(capacity)) - jnp.uint32(capacity))
    return blocks, overflow.astype(jnp.uint32)


def _scatter_blocks_fused(batch, dest, sub, num_blocks, num_sub, capacity,
                          side, valid, impl):
    """Fused block fill: the Pallas kernel assigns slots + exact histogram
    in two streaming passes over the (composite) ids, then each lane moves
    in ONE unique-index scatter (``mode="drop"`` discards the overflow/
    invalid sentinel rows).  Returns the 4-tuple shape of the grouped
    entry; the flat entry drops the group_counts member.

    Contract parity with the sort path: counts are the UNCLIPPED demand,
    group_counts the clip that keeps the lowest pids (the kernel drops
    exactly the tuples whose unclipped within-destination position passed
    capacity, i.e. the highest-pid tail), overflow the same
    sum(max(counts - capacity, 0)).  Within-block order is input order
    grouped by pid — sorted by ``sub`` as pack_blocks requires."""
    from tpu_radix_join.ops.pallas.partition import partition_slots_pallas
    key = dest.astype(jnp.uint32)
    if sub is not None:
        key = key * jnp.uint32(num_sub) + sub.astype(jnp.uint32)
    num_groups = num_blocks * num_sub
    if valid is not None:
        key = jnp.where(valid, key, jnp.uint32(num_groups))
    with _partition_pass():
        slots, ghist = partition_slots_pallas(
            key, num_groups=num_groups, group_size=num_sub,
            capacity=capacity, interpret=(impl == "pallas_interpret"))
    lanes, treedef = jax.tree.flatten(batch)
    pad_leaves = jax.tree.leaves(make_padding_like(batch, 1, side))
    # init buffers carry the pad value everywhere (dropped/overflow slots
    # stay sentinel-filled) and derive from the input lanes so their
    # varying-manual-axes type matches inside shard_map bodies
    masked = [
        (jnp.zeros((num_blocks * capacity,), lane.dtype)
         + lane[0] * lane.dtype.type(0) + pad[0]
         ).at[slots].set(lane, mode="drop")
        for lane, pad in zip(lanes, pad_leaves)
    ]
    blocks = jax.tree.unflatten(treedef, masked)
    group_raw = ghist.reshape(num_blocks, num_sub)
    counts = jnp.sum(group_raw, axis=1, dtype=jnp.uint32)
    cum = jnp.minimum(jnp.cumsum(group_raw, axis=1), jnp.uint32(capacity))
    group_counts = jnp.concatenate([cum[:, :1], cum[:, 1:] - cum[:, :-1]],
                                   axis=1)
    overflow = jnp.sum(
        jnp.maximum(counts, jnp.uint32(capacity)) - jnp.uint32(capacity))
    return blocks, counts, group_counts, overflow.astype(jnp.uint32)

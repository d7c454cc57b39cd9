"""Pallas TPU kernel: fused radix partition — histogram → scan → scatter.

The sort-based partitioning path (ops/radix.scatter_to_blocks) pays a full
``sort_kv_unstable`` over every lane to group tuples by destination, even
though the destination key has only ``fanout_bits`` of entropy and the
partition offsets are just an exclusive prefix scan of the per-tile
histograms (PAPERS.md: arXiv 2505.15112; the MPI_Scan-offload paper,
arXiv 1408.4939, is the same insight at the network layer).  This kernel
replaces the O(log^2 n)-stage sort with two streaming passes over the ids:

  * **pass 1** (grid phase 0): per-tile per-partition histograms,
    accumulated into one SMEM output block across sequential grid steps —
    no atomics, because TPU grid steps serialize on a core (the same
    freedom histogram.py exploits);
  * **carry** (first step of phase 1): the histogram is folded into
    per-partition write cursors in SMEM — the exclusive scan, a P-step
    scalar loop;
  * **pass 2** (grid phase 1): each tile is re-read and every tuple is
    assigned its final slot ``cursor[g] + rank_in_tile`` via masked
    VPU prefix sums; the cursors advance by the tile counts.

The kernel emits the per-tuple destination **slots** and the exact
histogram in one launch.  The physical lane movement is then a single
unique-index scatter per lane (``lane.at[slots].set``, radix.py) — each
lane crosses HBM exactly twice (read + scattered write) instead of riding
every stage of a bitonic sort.  Per-element scatter inside the kernel is
not expressible in Mosaic (no lane-granular dynamic stores), so the
slot/scatter split is the TPU-shaped factoring of the fused kernel: all
index arithmetic fused into two ids passes, data movement left to XLA's
scatter with indices known to be collision-free.

Like merge_scan.py, all in-kernel arithmetic is int32 (Mosaic does not
legalize unsigned reductions) and the in-tile prefix sums are
roll-and-mask Hillis-Steele scans on the Mosaic path; under
``interpret=True`` (tier-1 CPU parity and the host-CPU bench) the scans
use ``jnp.cumsum`` directly — byte-identical results, and the interpreted
kernel stays bandwidth-bound instead of paying the log-stage roll
emulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_radix_join.ops.pallas.merge_scan import _tile_cumsum, out_struct

#: max tile = ROWS x 128 uint32 = 128KB of VMEM per ref: the slot phase's
#: scan temporaries at 2048 rows overran the 16MB scoped-VMEM limit
ROWS = 256
LANES = 128
#: Per-group work is one masked prefix sum per tile; 256 covers the grouped
#: composite key (num_blocks * num_sub) at the default 8-node x 32-sub mesh.
#: The kernel takes one group more: reorder_by_partition adds the virtual
#: invalid partition as a real group.
MAX_PARTITIONS = 256


def smem_fill(ref, size: int, value: int) -> None:
    def body(g, c):
        ref[g] = jnp.int32(value)
        return c

    jax.lax.fori_loop(0, size, body, 0)


def mosaic_group_hist(ids: jnp.ndarray, hist_ref, num_groups: int) -> None:
    """Mosaic branch of the histogram phase: ``hist_ref[g] += #(ids == g)``.

    A ``fori_loop`` over the groups, not a Python unroll: an unrolled loop
    emits one masked reduction per group into the kernel body, and Mosaic's
    compile time grows with it (more than 5 minutes for 256 groups)."""
    def body(g, c):
        hit = (ids == g).astype(jnp.int32)
        # staged reduction (sublane, then lane) vectorizes on the VPU where
        # a flat jnp.sum lowers row-serially
        hist_ref[g] = hist_ref[g] + jnp.sum(jnp.sum(hit, axis=0))
        return c

    jax.lax.fori_loop(0, num_groups, body, 0)


def mosaic_group_slots(ids: jnp.ndarray, cur_ref, num_groups: int):
    """Mosaic branch of the slot phase: each tuple's slot is its group's
    cursor plus its rank among the tile's tuples of that group (a masked
    roll-and-mask prefix sum per group); the cursors advance by the tile's
    counts.  Rolled over groups for the same compile-time reason as
    :func:`mosaic_group_hist`."""
    def body(g, slots):
        hit = ids == g
        m = hit.astype(jnp.int32)
        incl = _tile_cumsum(m)
        cur = cur_ref[g]
        cur_ref[g] = cur + jnp.sum(jnp.sum(m, axis=0))
        return slots + jnp.where(hit, cur + (incl - m), 0)

    return jax.lax.fori_loop(0, num_groups, body,
                             jnp.zeros(ids.shape, jnp.int32))


def _kernel(ids_ref, slots_ref, hist_ref, cur_ref, *, num_groups: int,
            group_size: int, capacity: int | None, interpret: bool):
    """Grid (2, num_tiles): phase 0 = histogram, phase 1 = slot assignment."""
    ph = pl.program_id(0)
    t = pl.program_id(1)
    ids = ids_ref[:].astype(jnp.int32)      # invalid/pad ids == num_groups

    @pl.when(jnp.logical_and(ph == 0, t == 0))
    def _init_hist():
        if interpret:
            hist_ref[...] = jnp.zeros((num_groups,), jnp.int32)
        else:
            smem_fill(hist_ref, num_groups, 0)

    @pl.when(ph == 0)
    def _histogram():
        if interpret:
            # traced-JAX path: one scatter-add pass (fine on CPU; it is
            # only on TPU that XLA serializes bincount, and there the
            # Mosaic branch below runs instead)
            hist_ref[...] = hist_ref[...] + jnp.bincount(
                ids.reshape(-1), length=num_groups).astype(jnp.int32)
        else:
            mosaic_group_hist(ids, hist_ref, num_groups)
        # deterministic writeback for the not-yet-assigned slot block (it
        # is revisited and overwritten in phase 1)
        slots_ref[:] = jnp.zeros(ids.shape, jnp.uint32)

    @pl.when(jnp.logical_and(ph == 1, t == 0))
    def _init_cursors():
        # the exclusive scan of the histogram, folded straight into the
        # write cursors: dense mode chains globally; blocked mode restarts
        # at every destination (group_size consecutive groups share one
        # block) and offsets by the block base.  A num_groups-step scalar
        # SMEM loop — the "carry" between the two passes.
        def step(g, off):
            if capacity is None:
                cur_ref[g] = off
            else:
                off = jnp.where(g % group_size == 0, 0, off)
                cur_ref[g] = (g // group_size) * capacity + off
            return off + hist_ref[g]

        jax.lax.fori_loop(0, num_groups, step, jnp.int32(0))

    @pl.when(ph == 1)
    def _assign_slots():
        if interpret:
            # vectorized cumcount: one [tile, num_groups] one-hot prefix
            # sum ranks every group at once — a handful of wide traced ops
            # instead of num_groups masked scans (invalid ids match no
            # one-hot column, so they advance no cursor; their gathered
            # rank is garbage and masked below)
            flat = ids.reshape(-1)
            g = jnp.minimum(flat, num_groups - 1)
            onehot = (flat[:, None]
                      == jnp.arange(num_groups, dtype=jnp.int32)[None, :]
                      ).astype(jnp.int32)
            incl = jnp.cumsum(onehot, axis=0)
            rank = jnp.take_along_axis(incl, g[:, None], axis=1)[:, 0] - 1
            cur_vec = cur_ref[...]
            slots = (cur_vec[g] + rank).reshape(ids.shape)
            cur_ref[...] = cur_vec + incl[-1, :]
        else:
            slots = mosaic_group_slots(ids, cur_ref, num_groups)
        ok = ids < num_groups
        if capacity is not None:
            # a tuple whose *unclipped* within-destination position passed
            # capacity overflowed its block: drop it (counted by the exact
            # histogram; Window's overflow contract retries at 2x capacity)
            pos = slots - (ids // group_size) * capacity
            ok = jnp.logical_and(ok, pos < capacity)
        # -1 casts to 0xFFFFFFFF — out of range for every caller, so the
        # XLA-side .at[slots].set(..., mode="drop") discards these rows
        slots_ref[:] = jnp.where(ok, slots, jnp.int32(-1)).astype(jnp.uint32)


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "group_size", "capacity", "interpret"))
def partition_slots_pallas(ids: jnp.ndarray, *, num_groups: int,
                           group_size: int = 1,
                           capacity: int | None = None,
                           interpret: bool = False):
    """(slots uint32 [n], hist uint32 [num_groups]) for ``ids`` uint32 [n].

    ``slots[i]`` is tuple i's final position: with ``capacity=None`` a
    dense permutation target in [0, n) grouping equal ids contiguously in
    id order (input order within a group); with a capacity, a position in
    the ``[num_groups // group_size, capacity * group_size]``-shaped block
    layout where ``group_size`` consecutive ids share the block
    ``id // group_size`` and overflowing/invalid tuples get the
    0xFFFFFFFF sentinel (callers scatter with ``mode="drop"``).
    ``hist`` is the exact per-id count regardless of clipping.  Ids >=
    ``num_groups`` are counted nowhere and dropped — callers route invalid
    slots there, exactly as with histogram_pallas.
    """
    if num_groups > MAX_PARTITIONS + 1:
        raise ValueError(f"num_groups {num_groups} > {MAX_PARTITIONS + 1}")
    if num_groups % group_size:
        raise ValueError(f"num_groups {num_groups} not a multiple of "
                         f"group_size {group_size}")
    n = ids.shape[0]
    # shrink the tile for small inputs so tier-1-sized calls don't pay a
    # full 1MB pad (sublane counts must stay multiples of 8)
    rows = max(8, min(ROWS, ((n + LANES - 1) // LANES + 7) // 8 * 8))
    tile = rows * LANES
    pad = (-n) % tile
    if pad:
        ids = jnp.concatenate(
            [ids, jnp.full((pad,), num_groups, jnp.uint32)])
    num_tiles = (n + pad) // tile

    kernel = functools.partial(_kernel, num_groups=num_groups,
                               group_size=group_size, capacity=capacity,
                               interpret=interpret)
    slots, hist = pl.pallas_call(
        kernel,
        grid=(2, num_tiles),
        in_specs=[pl.BlockSpec((rows, LANES), lambda ph, t: (t, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec((rows, LANES), lambda ph, t: (t, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((num_groups,), lambda ph, t: (0,),
                                memory_space=pltpu.SMEM)],
        out_shape=(out_struct((num_tiles * rows, LANES), jnp.uint32, ids),
                   out_struct((num_groups,), jnp.int32, ids)),
        scratch_shapes=[pltpu.SMEM((num_groups,), jnp.int32)],
        interpret=interpret,
    )(ids.reshape(num_tiles * rows, LANES))
    return slots.reshape(-1)[:n], hist.astype(jnp.uint32)


"""Pallas TPU kernel: LSD radix sort as iterated partition passes.

PERF_NOTES' stage model pins single-chip throughput to ``lax.sort`` — the
floor under merge_count, the bucket build/probe, the verify xor-fold and
the grouped codec alike — and concludes a hand-written compare-exchange
network cannot beat it.  An LSD radix sort needs no compare network at
all: the fused histogram→carried-scan→scatter kernel of partition.py *is*
one digit pass, so sorting is iteration, not invention.  Each pass here

  * extracts an 8-bit digit from the key tile **in-kernel** (no
    materialized digit array crosses HBM),
  * accumulates per-tile SMEM histograms whose carry across sequential
    grid steps is the exclusive scan (partition.py's phase structure,
    generalizing the tiled-carry scan of PAPERS.md arXiv 2505.15112),
  * emits per-tuple slots, after which every lane moves with one
    collision-free ``.at[slots].set(..., mode="drop")`` scatter.

A pass groups equal digits contiguously **preserving input order within a
digit** (the partition kernel's documented dense-mode contract), so each
pass is stable and the least-significant-digit iteration is a correct
sort: 4 passes worst case for uint32, fewer whenever JHIST/WireSpec key
bounds prove the high digits constant (``data/tuples.effective_key_bits``
is the shared source of truth — a 16-bit-bounded key sorts in 2 passes).
64-bit keys ride split uint32 hi/lo lanes: the lo lane's passes run
first, then the hi lane's, chained by per-pass stability — exactly the
lexicographic ``num_keys=2`` contract of ``sort_lex_unstable``.

Like partition.py, in-kernel arithmetic is int32 except the uint32 digit
extraction (elementwise shifts legalize fine; it is unsigned *reductions*
Mosaic rejects), and ``interpret=True`` runs byte-identical traced-JAX
scans for CPU tier-1 parity and the host-mesh ``--sort-bench``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_radix_join.data.tuples import effective_key_bits
from tpu_radix_join.ops.pallas.merge_scan import out_struct
from tpu_radix_join.ops.pallas.partition import (mosaic_group_hist,
                                                 mosaic_group_slots,
                                                 smem_fill)

RADIX_BITS = 8
RADIX = 1 << RADIX_BITS      # == partition.MAX_PARTITIONS: the digit fanout
LANES = 128
#: the slot phase ranks against all 256 digit columns at once, so the
#: interpret-mode one-hot is [ROWS*128, 256] i32 — 32MB at 256 rows, which
#: keeps the host-CPU bench and tier-1 parity runs in cache-friendly
#: territory.  On the Mosaic path the tile is 128KB of VMEM per ref.
ROWS = 256


def num_radix_passes(key_bound: Optional[int] = None,
                     key_bits: int = 32) -> int:
    """Digit passes needed for keys < ``key_bound`` (None = full width).

    The pass-skip decision: passes the bound proves constant-zero are
    never launched.  ``ceil(effective_key_bits / 8)`` — 4 for full uint32,
    2 for a 16-bit bound, 1 for an 8-bit bound.
    """
    return -(-effective_key_bits(key_bound, 0, key_bits) // RADIX_BITS)


def _digit_kernel(keys_ref, slots_ref, hist_ref, cur_ref, *, shift: int,
                  n: int, interpret: bool):
    """Grid (2, num_tiles): phase 0 = digit histogram, phase 1 = slots.

    partition._kernel specialized to the sort pass: ``num_groups=RADIX``,
    dense mode (the slots are a permutation of [0, n)), ids produced
    in-kernel from the key tile instead of arriving precomputed, and pad
    rows invalidated by their flat position (every uint32 *key* value is
    valid, so there is no sentinel id to pad with).
    """
    ph = pl.program_id(0)
    t = pl.program_id(1)

    def digit_ids():
        # Read the key tile inside the phase bodies, not at the kernel's
        # top level: under shard_map the interpreter evaluates top-level
        # ops with the tile's varying mesh axes, and an op that mixes them
        # with a constant fails its varying-axes check.  Inside pl.when the
        # cond carries the axes through, as partition._kernel relies on.
        keys = keys_ref[:]
        rows, lanes = keys.shape
        # the 8-bit digit, extracted in uint32 (logical shift) then cast
        # for the int32 scan arithmetic below
        d = keys if shift == 0 else jnp.right_shift(keys, jnp.uint32(shift))
        d = (d & jnp.uint32(RADIX - 1)).astype(jnp.int32)
        # flat row-major position across the padded input: pad rows (>= n)
        # become the invalid id RADIX — counted nowhere, slot -1, dropped
        flat = (t * (rows * lanes)
                + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0) * lanes
                + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1))
        return jnp.where(flat < n, d, jnp.int32(RADIX))

    @pl.when(jnp.logical_and(ph == 0, t == 0))
    def _init_hist():
        if interpret:
            # one vector store: 256 unrolled scalar SMEM writes cost ~4s
            # of trace/lower time PER (shape, shift) jit entry on the
            # interpret path, which tier-1 pays for every pass
            hist_ref[...] = jnp.zeros((RADIX,), jnp.int32)
        else:
            smem_fill(hist_ref, RADIX, 0)

    @pl.when(ph == 0)
    def _histogram():
        ids = digit_ids()
        if interpret:
            hist_ref[...] = hist_ref[...] + jnp.bincount(
                ids.reshape(-1), length=RADIX).astype(jnp.int32)
        else:
            mosaic_group_hist(ids, hist_ref, RADIX)
        slots_ref[:] = jnp.zeros(ids.shape, jnp.uint32)

    @pl.when(jnp.logical_and(ph == 1, t == 0))
    def _init_cursors():
        # exclusive scan of the digit histogram -> write cursors: the
        # carry between the two passes.  Dense mode only, so the scan has
        # no per-block restart — on the interpret path it is one cumsum
        # (same trace-time economy as _init_hist); Mosaic keeps the
        # RADIX-step scalar SMEM loop partition.py uses
        if interpret:
            h = hist_ref[...]
            cur_ref[...] = jnp.cumsum(h) - h
        else:
            def step(g, off):
                cur_ref[g] = off
                return off + hist_ref[g]

            jax.lax.fori_loop(0, RADIX, step, jnp.int32(0))

    @pl.when(ph == 1)
    def _assign_slots():
        ids = digit_ids()
        if interpret:
            flat_ids = ids.reshape(-1)
            g = jnp.minimum(flat_ids, RADIX - 1)
            onehot = (flat_ids[:, None]
                      == jnp.arange(RADIX, dtype=jnp.int32)[None, :]
                      ).astype(jnp.int32)
            incl = jnp.cumsum(onehot, axis=0)
            rank = jnp.take_along_axis(incl, g[:, None], axis=1)[:, 0] - 1
            cur_vec = cur_ref[...]
            slots = (cur_vec[g] + rank).reshape(ids.shape)
            cur_ref[...] = cur_vec + incl[-1, :]
        else:
            slots = mosaic_group_slots(ids, cur_ref, RADIX)
        ok = ids < RADIX
        slots_ref[:] = jnp.where(ok, slots, jnp.int32(-1)).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("shift", "interpret"))
def radix_pass_slots_pallas(keys: jnp.ndarray, *, shift: int,
                            interpret: bool = False) -> jnp.ndarray:
    """Slots uint32 [n]: the stable grouping permutation of one digit pass.

    ``slots[i]`` is key i's destination when grouping by digit
    ``(keys >> shift) & 0xFF`` — a dense permutation of [0, n), digit
    order across groups, input order within a group.
    """
    if keys.dtype != jnp.uint32 or keys.ndim != 1:
        raise ValueError(
            f"radix pass wants a 1-D uint32 key lane, got "
            f"{keys.dtype} rank {keys.ndim}")
    n = keys.shape[0]
    rows = max(8, min(ROWS, ((n + LANES - 1) // LANES + 7) // 8 * 8))
    tile = rows * LANES
    pad = (-n) % tile
    if pad:
        # pad value is irrelevant: pad rows are invalidated by position
        keys = jnp.concatenate([keys, jnp.zeros((pad,), jnp.uint32)])
    num_tiles = (n + pad) // tile

    kernel = functools.partial(_digit_kernel, shift=shift, n=n,
                               interpret=interpret)
    slots, _ = pl.pallas_call(
        kernel,
        grid=(2, num_tiles),
        in_specs=[pl.BlockSpec((rows, LANES), lambda ph, t: (t, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec((rows, LANES), lambda ph, t: (t, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((RADIX,), lambda ph, t: (0,),
                                memory_space=pltpu.SMEM)],
        out_shape=(out_struct((num_tiles * rows, LANES), jnp.uint32, keys),
                   out_struct((RADIX,), jnp.int32, keys)),
        scratch_shapes=[pltpu.SMEM((RADIX,), jnp.int32)],
        interpret=interpret,
    )(keys.reshape(num_tiles * rows, LANES))
    return slots.reshape(-1)[:n]


def _apply_permutation(slots, arrs):
    # zeros_like + a[0]*0 inherits the vma under shard_map (same trick as
    # radix.reorder_by_partition); slots are collision-free by construction
    return [(jnp.zeros_like(a) + a[0] * a.dtype.type(0)
             ).at[slots].set(a, mode="drop") for a in arrs]


def radix_sort_pallas(operands: Sequence[jnp.ndarray], *, num_keys: int = 1,
                      key_bounds: Optional[Sequence[Optional[int]]] = None,
                      interpret: bool = False) -> Tuple[jnp.ndarray, ...]:
    """LSD radix sort of 1-D uint32 lanes; drop-in for ``lax.sort``.

    The first ``num_keys`` operands are lexicographic sort keys (most
    significant first — ``sort_lex_unstable``'s contract; split-lane
    64-bit keys pass (hi, lo) with ``num_keys=2``); the rest ride along as
    values.  ``key_bounds``, when given, holds one exclusive upper bound
    (or None) per key operand and shrinks that key's digit passes via
    ``num_radix_passes``.  Output order matches ``lax.sort`` exactly for
    any uint32 input — radix order *is* unsigned numeric order, sentinels
    (0xFFFFFFFE/0xFFFFFFFF pads) included.
    """
    arrs = [jnp.asarray(a) for a in operands]
    if not 1 <= num_keys <= len(arrs):
        raise ValueError(f"num_keys {num_keys} out of range for "
                         f"{len(arrs)} operands")
    first = arrs[0]
    for a in arrs:
        if a.ndim != 1 or a.shape != first.shape or a.dtype != jnp.uint32:
            raise ValueError(
                "radix sort wants equal-length 1-D uint32 lanes, got "
                f"{[(str(x.dtype), x.shape) for x in arrs]}")
    if key_bounds is not None and len(key_bounds) != num_keys:
        raise ValueError(f"key_bounds has {len(key_bounds)} entries for "
                         f"{num_keys} keys")
    n = first.shape[0]
    if n <= 1:
        return tuple(arrs)
    # least-significant key first; per-pass stability chains the passes
    # into a lexicographic sort across keys
    for ki in range(num_keys - 1, -1, -1):
        bound = None if key_bounds is None else key_bounds[ki]
        for p in range(num_radix_passes(bound)):
            slots = radix_pass_slots_pallas(
                arrs[ki], shift=RADIX_BITS * p, interpret=interpret)
            arrs = _apply_permutation(slots, arrs)
    return tuple(arrs)


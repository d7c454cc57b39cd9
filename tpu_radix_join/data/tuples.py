"""Tuple layout: the TPU-native data model.

Replaces the reference's POD types (``data/Tuple.h:19-20`` — ``{uint64 key;
uint64 rid}`` — and ``data/CompressedTuple.h:18`` — one packed ``uint64``).

TPU-first design: int64 arithmetic is slow/limited on TPU, so tuples are
structure-of-arrays batches of uint32 lanes instead of packed scalars:

  * ``TupleBatch``      — full tuples: ``key`` (low 32 key bits), optional
    ``key_hi`` (upper 32 bits when ``key_bits == 64``), ``rid``.
  * ``CompressedBatch`` — the shuffle wire format.  The reference compresses
    16B -> 8B by dropping the partition bits from the key and packing
    ``value = rid | (key >> FANOUT) << (FANOUT + PAYLOAD_BITS)``
    (``NetworkPartitioning.cpp:128-129``).  We keep the same information
    contract — the partition bits are implied by partition membership and
    reconstructed on unpack — as uint32 lanes: 2 lanes (8B/tuple) for 32-bit
    keys, matching the reference's 8B CompressedTuple on the wire.

Padding sentinels: statically-shaped shuffle blocks carry invalid slots.  A
slot is invalid iff its key lane(s) equal the side's sentinel; inner (R) and
outer (S) sentinels differ so padding can never produce a match.  Real keys
must therefore stay below ``0xFFFFFFFE`` in the top lane (enforced by the
generators in ``relation.py``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel key values for padded (invalid) slots, per relation side.
R_PAD_KEY = np.uint32(0xFFFFFFFE)   # inner/build side
S_PAD_KEY = np.uint32(0xFFFFFFFF)   # outer/probe side
PAD_RID = np.uint32(0xFFFFFFFF)


class TupleBatch(NamedTuple):
    """SoA batch of full tuples (analog of ``Tuple[]``, data/Tuple.h)."""

    key: jnp.ndarray                 # uint32 [n] — low 32 key bits
    rid: jnp.ndarray                 # uint32 [n]
    key_hi: Optional[jnp.ndarray] = None   # uint32 [n] when key_bits == 64

    @property
    def size(self) -> int:
        return self.key.shape[-1]


class CompressedBatch(NamedTuple):
    """SoA batch of compressed tuples (analog of ``CompressedTuple[]``).

    ``key_rem`` holds ``key >> network_fanout_bits`` (the surviving key bits,
    BuildProbe.cpp:98-106 compares exactly these); ``key_rem_hi`` the upper
    lane for 64-bit keys.
    """

    key_rem: jnp.ndarray             # uint32 [n]
    rid: jnp.ndarray                 # uint32 [n]
    key_rem_hi: Optional[jnp.ndarray] = None

    @property
    def size(self) -> int:
        return self.key_rem.shape[-1]


def partition_ids(batch: TupleBatch, fanout_bits: int) -> jnp.ndarray:
    """Radix partition id = low ``fanout_bits`` of the key.

    The reference's ``HASH_BIT_MODULO(key, mask, 0)`` (LocalHistogram.cpp:20,
    44-47).  Returns uint32 [n] in [0, 1 << fanout_bits).
    """
    mask = jnp.uint32((1 << fanout_bits) - 1)
    return batch.key & mask


def compress(batch: TupleBatch, fanout_bits: int) -> CompressedBatch:
    """Drop the partition bits from the key (NetworkPartitioning.cpp:128-129).

    The dropped bits are implied by which partition the tuple is routed to and
    are restored by :func:`decompress`.
    """
    f = jnp.uint32(fanout_bits)
    if batch.key_hi is None:
        return CompressedBatch(key_rem=batch.key >> f, rid=batch.rid)
    if fanout_bits == 0:
        return CompressedBatch(batch.key, batch.rid, batch.key_hi)
    lo = (batch.key >> f) | (batch.key_hi << jnp.uint32(32 - fanout_bits))
    hi = batch.key_hi >> f
    return CompressedBatch(key_rem=lo, rid=batch.rid, key_rem_hi=hi)


def decompress(comp: CompressedBatch, pid: jnp.ndarray, fanout_bits: int) -> TupleBatch:
    """Reconstruct full keys from remainder + partition id (inverse of compress)."""
    f = jnp.uint32(fanout_bits)
    if comp.key_rem_hi is None:
        return TupleBatch(key=(comp.key_rem << f) | pid.astype(jnp.uint32), rid=comp.rid)
    if fanout_bits == 0:
        return TupleBatch(comp.key_rem, comp.rid, comp.key_rem_hi)
    lo = (comp.key_rem << f) | pid.astype(jnp.uint32)
    hi = (comp.key_rem_hi << f) | (comp.key_rem >> jnp.uint32(32 - fanout_bits))
    return TupleBatch(key=lo, rid=comp.rid, key_hi=hi)


def probe_key(comp: CompressedBatch) -> jnp.ndarray:
    """The key material compared during probe (``value >> keyShift``,
    BuildProbe.cpp:98-106).  For 64-bit keys returns a [n, 2] (hi, lo) stack
    ordered so lexicographic comparison equals numeric comparison."""
    if comp.key_rem_hi is None:
        return comp.key_rem
    return jnp.stack([comp.key_rem_hi, comp.key_rem], axis=-1)


def pad_sentinel(side: str) -> np.uint32:
    if side == "inner":
        return R_PAD_KEY
    if side == "outer":
        return S_PAD_KEY
    raise ValueError(f"side must be 'inner' or 'outer', got {side!r}")


# TupleBatch and CompressedBatch share a positional layout:
# field 0 = primary key lane, field 1 = rid, field 2 = optional high key lane.
def _sentinel_lane(batch) -> jnp.ndarray:
    return batch[2] if batch[2] is not None else batch[0]


def valid_mask(batch, side: str) -> jnp.ndarray:
    """True for real tuples, False for padding slots (either batch type)."""
    return _sentinel_lane(batch) != pad_sentinel(side)


def make_padding_like(batch, n: int, side: str):
    """A block of n invalid tuples with the same structure as ``batch``."""
    sent = jnp.full((n,), pad_sentinel(side), dtype=jnp.uint32)
    rid = jnp.full((n,), PAD_RID, dtype=jnp.uint32)
    hi = sent if batch[2] is not None else None
    return type(batch)(sent, rid, hi)


def make_padding(n: int, side: str, wide: bool = False) -> CompressedBatch:
    """A block of n invalid compressed tuples."""
    sent = jnp.full((n,), pad_sentinel(side), dtype=jnp.uint32)
    rid = jnp.full((n,), PAD_RID, dtype=jnp.uint32)
    if wide:
        return CompressedBatch(key_rem=sent, rid=rid, key_rem_hi=sent)
    return CompressedBatch(key_rem=sent, rid=rid)


# --------------------------------------------------------------------- wire
# Bounds-aware bit-packed wire format for the shuffle exchange.
#
# After radix partitioning a tuple's low ``fanout_bits`` are implied by its
# partition id, and the sizing pre-pass knows tight key/rid bounds — so most
# shuffles can ship far less than the 8 B/tuple the 2-lane CompressedBatch
# costs (NetworkPartitioning.cpp:128-129 plays the same trick with a fixed
# 64-bit budget; here the budget itself shrinks to the measured bounds).
#
# Block layout (uint32 words), one block per (sender, destination) pair:
#
#   [ header: 2**fanout_bits words — per-partition valid counts ]
#   [ payload: ceil(capacity * tuple_bits / 32) + 1 words        ]
#
# Payload is a dense little-endian bitstream: slot ``s`` occupies bits
# ``[s*T, (s+1)*T)`` with ``T = key_rem_bits + rid_bits``; ``key_rem``
# (the key with fanout bits dropped) sits at offset 0 and ``rid`` at offset
# ``key_rem_bits``.  Senders sort each block by partition id, so the header
# counts let the receiver reconstruct every slot's pid positionally — which
# both restores the dropped key bits exactly and replaces the separate
# valid-count collective (the header IS the count side channel).  Slots at or
# past a block's total count unpack to the side's exact pad sentinels, so
# validity stays decidable from the packed words alone.


class WireSpec(NamedTuple):
    """Static geometry of the packed exchange (host-side, per program)."""

    fanout_bits: int        # radix bits dropped from keys (pid width)
    num_sub: int            # 2**fanout_bits — header words per block
    capacity: int           # tuple slots per block
    wide: bool              # 64-bit keys (key_hi lane present)
    key_rem_bits: int       # bits kept per key after dropping fanout bits
    rid_bits: int           # bits per rid
    tuple_bits: int         # key_rem_bits + rid_bits
    header_words: int       # == num_sub
    payload_words: int      # bitstream words incl. the spill-guard word
    block_words: int        # header_words + payload_words

    @property
    def bytes_per_block(self) -> int:
        return 4 * self.block_words

    @property
    def bytes_per_tuple(self) -> float:
        """Wire bytes per tuple slot (header amortized over the block)."""
        return self.bytes_per_block / self.capacity


def effective_key_bits(key_bound: Optional[int], fanout_bits: int = 0,
                       key_bits: int = 32) -> int:
    """Bits a key can actually occupy given its (exclusive) upper bound.

    ``key_bound`` is exclusive (keys < key_bound); ``None`` means the full
    lane width.  ``fanout_bits`` are the partition-selector bits already
    dropped by the caller (the wire codec shifts them out before packing).
    This is the single source of truth for every bounds-aware width
    decision: the packed exchange codec (``make_wire_spec``) sizes its
    field widths from it.
    """
    if not 0 <= fanout_bits < key_bits:
        raise ValueError(
            f"fanout_bits must be in [0, {key_bits}), got {fanout_bits}")
    if key_bound is None:
        return key_bits - fanout_bits
    if key_bound < 1:
        raise ValueError(f"key_bound must be >= 1, got {key_bound}")
    kb = max(1, ((int(key_bound) - 1) >> fanout_bits).bit_length())
    return min(kb, key_bits - fanout_bits)


def make_wire_spec(capacity: int, fanout_bits: int, wide: bool = False,
                   key_bound: Optional[int] = None,
                   rid_bound: Optional[int] = None) -> WireSpec:
    """Derive the packed-block geometry from the (static) bounds.

    ``key_bound``/``rid_bound`` are exclusive upper bounds (keys < key_bound).
    ``None`` falls back to the full lane width — still a win for 32-bit keys
    (the fanout bits drop) and always exact."""
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    key_bits = 64 if wide else 32
    kb = effective_key_bits(key_bound, fanout_bits, key_bits)
    if rid_bound is None:
        rb = 32
    else:
        if rid_bound < 1:
            raise ValueError(f"rid_bound must be >= 1, got {rid_bound}")
        rb = min(32, max(1, (int(rid_bound) - 1).bit_length()))
    t = kb + rb
    num_sub = 1 << fanout_bits
    # +1 spill-guard word: the last slot's high field may cross into one
    # word past ceil(capacity*T/32) during the shifted scatter-OR
    payload = (capacity * t + 31) // 32 + 1
    return WireSpec(fanout_bits=fanout_bits, num_sub=num_sub,
                    capacity=capacity, wide=wide, key_rem_bits=kb,
                    rid_bits=rb, tuple_bits=t, header_words=num_sub,
                    payload_words=payload,
                    block_words=num_sub + payload)


def _width_mask(width: int) -> jnp.ndarray:
    return jnp.uint32(0xFFFFFFFF if width >= 32 else (1 << width) - 1)


def _wire_fields(spec: WireSpec):
    """(offset_in_tuple, width, lane) triples; lane 0 = key_rem low 32 bits,
    lane 1 = key_rem high bits (wide only), lane 2 = rid.  Every field is
    <= 32 bits so it packs as one shifted uint32 (+ spill into the next
    word)."""
    kb = spec.key_rem_bits
    fields = []
    if kb <= 32:
        fields.append((0, kb, 0))
    else:
        fields.append((0, 32, 0))
        fields.append((32, kb - 32, 1))
    fields.append((kb, spec.rid_bits, 2))
    return fields


def pack_blocks(spec: WireSpec, blocks, group_counts: jnp.ndarray
                ) -> jnp.ndarray:
    """Pack scattered blocks into the wire bitstream.

    ``blocks``: TupleBatch with [num_blocks * capacity] lanes, each block's
    valid tuples contiguous at the front and sorted by partition id (the
    ``scatter_to_blocks_grouped`` contract).  ``group_counts``: uint32
    [num_blocks, 2**fanout_bits] clipped per-(block, pid) counts.  Returns
    uint32 [num_blocks * spec.block_words]."""
    nb = group_counts.shape[0]
    cap = spec.capacity
    f = spec.fanout_bits
    counts = jnp.sum(group_counts.astype(jnp.uint32), axis=1)      # [nb]
    slot = jnp.arange(nb * cap, dtype=jnp.uint32)
    blk = slot // jnp.uint32(cap)
    s_in_blk = slot % jnp.uint32(cap)
    ok = s_in_blk < counts[blk]

    key = blocks.key
    if spec.wide:
        if f:
            lo = (key >> jnp.uint32(f)) | (blocks.key_hi
                                           << jnp.uint32(32 - f))
            hi = blocks.key_hi >> jnp.uint32(f)
        else:
            lo, hi = key, blocks.key_hi
    else:
        lo = key >> jnp.uint32(f) if f else key
        hi = jnp.zeros_like(key)
    lanes = (lo, hi, blocks.rid)

    # init derived from an input lane so the varying-manual-axes type
    # matches inside shard_map bodies (same trick as scatter_to_blocks)
    words = (jnp.zeros((nb * spec.block_words,), jnp.uint32)
             + (key[0] & jnp.uint32(0)))
    # header region: the per-(block, pid) counts
    hidx = (jnp.arange(nb, dtype=jnp.uint32)[:, None]
            * jnp.uint32(spec.block_words)
            + jnp.arange(spec.num_sub, dtype=jnp.uint32)[None, :]).reshape(-1)
    words = words.at[hidx].add(group_counts.astype(jnp.uint32).reshape(-1),
                               mode="drop")
    base = blk * jnp.uint32(spec.block_words) + jnp.uint32(spec.header_words)
    for off, width, lane_i in _wire_fields(spec):
        v = jnp.where(ok, lanes[lane_i] & _width_mask(width), jnp.uint32(0))
        bitpos = s_in_blk * jnp.uint32(spec.tuple_bits) + jnp.uint32(off)
        widx = base + bitpos // jnp.uint32(32)
        boff = bitpos % jnp.uint32(32)
        # disjoint bit ranges make scatter-add equivalent to scatter-OR
        words = words.at[widx].add(v << boff, mode="drop")
        spill = jnp.where(boff == 0, jnp.uint32(0),
                          v >> ((jnp.uint32(32) - boff) & jnp.uint32(31)))
        words = words.at[widx + 1].add(spill, mode="drop")
    return words


def unpack_blocks(spec: WireSpec, words: jnp.ndarray, side: str):
    """Exact inverse of :func:`pack_blocks` on received wire words.

    Returns ``(TupleBatch with [num_blocks * capacity] lanes, counts uint32
    [num_blocks])``.  Valid slots reproduce the packed tuples bit-exactly
    (partition ids reconstructed positionally from the header counts); slots
    at or past each block's count are the side's exact pad sentinels."""
    if words.shape[0] % spec.block_words:
        raise ValueError(
            f"wire buffer of {words.shape[0]} words is not a multiple of "
            f"block_words={spec.block_words}")
    nb = words.shape[0] // spec.block_words
    cap = spec.capacity
    f = spec.fanout_bits
    hidx = (jnp.arange(nb, dtype=jnp.uint32)[:, None]
            * jnp.uint32(spec.block_words)
            + jnp.arange(spec.num_sub, dtype=jnp.uint32)[None, :])
    group_counts = words[hidx]                                   # [nb, P]
    counts = jnp.sum(group_counts, axis=1)                       # [nb]
    # positional pid: slot s of block b belongs to the first partition whose
    # within-block cumulative count exceeds s (blocks are pid-sorted)
    cum = jnp.cumsum(group_counts, axis=1)
    slot_in_blk = jnp.arange(cap, dtype=jnp.uint32)
    pid = jax.vmap(
        lambda c: jnp.searchsorted(c, slot_in_blk, side="right"))(cum)
    pid = jnp.minimum(pid, spec.num_sub - 1).astype(jnp.uint32).reshape(-1)

    slot = jnp.arange(nb * cap, dtype=jnp.uint32)
    blk = slot // jnp.uint32(cap)
    s_in_blk = slot % jnp.uint32(cap)
    ok = s_in_blk < counts[blk]
    base = blk * jnp.uint32(spec.block_words) + jnp.uint32(spec.header_words)
    nwords = jnp.uint32(words.shape[0] - 1)
    lanes = [None, None, None]
    for off, width, lane_i in _wire_fields(spec):
        bitpos = s_in_blk * jnp.uint32(spec.tuple_bits) + jnp.uint32(off)
        widx = base + bitpos // jnp.uint32(32)
        boff = bitpos % jnp.uint32(32)
        lo = words[widx] >> boff
        hi_w = words[jnp.minimum(widx + 1, nwords)]
        hi = jnp.where(boff == 0, jnp.uint32(0),
                       hi_w << ((jnp.uint32(32) - boff) & jnp.uint32(31)))
        lanes[lane_i] = (lo | hi) & _width_mask(width)
    lo = lanes[0] if lanes[0] is not None else jnp.zeros_like(slot)
    hi = lanes[1] if lanes[1] is not None else jnp.zeros_like(slot)
    rid = lanes[2]

    sent = pad_sentinel(side)
    if spec.wide:
        if f:
            key = (lo << jnp.uint32(f)) | pid
            key_hi = (hi << jnp.uint32(f)) | (lo >> jnp.uint32(32 - f))
        else:
            key, key_hi = lo, hi
        key = jnp.where(ok, key, sent)
        key_hi = jnp.where(ok, key_hi, sent)
    else:
        key = (lo << jnp.uint32(f)) | pid if f else lo
        key = jnp.where(ok, key, sent)
        key_hi = None
    rid = jnp.where(ok, rid, PAD_RID)
    return TupleBatch(key=key, rid=rid, key_hi=key_hi), counts

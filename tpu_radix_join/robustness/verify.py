"""End-to-end data-integrity verification: per-partition checksums.

The pipeline moves every tuple through a redistribution step (histogram ->
window allocation -> all_to_all exchange -> local partition/sort) whose
correctness was previously observable only through the final match count; a
bit-flip in flight — the TPU analogue of a corrupted RMA Put — would either
surface as an inscrutable wrong answer or vanish entirely.  This module
gives every network partition an order-independent fingerprint:

  * **count**  — tuples per partition (the conservation invariant the
    engine already tracks in aggregate, here per partition);
  * **sum**    — wraparound uint32 sum of the key lane (order-independent
    mod 2**32; catches value changes);
  * **xor**    — xor-fold of the key lane (ops/sorting.segmented_xor_fold;
    catches paired/bit-level changes that cancel in addition).

Wide (64-bit) keys add sum/xor rows for the hi lane.  The fingerprints are
computed over the pristine inputs *before* the exchange and re-derived from
the pipeline *after* the exchange (and after the local radix pass on the
bucket path); any partition whose rows disagree is **damaged**.  A
mismatch raises the ``data_corruption`` failure class (robustness/retry.py)
— or, under ``verify="repair"``, triggers partition-granular recompute in
the engine (operators/hash_join.py).

Everything here is traced-code-safe (pure jnp/lax) so the post-exchange
checksums ride inside the engine's shard_map programs as extra outputs;
the cross-device combine uses psum for count/sum and per-bit parity psum
for xor (global xor == per-bit popcount parity — no scatter-xor or
all_gather+reduce needed, and it composes with hierarchical meshes).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_radix_join.observability import stages
from tpu_radix_join.ops.sorting import segmented_xor_fold
from tpu_radix_join.robustness.retry import DATA_CORRUPTION


class DataCorruption(ValueError):
    """A per-partition integrity checksum disagreed across pipeline stages
    (or a key lane reached the reserved sentinel range — the streamed-lane
    corruption signature, ops/chunked.py).  Carries the machine-readable
    failure class, like CheckpointMismatch does."""

    failure_class = DATA_CORRUPTION

    def __init__(self, message: str, partitions=()):
        super().__init__(message)
        self.partitions = tuple(int(p) for p in partitions)


def checksum_rows(wide: bool) -> int:
    """Rows per relation fingerprint: count + (sum, xor) per key lane."""
    return 5 if wide else 3


@jax.named_scope(stages.CHECKS)
def device_partition_checksums(
    key: jnp.ndarray,
    pid: jnp.ndarray,
    num_partitions: int,
    valid: Optional[jnp.ndarray] = None,
    key_hi: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """This device's per-partition fingerprint halves.

    Returns ``(adds, xors)``: ``adds`` is ``[1 + lanes, P]`` uint32 (count
    row then per-lane wraparound sums — psum-combinable), ``xors`` is
    ``[lanes, P]`` uint32 (per-lane xor-folds — parity-combinable).
    Invalid lanes are routed to a discard bucket, so capacity-padded
    receive buffers fingerprint only their real tuples.
    """
    p = pid.astype(jnp.uint32)
    if valid is not None:
        p = jnp.where(valid, p, jnp.uint32(num_partitions))
    ones = jnp.ones_like(p)
    lanes = [key] if key_hi is None else [key, key_hi]

    def scatter_add(contrib):
        return jnp.zeros((num_partitions + 1,), jnp.uint32).at[p].add(
            contrib, mode="drop")[:num_partitions]

    adds = jnp.stack([scatter_add(ones)]
                     + [scatter_add(lane.astype(jnp.uint32))
                        for lane in lanes])
    xors = jnp.stack([segmented_xor_fold(p, lane, num_partitions)
                      for lane in lanes])
    return adds, xors


@jax.named_scope(stages.CHECKS)
def global_partition_checksums(
    key: jnp.ndarray,
    pid: jnp.ndarray,
    num_partitions: int,
    axis,
    valid: Optional[jnp.ndarray] = None,
    key_hi: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Mesh-global ``[rows, P]`` fingerprint (traced inside shard_map).

    count/sum rows combine by psum; xor rows by per-bit parity psum
    (``XOR over devices == popcount mod 2`` per bit — psum keeps this
    compatible with tuple axis names on hierarchical meshes, where
    all_gather+reduce would not compose as directly).
    """
    adds, xors = device_partition_checksums(key, pid, num_partitions,
                                            valid=valid, key_hi=key_hi)
    g_adds = jax.lax.psum(adds, axis)
    bits = jnp.arange(32, dtype=jnp.uint32)
    parity = jax.lax.psum((xors[..., None] >> bits) & jnp.uint32(1),
                          axis) & jnp.uint32(1)
    g_xors = jnp.sum(parity << bits, axis=-1).astype(jnp.uint32)
    return jnp.concatenate([g_adds, g_xors], axis=0)


def damaged_partitions(pre: np.ndarray, post: np.ndarray) -> np.ndarray:
    """Host-side compare of two ``[rows, P]`` fingerprints: the sorted
    partition ids whose rows disagree (empty == intact)."""
    pre = np.asarray(pre)
    post = np.asarray(post)
    if pre.shape != post.shape:
        raise ValueError(
            f"checksum shape mismatch: {pre.shape} vs {post.shape}")
    return np.nonzero((pre != post).any(axis=0))[0]


def cross_check_counts(partition_counts: np.ndarray, matches: int,
                       r_counts: np.ndarray,
                       s_counts: np.ndarray) -> Optional[str]:
    """Join-level invariants over the reported per-partition counts:
    their uint64 sum must equal the reported total, and no partition may
    report more matches than ``|R_p| * |S_p|`` (its cross-product bound).

    ``partition_counts`` is the host counts array reshaped ``[devices, P]``
    (per-device per-partition); ``r_counts``/``s_counts`` are the count
    rows of the global pre-exchange fingerprints.  Returns a human-readable
    violation description, or None when the invariants hold.
    """
    counts = np.asarray(partition_counts, dtype=np.uint64)
    total = int(counts.sum())
    if total != int(matches):
        return (f"sum of per-partition matches {total} != reported total "
                f"{int(matches)}")
    per_part = counts.sum(axis=0)
    bound = (np.asarray(r_counts, dtype=np.uint64)
             * np.asarray(s_counts, dtype=np.uint64))
    over = np.nonzero(per_part > bound)[0]
    if over.size:
        p = int(over[0])
        return (f"partition {p} reports {int(per_part[p])} matches, above "
                f"its |R_p|*|S_p| bound {int(bound[p])}")
    return None

"""Network partitioning: route every tuple to its partition's owner node.

Replaces ``tasks/NetworkPartitioning.{h,cpp}`` — the all-to-all shuffle
producer.  The reference's per-tuple hot loop (hash, compress, SWWC cacheline
append, AVX stream, 64KB ``MPI_Put`` with double buffering,
NetworkPartitioning.cpp:116-173) becomes three vectorized steps:

  1. partition id per tuple (radix bits, LocalHistogram.cpp:20);
  2. destination node per tuple via the AssignmentMap
     (``window->write``'s target resolution, Window.cpp:110);
  3. one dense block scatter + ``all_to_all`` (parallel/window.py).

Wire format parity: the reference ships 8B CompressedTuples; with 32-bit keys
our two uint32 lanes (full key + rid) are the same 8B/tuple, and keeping the
full key lets the receiver recompute partition ids instead of shipping them
(compression to key remainders happens at the probe boundary instead —
tuples.compress).  Communication/computation overlap (SURVEY.md §2.3 item 6)
is XLA's job: the scatter and the collective are in one program and XLA/ICI
pipeline them.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from tpu_radix_join.data.tuples import TupleBatch, partition_ids, valid_mask
from tpu_radix_join.observability import stages
from tpu_radix_join.parallel.window import Window, ExchangeResult


class NetworkPartitionResult(NamedTuple):
    batch: TupleBatch        # received tuples, [N * C] lanes, sentinel-padded
    valid: jnp.ndarray       # bool [N * C]
    pid: jnp.ndarray         # uint32 [N * C] — recomputed partition ids
    recv_counts: jnp.ndarray # uint32 [N]
    send_overflow: jnp.ndarray


@jax.named_scope(stages.PARTITION)
def network_partition(
    batch: TupleBatch,
    fanout_bits: int,
    assignment: jnp.ndarray,
    window: Window,
    valid: jnp.ndarray | None = None,
    exclude: jnp.ndarray | None = None,
    override: Tuple[jnp.ndarray, jnp.ndarray] | None = None,
) -> NetworkPartitionResult:
    """Runs inside shard_map over the mesh axis.

    ``exclude``: bool [n] — tuples withheld from the shuffle (the skew split
    pulls hot inner tuples out for replication instead, operators/skew.py).
    ``override``: (mask, dest) — tuples whose destination ignores the
    assignment map (hot outer tuples spread round-robin).
    """
    pid = partition_ids(batch, fanout_bits)
    dest = assignment[pid]
    if override is not None:
        dest = jnp.where(override[0], override[1], dest)
    if exclude is not None:
        valid = ~exclude if valid is None else (valid & ~exclude)
    # pid rides along for the packed wire codec: the bit-packed format drops
    # the fanout bits and the receiver restores them from the header's
    # per-partition counts (a no-op for codec="off" windows)
    res: ExchangeResult = window.exchange(batch, dest, valid=valid, pid=pid)
    recv_valid = valid_mask(res.batch, window.side)
    recv_pid = partition_ids(res.batch, fanout_bits)
    return NetworkPartitionResult(
        batch=res.batch, valid=recv_valid, pid=recv_pid,
        recv_counts=res.recv_counts, send_overflow=res.send_overflow,
    )


def receive_checksums(res: NetworkPartitionResult, num_partitions: int,
                      axis) -> jnp.ndarray:
    """Mesh-global ``[rows, P]`` integrity fingerprint of what the exchange
    delivered (robustness/verify.py), traced inside the same shard_map as
    the exchange itself.  Compared on the host against the pre-exchange
    fingerprint of what was sent: equal rows == the shuffle conserved every
    tuple and every key bit."""
    from tpu_radix_join.robustness import verify as _verify
    return _verify.global_partition_checksums(
        res.batch.key, res.pid, num_partitions, axis,
        valid=res.valid, key_hi=res.batch.key_hi)

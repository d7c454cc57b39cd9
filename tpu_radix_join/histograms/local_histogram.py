"""Per-shard partition histogram.

Replaces ``histograms/LocalHistogram.{h,cpp}``: one pass over the shard
counting tuples per network partition, radix = low
``NETWORK_PARTITIONING_FANOUT`` key bits (LocalHistogram.cpp:20,44-47).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_radix_join.data.tuples import TupleBatch, partition_ids
from tpu_radix_join.observability import stages
from tpu_radix_join.ops.radix import local_histogram


@jax.named_scope(stages.PARTITION)
def compute_local_histogram(batch: TupleBatch, fanout_bits: int,
                            valid: jnp.ndarray | None = None):
    """Returns (pid uint32 [n], histogram uint32 [1 << fanout_bits])."""
    pid = partition_ids(batch, fanout_bits)
    return pid, local_histogram(pid, 1 << fanout_bits, valid)

"""Global partition histogram.

Replaces ``histograms/GlobalHistogram.{h,cpp}``: the reference sums local
histograms with ``MPI_Allreduce(UINT64, SUM)`` (GlobalHistogram.cpp:37-42);
on a TPU mesh this is ``jax.lax.psum`` over the nodes axis — one ICI
all-reduce, called from inside the shard_map'd pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_radix_join.observability import stages


@jax.named_scope(stages.PARTITION)
def compute_global_histogram(local_hist: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """uint32 [P] -> uint32 [P], summed across the mesh axis."""
    return jax.lax.psum(local_hist, axis_name)

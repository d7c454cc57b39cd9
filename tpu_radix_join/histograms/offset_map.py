"""Write-offset computation for the shuffle.

Replaces ``histograms/OffsetMap.{h,cpp}``, whose three arrays let every rank
write into disjoint slices of every other rank's RMA window with zero
coordination (OffsetMap.cpp:59-93):

  * base offsets    — running sum of the global histogram in assignment order
    per target node (OffsetMap.cpp:59-73);
  * relative offsets — ``MPI_Exscan(SUM)`` of local histograms
    (OffsetMap.cpp:75-85);
  * absolute = base + relative (OffsetMap.cpp:87-93).

On the TPU mesh the *data plane* is a dense ``all_to_all`` of fixed-capacity
blocks (parallel/window.py), so absolute write offsets are not needed to avoid
races — but the same quantities drive the receive-side compaction (where each
sender's run lands inside the owner's contiguous partition storage) and the
conservation checks.  ``MPI_Exscan`` becomes an ``all_gather`` of local
histograms plus a masked sum over ranks below self — one ICI collective.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from tpu_radix_join.observability import stages
from tpu_radix_join.parallel.mesh import AxisName


class Offsets(NamedTuple):
    base: jnp.ndarray        # uint32 [P]   start of each partition in owner-order storage
    relative: jnp.ndarray    # uint32 [P]   this rank's exclusive prefix among ranks
    absolute: jnp.ndarray    # uint32 [P]   base + relative
    all_local_hists: jnp.ndarray  # uint32 [N, P] gathered local histograms


@jax.named_scope(stages.PARTITION)
def compute_offsets(
    local_hist: jnp.ndarray,
    global_hist: jnp.ndarray,
    assignment: jnp.ndarray,
    axis_name: AxisName,
) -> Offsets:
    """Runs inside shard_map; all shapes static.

    base[p]: for each owner node, its assigned partitions are laid out in
    partition-id order; base[p] is the running sum of global counts of the
    owner's earlier partitions (OffsetMap.cpp:59-73 does the same walk).
    """
    num_partitions = global_hist.shape[0]
    p_idx = jnp.arange(num_partitions, dtype=jnp.uint32)
    same_owner = assignment[None, :] == assignment[:, None]        # [P, P]
    earlier = p_idx[None, :] < p_idx[:, None]                      # [P, P]
    base = jnp.sum(
        jnp.where(same_owner & earlier, global_hist[None, :], 0), axis=1
    ).astype(jnp.uint32)

    all_hists = jax.lax.all_gather(local_hist, axis_name)          # [N, P]
    all_hists = all_hists.reshape((-1,) + local_hist.shape)        # flatten mesh axes
    my = jax.lax.axis_index(axis_name)
    ranks = jnp.arange(all_hists.shape[0], dtype=jnp.int32)
    relative = jnp.sum(
        jnp.where((ranks < my)[:, None], all_hists, 0), axis=0
    ).astype(jnp.uint32)

    return Offsets(base=base, relative=relative,
                   absolute=base + relative, all_local_hists=all_hists)

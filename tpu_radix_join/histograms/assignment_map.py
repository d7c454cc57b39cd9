"""Partition -> owner-node assignment.

Replaces ``histograms/AssignmentMap.{h,cpp}``.  The reference's policy is
round-robin ``p % numberOfNodes`` (AssignmentMap.cpp:41-43), but its
constructor takes both global histograms (AssignmentMap.cpp:17-23) — an API
shaped for load-aware assignment it never implements.  We implement both:

  * ``round_robin`` — exact parity with the reference.
  * ``load_aware``  — greedy longest-processing-time: partitions are taken in
    decreasing combined (R+S) size and each is assigned to the currently
    least-loaded node.  This is the capability the skew (Zipf) benchmark
    config targets (SURVEY.md §2.1 AssignmentMap note) and the distributed
    counterpart of the dormant GPU skew machinery
    (kernels_optimized.cu:301-344).

Both run identically on every node (deterministic on replicated global
histograms), so no broadcast is needed — same as the reference where every
rank recomputes the map.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_radix_join.observability import stages


def round_robin_assignment(num_partitions: int, num_nodes: int) -> jnp.ndarray:
    """assignment[p] = p % numberOfNodes (AssignmentMap.cpp:41-43)."""
    return (jnp.arange(num_partitions, dtype=jnp.uint32) % jnp.uint32(num_nodes))


def load_aware_assignment(
    inner_global_hist: jnp.ndarray, outer_global_hist: jnp.ndarray, num_nodes: int
) -> jnp.ndarray:
    """Greedy LPT assignment on combined partition weights.

    Static shapes throughout: a ``lax.scan`` over the (static) partition count,
    carrying per-node load accumulators.  The weight model is R+S tuple count —
    the shuffle bytes and probe work are both linear in it.
    """
    weight = inner_global_hist.astype(jnp.float32) + outer_global_hist.astype(jnp.float32)
    num_partitions = weight.shape[0]
    order = jnp.argsort(-weight)  # heaviest first

    def step(loads, p):
        node = jnp.argmin(loads).astype(jnp.uint32)
        loads = loads.at[node].add(weight[p])
        return loads, (p, node)

    _, (ps, nodes) = jax.lax.scan(step, jnp.zeros((num_nodes,), jnp.float32),
                                  order)
    assignment = jnp.zeros((num_partitions,), jnp.uint32).at[ps].set(nodes)
    return assignment


@jax.named_scope(stages.PARTITION)
def compute_partition_assignment(
    inner_global_hist: jnp.ndarray,
    outer_global_hist: jnp.ndarray,
    num_nodes: int,
    policy: str = "round_robin",
) -> jnp.ndarray:
    """uint32 [P] with values in [0, num_nodes)."""
    num_partitions = inner_global_hist.shape[0]
    if policy == "round_robin":
        return round_robin_assignment(num_partitions, num_nodes)
    if policy == "load_aware":
        return load_aware_assignment(inner_global_hist, outer_global_hist, num_nodes)
    raise ValueError(f"unknown assignment policy {policy!r}")

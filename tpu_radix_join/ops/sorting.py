"""The pipeline's sort primitives: ``jax.lax.sort``, unstable, named.

Every hot reorder is an *unstable* sort: the join's semantics never
depend on the relative order of equal keys (payload lanes travel with
their key in key-value sorts; probe disciplines are order-independent
within an equal-key run), and on v5e an unstable ``lax.sort`` is ~2x the
speed of the stable sort ``jnp.sort``/``jnp.argsort`` emit (measured
44.6ms vs 93ms at 32M uint32).

Every hot sort goes through these wrappers so that its device work sits
under the ``trj.sort`` scope, which the per-stage sort time reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_radix_join.observability import stages


@jax.named_scope(stages.SORT)
def sort_unstable(x: jnp.ndarray, dimension: int = -1) -> jnp.ndarray:
    """Unstable sort of one array along ``dimension``."""
    return jax.lax.sort([x], dimension=dimension, is_stable=False)[0]


@jax.named_scope(stages.SORT)
def sort_kv_unstable(key: jnp.ndarray, *values: jnp.ndarray):
    """Unstable key-value sort; returns (sorted key, *values in key order)."""
    return jax.lax.sort((key, *values), num_keys=1, is_stable=False)


@jax.named_scope(stages.SORT)
def sort_lex_unstable(*operands: jnp.ndarray, num_keys: int,
                      dimension: int = -1):
    """Unstable lexicographic sort on the first ``num_keys`` operands
    (remaining operands ride along as values).  Split-lane 64-bit keys
    are the ``num_keys=2`` (hi, lo) case."""
    return jax.lax.sort(operands, num_keys=num_keys, dimension=dimension,
                        is_stable=False)


def segmented_xor_fold(segment: jnp.ndarray, values: jnp.ndarray,
                       num_segments: int) -> jnp.ndarray:
    """Per-segment xor-fold: ``out[q] = XOR of values[i] where segment[i] == q``.

    XLA has no scatter-xor, so the fold goes through the pipeline's native
    reorder primitive instead: sort values by segment id, prefix-xor them
    with an associative scan, then difference the prefix at consecutive
    segment boundaries (located by searchsorted, which also handles empty
    segments — their fold is 0).  Order-independence is inherited from xor
    itself, so the unstable sort is safe.  The segment ``num_segments``
    itself acts as a discard bucket — callers route invalid lanes to
    exactly that value.

    The integrity-verification checksums (robustness/verify.py) are the
    consumer: xor catches the bit-flip corruptions that a wrapping uint32
    sum can miss (paired flips cancel in addition far more easily than in
    parity per bit position).
    """
    seg_s, val_s = sort_kv_unstable(segment.astype(jnp.uint32),
                                    values.astype(jnp.uint32))
    prefix = jax.lax.associative_scan(jnp.bitwise_xor, val_s)
    # E[q] = prefix-xor through the last element with segment <= q
    idx = jnp.searchsorted(seg_s, jnp.arange(num_segments, dtype=jnp.uint32),
                           side="right") - 1
    bounded = jnp.where(idx >= 0, prefix[jnp.clip(idx, 0)], jnp.uint32(0))
    shifted = jnp.concatenate([jnp.zeros((1,), jnp.uint32), bounded[:-1]])
    return bounded ^ shifted

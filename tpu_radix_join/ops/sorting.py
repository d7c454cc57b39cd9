"""Shared sort primitives and the xla-vs-Pallas radix-sort switch.

Every hot reorder in the pipeline is an *unstable* sort: the join's
semantics never depend on the relative order of equal keys (payload lanes
travel with their key in key-value sorts; probe disciplines are
order-independent within an equal-key run), and on v5e an unstable
``lax.sort`` is ~2x the speed of the stable sort ``jnp.sort``/
``jnp.argsort`` emit (measured 44.6ms vs 93ms at 32M uint32).

Centralised here so the *implementation* is one edit for every caller:
``merge_count.presort_keys``, the build/probe bucket paths, chunked.py,
the verify xor-fold, and the grouped codec all route through these three
functions, and each resolves between two arms at trace time:

  * ``xla`` — ``jax.lax.sort``;
  * ``pallas`` / ``pallas_interpret`` — the Pallas LSD radix sort
    (ops/pallas/radix_sort.py): 4 digit passes worst case for uint32,
    fewer when a key bound shrinks the effective width, no compare
    network at all.

``auto`` (the default, process-bindable via ``set_default_sort_impl``
from JoinConfig.sort_impl) is ``xla`` for every size, shape and backend:
on a TPU v5e ``lax.sort`` beats the radix sort at every size measured,
2^18 to 67M elements, bounded keys or not, by 9x to 60x, and the 20M x
20M join's program runs in 75 ms against 2647 ms with the radix arm
(PERF.md §5).  The radix sort runs only when forced.  Structural
ineligibility (batched 2-D sorts, non-uint32 lanes) routes to XLA even
when the kernel is forced: forcing selects the impl for the sorts the
kernel can express, it does not redefine what it can express.
"""

from __future__ import annotations

from contextlib import nullcontext

import jax
import jax.numpy as jnp

from tpu_radix_join.observability import stages
from tpu_radix_join.ops.pallas.radix_sort import radix_sort_pallas
from tpu_radix_join.performance.measurements import SORTPASS

SORT_IMPLS = ("auto", "xla", "pallas", "pallas_interpret")

# Sort-impl selection happens at TRACE time (these functions run
# inside jit/shard_map bodies where no host counter can tick per
# execution), so the observability hook lives at module level, exactly
# like ops/radix's partition observer: the engine registers its
# Measurements once and every traced sort site records which arm it took.
_sort_observer: dict = {"meas": None}
_default_impl: dict = {"impl": "auto"}


def install_sort_observer(measurements) -> None:
    """Register a performance.Measurements (or None) to receive SORTPASS
    ticks and radix-sort spans from trace-time impl selection.
    Process-global: the most recent engine wins, which is the engine
    whose programs are being traced."""
    _sort_observer["meas"] = measurements


def set_default_sort_impl(impl: str) -> None:
    """Bind the process-default sort impl (JoinConfig.sort_impl lands here
    via HashJoin).  The sort primitives are called from deep inside ops/
    with no config in reach — that is the point of the switch: callers
    inherit it with zero call-site edits — so the engine re-asserts its
    configured impl before tracing.  Compiled programs keep the impl they
    traced with."""
    if impl not in SORT_IMPLS:
        raise ValueError(
            f"unknown sort impl {impl!r} (expected one of {SORT_IMPLS})")
    _default_impl["impl"] = impl


def _sort_span(impl: str, site: str, elems: int):
    """Span bracketing the trace-time construction of one radix sort —
    mirrored into the flight recorder ring like every span."""
    m = _sort_observer["meas"]
    if m is None:
        return nullcontext()
    m.incr(SORTPASS)
    return m.span("radix_sort", impl=impl, site=site, elems=int(elems))


def _radix_eligible(operands, dimension: int) -> bool:
    """Shapes the radix kernel expresses: equal-length 1-D uint32 lanes
    sorted along their only axis.  Batched (2-D) sorts and non-uint32
    lanes stay on lax.sort."""
    first = operands[0]
    if first.ndim != 1 or dimension not in (-1, 0):
        return False
    return all(o.ndim == 1 and o.shape == first.shape
               and o.dtype == jnp.uint32 for o in operands)


def resolve_sort_impl(impl: str | None, eligible: bool = True) -> str:
    """Resolve a sort ``impl`` request to a concrete arm.

    ``None`` reads the process default (``set_default_sort_impl``).
    ``auto`` and ``xla`` are ``lax.sort``; ``pallas``/``pallas_interpret``
    force the radix kernel for every eligible sort (interpret = traced
    JAX ops, the tier-1 CPU parity path)."""
    if impl is None:
        impl = _default_impl["impl"]
    if impl in ("auto", "xla") or not eligible:
        return "xla"
    return impl


@jax.named_scope(stages.SORT)
def sort_unstable(x: jnp.ndarray, dimension: int = -1, *,
                  impl: str | None = None,
                  key_bound: int | None = None) -> jnp.ndarray:
    """Unstable sort of one array along ``dimension``."""
    eligible = _radix_eligible((x,), dimension)
    r = resolve_sort_impl(impl, eligible)
    if r in ("pallas", "pallas_interpret"):
        with _sort_span(r, "sort_unstable", x.size):
            return radix_sort_pallas(
                (x,), num_keys=1, key_bounds=(key_bound,),
                interpret=(r == "pallas_interpret"))[0]
    return jax.lax.sort([x], dimension=dimension, is_stable=False)[0]


@jax.named_scope(stages.SORT)
def sort_kv_unstable(key: jnp.ndarray, *values: jnp.ndarray,
                     impl: str | None = None, key_bound: int | None = None):
    """Unstable key-value sort; returns (sorted key, *values in key order)."""
    eligible = _radix_eligible((key, *values), -1)
    r = resolve_sort_impl(impl, eligible)
    if r in ("pallas", "pallas_interpret"):
        with _sort_span(r, "sort_kv_unstable", key.size):
            return radix_sort_pallas(
                (key, *values), num_keys=1, key_bounds=(key_bound,),
                interpret=(r == "pallas_interpret"))
    return jax.lax.sort((key, *values), num_keys=1, is_stable=False)


@jax.named_scope(stages.SORT)
def sort_lex_unstable(*operands: jnp.ndarray, num_keys: int,
                      dimension: int = -1, impl: str | None = None,
                      key_bounds=None):
    """Unstable lexicographic sort on the first ``num_keys`` operands
    (remaining operands ride along as values).  Split-lane 64-bit keys
    are the ``num_keys=2`` (hi, lo) case; on the radix arm the lo lane's
    digit passes run first and stability chains them under the hi
    lane's."""
    eligible = _radix_eligible(operands, dimension)
    r = resolve_sort_impl(impl, eligible)
    if r in ("pallas", "pallas_interpret"):
        with _sort_span(r, "sort_lex_unstable", operands[0].size):
            return radix_sort_pallas(
                operands, num_keys=num_keys, key_bounds=key_bounds,
                interpret=(r == "pallas_interpret"))
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
    itself, so the unstable sort is safe (and the sort inherits the
    xla-vs-pallas switch through sort_kv_unstable, with the segment count
    as a free key bound).  The segment ``num_segments`` itself acts as a
    discard bucket — callers route invalid lanes to exactly that value
    (not merely "anything larger": the bounded radix passes only order
    segments below ``num_segments + 1``).

    The integrity-verification checksums (robustness/verify.py) are the
    consumer: xor catches the bit-flip corruptions that a wrapping uint32
    sum can miss (paired flips cancel in addition far more easily than in
    parity per bit position).
    """
    seg_s, val_s = sort_kv_unstable(segment.astype(jnp.uint32),
                                    values.astype(jnp.uint32),
                                    key_bound=num_segments + 1)
    prefix = jax.lax.associative_scan(jnp.bitwise_xor, val_s)
    # E[q] = prefix-xor through the last element with segment <= q
    idx = jnp.searchsorted(seg_s, jnp.arange(num_segments, dtype=jnp.uint32),
                           side="right") - 1
    bounded = jnp.where(idx >= 0, prefix[jnp.clip(idx, 0)], jnp.uint32(0))
    shifted = jnp.concatenate([jnp.zeros((1,), jnp.uint32), bounded[:-1]])
    return bounded ^ shifted

"""Out-of-core chunked join: relations larger than device memory.

Replaces the reference's ``LD`` (large data) GPU capability — histograms,
reorders and probes indexed by ``iterCount`` so relations bigger than GPU
memory stream through in 128M-tuple chunks (``data/data.hpp:13-20,69-84``;
``LD`` kernels ``operators/gpu/kernels.cu:563-858``).

TPU design: ``jax.lax.scan`` over probe-side slabs.  The build side is sorted
once and stays resident in HBM; each scan step counts one outer slab's
matches with the merge-count discipline against the sorted inner.  Because
scan reuses one compiled step, HBM working-set per step is
O(inner + slab) regardless of total outer size — the `lax.scan`-over-slabs
shape SURVEY.md §5.7 prescribes.  For inner sides that exceed memory as well,
``chunked_join_grid`` streams both sides (outer scan nested in a Python loop
over inner chunks, accumulating partial counts — every (i, j) chunk pair is
probed exactly once, matching the LD kernels' two-level iterCount indexing).

Pipelined grid engine (``pipeline="on"``): the synchronous grid loop pays
three serial taxes per pair — it re-sorts the same inner chunk inside every
pair, blocks on a per-pair host readback (a dispatch round trip that does
not pipeline, PERF_NOTES "Dispatch overhead"), and fsyncs a checkpoint on the
critical path.  The pipelined engine removes all three, the same
overlap discipline as the reference's double-buffered 64KB ``MPI_Put``
windows (NetworkPartitioning.cpp:116-173):

  * **inner-sort reuse** — each inner chunk is sorted once per grid *row*
    (ops/merge_count.presort_keys) and every outer slab of the row probes
    it by binary search (merge_count_presorted): ``(n_outer_chunks - 1)``
    redundant sorts per row eliminated, observable as the SORTREUSE
    counter;
  * **double-buffered prefetch** — a bounded background stage
    (:class:`_Prefetcher`) generates/stages chunk ``j+1`` on device (and
    hoists its ``key_range="auto"`` max-key bound off the critical path)
    while pair ``(i, j)`` computes; per-pair counts stay on-device and
    readbacks drain through a bounded pending queue ("readback_flush"
    spans), so the host loop stops serializing on the readback round trip;
  * **write-behind checkpoints** — realized totals flush through
    robustness/checkpoint.AsyncCheckpointWriter ("ckpt_flush" spans)
    while the next pair computes; only *resolved* pair totals are ever
    enqueued, so every state on disk still satisfies the "every saved
    pair is realized" resume invariant, with a flush barrier + one final
    synchronous save at completion.

``pipeline="off"`` (the function default) keeps the synchronous loop as
the fallback and A/B lever; ``"auto"`` turns the pipeline on for any grid
larger than 1x1 (the CLI ``--grid-pipeline`` default).
"""

from __future__ import annotations

import contextlib
import functools
import os
import queue as _queue
import threading
from collections import deque
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_radix_join.data.tuples import TupleBatch
from tpu_radix_join.utils.hostsync import host_readback
from tpu_radix_join.ops.merge_count import (
    MAX_MERGE_KEY,
    merge_count_chunks,
    merge_count_per_partition_full,
    merge_count_presorted,
    merge_count_wide_per_partition,
    presort_keys,
)


@functools.partial(jax.jit, static_argnames=("num_slabs",))
def _scan_probe(r_keys: jnp.ndarray, s_keys: jnp.ndarray, num_slabs: int):
    """(per-slab counts uint32 [num_slabs], max single-tuple match weight)
    for s_keys split into ``num_slabs`` slabs.  The max weight feeds the
    caller's uint32-overflow guard (chunked_join_count)."""
    slabs = s_keys.reshape(num_slabs, -1)

    def step(carry, slab):
        # per-slab partial counts; chunked uint32 sums stay overflow-safe
        # as long as the caller-checked weight bound holds
        c, mw = merge_count_chunks(r_keys, slab, num_chunks=1024,
                                   return_max_weight=True)
        return carry, (jnp.sum(c, dtype=jnp.uint32), mw)

    _, (per_slab, mws) = jax.lax.scan(step, jnp.uint32(0), slabs)
    return per_slab, jnp.max(mws)


@functools.partial(jax.jit, static_argnames=("num_slabs",))
def _scan_probe_full(r_keys: jnp.ndarray, s_keys: jnp.ndarray,
                     num_slabs: int):
    """Full-key-range twin of :func:`_scan_probe`: the 2-key lexicographic
    count (merge_count_per_partition_full, fanout 0) for workloads whose
    keys exceed the 31-bit packing — which would silently map to the
    reserved pack-pads (zero matches) in the packed discipline."""
    slabs = s_keys.reshape(num_slabs, -1)

    def step(carry, slab):
        c, mw = merge_count_per_partition_full(r_keys, slab, 0,
                                               return_max_weight=True)
        return carry, (c[0], mw)

    _, (per_slab, mws) = jax.lax.scan(step, jnp.uint32(0), slabs)
    return per_slab, jnp.max(mws)


@functools.partial(jax.jit, static_argnames=("num_slabs",))
def _scan_probe_wide(r_lo, r_hi, s_lo, s_hi, num_slabs: int):
    """Wide-key (hi/lo lane) twin of :func:`_scan_probe`."""
    slabs = (s_lo.reshape(num_slabs, -1), s_hi.reshape(num_slabs, -1))

    def step(carry, slab):
        lo, hi = slab
        c, mw = merge_count_wide_per_partition(r_lo, r_hi, lo, hi, 0,
                                               return_max_weight=True)
        return carry, (jnp.sum(c, dtype=jnp.uint32), mw)

    _, (per_slab, mws) = jax.lax.scan(step, jnp.uint32(0), slabs)
    return per_slab, jnp.max(mws)


@functools.partial(jax.jit, static_argnames=("num_slabs",))
def _scan_probe_presorted(r_sorted: jnp.ndarray, s_keys: jnp.ndarray,
                          num_slabs: int):
    """Presorted-inner twin of :func:`_scan_probe`: the binary-search probe
    (ops/merge_count.merge_count_presorted) against a row-resident sorted
    inner — no per-pair union sort, no 31-bit packing (the full
    sub-sentinel key range joins natively).  The pipelined grid's
    sort-reuse engine: one :func:`presort_keys` per grid row feeds every
    outer chunk of that row through here."""
    slabs = s_keys.reshape(num_slabs, -1)

    def step(carry, slab):
        c, mw = merge_count_presorted(r_sorted, slab, return_max_weight=True)
        return carry, (c, mw)

    _, (per_slab, mws) = jax.lax.scan(step, jnp.uint32(0), slabs)
    return per_slab, jnp.max(mws)


def _sentinel_corruption(mx: int):
    from tpu_radix_join.robustness.verify import DataCorruption
    from tpu_radix_join.data.tuples import pad_sentinel
    return DataCorruption(
        f"keys reach the pad sentinel range (max {mx:#x}): "
        f"uint32 keys must stay <= "
        f"{int(pad_sentinel('inner')) - 1:#x} — a key lane in "
        f"the sentinel range is the streamed-lane corruption "
        f"signature (such tuples would silently pad-match)")


def _narrow_violation(mx: int) -> ValueError:
    return ValueError(
        f"key contract violation: key_range='narrow' but max key "
        f"{mx:#x} exceeds the 31-bit packing limit "
        f"{MAX_MERGE_KEY:#x} — such keys pack to the reserved "
        f"zero-match pads (silent undercount); use key_range='full' "
        f"or 'auto'")


def _check_weight_window(maxw: int, window: int) -> None:
    """uint32-overflow guard: every accumulation window (the per-slab total
    and the chunk partials inside it) is bounded by max_weight x window
    width; a wrapped window would return a wrong count silently (the
    reference's uint64 RESULT_COUNTER is immune, HashJoin.h:26)."""
    if maxw > (2**32 - 1) // window:
        raise OverflowError(
            f"uint32 count-window overflow risk: max inner multiplicity "
            f"{maxw} x window {window} can reach 2**32 — "
            f"shrink slab_size or deduplicate the inner side")


def chunked_join_count(r: TupleBatch, s: TupleBatch, slab_size: int,
                       key_range: str = "auto",
                       key_bound: int | None = None) -> int:
    """Exact match count streaming the outer side in ``slab_size`` slabs.

    Ragged sizes (streamed chunks, short final chunks) are padded up to a
    slab multiple with the outer-side sentinel, which matches nothing by the
    pad-key contract (tuples.py).  Wide (64-bit) batches — e.g. from a
    ``Relation(key_bits=64)`` stream — take the hi/lo lexicographic count;
    mixed-width inputs raise rather than silently truncate.

    ``key_range`` mirrors ``JoinConfig.key_range`` for the 32-bit path:
    "auto" probes the chunks' max key (2 HBM scans + a readback per call)
    and routes keys above the 31-bit packing to the full-range count;
    callers with a static bound — e.g. grid drivers over unique Relations,
    whose keys never reach 2**31 (relation.py size cap) — pass "narrow"
    (or "full") to skip the probe on every grid pair.

    ``key_bound`` (optional) is a precomputed INCLUSIVE max over both
    chunks' key lanes: it replaces "auto"'s per-call device probe (and
    "narrow"'s deferred contract reduction) with host arithmetic, so a
    grid driver that caches one max-key readback per *chunk* stops paying
    a 2-scan + readback sync on every *pair* (chunked_join_grid does
    exactly this).  The sentinel-range corruption check and the narrow
    31-bit contract check still fire, from the bound.
    """
    if key_range not in ("auto", "narrow", "full"):
        raise ValueError(f"unknown key range mode {key_range!r}")
    from tpu_radix_join.data.tuples import pad_sentinel
    if (r.key_hi is None) != (s.key_hi is None):
        raise ValueError(
            "mixed key widths: one side carries a key_hi lane and the other "
            "does not — refusing to run a silently-truncated join")
    keys = s.key
    n = keys.shape[0]
    pad = (-n) % slab_size
    fill = pad_sentinel("outer")
    mx_narrow = None
    if pad:
        keys = jnp.concatenate(
            [keys, jnp.full((pad,), fill, keys.dtype)])
    if r.key_hi is not None:
        s_hi = s.key_hi
        if pad:
            # sentinel in BOTH lanes (the make_padding wide=True contract)
            s_hi = jnp.concatenate(
                [s_hi, jnp.full((pad,), fill, s_hi.dtype)])
        per_slab, maxw = _scan_probe_wide(r.key, r.key_hi, keys, s_hi,
                                          (n + pad) // slab_size)
    else:
        # keys above the 31-bit packing would silently land on the reserved
        # pack-pads (zero matches) in merge_count_chunks; under "auto",
        # probe the real max (pre-padding — the sentinel fill is always the
        # uint32 max) and route to the full-range lexicographic count
        full = key_range == "full"
        if key_range == "auto":
            mx = (int(key_bound) if key_bound is not None else
                  int(host_readback(jnp.maximum(jnp.max(r.key),
                                             jnp.max(s.key)))))
            if mx >= int(pad_sentinel("inner")):
                raise _sentinel_corruption(mx)
            full = mx > MAX_MERGE_KEY
        if full:
            per_slab, maxw = _scan_probe_full(r.key, keys,
                                              (n + pad) // slab_size)
        else:
            per_slab, maxw = _scan_probe(r.key, keys, (n + pad) // slab_size)
            if key_range == "narrow":
                # "narrow" asserts a static key bound instead of paying
                # "auto"'s pre-scan sync — but an asserted contract still
                # has to be *checked*: keys above the 31-bit packing land on
                # the reserved pack-pads and count zero matches, an
                # undercount with ok-looking output.  With a precomputed
                # bound the check is host arithmetic; otherwise dispatch
                # the max-key reduction after the scan so it rides the
                # maxw readback below (detection without an extra sync).
                if key_bound is not None:
                    if int(key_bound) > MAX_MERGE_KEY:
                        raise _narrow_violation(int(key_bound))
                else:
                    mx_narrow = jnp.maximum(jnp.max(r.key), jnp.max(s.key))
    if mx_narrow is not None:
        mx = int(host_readback(mx_narrow))
        if mx > MAX_MERGE_KEY:
            raise _narrow_violation(mx)
    window = max(slab_size, -(-(r.key.shape[0] + slab_size) // 1024))
    _check_weight_window(int(host_readback(maxw)), window)
    return int(host_readback(per_slab).astype(np.uint64).sum())


class _Prefetcher:
    """Bounded background chunk stager for the pipelined grid.

    Pulls chunks from ``it`` on a daemon thread, forces their device
    generation (JAX dispatch is lazy for generator-fed grids — see
    data/streaming.stream_chunks_device), and — for 32-bit chunks —
    hoists the ``key_range="auto"`` max-key readback off the critical
    path.  Hands ``(chunk, bound)`` pairs to the consumer through a
    queue of ``depth`` slots: with the consumer busy on pair ``(i, j)``
    the thread is already staging chunk ``j+1`` (and blocks once the
    queue fills — bounded lookahead, bounded memory).

    Each staged chunk is one "prefetch" span (recorded from this thread;
    SpanTracer keeps per-name stacks, so producer spans interleave safely
    with the consumer's "grid_pair" spans) plus one PREFETCH count.
    Iterator exceptions are captured and re-raised at the consuming
    ``next()`` — a corrupt or failing stream fails the pair loop, not a
    daemon thread.
    """

    _DONE = object()

    def __init__(self, it, depth: int, measurements, side: str):
        self._q = _queue.Queue(maxsize=max(1, depth))
        self._meas = measurements
        self._side = side
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, args=(it,),
            name=f"grid-prefetch-{side}", daemon=True)
        self._thread.start()

    def _run(self, it):
        from tpu_radix_join.performance.measurements import PREFETCH
        try:
            for idx, chunk in enumerate(it):
                if self._stop.is_set():
                    return
                span = (self._meas.span("prefetch", side=self._side,
                                        chunk=idx)
                        if self._meas is not None
                        else contextlib.nullcontext())
                with span:
                    bound = None
                    if getattr(chunk, "key_hi", None) is None:
                        # the bound readback doubles as the staging fence
                        bound = int(host_readback(jnp.max(chunk.key)))
                    else:
                        jax.block_until_ready(chunk.key)
                if self._meas is not None:
                    self._meas.incr(PREFETCH)
                self._put((chunk, bound))
            self._put(self._DONE)
        except BaseException as e:      # re-raised at the consumer
            self._put(e)

    def _put(self, item) -> None:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except _queue.Full:
                continue

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is self._DONE:
            raise StopIteration
        if isinstance(item, BaseException):
            raise item
        return item

    def close(self) -> None:
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except _queue.Empty:
            pass
        self._thread.join(timeout=5)


def chunked_join_grid(r_chunks, s_chunks, slab_size: int,
                      checkpoint_path: str | None = None,
                      checkpoint_tag: str = "",
                      progress: bool = False,
                      key_range: str = "auto",
                      measurements=None,
                      retry_policy=None,
                      retry_on=None,
                      plan=None,
                      pipeline: str = "off",
                      prefetch_depth: int = 2,
                      readback_depth: int = 2) -> int:
    """Both sides streamed; each inner chunk is joined against every outer
    chunk exactly once.

    ``s_chunks`` is consumed once per inner chunk, so pass either a
    re-iterable (list/tuple) or — for outer sides too large to keep resident
    — a zero-argument factory returning a fresh iterator per inner chunk
    (e.g. ``lambda: stream_chunks(s_rel, node, c)``), which keeps device
    memory at O(chunk).  A bare one-shot iterator is materialized up front
    (resident, but never silently exhausted).

    ``pipeline`` selects the engine: "off" (default) is the synchronous
    loop — one probe, one readback, one checkpoint fsync per pair, in
    program order; "on" is the pipelined engine (module docstring):
    once-per-row inner sorts probed by binary search, ``prefetch_depth``
    chunks of background staging, readbacks deferred through a
    ``readback_depth`` pending window, and write-behind checkpoints.
    "auto" resolves to "on" for any grid larger than a single pair.  Both
    modes return identical totals and share the checkpoint format — a run
    killed under either mode resumes under either mode.

    ``checkpoint_path`` adds resume support for long grid joins — a
    capability the single-shot reference lacks entirely (SURVEY.md §5.4):
    after every (inner, outer) chunk pair the accumulated count and the next
    pair's (i, j) indices are written atomically (fsync + rename); a rerun
    with the same arguments skips completed pairs (skipped chunks are
    regenerated but not probed — generation is cheap, probes are not).  The
    file is left in place on completion with ``"done": true``.  A
    fingerprint (slab size + caller-supplied ``checkpoint_tag`` + the
    planner's strategy/chunking when a ``plan`` is given) guards
    against resuming a different join from a stale file — pass a tag that
    identifies the input relations; mismatches raise instead of silently
    returning the wrong total, and unreadable files restart from zero.
    Saved states additionally record the grid's discovered row/col extents:
    a generator-fed grid has ``rows``/``cols`` None in its fingerprint, so
    without them a resume with the same tag but a different chunking would
    mis-resume — the extent check fails fast instead (CheckpointMismatch).
    Checkpoint mechanics (atomic rename, corruption policy, counters) live
    in robustness/checkpoint.CheckpointManager; pipelined mode flushes
    saves through AsyncCheckpointWriter (write-behind, latest-wins
    coalescing — CKPTSAVE may be lower than the pair count, but every
    saved state is realized).

    ``measurements`` (optional) receives CKPTSAVE/CKPTLOAD from the
    manager plus GRIDPAIRS — the number of chunk pairs actually probed,
    which a resumed run keeps at (total pairs - completed pairs): the
    zero-recompute guarantee tests assert on — and, in pipelined mode,
    PREFETCH/SORTREUSE with "prefetch"/"readback_flush"/"ckpt_flush"
    spans.  ``retry_policy`` (a robustness.retry.RetryPolicy) retries each
    pair probe on transient errors (``retry_on`` exception classes,
    default the injectable TransientFault), so a transient backend error
    costs one backoff instead of the whole grid.
    """
    if callable(s_chunks):
        s_iter = s_chunks
    else:
        if not isinstance(s_chunks, (list, tuple)):
            s_chunks = list(s_chunks)
        s_iter = lambda: s_chunks

    if pipeline not in ("off", "on", "auto"):
        raise ValueError(f"unknown grid pipeline mode {pipeline!r} "
                         f"(want off|on|auto)")
    rows_known = (len(r_chunks) if isinstance(r_chunks, (list, tuple))
                  else None)
    cols_known = (len(s_chunks) if isinstance(s_chunks, (list, tuple))
                  else None)
    if pipeline == "auto":
        # a 1x1 grid has nothing to overlap (one pair, one readback); any
        # larger grid amortizes the prefetch/writer threads immediately
        pipeline = "off" if rows_known == 1 and cols_known == 1 else "on"

    if checkpoint_path and not checkpoint_tag:
        raise ValueError(
            "checkpoint_path requires a checkpoint_tag identifying the input "
            "relations — an untagged checkpoint resumed against different "
            "data would silently return a wrong total")
    from tpu_radix_join.performance.measurements import (GRIDPAIRS,
                                                         SORTREUSE)
    from tpu_radix_join.robustness import faults as _faults
    from tpu_radix_join.robustness.checkpoint import (AsyncCheckpointWriter,
                                                      CheckpointManager,
                                                      CheckpointMismatch)
    from tpu_radix_join.robustness.retry import execute as _retry_execute

    fingerprint = {"slab": int(slab_size), "tag": checkpoint_tag,
                   "rows": rows_known, "cols": cols_known}
    if plan is not None:
        # a planner-driven grid (main.py --plan) folds the plan identity in:
        # resuming under a different chunking or strategy walks a different
        # grid, so the stale checkpoint must mismatch, not mis-resume
        fingerprint["plan"] = {"strategy": plan.strategy,
                               "chunk_tuples": plan.chunk_tuples}
    ckpt = (CheckpointManager(checkpoint_path, fingerprint, measurements)
            if checkpoint_path else None)
    start_i, start_j, total = 0, 0, 0
    saved_rows = saved_cols = None
    if ckpt is not None:
        state = ckpt.load()
        if state is not None:
            saved_rows, saved_cols = state.get("rows"), state.get("cols")
            # extent hardening: the fingerprint's rows/cols are None for
            # generator-fed grids, so a stale file with the same tag but a
            # different grid shape would otherwise mis-resume
            for name, saved, known in (("rows", saved_rows, rows_known),
                                       ("cols", saved_cols, cols_known)):
                if saved is not None and known is not None and saved != known:
                    raise CheckpointMismatch(
                        f"checkpoint {checkpoint_path} was saved from a grid "
                        f"with {saved} {name.rstrip('s')} chunk(s), but this "
                        f"run walks {known} — same tag, different grid "
                        f"shape; remove the checkpoint or fix the inputs")
            if state.get("done"):
                return int(state["total"])
            start_i, start_j = int(state["i"]), int(state["j"])
            total = int(state["total"])
    # best-known column extent (list length, checkpoint, or discovered at
    # the end of the first iterated row) — feeds ETA + resume accounting
    cols = cols_known if cols_known is not None else saved_cols
    if progress and (start_i or start_j):
        if cols:
            print(f"[grid] resume: skipping {start_i * cols + start_j} "
                  f"completed pair(s) (cursor i={start_i}, j={start_j})",
                  flush=True)
        else:
            print(f"[grid] resume: skipping completed pairs before cursor "
                  f"(i={start_i}, j={start_j})", flush=True)

    def state_dict(i: int, j: int, total: int, done: bool = False) -> dict:
        state = {"i": i, "j": j, "total": total}
        if cols is not None:
            state["cols"] = cols
        rows = rows_known if rows_known is not None else (i if done else None)
        if rows is not None:
            state["rows"] = rows
        return state

    def note_cols(n: int) -> None:
        nonlocal cols
        if saved_cols is not None and n != saved_cols:
            raise CheckpointMismatch(
                f"checkpoint {checkpoint_path} was saved from a grid with "
                f"{saved_cols} outer chunk(s) per row, but this run "
                f"discovered {n} — same tag, different grid shape; remove "
                f"the checkpoint or fix the inputs")
        if cols is None:
            cols = n

    import time as _time

    def span(name, **kw):
        return (measurements.span(name, **kw) if measurements is not None
                else contextlib.nullcontext())

    t0 = _time.perf_counter()
    start_pairs = start_i * cols + start_j if cols else 0
    done_this_run = 0

    def report(i: int, j: int) -> None:
        if not progress:
            return
        elapsed = _time.perf_counter() - t0
        rate = done_this_run / elapsed if elapsed > 0 else 0.0
        line = (f"[grid] pair ({i}, {j}) done, total={total:,}, "
                f"t={elapsed:.1f}s, {rate:.2f} pairs/s")
        if rows_known is not None and cols and rate > 0:
            remaining = max(0, rows_known * cols - start_pairs
                            - done_this_run)
            line += f", eta={remaining / rate:.0f}s"
        print(line, flush=True)

    # ``key_range="auto"`` max-key hoist: one device max + readback per
    # CHUNK (cached by chunk id — the outer side repeats every row),
    # instead of the per-PAIR 2-scan + readback sync inside
    # chunked_join_count.  Wide chunks have no 32-bit range discipline.
    s_bounds: dict = {}

    def chunk_bound(batch) -> int:
        return int(host_readback(jnp.max(batch.key)))

    last_i = start_i

    def run_sync() -> int:
        nonlocal total, last_i, done_this_run
        for i, r in enumerate(r_chunks):
            if i < start_i:
                continue
            row_start_j = start_j if i == start_i else 0
            rb = (chunk_bound(r)
                  if key_range == "auto" and r.key_hi is None else None)
            row_cols = 0
            for j, s in enumerate(s_iter()):
                row_cols = j + 1
                if j < row_start_j:
                    continue
                # a simulated hard kill lands between the last save and the
                # next probe — the checkpoint already covers every finished
                # pair, so the resume recomputes nothing
                _faults.check(_faults.GRID_KILL, measurements)
                kb = None
                if rb is not None and s.key_hi is None:
                    sb = s_bounds.get(j)
                    if sb is None:
                        sb = s_bounds[j] = chunk_bound(s)
                    kb = max(rb, sb)

                def probe(r=r, s=s, kb=kb):
                    _faults.check(_faults.GRID_TRANSIENT, measurements)
                    return chunked_join_count(r, s,
                                              min(slab_size, s.key.shape[0]),
                                              key_range=key_range,
                                              key_bound=kb)

                with span("grid_pair", i=i, j=j):
                    if retry_policy is not None:
                        total += _retry_execute(
                            probe, retry_policy,
                            retryable=retry_on or (_faults.TransientFault,),
                            measurements=measurements,
                            label=f"grid_pair({i},{j})")
                    else:
                        total += probe()
                if measurements is not None:
                    measurements.incr(GRIDPAIRS)
                done_this_run += 1
                if ckpt is not None:
                    ckpt.save(state_dict(i, j + 1, total))
                report(i, j)
            note_cols(row_cols)
            last_i = i + 1
        if ckpt is not None:
            ckpt.save(state_dict(last_i, 0, total, done=True), done=True)
        return total

    def dispatch_probe(r, s, r_sorted, kb):
        """Dispatch one pair's device probe, leaving the counts on device:
        (per_slab device array, maxw device scalar, overflow window)."""
        from tpu_radix_join.data.tuples import pad_sentinel
        if (r.key_hi is None) != (s.key_hi is None):
            raise ValueError(
                "mixed key widths: one side carries a key_hi lane and the "
                "other does not — refusing to run a silently-truncated join")
        slab = min(slab_size, s.key.shape[0])
        keys = s.key
        n = keys.shape[0]
        pad = (-n) % slab
        fill = pad_sentinel("outer")
        if pad:
            keys = jnp.concatenate(
                [keys, jnp.full((pad,), fill, keys.dtype)])
        if r.key_hi is not None:
            # wide chunks keep the per-pair union sort (no presorted-probe
            # discipline for 2-lane keys yet) but still ride the prefetch +
            # deferred-readback + write-behind stages
            s_hi = s.key_hi
            if pad:
                s_hi = jnp.concatenate(
                    [s_hi, jnp.full((pad,), fill, s_hi.dtype)])
            per_slab, maxw = _scan_probe_wide(r.key, r.key_hi, keys, s_hi,
                                              (n + pad) // slab)
            window = max(slab, -(-(r.key.shape[0] + slab) // 1024))
        else:
            # the binary-search probe compares raw uint32 keys, so an inner
            # key in the sentinel range would pad-match the outer fill —
            # the bound check makes that loud for every key_range mode
            if kb is None:
                kb = max(chunk_bound(r), chunk_bound(s))
            if kb >= int(pad_sentinel("inner")):
                raise _sentinel_corruption(kb)
            if key_range == "narrow" and kb > MAX_MERGE_KEY:
                raise _narrow_violation(kb)
            per_slab, maxw = _scan_probe_presorted(r_sorted, keys,
                                                   (n + pad) // slab)
            window = slab
        return per_slab, maxw, window

    def run_pipelined() -> int:
        nonlocal total, last_i, done_this_run
        writer = AsyncCheckpointWriter(ckpt) if ckpt is not None else None
        pending = deque()   # (i, j, per_slab, maxw, window), dispatch order

        def resolve_until(limit: int) -> None:
            nonlocal total, done_this_run
            if len(pending) <= limit:
                return
            # batched host readbacks: pairs resolve in dispatch order, so
            # the realized prefix — the only thing ever checkpointed —
            # advances in row-major order, same as the synchronous loop
            with span("readback_flush", drained=len(pending) - limit):
                while len(pending) > limit:
                    pi, pj, per_slab, maxw, window = pending.popleft()
                    _check_weight_window(int(host_readback(maxw)), window)
                    total += int(host_readback(per_slab)
                                 .astype(np.uint64).sum())
                    done_this_run += 1
                    if writer is not None:
                        writer.save(state_dict(pi, pj + 1, total))
                    report(pi, pj)

        prefetchers = []

        def open_prefetcher(it, depth, side):
            pf = _Prefetcher(it, depth, measurements, side)
            prefetchers.append(pf)
            return pf

        try:
            inner_pf = open_prefetcher(iter(r_chunks), 1, "inner")
            for i, (r, rb) in enumerate(inner_pf):
                if i < start_i:
                    continue
                row_start_j = start_j if i == start_i else 0
                r_sorted = None     # built at the row's first probed pair
                outer_pf = open_prefetcher(iter(s_iter()), prefetch_depth,
                                           "outer")
                row_cols = 0
                for j, (s, sb) in enumerate(outer_pf):
                    row_cols = j + 1
                    if j < row_start_j:
                        continue
                    _faults.check(_faults.GRID_KILL, measurements)
                    reused = r_sorted is not None
                    if r.key_hi is None and r_sorted is None:
                        r_sorted = presort_keys(r.key)
                    kb = (max(rb, sb) if rb is not None and sb is not None
                          else None)

                    def dispatch(r=r, s=s, rs=r_sorted, kb=kb):
                        _faults.check(_faults.GRID_TRANSIENT, measurements)
                        return dispatch_probe(r, s, rs, kb)

                    with span("grid_pair", i=i, j=j):
                        if retry_policy is not None:
                            res = _retry_execute(
                                dispatch, retry_policy,
                                retryable=retry_on
                                or (_faults.TransientFault,),
                                measurements=measurements,
                                label=f"grid_pair({i},{j})")
                        else:
                            res = dispatch()
                    if measurements is not None:
                        measurements.incr(GRIDPAIRS)
                        if reused:
                            measurements.incr(SORTREUSE)
                    pending.append((i, j, *res))
                    resolve_until(readback_depth)
                prefetchers.remove(outer_pf)
                outer_pf.close()
                note_cols(row_cols)
                last_i = i + 1
            resolve_until(0)
            if writer is not None:
                # flush barrier, then ONE synchronous final save: the done
                # marker must be durable before the total is returned
                writer.flush()
                ckpt.save(state_dict(last_i, 0, total, done=True), done=True)
            return total
        finally:
            for pf in prefetchers:
                pf.close()
            if writer is not None:
                # close() flushes whatever realized state was enqueued —
                # on an error path that preserves the most progress a
                # resume may legally claim (every flushed pair resolved)
                writer.close()

    return run_pipelined() if pipeline == "on" else run_sync()

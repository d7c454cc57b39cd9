"""Atomic checkpoint/resume for out-of-core joins.

Generalizes the checkpoint discipline that grew inside
``ops/chunked.chunked_join_grid`` into a reusable manager, so a killed
1B-row grid run resumes from its last completed chunk pair instead of
restarting (the single-shot reference has no such capability, SURVEY.md
§5.4).  File format (JSON, one object):

    {"<cursor/count fields...>", "done": bool, "fingerprint": {...}}

Rules:

  * **Atomicity** — writes go to ``<path>.tmp.<pid>`` then ``fsync`` +
    ``os.replace``: a reader never
    observes a torn file, a crash mid-write leaves the previous checkpoint
    intact.
  * **Fingerprint** — a JSON-serializable dict identifying the run
    (slab size, input tag, grid shape, ...).  ``load`` raises
    :class:`CheckpointMismatch` when the file's fingerprint differs:
    resuming a *different* join from a stale file would silently return a
    wrong total.  Callers choose the fields; equality is exact.
  * **Corruption** — unreadable/truncated files restart from scratch
    (``load`` returns None) rather than wedging every rerun.
  * **Durability beats availability for writes** — a failed *save* must not
    kill a healthy multi-hour join: I/O errors are swallowed into a
    ``checkpoint_save_failed`` trace event (the run just loses one resume
    point).

Counters: ``CKPTSAVE`` per checkpoint written, ``CKPTLOAD`` per successful
resume (missing files count neither).
"""

from __future__ import annotations

import atexit
import contextlib
import json
import os
from typing import Dict, Optional

from tpu_radix_join.performance.measurements import CKPTLOAD, CKPTSAVE
from tpu_radix_join.robustness import faults as _faults
from tpu_radix_join.robustness.retry import CHECKPOINT_MISMATCH


class CheckpointMismatch(ValueError):
    """Checkpoint fingerprint does not match the current run config."""

    failure_class = CHECKPOINT_MISMATCH


class CheckpointManager:
    """One checkpoint file + fingerprint guard (see module docstring)."""

    def __init__(self, path: str, fingerprint: dict, measurements=None):
        self.path = path
        self.fingerprint = fingerprint
        self.measurements = measurements

    def _span(self, name: str):
        m = self.measurements
        return m.span(name) if m is not None else contextlib.nullcontext()

    def load(self) -> Optional[dict]:
        """The saved state dict (including ``done``), or None when there is
        nothing valid to resume from.  Raises :class:`CheckpointMismatch` on
        a fingerprint conflict — never silently resumes the wrong join."""
        m = self.measurements
        if not os.path.exists(self.path):
            return None
        try:
            with self._span("ckpt_load"):
                _faults.check(_faults.CKPT_LOAD, m)
                with open(self.path) as f:
                    state = json.load(f)
                saved_fp = state.pop("fingerprint")
        except (json.JSONDecodeError, KeyError, OSError) as e:
            # truncated/corrupt checkpoint: restart from zero rather than
            # wedging every rerun on an unreadable file
            if m is not None:
                m.event("checkpoint_corrupt", path=self.path, error=repr(e))
            return None
        if saved_fp != self.fingerprint:
            raise CheckpointMismatch(
                f"checkpoint {self.path} belongs to a different join "
                f"({saved_fp} != {self.fingerprint}); remove it or use a "
                f"distinct fingerprint/tag")
        if m is not None:
            m.incr(CKPTLOAD)
            m.event("checkpoint_load", path=self.path,
                    done=bool(state.get("done")))
        return state

    def save(self, state: dict, done: bool = False,
             span: str = "ckpt_save") -> bool:
        """Atomically persist ``state`` (+ ``done`` + fingerprint); returns
        False (after recording a trace event) on I/O failure instead of
        raising — losing one resume point must not kill the join.

        ``span`` names the timeline span the write is recorded under:
        "ckpt_save" for synchronous critical-path saves, "ckpt_flush" when
        the write happens on the :class:`AsyncCheckpointWriter`'s flush
        thread (off the critical path — the distinction is what the
        overlap timeline shows)."""
        m = self.measurements
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            with self._span(span):
                _faults.check(_faults.CKPT_SAVE, m)
                with open(tmp, "w") as f:
                    json.dump({**state, "done": done,
                               "fingerprint": self.fingerprint}, f)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, self.path)
        except OSError as e:
            if m is not None:
                m.event("checkpoint_save_failed", path=self.path,
                        error=repr(e))
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        if m is not None:
            m.incr(CKPTSAVE)
        return True


class AsyncCheckpointWriter:
    """Write-behind mode for a :class:`CheckpointManager`: ``save()``
    enqueues and returns immediately; a single daemon thread performs the
    fsync + rename while the caller computes the next chunk pair
    (ops/chunked.py pipelined grid).

    Semantics that preserve the "every saved pair is realized" resume
    invariant:

      * **Latest-wins coalescing** — the queue holds at most ONE pending
        state; enqueueing replaces it.  A newer state always covers a
        strict superset of realized pairs, so dropping the older write
        loses at most one resume point, never correctness (the same
        trade the manager's swallowed-save rule already makes).
      * **Callers enqueue only realized states** — the grid resolves a
        pair's device counts to a host total *before* enqueueing, so no
        state on disk ever claims an unrealized pair.
      * **flush() is a barrier** — returns only once every enqueued state
        has hit the disk (or failed into the manager's
        ``checkpoint_save_failed`` event); the grid flushes before its
        final synchronous ``done=True`` save and on every exit path.

    Writes are recorded under the "ckpt_flush" span (the timeline shows
    them overlapping the next pair's "grid_pair" span instead of
    serializing after it).
    """

    def __init__(self, manager: CheckpointManager):
        import threading
        self._mgr = manager
        self._cond = threading.Condition()
        self._pending = None          # (state, done) | None
        self._busy = False
        self._stop = False
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="ckpt-write-behind", daemon=True)
        self._thread.start()
        # The flush thread is a daemon: a clean sys.exit between save()
        # and flush() would kill it mid-queue and silently drop the final
        # checkpoint.  Registering close() guarantees the interpreter
        # drains the queue on any non-SIGKILL exit; explicit close()
        # unregisters so a long-lived process doesn't accumulate dead
        # callbacks.
        atexit.register(self.close)

    def save(self, state: dict, done: bool = False) -> None:
        with self._cond:
            self._pending = (dict(state), done)
            self._cond.notify_all()

    def _run(self):
        while True:
            with self._cond:
                while self._pending is None and not self._stop:
                    self._cond.wait()
                if self._pending is None:
                    return            # stopped with nothing left to write
                state, done = self._pending
                self._pending = None
                self._busy = True
            try:
                self._mgr.save(state, done=done, span="ckpt_flush")
            finally:
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()

    def flush(self) -> None:
        """Barrier: every state enqueued before this call is on disk (or
        recorded as a failed save) when it returns."""
        with self._cond:
            while self._pending is not None or self._busy:
                self._cond.wait()

    def close(self) -> None:
        """Flush outstanding writes and stop the thread (idempotent —
        safe to call explicitly, from ``with``-exit, and again from the
        atexit hook)."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._stop = True
            self._cond.notify_all()
        self._thread.join()
        try:
            atexit.unregister(self.close)
        except Exception:       # pragma: no cover - interpreter teardown
            pass

    def __enter__(self) -> "AsyncCheckpointWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class PartitionManifest:
    """Append-only per-partition completion manifest (elastic recovery).

    Extends the checkpoint discipline from "one cursor per grid run" to
    *partition granularity*: one JSONL line per realized network
    partition —

        {"fingerprint": {...}, "schema": 1}          # header line
        {"partition": 3, "count": 4096, "owner": 1, "epoch": 0}
        ...

    Rules carried over from :class:`CheckpointManager`:

      * **Kill-never-overclaims** — callers append a line only AFTER the
        partition's count is realized on host; the last line of a
        killed writer may be torn and is skipped on read, so the
        manifest never claims unrealized work.
      * **Fingerprint guard** — the header binds the manifest to one
        (inputs, geometry) identity; a conflicting header raises
        :class:`CheckpointMismatch` (resuming counts from a different
        join would splice wrong totals), a corrupt header restarts from
        zero.
      * **Durability beats availability** — a failed append is swallowed
        into a ``manifest_append_failed`` event (the run loses one
        resume point, not its life).

    Recovery (robustness/recovery.py) reads :meth:`completed` to skip
    every realized partition and recompute exactly the lost rank's
    unfinished ones; the ``owner``/``epoch`` stamps make the recovery
    timeline reconstructible in post-mortem bundles.

    **Fencing (hedge-never-double-counts)** — per partition, a line at a
    strictly newer epoch supersedes (a partition re-realized after a
    membership change owns its new count), but within one epoch the
    FIRST writer wins: when a straggler hedge (robustness/straggler.py)
    realizes a partition before its slow original owner does, the
    original's late line is dead on arrival — read-side arbitration, so
    two uncoordinated appenders can never sum the same partition twice.
    :meth:`claim` records hedge intent (forensics + the HEDGEWIN /
    SPECWASTE split); the *done* line remains the only count arbiter.
    """

    def __init__(self, path: str, fingerprint: dict, measurements=None):
        self.path = path
        self.fingerprint = fingerprint
        self.measurements = measurements
        self._ensure_header()

    def _ensure_header(self) -> None:
        m = self.measurements
        header = None
        if os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    header = json.loads(f.readline())
            except (OSError, json.JSONDecodeError) as e:
                if m is not None:
                    m.event("manifest_corrupt", path=self.path,
                            error=repr(e))
                header = None
        if header is not None:
            if header.get("fingerprint") != self.fingerprint:
                raise CheckpointMismatch(
                    f"partition manifest {self.path} belongs to a different "
                    f"join ({header.get('fingerprint')} != "
                    f"{self.fingerprint}); remove it or use a distinct "
                    f"fingerprint/tag")
            return
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump({"fingerprint": self.fingerprint, "schema": 1}, f)
                f.write("\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        except OSError as e:
            if m is not None:
                m.event("manifest_init_failed", path=self.path,
                        error=repr(e))
            try:
                os.remove(tmp)
            except OSError:
                pass

    def mark_done(self, partition: int, count: int, owner: int,
                  epoch: int = 0) -> bool:
        """Append one realized-partition line; False (after an event) on
        I/O failure instead of raising."""
        m = self.measurements
        rec = {"partition": int(partition), "count": int(count),
               "owner": int(owner), "epoch": int(epoch)}
        try:
            with open(self.path, "a") as f:
                json.dump(rec, f)
                f.write("\n")
                f.flush()
                os.fsync(f.fileno())
        except OSError as e:
            if m is not None:
                m.event("manifest_append_failed", path=self.path,
                        error=repr(e))
            return False
        if m is not None:
            m.incr(CKPTSAVE)
        return True

    def mark_many(self, counts: Dict[int, int], owner_of, epoch: int = 0
                  ) -> int:
        """Bulk append (join epilogue: every partition realized at once).
        ``owner_of(p)`` maps a partition to its owner rank.  Returns the
        number of lines written."""
        n = 0
        for p, c in counts.items():
            if self.mark_done(p, c, owner_of(p), epoch):
                n += 1
        return n

    def completed(self) -> Dict[int, dict]:
        """``{partition: {"count", "owner", "epoch"}}`` of every realized
        partition; torn/corrupt lines are skipped — the
        kill-never-overclaims read side.  Arbitration per partition: a
        strictly newer epoch supersedes, and within one epoch the first
        writer wins (the hedge fence — a late-finishing original can
        never displace the speculative count that already landed)."""
        out: Dict[int, dict] = {}
        try:
            with open(self.path) as f:
                lines = f.readlines()
        except OSError:
            return out
        for line in lines[1:]:
            try:
                rec = json.loads(line)
                if "count" not in rec:
                    continue        # claim line, not a done line
                p = int(rec["partition"])
                ep = int(rec.get("epoch", 0))
                if p in out and ep <= out[p]["epoch"]:
                    continue        # first writer already won this epoch
                out[p] = {"count": int(rec["count"]),
                          "owner": int(rec["owner"]), "epoch": ep}
            except (ValueError, KeyError, json.JSONDecodeError):
                continue
        return out

    # ------------------------------------------------------------- claims
    def claim(self, partition: int, owner: int, epoch: int = 0) -> bool:
        """Record hedge intent on a partition; returns True when this
        ``(owner, epoch)`` holds the claim (first claimant at the highest
        epoch), False when a rival claimed it first or the partition is
        already done at ``epoch`` or newer.  Claims are advisory — they
        split HEDGEWIN from SPECWASTE and render in the post-mortem
        timeline — while the *done*-line fence in :meth:`completed`
        remains the count arbiter, so a lost claim race can waste work
        but never double-count."""
        m = self.measurements
        done = self.completed().get(int(partition))
        if done is not None and done["epoch"] >= int(epoch):
            return False
        holder = self.claims().get(int(partition))
        if holder is not None and holder["epoch"] >= int(epoch):
            return (holder["owner"] == int(owner)
                    and holder["epoch"] == int(epoch))
        rec = {"partition": int(partition), "claim": True,
               "owner": int(owner), "epoch": int(epoch)}
        try:
            with open(self.path, "a") as f:
                json.dump(rec, f)
                f.write("\n")
                f.flush()
                os.fsync(f.fileno())
        except OSError as e:
            if m is not None:
                m.event("manifest_append_failed", path=self.path,
                        error=repr(e))
            return False
        if m is not None:
            m.event("hedge_claim", partition=int(partition),
                    owner=int(owner), epoch=int(epoch))
        return True

    def claims(self) -> Dict[int, dict]:
        """``{partition: {"owner", "epoch"}}`` of every claimed partition,
        arbitrated like :meth:`completed` (newer epoch supersedes, first
        claimant wins within an epoch)."""
        out: Dict[int, dict] = {}
        try:
            with open(self.path) as f:
                lines = f.readlines()
        except OSError:
            return out
        for line in lines[1:]:
            try:
                rec = json.loads(line)
                if not rec.get("claim"):
                    continue
                p = int(rec["partition"])
                ep = int(rec.get("epoch", 0))
                if p in out and ep <= out[p]["epoch"]:
                    continue
                out[p] = {"owner": int(rec["owner"]), "epoch": ep}
            except (ValueError, KeyError, json.JSONDecodeError):
                continue
        return out

    def audit(self) -> dict:
        """The double-count audit the chaos soak asserts on: the fenced
        total (sum of winning counts), plus every partition where a
        second writer's same-epoch line was fenced out — absorbed
        double-count attempts, each one a would-have-been wrong total."""
        winners = self.completed()
        fenced: Dict[int, int] = {}
        try:
            with open(self.path) as f:
                lines = f.readlines()
        except OSError:
            lines = []
        for line in lines[1:]:
            try:
                rec = json.loads(line)
                if "count" not in rec:
                    continue
                p = int(rec["partition"])
                win = winners.get(p)
                if (win is not None and int(rec.get("epoch", 0)) == win["epoch"]
                        and int(rec["owner"]) != win["owner"]):
                    fenced[p] = fenced.get(p, 0) + 1
            except (ValueError, KeyError, json.JSONDecodeError):
                continue
        return {"total": sum(rec["count"] for rec in winners.values()),
                "partitions": len(winners),
                "fenced_duplicates": fenced}

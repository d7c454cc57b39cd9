"""Merge per-rank span files into one aligned Chrome-trace timeline.

Each rank's ``<rank>.spans.json`` (observability/spans.py) carries event
timestamps relative to that rank's own wall-clock epoch anchor.  The merge
shifts every rank onto the earliest anchor's clock — host phase spans
and robustness instant events from every rank then share one timeline a
single Perfetto load can scrub across ranks (the cross-rank view the
reference's per-rank ``.perf`` scalars never had).  Device operations are
not here: they live in the profiler's own trace (``--trace``), where the
program's ``trj.*`` host spans share their clock.
"""

from __future__ import annotations

import glob
import json
import os
from typing import List, Optional, Tuple

from tpu_radix_join.observability.spans import SPAN_SUFFIX


def find_span_files(timeline_dir: str) -> List[str]:
    return sorted(
        glob.glob(os.path.join(timeline_dir, "**", f"*{SPAN_SUFFIX}"),
                  recursive=True))


def _load(path: str) -> Tuple[Optional[dict], Optional[str]]:
    """Read one span file; returns (doc, None) or (None, skip-reason).
    The reason travels into the merge metadata and warnings so a partial
    merge names *why* each file was dropped, not just that it was."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError as e:
        return None, f"unreadable ({e.__class__.__name__}: {e})"
    except ValueError as e:
        return None, f"malformed JSON (torn write? {e})"
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return None, "not a span file (no traceEvents object)"
    return doc, None


def merge_timeline(timeline_dir: str,
                   out_path: Optional[str] = None) -> Optional[dict]:
    """Merge every ``*.spans.json`` under ``timeline_dir``.

    Returns the merged Chrome-trace object (written to ``out_path`` when
    given), or None when the directory holds no span files.

    Partial-tolerant by design: a rank killed mid-run (watchdog, SIGKILL)
    leaves a truncated or absent span file, and the surviving ranks'
    timeline is exactly what the post-mortem needs.  Unreadable files are
    skipped but *named* (``metadata["corrupt_files"]``), and ranks absent
    from a world whose size the tracer tags declare (``tags.nodes``) are
    listed in ``metadata["missing_ranks"]`` so the merge says out loud
    that it is partial instead of silently narrowing the world.
    """
    docs: List[Tuple[str, dict]] = []
    corrupt: List[str] = []
    corrupt_reasons: List[dict] = []
    for path in find_span_files(timeline_dir):
        doc, reason = _load(path)
        if doc is not None:
            docs.append((path, doc))
        else:
            corrupt.append(os.path.basename(path))
            corrupt_reasons.append({"file": os.path.basename(path),
                                    "reason": reason})
    if not docs:
        return None

    anchors = []
    for path, doc in docs:
        md = doc.get("metadata", {})
        anchors.append(float(md.get("epoch_s", 0.0)))
    t0 = min(anchors)

    merged: List[dict] = []
    ranks = {}
    for (path, doc), epoch_s in zip(docs, anchors):
        md = doc.get("metadata", {})
        rank = int(md.get("rank", 0))
        shift_us = (epoch_s - t0) * 1e6
        ranks[rank] = {
            "file": os.path.basename(path),
            "trace_id": md.get("trace_id"),
            "epoch_s": epoch_s,
            "clock_shift_us": round(shift_us, 3),
            "tags": md.get("tags", {}),
        }
        for ev in doc["traceEvents"]:
            ev = dict(ev)
            if "ts" in ev:
                ev["ts"] = float(ev["ts"]) + shift_us
            merged.append(ev)

    # expected world size: the largest ``nodes`` tag any rank declared
    # (Measurements.attach_tracer stamps it); 0 when no rank carried one
    expected = 0
    for info in ranks.values():
        try:
            expected = max(expected, int(info["tags"].get("nodes", 0)))
        except (TypeError, ValueError):
            pass
    missing = sorted(set(range(expected)) - set(ranks))
    doc = {
        "traceEvents": merged,
        "displayTimeUnit": "ms",
        "metadata": {
            "t0_epoch_s": t0,
            "ranks": {str(r): info for r, info in sorted(ranks.items())},
            "clock": "us since earliest rank epoch anchor",
            "expected_ranks": expected or len(ranks),
            "missing_ranks": missing,
            "corrupt_files": corrupt,
            "corrupt_file_reasons": corrupt_reasons,
            "partial": bool(missing or corrupt),
        },
    }
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                    exist_ok=True)
        tmp = f"{out_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, out_path)
    return doc

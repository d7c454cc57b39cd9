"""Cross-run telemetry ledger: the planner's long-term memory.

Every subsystem already measures itself — phase timers and counters
(performance/measurements.py), plan-vs-actual audit tables
(planner/audit.py), BENCH JSON lines (bench.py), per-query service
outcomes (service/session.py) — but each run's evidence dies with its
artifact directory.  The ledger is the append-only, schema-versioned
JSONL store that outlives runs: one row per observation, written at run
end from the live registry (main.py ``--ledger-dir``), per query by a
resident session, per bench by bench.py, and backfillable from committed
artifacts (``tools_make_report.py --emit-ledger``).

``planner/calibrate.py`` consumes these rows to re-fit the device
profile's REQUIRED_CONSTANTS and to attribute persistent PLANDRIFT to
the constant behind the drifting cost term — the continuously refreshed
profile ROADMAP item 2's layout search is blocked on.

Row shape (schema v1)::

    {"schema_version": 1, "kind": "run"|"bench"|"query"|"obs",
     "run_id": ..., "t_epoch_s": ..., **payload}

Reader discipline matches metrics.load_samples: torn lines (a killed
writer's last record) are skipped, and rows stamped with a NEWER schema
than this build understands are skipped rather than misread — an old
reader must never silently misinterpret a future field.
"""

from __future__ import annotations

import glob
import itertools
import json
import os
import socket
import time
from typing import Dict, List, Optional, Tuple

LEDGER_SCHEMA_VERSION = 1
LEDGER_BASENAME = "ledger.jsonl"

#: row kinds the fitter understands ("obs" = a pre-reduced single-constant
#: observation, the extension point for future probes)
KINDS = ("run", "bench", "query", "obs")

#: bench.py's fixed workload — BENCH rows that predate the "size" tag
#: (rounds 1..9) all measured this 16M-per-side join
BENCH_DEFAULT_SIZE = 1 << 24

_seq = itertools.count()


def default_ledger_dir() -> str:
    """Where ``--profile auto`` looks for a ledger + fitted profile when no
    ``--ledger-dir`` is given: the environment override, else the
    repo-conventional ``artifacts/ledger``."""
    return (os.environ.get("TPU_RADIX_LEDGER_DIR")
            or os.path.join("artifacts", "ledger"))


def run_fingerprint(extra: Optional[dict] = None) -> dict:
    """Identity of the software stack a row was measured under (config and
    mesh ride in the payload; jax/jaxlib versions and backend here) — a
    fit must be able to exclude rows from a different XLA."""
    fp: Dict[str, object] = {"host": socket.gethostname()}
    try:
        import jax
        fp["jax"] = jax.__version__
        fp["backend"] = jax.default_backend()
    except Exception:                      # noqa: BLE001 — best-effort only
        pass
    try:
        import jaxlib.version
        fp["jaxlib"] = jaxlib.version.__version__
    except Exception:                      # noqa: BLE001
        pass
    if extra:
        fp.update(extra)
    return fp


class Ledger:
    """Append-only JSONL ledger at ``<dir>/ledger.jsonl`` (or an explicit
    ``*.jsonl`` path).  Appends are single-write + flush, so concurrent
    writers interleave whole lines and a SIGKILL tears at most one row —
    which the tolerant reader then skips."""

    def __init__(self, dir_or_path: str):
        self.path = (dir_or_path if dir_or_path.endswith(".jsonl")
                     else os.path.join(dir_or_path, LEDGER_BASENAME))

    def append(self, kind: str, payload: dict,
               run_id: Optional[str] = None,
               t_epoch_s: Optional[float] = None) -> dict:
        if kind not in KINDS:
            raise ValueError(f"unknown ledger row kind {kind!r} "
                             f"(want one of {KINDS})")
        row = {"schema_version": LEDGER_SCHEMA_VERSION,
               "kind": kind,
               "run_id": run_id or
               f"{kind}-{os.getpid()}-{int(time.time())}-{next(_seq)}",
               "t_epoch_s": round(t_epoch_s if t_epoch_s is not None
                                  else time.time(), 3)}
        for k, v in payload.items():
            if k not in row and v is not None:
                row[k] = v
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(self.path, "a") as f:
            f.write(json.dumps(row, default=str) + "\n")
            f.flush()
        return row

    def rows(self, kind: Optional[str] = None) -> List[dict]:
        return load_rows(self.path, kind=kind)


def load_rows(path: str, kind: Optional[str] = None) -> List[dict]:
    """Tolerant ledger read: missing file -> [], torn lines skipped,
    rows from a newer schema skipped (never misread)."""
    if path and not path.endswith(".jsonl"):
        path = os.path.join(path, LEDGER_BASENAME)
    out: List[dict] = []
    try:
        f = open(path)
    except OSError:
        return out
    with f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if not isinstance(row, dict):
                continue
            if int(row.get("schema_version", 1)) > LEDGER_SCHEMA_VERSION:
                continue
            if kind is not None and row.get("kind") != kind:
                continue
            out.append(row)
    return out


# --------------------------------------------------------- payload builders
def run_payload(measurements, config: Optional[dict] = None,
                workload: Optional[dict] = None,
                fingerprint: Optional[dict] = None) -> dict:
    """Distill a live Measurements registry into one ``kind="run"`` row:
    phase times, non-zero counters, the plan and its plan-vs-actual audit
    table when present, the workload geometry, and the stack fingerprint.
    The flight-recorder ring stays in forensics bundles — the ledger keeps
    reduced observations, not raw event streams."""
    m = measurements
    payload: Dict[str, object] = {
        "fingerprint": fingerprint or run_fingerprint(
            {"nodes": getattr(m, "num_nodes", 1)}),
        "times_us": {k: round(float(v), 1) for k, v in m.times_us.items()},
        "counters": {k: int(v) for k, v in m.counters.items() if v},
    }
    wl = workload or {k: m.meta[k] for k in
                      ("tuples_per_node", "global_size", "nodes")
                      if k in m.meta}
    if wl:
        payload["workload"] = wl
    for key in ("plan", "plan_vs_actual", "exchange_plan", "failure_class"):
        if m.meta.get(key) is not None:
            payload[key] = m.meta[key]
    cfg = config if config is not None else m.meta.get("config")
    if isinstance(cfg, dict):
        payload["config"] = {k: v for k, v in cfg.items()
                             if isinstance(v, (int, float, str, bool))}
        if cfg.get("repeat"):
            payload["repeat"] = int(cfg["repeat"])
    return payload


def bench_payload(doc: dict,
                  size_default: int = BENCH_DEFAULT_SIZE) -> Optional[dict]:
    """One ``kind="bench"`` row from a BENCH result dict or the runner's
    artifact wrapper (``{"parsed": {...}, "rc": N, ...}``).  Returns None
    when there is no parsed result at all (a round whose capture died
    before the JSON line)."""
    parsed = doc.get("parsed") if isinstance(doc.get("parsed"), dict) else doc
    if not isinstance(parsed, dict) or "metric" not in parsed:
        return None
    payload: Dict[str, object] = {
        "metric": parsed["metric"],
        "value": float(parsed.get("value") or 0.0),
        "unit": parsed.get("unit", ""),
        "size": int(parsed.get("size") or size_default),
    }
    for k, v in parsed.items():
        if k not in payload and isinstance(v, (int, float, str, bool)):
            payload[k] = v
    if doc is not parsed and "rc" in doc:
        payload["rc"] = doc["rc"]
    return payload


def rows_from_perf_dir(d: str) -> List[Tuple[str, dict]]:
    """``(run_id, payload)`` run rows from one committed perf artifact dir
    (``<rank>.perf`` + ``<rank>.info``) — the backfill path that turns
    rounds 1..8's chip evidence into fit samples."""
    from tpu_radix_join.performance.measurements import Measurements

    out: List[Tuple[str, dict]] = []
    try:
        ranks = Measurements.load(d)
    except (OSError, ValueError):
        return out
    base = os.path.basename(d.rstrip("/"))
    for m in ranks:
        meta: dict = {}
        info_path = os.path.join(d, f"{m.node_id}.info")
        try:
            with open(info_path) as f:
                meta = json.load(f)
        except (OSError, ValueError):
            pass
        cfg = meta.get("config") or {}
        wl = {k: meta[k] for k in
              ("tuples_per_node", "global_size", "nodes") if k in meta}
        payload = run_payload(
            m, config=cfg, workload=wl or None,
            fingerprint={"host": meta.get("host", "?"),
                         "nodes": meta.get("nodes", m.num_nodes),
                         "artifact": d})
        for key in ("plan", "plan_vs_actual", "failure_class"):
            if meta.get(key) is not None:
                payload[key] = meta[key]
        out.append((f"{base}:{m.node_id}", payload))
    return out


def ingest_artifacts(base_dir: str, out_path: str) -> Dict[str, int]:
    """Backfill: distill the bench.py result files (``BENCH_*.json``) and
    every ``perf_*`` dir under ``base_dir`` (one level of nesting allowed:
    ``<dir>/*/perf_*``) into ledger rows at ``out_path``.  Row timestamps
    are the artifacts' file mtimes, so backfilled provenance keeps its real
    age.  Returns ``{"bench": n, "run": n}``."""
    led = Ledger(out_path)
    counts = {"bench": 0, "run": 0}
    for path in sorted(glob.glob(os.path.join(base_dir, "BENCH_*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        payload = bench_payload(doc)
        if payload is None:
            continue
        stem = os.path.splitext(os.path.basename(path))[0]
        led.append("bench", payload, run_id=stem,
                   t_epoch_s=os.path.getmtime(path))
        counts["bench"] += 1
    perf_dirs = sorted(glob.glob(os.path.join(base_dir, "perf_*")))
    perf_dirs += sorted(glob.glob(os.path.join(base_dir, "*", "perf_*")))
    for d in perf_dirs:
        if not os.path.isdir(d):
            continue
        for run_id, payload in rows_from_perf_dir(d):
            led.append("run", payload, run_id=run_id,
                       t_epoch_s=os.path.getmtime(d))
            counts["run"] += 1
    return counts

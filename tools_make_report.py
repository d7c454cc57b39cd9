"""Assemble the round's chip evidence into one summary table.

    python tools_make_report.py [chiprun_out]
    python tools_make_report.py chiprun_out --emit-profile out.json \
        [--profile-name v5e_fit]
    python tools_make_report.py chiprun_out --emit-timeline out.json
    python tools_make_report.py chiprun_out --emit-ledger artifacts/ledger

Reads every perf dir (`<rank>.perf`/`<rank>.info`) and trace breakdown
(`trace_*/breakdown.json`) under the artifact dir (by default
``chiprun_out/``, where chip runs leave their outputs) and prints a
markdown summary (per-workload phase columns in ms/join net of repeats,
JPROCRATE, CTOTAL where present, trace sort shares).
The output is the raw material for BASELINE.md's achieved tables — numbers
come straight from the committed artifacts, no hand transcription.

``--emit-profile`` distills the same artifacts into a planner device
profile (tpu_radix_join/planner/profile.py) instead of a table: measured
SDISPATCH becomes ``dispatch_floor_ms``, a device-plane sort-discipline
trace breakdown becomes ``sort_stage_unit_ms``, every derived constant
cites the artifact it came from, and constants the artifacts cannot
measure keep the base profile's committed values + citations.

``--emit-timeline`` merges the per-rank ``<rank>.spans.json`` files a
``--timeline-dir`` run left under the artifact dir into one Chrome-trace
JSON on a shared clock (observability.timeline.merge_timeline) — load the
output in Perfetto / chrome://tracing.

``--emit-ledger OUT`` backfills the cross-run telemetry ledger
(observability/ledger.py) from an artifact dir: every ``BENCH_*.json``
bench.py result line saved there becomes a ``kind="bench"`` row and every
``perf_*`` dir (one nesting level allowed) a ``kind="run"`` row,
timestamped by file mtime.  The backfilled ledger is what
``tools_profile_fit.py fit`` turns into a provenance-carrying schema-v3
profile.
"""

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tpu_radix_join.performance.measurements import Measurements

PHASES = ("JHIST", "JMPI", "SLOCPREP", "JPROC", "BPBUILD", "BPPROBE",
          "CTOTAL", "SDISPATCH")


def perf_row(d):
    ms = Measurements.load(d)
    if not ms:
        return None
    m = ms[0]
    repeat = 1
    info_path = os.path.join(d, f"{m.node_id}.info")
    meta = {}
    if os.path.exists(info_path):
        with open(info_path) as f:
            meta = json.load(f)
        repeat = int((meta.get("config") or {}).get("repeat") or 1)
    pipelined = bool((meta.get("config") or {}).get("pipeline_repeats"))
    row = {"dir": os.path.basename(d), "repeat": repeat,
           "pipelined": "y" if pipelined else "",
           "key_range": meta.get("key_range", "")}
    # Once-per-invocation tags: in --pipeline-repeats runs the sizing
    # pre-pass (JHIST) executes once for the whole batch of dispatches, so
    # dividing it by repeat would report a per-join cost no join pays;
    # synchronous repeats re-run it per join, where dividing is right.
    once_per_call = ("JHIST",) if pipelined else ()
    for tag in PHASES:
        if tag in m.times_us:
            div = 1 if (tag == "SDISPATCH" or tag in once_per_call) else repeat
            row[tag] = m.times_us[tag] / div / 1e3
    if "JPROCRATE" in m.counters:
        row["JPROCRATE_M/s"] = m.counters["JPROCRATE"] / 1e6
    if "RESULTS" in m.counters:
        # raw registry value: the driver stores the single-join count for
        # synchronous repeats, the cumulative for pipelined mode — dividing
        # here would guess wrong for one of them
        row["RESULTS"] = m.counters["RESULTS"]
    return row


def emit_profile(base_dir: str, out_path: str, name: str = None) -> int:
    """Distill one round's chip artifacts into a planner device profile."""
    from tpu_radix_join.performance.measurements import DEVICE_PLANE
    from tpu_radix_join.planner.profile import (SORT_REF_ELEMS, load_profile,
                                                sort_stage_units)

    base = load_profile()
    updates = {}

    # dispatch floor: the per-program SDISPATCH column; median over ranks
    # and runs (a single outlier dispatch must not define the profile)
    floors = []
    for d in sorted(glob.glob(os.path.join(base_dir, "perf_*"))):
        for m in Measurements.load(d) or []:
            if "SDISPATCH" in m.times_us:
                floors.append((m.times_us["SDISPATCH"] / 1e3,
                               os.path.basename(d)))
    if floors:
        floors.sort()
        val, src = floors[len(floors) // 2]
        updates["dispatch_floor_ms"] = {
            "value": round(val, 3),
            "source": f"artifact:{base_dir}/{src} SDISPATCH "
                      f"(median of {len(floors)} runs)"}

    # sort stage unit: newest device-plane sort-discipline trace breakdown,
    # normalized by the stage model (unit = t / ((M/ref) * U(M)))
    for path in sorted(glob.glob(os.path.join(base_dir, "trace_*",
                                              "breakdown.json")),
                       reverse=True):
        try:
            with open(path) as f:
                bd = json.load(f)
        except (OSError, ValueError):
            continue
        if (bd.get("sort_share") and bd.get("size")
                and bd.get("discipline", "sort") == "sort"
                and bd.get("plane", "").startswith(DEVICE_PLANE)):
            union = 2 * int(bd["size"])
            t_sort = bd["busy_us"] * bd["sort_share"] / bd["iters"] / 1e3
            unit = t_sort / ((union / SORT_REF_ELEMS)
                            * sort_stage_units(union))
            updates["sort_stage_unit_ms"] = {
                "value": round(unit, 5),
                "source": f"artifact:{os.path.relpath(path)} "
                          f"(sort_share over {bd['iters']} iters, "
                          f"union {union})"}
            break

    if not updates:
        print(f"WARNING: no distillable measurements under {base_dir}; "
              f"emitting the base profile's committed constants unchanged",
              file=sys.stderr)
    prof = base.replace_constants(
        name=name or f"{base.name}+{os.path.basename(base_dir.rstrip('/'))}",
        **updates)
    prof.save(out_path)
    print(f"wrote {out_path} ({prof.name}): "
          f"{', '.join(sorted(updates)) or 'no constants refreshed'}")
    return 0


def emit_timeline(base_dir: str, out_path: str) -> int:
    """Merge per-rank span files under ``base_dir`` into one Chrome trace."""
    from tpu_radix_join.observability.timeline import merge_timeline

    doc = merge_timeline(base_dir, out_path=out_path)
    if doc is None:
        print(f"ERROR: no *.spans.json under {base_dir} — run the driver "
              f"with --timeline-dir first", file=sys.stderr)
        return 1
    md = doc["metadata"]
    spans = sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")
    instants = sum(1 for e in doc["traceEvents"] if e.get("ph") == "i")
    print(f"wrote {out_path}: {len(md['ranks'])} rank(s), {spans} spans, "
          f"{instants} instant events on one clock "
          f"(t0={md['t0_epoch_s']:.3f}); load in Perfetto/chrome://tracing")
    # a watchdog-killed / SIGKILLed rank leaves no (or a torn) span file;
    # the merge is partial-tolerant, but the gap must be said out loud
    if md.get("missing_ranks"):
        print(f"WARNING: missing_ranks={md['missing_ranks']} — "
              f"{len(md['missing_ranks'])} of {md['expected_ranks']} "
              f"expected rank(s) left no readable span file; the timeline "
              f"is PARTIAL", file=sys.stderr)
    if md.get("corrupt_files"):
        # name each skipped file *and why* — a torn write, a permissions
        # problem, and a non-span JSON all want different operator action
        reasons = {e["file"]: e["reason"]
                   for e in md.get("corrupt_file_reasons", [])}
        detail = "; ".join(
            f"{f}: {reasons.get(f, 'unknown reason')}"
            for f in md["corrupt_files"])
        print(f"WARNING: skipped {len(md['corrupt_files'])} span "
              f"file(s) — {detail}", file=sys.stderr)
    return 0


def emit_ledger(base_dir: str, out_path: str) -> int:
    """Backfill the cross-run ledger from an artifact dir's BENCH/perf files."""
    from tpu_radix_join.observability.ledger import Ledger, ingest_artifacts

    counts = ingest_artifacts(base_dir, out_path)
    total = counts["bench"] + counts["run"]
    print(f"wrote {Ledger(out_path).path}: {counts['bench']} bench row(s), "
          f"{counts['run']} run row(s)")
    if total == 0:
        print(f"WARNING: nothing to ingest under {base_dir}",
              file=sys.stderr)
        return 1
    return 0


def main() -> int:
    argv = sys.argv[1:]
    emit = prof_name = timeline = ledger = None
    if "--emit-profile" in argv:
        i = argv.index("--emit-profile")
        emit = argv[i + 1]
        del argv[i:i + 2]
    if "--profile-name" in argv:
        i = argv.index("--profile-name")
        prof_name = argv[i + 1]
        del argv[i:i + 2]
    if "--emit-timeline" in argv:
        i = argv.index("--emit-timeline")
        timeline = argv[i + 1]
        del argv[i:i + 2]
    if "--emit-ledger" in argv:
        i = argv.index("--emit-ledger")
        ledger = argv[i + 1]
        del argv[i:i + 2]
    base = argv[0] if argv else "chiprun_out"
    if ledger is not None:
        return emit_ledger(base, ledger)
    if timeline is not None:
        return emit_timeline(base, timeline)
    if emit is not None:
        return emit_profile(base, emit, prof_name)
    print(f"# Evidence summary: {base}\n")

    rows = [r for r in (perf_row(d) for d in sorted(
        glob.glob(os.path.join(base, "perf_*")))) if r]
    if rows:
        # the pipelined column only appears when some run used it, so
        # tables over legacy artifacts keep their committed shape
        keys = ["dir", "repeat"] + (
            ["pipelined"] if any(r["pipelined"] for r in rows) else []
        ) + ["key_range"] + [
            k for k in (*PHASES, "JPROCRATE_M/s", "RESULTS")
            if any(k in r for r in rows)]
        print("\n## Perf artifacts (ms/join; SDISPATCH = floor per program)\n")
        print("| " + " | ".join(keys) + " |")
        print("|" + "---|" * len(keys))
        for r in rows:
            cells = []
            for k in keys:
                v = r.get(k, "")
                cells.append(f"{v:.1f}" if isinstance(v, float) else str(v))
            print("| " + " | ".join(cells) + " |")

    traces = sorted(glob.glob(os.path.join(base, "trace_*",
                                           "breakdown.json")))
    if traces:
        print("\n## Trace breakdowns\n")
        for path in traces:
            with open(path) as f:
                bd = json.load(f)
            per_iter = bd["busy_us"] / bd["iters"] / 1e3
            print(f"- {os.path.relpath(path, base)}: plane `{bd['plane']}`, "
                  f"{per_iter:.1f} ms/iter device-busy, "
                  f"sort share {100 * bd['sort_share']:.1f}%")
            top = sorted(bd["ops"].items(), key=lambda kv: -kv[1]["us"])[:5]
            for name, v in top:
                print(f"    - {v['us'] / bd['iters'] / 1e3:8.2f} ms/iter  "
                      f"{name[:80]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

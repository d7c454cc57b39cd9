"""Observability layer end to end: span timelines through the real driver,
live metrics sampling, the cross-rank merge, and the regression gate.

Covers the ISSUE 3 acceptance criteria directly:

  * a CPU driver run with ``--timeline-dir`` exports a well-formed
    Chrome-trace span file + >= 1 metrics sample (smoke, in-process);
  * a 2-rank run's per-rank span files merge via ``tools_make_report.py
    --emit-timeline`` into ONE timeline on a shared clock;
  * ``tools_check_regress.py`` flags a synthetic 2x JTOTAL regression and
    passes an unchanged result.
"""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

from tpu_radix_join.main import main
from tpu_radix_join.observability import (MetricsSampler, SpanTracer,
                                          load_samples, merge_timeline)
from tpu_radix_join.observability.regress import (check_result, compare_tags,
                                                  extract_tags, format_table,
                                                  parse_tag_thresholds)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_spans(path):
    with open(path) as f:
        doc = json.load(f)
    assert "traceEvents" in doc and "metadata" in doc
    return doc


def _events(doc, ph=None, name=None):
    return [e for e in doc["traceEvents"]
            if (ph is None or e.get("ph") == ph)
            and (name is None or e.get("name") == name)]


# -------------------------------------------------------------- driver smoke

def test_driver_timeline_and_metrics_smoke(tmp_path):
    """CPU driver + --timeline-dir + --metrics-interval: well-formed Chrome
    trace with the phase vocabulary as spans, >= 1 metrics sample."""
    d = str(tmp_path)
    rc = main(["--tuples-per-node", "2048", "--nodes", "2",
               "--timeline-dir", d, "--metrics-interval", "0.05"])
    assert rc == 0

    doc = _load_spans(os.path.join(d, "0.spans.json"))
    md = doc["metadata"]
    assert md["rank"] == 0 and md["epoch_s"] > 0 and md["trace_id"]
    spans = {e["name"] for e in _events(doc, ph="X")}
    # the Measurements vocabulary mirrors into the timeline automatically
    assert {"JTOTAL", "JHIST", "JPROC"} <= spans
    for e in _events(doc, ph="X"):
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["pid"] == 0
    # every span carries the file-level tags (nodes) in args
    jt = _events(doc, ph="X", name="JTOTAL")[0]
    assert jt["args"].get("nodes") == 2
    # metadata events name the process/thread for Perfetto
    assert _events(doc, ph="M", name="process_name")

    samples = load_samples(os.path.join(d, "0.metrics.jsonl"))
    assert len(samples) >= 1
    assert "host" in samples[0] and "t_epoch_s" in samples[0]
    # the final (stop-time) sample snapshots the finished phase registry
    assert "JTOTAL" in samples[-1]["times_us"]


def test_driver_metrics_interval_needs_a_dir():
    with pytest.raises(SystemExit):
        main(["--tuples-per-node", "1024", "--metrics-interval", "0.1"])


def test_grid_driver_timeline_pairs_and_checkpoints(tmp_path):
    """Grid mode: per-pair spans, checkpoint-save spans, and the
    chunked_grid strategy tag all land on the timeline.  The default
    --grid-pipeline auto runs the pipelined engine on this 2x2 grid, so
    per-pair saves ride the write-behind thread (ckpt_flush spans) and
    only the final done marker is a synchronous ckpt_save."""
    tl = str(tmp_path / "tl")
    rc = main(["--nodes", "1", "--tuples-per-node", "4096",
               "--grid-chunk-tuples", "2048",
               "--checkpoint-dir", str(tmp_path / "ckpt"),
               "--timeline-dir", tl])
    assert rc == 0
    doc = _load_spans(os.path.join(tl, "0.spans.json"))
    pairs = _events(doc, ph="X", name="grid_pair")
    assert len(pairs) == 4                      # 2x2 chunk grid
    assert {(e["args"]["i"], e["args"]["j"]) for e in pairs} == {
        (0, 0), (0, 1), (1, 0), (1, 1)}
    assert all(e["args"].get("strategy") == "chunked_grid" for e in pairs)
    assert len(_events(doc, ph="X", name="ckpt_save")) >= 1   # done marker
    assert len(_events(doc, ph="X", name="ckpt_flush")) >= 1  # write-behind
    assert len(_events(doc, ph="X", name="prefetch")) >= 2    # staged chunks


# ---------------------------------------------------------- cross-rank merge

def test_two_rank_timeline_merge(tmp_path):
    """Two real jax.distributed CPU processes x --timeline-dir, merged by
    ``tools_make_report.py --emit-timeline`` into one aligned timeline:
    both ranks' host phases on one clock, per-rank shift recorded."""
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    d = str(tmp_path)
    procs = []
    for rank in range(2):
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            JAX_NUM_PROCESSES="2",
            JAX_PROCESS_ID=str(rank),
            PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "tpu_radix_join.main",
             "--tuples-per-node", "1024", "--nodes", "8", "--hosts", "2",
             "--timeline-dir", d, "--metrics-interval", "0.1"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env=env, text=True, cwd=REPO))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    joined = "\n---- rank boundary ----\n".join(outs)
    assert all(p.returncode == 0 for p in procs), joined
    for rank in range(2):
        assert os.path.exists(os.path.join(d, f"{rank}.spans.json")), joined
        assert load_samples(os.path.join(d, f"{rank}.metrics.jsonl")), joined

    merged_path = str(tmp_path / "merged.json")
    cp = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools_make_report.py"),
         d, "--emit-timeline", merged_path],
        capture_output=True, text=True, cwd=REPO)
    assert cp.returncode == 0, cp.stdout + cp.stderr
    assert "2 rank(s)" in cp.stdout, cp.stdout

    with open(merged_path) as f:
        merged = json.load(f)
    md = merged["metadata"]
    assert set(md["ranks"]) == {"0", "1"}
    # the earliest rank anchors the shared clock; the other carries the
    # positive epoch-delta shift
    shifts = [md["ranks"][r]["clock_shift_us"] for r in ("0", "1")]
    assert min(shifts) == 0.0 and max(shifts) >= 0.0
    for rank in (0, 1):
        spans = {e["name"] for e in merged["traceEvents"]
                 if e.get("ph") == "X" and e.get("pid") == rank}
        assert "JTOTAL" in spans, f"rank {rank} host phases missing"
    assert all(e["ts"] >= 0 for e in merged["traceEvents"] if "ts" in e)


def test_merge_timeline_aligns_anchors(tmp_path):
    """Unit-level clock alignment: two tracers with epoch anchors 1.5s
    apart merge with a 1.5e6 us shift on the later rank."""
    t0 = 1_000_000.0
    # monotonic anchors taken (as if) in two other processes, earlier on
    # this clock: spans read from them never have negative timestamps
    now = time.perf_counter()
    a = SpanTracer(rank=0, epoch_s=t0, mono_s=now - 100.0)
    b = SpanTracer(rank=1, epoch_s=t0 + 1.5, mono_s=now - 200.0)
    for tr in (a, b):
        tr.begin("JTOTAL")
        tr.end("JTOTAL")
        tr.instant("checkpoint_load", path="x")
        tr.save(str(tmp_path))
    merged = merge_timeline(str(tmp_path))
    md = merged["metadata"]
    assert md["t0_epoch_s"] == t0
    assert md["ranks"]["0"]["clock_shift_us"] == 0.0
    assert md["ranks"]["1"]["clock_shift_us"] == pytest.approx(1.5e6)
    r1 = [e for e in merged["traceEvents"]
          if e.get("pid") == 1 and e.get("ph") == "X"]
    assert r1 and all(e["ts"] >= 1.5e6 for e in r1)
    instants = [e for e in merged["traceEvents"] if e.get("ph") == "i"]
    assert len(instants) == 2


def test_merge_timeline_empty_dir(tmp_path):
    assert merge_timeline(str(tmp_path)) is None


# ------------------------------------------------------------- span tracer

def test_tracer_reentrant_and_crash_save(tmp_path):
    """Re-entered phases (retry) nest innermost-first; save() closes spans
    a crash left open and marks them."""
    tr = SpanTracer(rank=3)
    tr.begin("JPROC")
    tr.begin("JPROC")           # retry attempt re-enters the phase
    tr.end("JPROC")
    tr.end("JPROC", attempts=2)
    tr.end("JPROC")             # stray stop: dropped, not an error
    tr.begin("JTOTAL")          # crash before stop
    path = tr.save(str(tmp_path))
    doc = _load_spans(path)
    assert os.path.basename(path) == "3.spans.json"
    jp = _events(doc, ph="X", name="JPROC")
    assert len(jp) == 2
    assert jp[1]["args"]["attempts"] == 2
    jt = _events(doc, ph="X", name="JTOTAL")
    assert len(jt) == 1 and jt[0]["args"]["unclosed"] is True


def test_measurements_mirror_and_span(tmp_path):
    """Measurements.start/stop/event mirror into an attached tracer;
    Measurements.span records timeline-only spans (no times_us tag)."""
    from tpu_radix_join.performance.measurements import Measurements
    m = Measurements(node_id=0, num_nodes=1)
    tr = m.attach_tracer(nodes=1)
    m.start("JHIST")
    m.stop("JHIST")
    m.event("checkpoint_load", path="x", done=False)
    with m.span("grid_pair", i=1, j=2):
        pass
    names = {e["name"] for e in tr.events}
    assert {"JHIST", "checkpoint_load", "grid_pair"} <= names
    assert "grid_pair" not in m.times_us          # timeline-only
    pair = [e for e in tr.events if e["name"] == "grid_pair"][0]
    assert pair["args"]["i"] == 1 and pair["args"]["j"] == 2
    # shared anchors: the tracer's epoch is the registry's epoch
    assert tr.epoch_s == m.meta["epoch_s"]


def test_measurements_event_epoch_timestamps():
    """Satellite (b): events carry both the raw monotonic t_s and the
    epoch-anchored t_epoch_s the merger aligns on."""
    from tpu_radix_join.performance.measurements import Measurements
    m = Measurements()
    m.event("fault_injected", site="GRID_TRANSIENT")
    ev = m.meta["events"][-1]
    assert ev["event"] == "fault_injected"
    assert "t_s" in ev and "t_epoch_s" in ev
    # anchored twin: epoch timestamp sits at/after the init-time anchor
    # and within a sane window of it
    assert 0.0 <= ev["t_epoch_s"] - m.meta["epoch_s"] < 60.0


# ---------------------------------------------------------- metrics sampler

def test_metrics_sampler_counters_and_torn_lines(tmp_path):
    from tpu_radix_join.performance.measurements import GRIDPAIRS, Measurements
    m = Measurements()
    m.incr(GRIDPAIRS, 3)
    path = str(tmp_path / "0.metrics.jsonl")
    with MetricsSampler(path, interval_s=0.05, measurements=m):
        m.start("JTOTAL")
    samples = load_samples(path)
    assert len(samples) >= 2                    # start + stop at minimum
    assert samples[-1]["counters"]["GRIDPAIRS"] == 3
    assert samples[-1]["open_phases"] == ["JTOTAL"]
    assert samples[-1]["t_rel_s"] >= samples[0]["t_rel_s"]
    # a torn final line (SIGKILL mid-write) is skipped, not fatal
    with open(path, "a") as f:
        f.write('{"t_epoch_s": 1.0, "trunc')
    assert len(load_samples(path)) == len(samples)


def test_metrics_sampler_rejects_bad_interval(tmp_path):
    with pytest.raises(ValueError):
        MetricsSampler(str(tmp_path / "x.jsonl"), interval_s=0.0)


# ---------------------------------------------------------- regression gate

BASE = {"tags": {"JTOTAL": 100.0, "JPROC": 40.0, "value": 2.0e9}}


def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


def _run_gate(tmp_path, fresh, *extra):
    base = _write(tmp_path, "base.json", BASE)
    fp = _write(tmp_path, "fresh.json", fresh)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools_check_regress.py"),
         fp, "--baseline", base, *extra],
        capture_output=True, text=True, cwd=REPO)


def test_gate_flags_2x_jtotal(tmp_path):
    """Acceptance: a synthetic 2x JTOTAL regression exits non-zero with a
    readable per-tag delta table."""
    cp = _run_gate(tmp_path, {"tags": {"JTOTAL": 200.0, "JPROC": 40.0,
                                       "value": 2.0e9}})
    assert cp.returncode == 1, cp.stdout + cp.stderr
    assert "JTOTAL" in cp.stdout and "+100.0" in cp.stdout
    assert "REGRESSED: 1 tag(s)" in cp.stdout


def test_gate_passes_unchanged(tmp_path):
    cp = _run_gate(tmp_path, BASE)
    assert cp.returncode == 0, cp.stdout + cp.stderr
    assert "ok: no tag past threshold" in cp.stdout


def test_gate_allowlist_and_tag_threshold(tmp_path):
    # allowlisted regression passes; a tightened per-tag threshold fails a
    # delta the default 25% would wave through
    fresh = {"tags": {"JTOTAL": 200.0, "JPROC": 44.0, "value": 2.0e9}}
    cp = _run_gate(tmp_path, fresh, "--allow", "JTOTAL")
    assert cp.returncode == 0, cp.stdout + cp.stderr
    assert "allowed" in cp.stdout
    cp = _run_gate(tmp_path, fresh, "--allow", "JTOTAL",
                   "--tag-threshold", "JPROC=0.05")
    assert cp.returncode == 1
    assert "JPROC" in cp.stdout


def test_gate_throughput_direction(tmp_path):
    """Higher-better tags regress on DROP: halved throughput fails even
    though the number shrank."""
    cp = _run_gate(tmp_path, {"tags": {"JTOTAL": 100.0, "JPROC": 40.0,
                                       "value": 1.0e9}})
    assert cp.returncode == 1
    assert "value" in cp.stdout


def test_gate_missing_tag_strict(tmp_path):
    fresh = {"tags": {"JTOTAL": 100.0, "value": 2.0e9}}     # JPROC vanished
    assert _run_gate(tmp_path, fresh).returncode == 0
    assert _run_gate(tmp_path, fresh, "--strict").returncode == 1


def test_gate_empty_baseline_passes_with_note(tmp_path):
    """The repo's published-{} BASELINE.json has no numeric tags: nothing
    to compare is not a regression."""
    base = _write(tmp_path, "empty.json", {"published": {}})
    fp = _write(tmp_path, "fresh.json", BASE)
    cp = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools_check_regress.py"),
         fp, "--baseline", base], capture_output=True, text=True, cwd=REPO)
    assert cp.returncode == 0, cp.stdout + cp.stderr
    assert "no numeric tags" in cp.stdout


def test_gate_usage_errors(tmp_path):
    fp = _write(tmp_path, "fresh.json", BASE)
    base = _write(tmp_path, "base.json", BASE)
    cp = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools_check_regress.py"),
         fp, "--baseline", str(tmp_path / "nope.json")],
        capture_output=True, text=True, cwd=REPO)
    assert cp.returncode == 2
    cp = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools_check_regress.py"),
         fp, "--baseline", base, "--tag-threshold", "JTOTAL"],
        capture_output=True, text=True, cwd=REPO)
    assert cp.returncode == 2


def test_check_result_in_process(tmp_path):
    """bench.py's --check-regress hook: in-memory fresh dict vs baseline
    file, same verdicts as the CLI."""
    base = _write(tmp_path, "base.json", BASE)
    code, report = check_result({"JTOTAL": 200.0, "JPROC": 40.0,
                                 "value": 2.0e9}, base)
    assert code == 1 and "JTOTAL" in report
    code, report = check_result(BASE["tags"], base)
    assert code == 0


def test_extract_and_compare_units():
    assert extract_tags({"parsed": {"tags": {"a": 1, "rc": 0,
                                             "flag": True, "s": "x"}}}) == \
        {"a": 1.0}
    rows = compare_tags({"a": 10.0, "zero": 0.0}, {"a": 10.0, "zero": 1.0,
                                                   "fresh_only": 5.0})
    by = {r["tag"]: r for r in rows}
    assert by["a"]["status"] == "ok"
    assert by["zero"]["status"] == "regressed"      # 0 -> 1 cost: inf delta
    assert by["fresh_only"]["status"] == "new"
    assert rows[0]["tag"] == "zero"                 # worst first
    table = format_table(rows)
    assert "zero" in table and "inf" in table
    assert parse_tag_thresholds(["A=0.1", "B=0.5"]) == {"A": 0.1, "B": 0.5}
    with pytest.raises(ValueError):
        parse_tag_thresholds(["A"])

"""Cross-run telemetry ledger (observability/ledger.py), compile-event
telemetry (observability/compilemon.py), and metrics-heartbeat size-cap
rotation (observability/metrics.py).

The ledger is the planner's long-term memory: these tests pin the row
schema, the tolerant-reader discipline (torn lines, newer schemas), the
payload builders the run-end/per-query/bench writers use, and the
artifact backfill path chip-run outputs flow through."""

import json
import os
import subprocess
import sys

import pytest

from tpu_radix_join.observability.ledger import (BENCH_DEFAULT_SIZE,
                                                 LEDGER_SCHEMA_VERSION,
                                                 Ledger, bench_payload,
                                                 default_ledger_dir,
                                                 ingest_artifacts, load_rows,
                                                 rows_from_perf_dir,
                                                 run_payload)
from tpu_radix_join.performance.measurements import (COMPILEMS, NCOMPILE,
                                                     WIREBYTES, Measurements)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- core I/O
def test_append_rows_roundtrip_and_kind_filter(tmp_path):
    led = Ledger(str(tmp_path))
    r1 = led.append("run", {"counters": {"JTOTAL": 1}})
    r2 = led.append("bench", {"metric": "m", "value": 1.0})
    assert r1["schema_version"] == LEDGER_SCHEMA_VERSION
    assert r1["run_id"] and r1["run_id"] != r2["run_id"]
    assert led.path.endswith("ledger.jsonl")
    assert [r["kind"] for r in led.rows()] == ["run", "bench"]
    assert [r["kind"] for r in led.rows(kind="bench")] == ["bench"]


def test_append_rejects_unknown_kind(tmp_path):
    with pytest.raises(ValueError, match="kind"):
        Ledger(str(tmp_path)).append("nope", {})


def test_explicit_jsonl_path_and_custom_run_id(tmp_path):
    path = str(tmp_path / "custom.jsonl")
    row = Ledger(path).append("obs", {"constant": "hbm_gbps", "value": 1.0},
                              run_id="my-run", t_epoch_s=123.0)
    assert row["run_id"] == "my-run" and row["t_epoch_s"] == 123.0
    assert load_rows(path)[0]["constant"] == "hbm_gbps"


def test_reader_skips_torn_lines_and_newer_schema(tmp_path):
    led = Ledger(str(tmp_path))
    led.append("run", {"a": 1})
    with open(led.path, "a") as f:
        f.write(json.dumps({"schema_version": LEDGER_SCHEMA_VERSION + 1,
                            "kind": "run", "future": True}) + "\n")
        f.write('{"kind": "run", "torn...')      # killed-writer tail
    rows = load_rows(led.path)
    assert len(rows) == 1 and rows[0]["a"] == 1


def test_missing_ledger_reads_empty(tmp_path):
    assert load_rows(str(tmp_path / "absent")) == []


def test_default_ledger_dir_env_override(monkeypatch):
    monkeypatch.setenv("TPU_RADIX_LEDGER_DIR", "/x/y")
    assert default_ledger_dir() == "/x/y"
    monkeypatch.delenv("TPU_RADIX_LEDGER_DIR")
    assert default_ledger_dir() == os.path.join("artifacts", "ledger")


# ------------------------------------------------------------------- payloads
def test_run_payload_distills_registry():
    m = Measurements(node_id=0, num_nodes=2)
    m.add_time_us("JTOTAL", 5000.0)
    m.incr(WIREBYTES, by=4096)
    m.counters["ZERO"] = 0                       # zero counters are dropped
    m.meta.update(tuples_per_node=1 << 10, global_size=1 << 11, nodes=2,
                  plan_vs_actual={"drift_pct": 3.0},
                  config={"repeat": 2, "nested": {"x": 1}})
    p = run_payload(m)
    assert p["times_us"]["JTOTAL"] == 5000.0
    assert p["counters"] == {"WIREBYTES": 4096}
    assert p["workload"]["global_size"] == 1 << 11
    assert p["plan_vs_actual"]["drift_pct"] == 3.0
    assert p["repeat"] == 2
    assert "nested" not in p["config"]           # scalars only
    assert "host" in p["fingerprint"]


def test_bench_payload_unwraps_runner_wrapper():
    doc = {"n": 1, "rc": 0, "parsed": {"metric": "m", "value": 2.5,
                                       "unit": "u", "extra": 7,
                                       "planned": {"strategy": "x"}}}
    p = bench_payload(doc)
    assert p["metric"] == "m" and p["value"] == 2.5 and p["rc"] == 0
    assert p["size"] == BENCH_DEFAULT_SIZE       # pre-"size" rounds
    assert p["extra"] == 7 and "planned" not in p    # scalars only
    assert bench_payload({"rc": 2, "tail": "died"}) is None
    assert bench_payload({"metric": "m", "value": 1.0,
                          "size": 64})["size"] == 64


def test_rows_from_perf_dir_roundtrip(tmp_path):
    m = Measurements(node_id=0, num_nodes=1)
    m.add_time_us("JTOTAL", 1000.0)
    m.meta.update(tuples_per_node=256, global_size=256, nodes=1)
    m.store(str(tmp_path))
    rows = rows_from_perf_dir(str(tmp_path))
    assert len(rows) == 1
    run_id, payload = rows[0]
    assert run_id.endswith(":0")
    assert payload["times_us"]["JTOTAL"] == 1000.0
    assert payload["workload"]["global_size"] == 256


def _artifact_dir(base):
    """A chip-run artifact dir as bench.py and main.py leave it: two bench
    result lines (one wrapped with its rc, one dead before its JSON line)
    and one perf dir."""
    os.makedirs(base)
    for name, doc in (
            ("BENCH_a.json", {"metric": "single_chip_join_throughput",
                              "value": 7.0e8, "unit": "tuples/sec"}),
            ("BENCH_b.json", {"rc": 0, "parsed": {
                "metric": "single_chip_join_throughput", "value": 7.1e8,
                "unit": "tuples/sec", "size": 1 << 24}}),
            ("BENCH_c.json", {"rc": 2, "tail": "died"})):
        with open(os.path.join(base, name), "w") as f:
            json.dump(doc, f)
    m = Measurements(node_id=0, num_nodes=1)
    m.add_time_us("JTOTAL", 1000.0)
    m.meta.update(tuples_per_node=256, global_size=256, nodes=1)
    m.store(os.path.join(base, "run1", "perf_16m"))
    return base


def test_ingest_artifacts_backfills_history(tmp_path):
    base = _artifact_dir(str(tmp_path / "art"))
    out = str(tmp_path / "ledger")
    counts = ingest_artifacts(base, out)
    # BENCH_a/b parsed; BENCH_c died before its JSON line (rc=2)
    assert counts == {"bench": 2, "run": 1}
    rows = load_rows(out)
    bench = [r for r in rows if r["kind"] == "bench"]
    assert {r["run_id"] for r in bench} == {"BENCH_a", "BENCH_b"}
    assert all(r["metric"] == "single_chip_join_throughput" for r in bench)


def test_emit_ledger_cli(tmp_path):
    base = _artifact_dir(str(tmp_path / "art"))
    out = subprocess.run(
        [sys.executable, "tools_make_report.py", base, "--emit-ledger",
         str(tmp_path / "led")],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "2 bench row(s)" in out.stdout
    assert load_rows(str(tmp_path / "led"))


# -------------------------------------------------------------- compilemon
def test_compile_monitor_counts_backend_compiles():
    import jax
    import jax.numpy as jnp

    from tpu_radix_join.observability.compilemon import (
        install_compile_monitor, uninstall_compile_monitor)

    m = Measurements(node_id=0, num_nodes=1)
    install_compile_monitor(m)
    install_compile_monitor(m)                   # idempotent
    try:
        # a fresh closure + unique shape forces a real backend compile
        fn = jax.jit(lambda a: a * jnp.int32(3) + jnp.int32(41))
        jax.block_until_ready(fn(jnp.arange(641, dtype=jnp.int32)))
        assert m.counters.get(NCOMPILE, 0) >= 1
        assert COMPILEMS in m.counters
    finally:
        uninstall_compile_monitor(m)
    n = m.counters.get(NCOMPILE, 0)
    fn2 = jax.jit(lambda a: a - jnp.int32(7))
    jax.block_until_ready(fn2(jnp.arange(643, dtype=jnp.int32)))
    assert m.counters.get(NCOMPILE, 0) == n      # uninstalled: inert


# ------------------------------------------------------- heartbeat rotation
def test_metrics_sampler_rotates_at_size_cap(tmp_path):
    from tpu_radix_join.observability.metrics import (MetricsSampler,
                                                      load_samples)

    path = str(tmp_path / "0.metrics.jsonl")
    s = MetricsSampler(path, interval_s=60.0, rotate_bytes=600,
                       rotate_keep=2)
    s._file = open(path, "a")                    # sample without the thread
    for _ in range(40):
        s.sample()
    s._file.close()
    s._file = None
    assert s.rotations >= 2
    assert os.path.getsize(path) < 600 + 2048    # live file stays bounded
    assert os.path.exists(path + ".1") and os.path.exists(path + ".2")
    assert not os.path.exists(path + ".3")       # beyond keep: dropped
    merged = load_samples(path, include_rotated=True)
    assert len(merged) > len(load_samples(path))
    ts = [r["t_epoch_s"] for r in merged]
    assert ts == sorted(ts)                      # chronological across cap


def test_metrics_sampler_rejects_bad_rotation_params(tmp_path):
    from tpu_radix_join.observability.metrics import MetricsSampler
    with pytest.raises(ValueError):
        MetricsSampler(str(tmp_path / "m"), rotate_bytes=0)
    with pytest.raises(ValueError):
        MetricsSampler(str(tmp_path / "m"), rotate_keep=0)


def test_load_samples_missing_live_file_still_raises(tmp_path):
    from tpu_radix_join.observability.metrics import load_samples
    with pytest.raises(OSError):
        load_samples(str(tmp_path / "absent.metrics.jsonl"))

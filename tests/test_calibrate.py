"""Profile auto-calibration loop (planner/calibrate.py,
tools_profile_fit.py, --profile auto): ground-truth constants are
recovered from synthetic ledger samples within the reported CI, stale
constants trip on injected persistent drift, schema-v3 provenance
round-trips while v1/v2 profiles keep loading, and under-sampled fits
are refused at the CLI boundary."""

import json
import os
import subprocess
import sys

import pytest

from tpu_radix_join.observability.ledger import Ledger
from tpu_radix_join.planner.calibrate import (TERM_TO_CONSTANT,
                                              UnderSampledError,
                                              collect_samples, detect_stale,
                                              diff_profiles, fit_profile,
                                              robust_fit)
from tpu_radix_join.planner.profile import (FITTED_PROFILE_BASENAME,
                                            SORT_REF_ELEMS, DeviceProfile,
                                            format_provenance, load_profile,
                                            resolve_profile,
                                            sort_stage_units)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_row(unit_ms, size=1 << 24, rid="b0"):
    """A bench row whose throughput encodes a known sort-stage unit."""
    union = 2 * size
    t_ms = unit_ms * (union / SORT_REF_ELEMS) * sort_stage_units(union)
    return {"kind": "bench", "run_id": rid,
            "metric": "single_chip_join_throughput",
            "value": union / (t_ms / 1e3), "size": size}


def _partition_row(unit_ms, size=1 << 24, rid="p0"):
    """A --partition-bench row whose fused-kernel wall encodes a known
    ms/Mtuple/pass unit (the kernel makes two passes over the ids)."""
    kernel_ms = unit_ms * 2.0 * size / 1e6
    return {"kind": "bench", "run_id": rid,
            "metric": "partition_fused_speedup", "value": 1.7,
            "size": size,
            "partition_kernel_ms": kernel_ms,
            "partition_ms": kernel_ms * 1.6,
            "partition_sort_ms": kernel_ms * 2.8,
            "partition_unit_ms": unit_ms}


def _drift_row(rid, drift_pct, term="shuffle", predicted_ms=40.0):
    return {"kind": "run", "run_id": rid,
            "plan_vs_actual": {"drift_pct": drift_pct,
                               "terms": [
                                   {"term": term,
                                    "predicted_ms": predicted_ms,
                                    "actual_ms": None},
                                   {"term": "dispatch", "predicted_ms": 1.0,
                                    "actual_ms": None}]}}


# ------------------------------------------------------------ sample -> fit
def test_sort_unit_recovered_within_ci():
    truth = 0.25
    rows = [_bench_row(truth * f, rid=f"b{i}")
            for i, f in enumerate((0.97, 1.0, 1.02, 1.01, 0.99))]
    prof, fits = fit_profile(rows, base=load_profile())
    fit = fits["sort_stage_unit_ms"]
    lo, hi = fit.ci95
    assert lo <= truth <= hi
    assert abs(fit.value - truth) / truth < 0.05
    assert fit.n == 5 and "b0" in fit.runs


def test_dispatch_and_ici_samples_from_run_rows():
    rows = []
    for i in range(3):
        rows.append({"kind": "run", "run_id": f"r{i}",
                     "times_us": {"SDISPATCH": 98_000.0 + i * 1000,
                                  "JMPI": 1_000_000.0},
                     "counters": {"WIREBYTES": 50_000_000_000}})
    # tiny-run intercept: JTOTAL at <= 64K tuples is pure floor
    rows.append({"kind": "run", "run_id": "tiny",
                 "times_us": {"JTOTAL": 101_000.0},
                 "workload": {"global_size": 4096}})
    samples = collect_samples(rows)
    assert len(samples["dispatch_floor_ms"]) == 4
    assert len(samples["ici_bytes_per_s"]) == 3
    _, fits = fit_profile(rows, base=load_profile())
    assert abs(fits["dispatch_floor_ms"].value - 99.0) < 3.0
    assert fits["ici_bytes_per_s"].value == pytest.approx(5e10)


def test_partition_unit_recovered_within_ci():
    truth = 0.09
    rows = [_partition_row(truth * f, rid=f"p{i}")
            for i, f in enumerate((0.98, 1.0, 1.03, 1.0, 0.99))]
    prof, fits = fit_profile(rows, base=load_profile())
    fit = fits["partition_pass_unit_ms"]
    lo, hi = fit.ci95
    assert lo <= truth <= hi
    assert abs(fit.value - truth) / truth < 0.05
    prov = prof.provenance("partition_pass_unit_ms")
    assert prov["origin"] == "fit" and prov["n"] == 5
    assert "p0" in prov["runs"]


def test_partition_unit_falls_back_to_reduced_tag():
    # a row missing the primary kernel wall still contributes through the
    # pre-reduced partition_unit_ms tag
    row = _partition_row(0.08, rid="p9")
    del row["partition_kernel_ms"]
    samples = collect_samples([row])
    assert [s.value for s in samples["partition_pass_unit_ms"]] == [0.08]


def test_obs_rows_feed_any_constant():
    rows = [{"kind": "obs", "run_id": f"o{i}", "constant": "hbm_gbps",
             "value": 100.0 + i} for i in range(3)]
    _, fits = fit_profile(rows, base=load_profile())
    assert fits["hbm_gbps"].value == 101.0


def test_robust_fit_resists_outlier():
    from tpu_radix_join.planner.calibrate import Sample
    vals = [1.0, 1.01, 0.99, 1.02, 50.0]          # one cold-cache outlier
    fit = robust_fit([Sample(v, f"r{i}") for i, v in enumerate(vals)])
    assert abs(fit.value - 1.0) < 0.05


def test_under_sampled_fit_refused():
    with pytest.raises(UnderSampledError):
        fit_profile([], base=load_profile())
    with pytest.raises(UnderSampledError):
        # one sample < min_samples=2
        fit_profile([_bench_row(0.2)], base=load_profile())


# ------------------------------------------------------------ schema v3
def test_v3_profile_roundtrips_with_provenance(tmp_path):
    rows = [_bench_row(0.2, rid=f"b{i}") for i in range(2)]
    prof, _ = fit_profile(rows, base=load_profile(), fitted_at=1000.0)
    path = str(tmp_path / "p.json")
    prof.save(path)
    back = load_profile(path)
    assert back.schema_version == 6
    prov = back.provenance("sort_stage_unit_ms")
    assert prov["origin"] == "fit" and prov["n"] == 2
    assert prov["runs"] == ["b0", "b1"]
    assert len(prov["ci95"]) == 2 and prov["fitted_at_epoch_s"] == 1000.0
    assert back.freshness() == 1000.0
    # every constant carries provenance, fitted or inherited
    assert all(back.provenance(k) is not None for k in back.constants)
    assert back.provenance("hbm_gbps")["origin"] == "committed"


def test_v1_shim_and_committed_still_load(tmp_path):
    committed = load_profile("v5e_lite")          # the checked-in v6
    assert committed.schema_version == 6
    assert committed.freshness() is None          # no provenance: never fit
    v1 = {"schema_version": 1, "name": "old",
          "constants": {k: dict(committed.constants[k])
                        for k in committed.constants
                        if k not in ("ici_bytes_per_s",
                                     "partition_pass_unit_ms",
                                     "result_cache_lookup_ms")}}
    path = str(tmp_path / "v1.json")
    with open(path, "w") as f:
        json.dump(v1, f)
    back = load_profile(path)
    assert back.value("ici_bytes_per_s") == committed.value("ici_gbps") * 1e9
    # v4 shim: the partition pass unit derives from the cited bandwidth
    assert back.value("partition_pass_unit_ms") == pytest.approx(
        8.0 / committed.value("hbm_gbps"), rel=1e-3)
    assert back.source("partition_pass_unit_ms").startswith("shim:")
    # v6 shim: the result-cache probe derives from the dispatch floor
    assert back.value("result_cache_lookup_ms") == pytest.approx(
        committed.value("dispatch_floor_ms") / 10.0, rel=1e-3)
    assert back.source("result_cache_lookup_ms").startswith("shim:")


def test_v3_profile_shims_partition_unit(tmp_path):
    committed = load_profile("v5e_lite")
    v3 = {"schema_version": 3, "name": "old3",
          "constants": {k: dict(committed.constants[k])
                        for k in committed.constants
                        if k != "partition_pass_unit_ms"}}
    path = str(tmp_path / "v3.json")
    with open(path, "w") as f:
        json.dump(v3, f)
    back = load_profile(path)
    assert back.value("partition_pass_unit_ms") == pytest.approx(
        8.0 / committed.value("hbm_gbps"), rel=1e-3)
    assert "schema v3" in back.source("partition_pass_unit_ms")


def test_fingerprint_ignores_provenance():
    base = load_profile()
    prof, _ = fit_profile([_bench_row(base.value("sort_stage_unit_ms"),
                                      rid=f"b{i}") for i in range(2)],
                          base=base, name=base.name)
    # same values -> same fingerprint constants: provenance must not
    # invalidate plan caches
    fp = prof.fingerprint()["constants"]
    assert set(fp) == set(base.fingerprint()["constants"])


# ------------------------------------------------------------- staleness
def test_stale_trips_on_persistent_drift_attributed_to_constant():
    rows = [_drift_row(f"d{i}", 60.0) for i in range(3)]
    stale = detect_stale(rows)
    assert TERM_TO_CONSTANT["shuffle"] == "ici_bytes_per_s"
    assert "ici_bytes_per_s" in stale
    info = stale["ici_bytes_per_s"]
    assert info["hits"] == 3 and info["mean_drift_pct"] == 60.0
    assert info["runs"] == ["d0", "d1", "d2"]


def test_stale_needs_persistence_and_threshold():
    assert detect_stale([_drift_row("a", 60.0)] * 2) == {}   # < min_persist
    assert detect_stale([_drift_row(f"x{i}", 10.0)          # under threshold
                         for i in range(5)]) == {}


def test_format_provenance_shows_stale_column():
    prof, _ = fit_profile([_bench_row(0.2, rid=f"b{i}") for i in range(2)],
                          base=load_profile())
    stale = detect_stale([_drift_row(f"d{i}", 80.0) for i in range(3)])
    txt = format_provenance(prof, stale=stale)
    assert "STALE (80% drift)" in txt
    assert "tools_profile_fit.py refresh" in txt
    clean = format_provenance(prof)
    assert "STALE" not in clean and txt != clean


# ----------------------------------------------------------- resolve auto
def test_resolve_profile_prefers_fresh_fit_then_falls_back(tmp_path):
    assert resolve_profile("v5e_lite") == "v5e_lite"      # passthrough
    d = str(tmp_path)
    assert resolve_profile("auto", ledger_dir=d) == "v5e_lite"  # no fit yet
    prof, _ = fit_profile([_bench_row(0.2, rid=f"b{i}") for i in range(2)],
                          base=load_profile())
    fitted = os.path.join(d, FITTED_PROFILE_BASENAME)
    prof.save(fitted)
    assert resolve_profile("auto", ledger_dir=d) == fitted
    # an aged fit loses to the committed snapshot
    assert resolve_profile("auto", ledger_dir=d,
                           fresh_s=0.0) == "v5e_lite"


# ----------------------------------------------------------------- CLIs
def _cli(*argv, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    if env:
        e.update(env)
    return subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, cwd=REPO, timeout=180, env=e)


def test_profile_fit_cli_fit_and_diff(tmp_path):
    led = Ledger(str(tmp_path))
    for i in range(3):
        led.append("bench", _bench_row(0.3, rid=f"b{i}"))
    out = _cli("tools_profile_fit.py", "fit", "--ledger", str(tmp_path))
    assert out.returncode == 0, out.stderr
    assert "fitted 1/11 constants" in out.stdout
    fitted = str(tmp_path / FITTED_PROFILE_BASENAME)
    assert load_profile(fitted).schema_version == 6
    # 0.3 vs committed 0.147 is > 25% -> diff gates
    out = _cli("tools_profile_fit.py", "diff", "v5e_lite", fitted)
    assert out.returncode == 1
    out = _cli("tools_profile_fit.py", "diff", "v5e_lite", fitted,
               "--threshold", "2.0")
    assert out.returncode == 0


def test_profile_fit_cli_refuses_under_sampled(tmp_path):
    # tier-1 satellite: an under-sampled ledger must exit 2, not emit a
    # profile that merely echoes its base under a "fit" label
    out = _cli("tools_profile_fit.py", "fit", "--ledger", str(tmp_path))
    assert out.returncode == 2
    assert "no ledger rows" in out.stderr
    Ledger(str(tmp_path)).append("bench", _bench_row(0.2))
    out = _cli("tools_profile_fit.py", "fit", "--ledger", str(tmp_path))
    assert out.returncode == 2
    assert "under-sampled" in out.stderr
    assert not os.path.exists(str(tmp_path / FITTED_PROFILE_BASENAME))


def test_profile_fit_cli_refresh_flags_stale(tmp_path):
    led = Ledger(str(tmp_path))
    for i in range(2):
        led.append("bench", _bench_row(0.2, rid=f"b{i}"))
    for i in range(3):
        led.append("run", _drift_row(f"d{i}", 70.0))
    out = _cli("tools_profile_fit.py", "refresh", "--ledger", str(tmp_path))
    assert out.returncode == 1                    # stale evidence found
    assert "stale constants re-fit" in out.stdout
    assert "ici_bytes_per_s" in out.stdout


def test_plan_explain_shows_provenance_and_refit_changes_it(tmp_path):
    env = {"TPU_RADIX_LEDGER_DIR": str(tmp_path)}
    base_out = _cli("-m", "tpu_radix_join.main", "--plan", "explain",
                    "--tuples-per-node", "4096", "--nodes", "1", env=env)
    assert base_out.returncode == 0, base_out.stderr
    assert "provenance/staleness" in base_out.stdout
    assert "calibrate" in base_out.stdout        # committed sources cited
    # build a ledger with drift + samples, fit, and explain under auto
    led = Ledger(str(tmp_path))
    for i in range(2):
        led.append("bench", _bench_row(0.3, rid=f"b{i}"))
    for i in range(3):
        led.append("run", _drift_row(f"d{i}", 70.0))
    out = _cli("tools_profile_fit.py", "fit", "--ledger", str(tmp_path))
    assert out.returncode == 0, out.stderr
    auto_out = _cli("-m", "tpu_radix_join.main", "--plan", "explain",
                    "--profile", "auto", "--tuples-per-node", "4096",
                    "--nodes", "1", env=env)
    assert auto_out.returncode == 0, auto_out.stderr
    assert "[PROFILE] auto ->" in auto_out.stderr
    assert "origin" in auto_out.stdout and "fit" in auto_out.stdout
    assert "STALE" in auto_out.stdout            # injected drift surfaces
    # the re-fit moved sort_stage_unit_ms to 0.3: predictions differ
    assert auto_out.stdout != base_out.stdout


def test_diff_profiles_table():
    a = load_profile()
    b = a.replace_constants(**{"hbm_gbps": {
        "value": 2 * a.value("hbm_gbps"), "source": "x"}})
    rows = {r["constant"]: r for r in diff_profiles(a, b)}
    assert rows["hbm_gbps"]["rel_delta"] == 1.0
    assert rows["ici_gbps"]["rel_delta"] == 0.0

"""Planner subsystem (tpu_radix_join/planner/): device profiles, the
analytic cost model's crossover points, plan selection, the warm-start
plan cache, and the CLI/report wiring.

The crossover tests drive the cost model through the regime boundaries the
chip measurements established (PERF_NOTES.md): in-core -> chunked at the
memory budget, narrow -> full-range at MAX_MERGE_KEY, fused -> split
separated by exactly the dispatch floor.  The cache tests mirror
test_checkpoint_resume.py's hit/miss/corruption/fingerprint discipline,
plus the acceptance observable: a warm second run skips the engine's
sizing pre-pass (no JHIST; CKPTLOAD fires instead).
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from tpu_radix_join.ops.merge_count import MAX_MERGE_KEY
from tpu_radix_join.planner import (JoinPlan, PlanCache, Workload,
                                    explain_table, load_profile, plan_join)
from tpu_radix_join.planner.cache import ManifestMismatch
from tpu_radix_join.planner.cost_model import (PROGRAMS,
                                               enumerate_strategies)
from tpu_radix_join.planner.plan import PlanError
from tpu_radix_join.planner.profile import (REQUIRED_CONSTANTS,
                                            DeviceProfile, ProfileError)

PROF = load_profile()


def _strategy(costs, name):
    return next(c for c in costs if c.strategy == name)


# ----------------------------------------------------------------- profile

def test_checked_in_profile_has_all_cited_constants():
    for key in REQUIRED_CONSTANTS:
        assert PROF.value(key) > 0
        assert PROF.source(key).strip(), key


def test_cost_model_constants_all_declared_required():
    """Every constant the cost model reads must be in REQUIRED_CONSTANTS —
    the guard that a new cost term cannot ship with an uncited, unprofiled
    coefficient."""
    import re

    import tpu_radix_join.planner.cost_model as cm
    with open(cm.__file__) as f:
        used = set(re.findall(r'profile\.value\("([a-z_]+)"\)', f.read()))
    assert used, "cost model reads no profile constants?"
    assert used <= set(REQUIRED_CONSTANTS), used - set(REQUIRED_CONSTANTS)


def test_uncited_constant_rejected():
    bad = {k: dict(PROF.constants[k]) for k in PROF.constants}
    bad["hbm_gbps"] = {"value": 105.0, "source": "  "}
    with pytest.raises(ProfileError, match="uncited"):
        DeviceProfile(name="bad", constants=bad)


def test_missing_constant_rejected():
    bad = {k: PROF.constants[k] for k in PROF.constants if k != "ici_gbps"}
    with pytest.raises(ProfileError, match="ici_gbps"):
        DeviceProfile(name="bad", constants=bad)


def test_newer_schema_rejected():
    with pytest.raises(ProfileError, match="schema_version"):
        DeviceProfile(name="future", constants=dict(PROF.constants),
                      schema_version=99)


def test_profile_roundtrip_and_fingerprint(tmp_path):
    path = str(tmp_path / "p.json")
    PROF.save(path)
    again = load_profile(path)
    assert again.fingerprint() == PROF.fingerprint()
    tweaked = PROF.replace_constants(
        hbm_gbps={"value": 1.0, "source": "test"})
    assert tweaked.fingerprint() != PROF.fingerprint()


# ------------------------------------------------------------- crossovers

def test_crossover_memory_budget_routes_to_chunked():
    """Same relation, shrinking budget: in-core until the working set no
    longer fits, then the chunked grid is the only feasible discipline."""
    w_fits = Workload(r_tuples=1 << 20, s_tuples=1 << 20, key_bound=1 << 20)
    plan, costs = plan_join(PROF, w_fits)
    assert plan.engine == "incore"
    assert _strategy(costs, "chunked_grid").feasible

    w_oom = dataclasses.replace(w_fits, memory_budget_bytes=1 << 20)
    plan, costs = plan_join(PROF, w_oom)
    assert plan.engine == "chunked"
    # the pipelined grid row (sort-reuse + overlap) undercuts the
    # synchronous grid, so OOM workloads route to it with pipeline on
    assert plan.strategy == "chunked_grid_pipelined"
    assert plan.grid_pipeline == "on"
    assert plan.chunk_tuples and plan.chunk_tuples & (plan.chunk_tuples - 1) == 0
    assert not _strategy(costs, "incore_fused_sort_narrow").feasible


def test_crossover_key_bound_narrow_to_full():
    """key_bound straddling MAX_MERGE_KEY flips the 31-bit packed fast
    path infeasible; the full-range row absorbs the 1.7x sort factor."""
    at_limit = Workload(r_tuples=1 << 20, s_tuples=1 << 20,
                        key_bound=MAX_MERGE_KEY + 1)   # max key == limit
    plan, costs = plan_join(PROF, at_limit)
    assert plan.key_range == "narrow"
    assert _strategy(costs, "incore_fused_sort_narrow").feasible

    over = dataclasses.replace(at_limit, key_bound=MAX_MERGE_KEY + 2)
    plan, costs = plan_join(PROF, over)
    assert plan.key_range == "full"
    assert plan.strategy == "incore_fused_sort_full"
    row = _strategy(costs, "incore_fused_sort_narrow")
    assert not row.feasible and "packing limit" in row.note
    # the full-range penalty is the profiled factor, applied to sort only
    narrow_sort = _strategy(costs, "incore_fused_sort_full").terms["sort"]
    base_sort = narrow_sort / PROF.value("full_range_sort_factor")
    assert narrow_sort > base_sort


def test_crossover_fused_vs_split_is_exactly_the_dispatch_floor():
    """The split's cost excess over fused is programs_delta x floor — and
    with the floor zeroed the two tie, with fused winning the tie-break."""
    w = Workload(r_tuples=1 << 22, s_tuples=1 << 22, key_bound=1 << 22,
                 num_nodes=8)
    costs = enumerate_strategies(PROF, w)
    fused = _strategy(costs, "incore_fused_sort_narrow")
    split = _strategy(costs, "incore_split_sort_narrow")
    delta = (PROGRAMS["split_sort"] - PROGRAMS["fused"]) \
        * PROF.value("dispatch_floor_ms")
    assert split.cost_ms - fused.cost_ms == pytest.approx(delta, rel=1e-6)

    free = PROF.replace_constants(
        dispatch_floor_ms={"value": 0.0, "source": "test: zeroed floor"})
    plan, _ = plan_join(free, w)
    assert plan.fused and plan.strategy == "incore_fused_sort_narrow"


def test_pipelined_repeats_amortize_fused_dispatch_only():
    """Repeats divide the fused dispatch floor; the phase split cannot
    pipeline (fence per program), so its floor stays per join."""
    w1 = Workload(r_tuples=1 << 22, s_tuples=1 << 22, key_bound=1 << 22,
                  num_nodes=8, repeats=1)
    w10 = dataclasses.replace(w1, repeats=10)
    fused1 = _strategy(enumerate_strategies(PROF, w1),
                       "incore_fused_sort_narrow").terms["dispatch"]
    fused10 = _strategy(enumerate_strategies(PROF, w10),
                        "incore_fused_sort_narrow").terms["dispatch"]
    # terms carry 3 decimals: with the chip's sub-ms floor that rounding,
    # not the relative error, bounds the comparison
    assert fused10 == pytest.approx(fused1 / 10, rel=1e-6, abs=5e-4)
    split1 = _strategy(enumerate_strategies(PROF, w1),
                       "incore_split_sort_narrow").terms["dispatch"]
    split10 = _strategy(enumerate_strategies(PROF, w10),
                        "incore_split_sort_narrow").terms["dispatch"]
    assert split10 == split1


def test_wide_keys_never_narrow():
    plan, costs = plan_join(PROF, Workload(r_tuples=1 << 20,
                                           s_tuples=1 << 20, key_bits=64))
    assert not _strategy(costs, "incore_fused_sort_narrow").feasible
    assert plan.key_range == "auto"


def test_chunked_grid_single_node_only():
    costs = enumerate_strategies(PROF, Workload(
        r_tuples=1 << 20, s_tuples=1 << 20, num_nodes=8))
    assert not _strategy(costs, "chunked_grid").feasible


def test_explain_table_lists_every_strategy():
    plan, costs = plan_join(PROF, Workload(r_tuples=1 << 20,
                                           s_tuples=1 << 20,
                                           key_bound=1 << 20))
    table = explain_table(costs, plan)
    for c in costs:
        assert c.strategy in table
    assert "predicted_ms" in table and "chosen:" in table


# ------------------------------------------------------------------ plans

def test_plan_roundtrip_and_validation(tmp_path):
    plan, _ = plan_join(PROF, Workload(r_tuples=1 << 20, s_tuples=1 << 20,
                                       key_bound=1 << 20))
    path = str(tmp_path / "plan.json")
    plan.save(path)
    assert JoinPlan.load(path) == plan
    doc = plan.to_dict()
    with pytest.raises(PlanError, match="unknown plan fields"):
        JoinPlan.from_dict({**doc, "surprise": 1})
    with pytest.raises(PlanError, match="schema_version"):
        JoinPlan.from_dict({**doc, "schema_version": 99})
    with pytest.raises(PlanError, match="engine"):
        JoinPlan.from_dict({**doc, "engine": "warp"})


# ------------------------------------------------------------------ cache

def _cache(tmp_path, profile=PROF, meas=None):
    return PlanCache(str(tmp_path / "cache"), profile, measurements=meas)


def test_cache_miss_then_hit(tmp_path):
    cache = _cache(tmp_path)
    fp = {"config": 1}
    assert cache.lookup(100, 100, fp) == (None, None)
    plan, _ = plan_join(PROF, Workload(r_tuples=100, s_tuples=100))
    cache.store(100, 100, fp, plan=plan,
                capacities={"cap_r": 64, "cap_s": 128, "local_slack": 1})
    got_plan, caps = cache.lookup(100, 100, fp)
    assert got_plan == plan
    assert caps == {"cap_r": 64, "cap_s": 128, "local_slack": 1}
    # different shapes / config: distinct entries, still misses
    assert cache.lookup(200, 100, fp) == (None, None)
    assert cache.lookup(100, 100, {"config": 2}) == (None, None)


def test_cache_store_merges_plan_and_capacities(tmp_path):
    cache = _cache(tmp_path)
    fp = {"config": 1}
    plan, _ = plan_join(PROF, Workload(r_tuples=100, s_tuples=100))
    cache.store(100, 100, fp, plan=plan)
    cache.store(100, 100, fp, capacities={"cap_r": 8, "cap_s": 8})
    got_plan, caps = cache.lookup(100, 100, fp)
    assert got_plan == plan and caps == {"cap_r": 8, "cap_s": 8}


def test_cache_corruption_is_a_miss(tmp_path):
    from tpu_radix_join.performance.measurements import Measurements
    meas = Measurements()
    cache = _cache(tmp_path, meas=meas)
    fp = {"config": 1}
    cache.store(100, 100, fp, capacities={"cap_r": 8, "cap_s": 8})
    [entry] = [p for p in os.listdir(cache.cache_dir)
               if p.startswith("plan_")]
    with open(os.path.join(cache.cache_dir, entry), "w") as f:
        f.write('{"trunca')
    assert cache.lookup(100, 100, fp) == (None, None)
    assert any(e.get("event") == "checkpoint_corrupt" for e in meas.meta.get("events", []))


def test_cache_profile_change_is_a_stale_miss(tmp_path):
    from tpu_radix_join.performance.measurements import Measurements
    cache = _cache(tmp_path)
    fp = {"config": 1}
    cache.store(100, 100, fp, capacities={"cap_r": 8, "cap_s": 8})
    meas = Measurements()
    recal = PROF.replace_constants(
        hbm_gbps={"value": 9.0, "source": "test"})
    cache2 = PlanCache(cache.cache_dir, recal, measurements=meas)
    assert cache2.lookup(100, 100, fp) == (None, None)
    assert any(e.get("event") == "plan_cache_stale" for e in meas.meta.get("events", []))
    # storing under the new profile overwrites; the old profile now misses
    cache2.store(100, 100, fp, capacities={"cap_r": 16, "cap_s": 16})
    assert cache2.lookup(100, 100, fp)[1] == {"cap_r": 16, "cap_s": 16}
    assert cache.lookup(100, 100, fp) == (None, None)


def test_manifest_detects_rank_and_profile_mismatch(tmp_path):
    cache = _cache(tmp_path)
    cache.check_manifest(num_ranks=2)          # fresh dir: no manifest yet
    assert cache.write_manifest(num_ranks=2, rank=0)
    cache.check_manifest(num_ranks=2)          # same topology: fine
    with pytest.raises(ManifestMismatch, match="2-rank"):
        cache.check_manifest(num_ranks=4)
    recal = PROF.replace_constants(
        hbm_gbps={"value": 9.0, "source": "test"})
    with pytest.raises(ManifestMismatch, match="constants"):
        PlanCache(cache.cache_dir, recal).check_manifest(num_ranks=2)
    # non-zero ranks never write
    assert cache.write_manifest(num_ranks=8, rank=1)
    cache.check_manifest(num_ranks=2)


# ------------------------------------------- engine warm start (tentpole)

def _batches(n, seed=0):
    import jax.numpy as jnp

    from tpu_radix_join.data.tuples import TupleBatch
    rng = np.random.default_rng(seed)
    mk = lambda k: TupleBatch(key=jnp.asarray(k),
                              rid=jnp.arange(n, dtype=jnp.uint32))
    return (mk(rng.integers(0, 1 << 20, n, dtype=np.uint32)),
            mk(rng.integers(0, 1 << 20, n, dtype=np.uint32)))


def test_warm_start_skips_sizing_prepass(tmp_path):
    """The acceptance observable: cold run sizes (JHIST present, entry
    saved); warm run skips the pre-pass (no JHIST, CKPTLOAD fired) and
    returns the identical count."""
    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.performance.measurements import Measurements
    r, s = _batches(1 << 12)
    cfg = JoinConfig(num_nodes=8)

    m_cold = Measurements()
    cold = HashJoin(cfg, measurements=m_cold,
                    plan_cache=_cache(tmp_path, meas=m_cold)).join_arrays(r, s)
    assert cold.ok
    assert "JHIST" in m_cold.times_us
    assert m_cold.counters.get("CKPTSAVE", 0) >= 1
    assert m_cold.counters.get("CKPTLOAD", 0) == 0

    m_warm = Measurements()
    warm = HashJoin(cfg, measurements=m_warm,
                    plan_cache=_cache(tmp_path, meas=m_warm)).join_arrays(r, s)
    assert warm.ok and warm.matches == cold.matches
    assert "JHIST" not in m_warm.times_us
    assert m_warm.counters.get("CKPTLOAD", 0) >= 1


def test_warm_start_invalidated_by_profile_change(tmp_path):
    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.performance.measurements import Measurements
    r, s = _batches(1 << 12)
    cfg = JoinConfig(num_nodes=8)
    m1 = Measurements()
    assert HashJoin(cfg, measurements=m1,
                    plan_cache=_cache(tmp_path, meas=m1)).join_arrays(r, s).ok
    recal = PROF.replace_constants(
        sort_stage_unit_ms={"value": 9.9, "source": "test"})
    m2 = Measurements()
    res = HashJoin(cfg, measurements=m2,
                   plan_cache=_cache(tmp_path, profile=recal,
                                     meas=m2)).join_arrays(r, s)
    assert res.ok
    assert "JHIST" in m2.times_us   # sized again: stale entry not trusted


def test_engine_without_cache_unchanged(tmp_path):
    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.performance.measurements import Measurements
    r, s = _batches(1 << 12)
    m = Measurements()
    res = HashJoin(JoinConfig(num_nodes=8), measurements=m).join_arrays(r, s)
    assert res.ok
    assert "JHIST" in m.times_us
    assert m.counters.get("CKPTSAVE", 0) == 0


# -------------------------------------------------------------------- CLI

def test_cli_plan_explain_prints_cost_table(capsys):
    from tpu_radix_join.main import main
    rc = main(["--tuples-per-node", "4096", "--nodes", "8",
               "--plan", "explain"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "predicted_ms" in out
    assert "incore_fused_sort_narrow" in out
    assert "chunked_grid" in out
    assert "chosen:" in out


def test_cli_plan_auto_runs_and_caches(capsys, tmp_path):
    from tpu_radix_join.main import main
    cache_dir = str(tmp_path / "pc")
    argv = ["--tuples-per-node", "2048", "--nodes", "8", "--plan", "auto",
            "--plan-cache-dir", cache_dir]
    assert main(argv) == 0
    cold = capsys.readouterr().out
    assert "[PLAN] strategy=" in cold
    assert "JHIST" in cold
    assert main(argv) == 0
    warm = capsys.readouterr().out
    assert "[PLAN] strategy=" in warm
    assert "JHIST" not in warm          # sizing pre-pass skipped
    assert "CKPTLOAD" in warm
    assert "[RESULTS] Tuples: 16384" in warm


def test_cli_plan_from_file(capsys, tmp_path):
    from tpu_radix_join.main import main
    plan, _ = plan_join(PROF, Workload(r_tuples=1 << 14, s_tuples=1 << 14,
                                       key_bound=1 << 14, num_nodes=8))
    path = str(tmp_path / "plan.json")
    plan.save(path)
    rc = main(["--tuples-per-node", "2048", "--nodes", "8", "--plan", path])
    assert rc == 0
    out = capsys.readouterr().out
    assert f"[PLAN] strategy={plan.strategy}" in out
    assert "[RESULTS] Tuples: 16384" in out


def test_cli_manifest_mismatch_fails_fast(capsys, tmp_path):
    from tpu_radix_join.main import main
    cache_dir = str(tmp_path / "pc")
    cache = PlanCache(cache_dir, PROF)
    cache.write_manifest(num_ranks=4, rank=0)   # pretend a 4-host run wrote it
    rc = main(["--tuples-per-node", "1024", "--nodes", "2", "--plan", "auto",
               "--plan-cache-dir", cache_dir])
    assert rc == 2
    err = capsys.readouterr().err
    assert "4-rank" in err


def test_grid_checkpoint_rejects_different_plan(tmp_path):
    import jax.numpy as jnp

    from tpu_radix_join.data.tuples import TupleBatch
    from tpu_radix_join.ops.chunked import chunked_join_grid
    from tpu_radix_join.robustness.checkpoint import CheckpointMismatch
    keys = np.arange(4096, dtype=np.uint32)
    chunk = TupleBatch(key=jnp.asarray(keys),
                       rid=jnp.arange(4096, dtype=jnp.uint32))
    ckpt = str(tmp_path / "grid.ckpt")
    plan_a = JoinPlan(engine="chunked", strategy="chunked_grid",
                      chunk_tuples=4096)
    total = chunked_join_grid([chunk], [chunk], 1024, checkpoint_path=ckpt,
                              checkpoint_tag="t", plan=plan_a)
    assert total == 4096
    plan_b = dataclasses.replace(plan_a, chunk_tuples=2048)
    with pytest.raises(CheckpointMismatch):
        chunked_join_grid([chunk], [chunk], 1024, checkpoint_path=ckpt,
                          checkpoint_tag="t", plan=plan_b)


# ----------------------------------------------- report / profile tooling

def test_print_results_surfaces_failure_classes(capsys):
    from tpu_radix_join.performance import print_results
    from tpu_radix_join.performance.measurements import Measurements
    ok, bad = Measurements(node_id=0), Measurements(node_id=1)
    ok.meta["failure_class"] = "ok"
    bad.meta["failure_class"] = "capacity_overflow"
    print_results([ok, bad])
    out = capsys.readouterr().out
    assert "FailureClasses: 1/2 ranks not ok" in out
    assert "rank1=capacity_overflow" in out
    print_results([ok])
    assert "FailureClasses: ok x1" in capsys.readouterr().out


def test_emit_profile_distills_artifacts(tmp_path):
    import tools_make_report as tmr
    art = tmp_path / "chip_rX"
    perf = art / "perf_16m_sort"
    perf.mkdir(parents=True)
    (perf / "0.perf").write_text("SDISPATCH\t123000\tus\n")
    trace = art / "trace_pipeline"
    trace.mkdir()
    (trace / "breakdown.json").write_text(json.dumps({
        "plane": "/device:TPU:0", "busy_us": 2e5, "iters": 10,
        "sort_share": 0.5, "size": 1 << 24, "discipline": "sort"}))
    out = str(tmp_path / "prof.json")
    assert tmr.emit_profile(str(art), out, name="v5e_test") == 0
    prof = load_profile(out)
    assert prof.name == "v5e_test"
    assert prof.value("dispatch_floor_ms") == pytest.approx(123.0)
    assert "artifact:" in prof.source("dispatch_floor_ms")
    # sort unit: 10 ms/iter sort over a 33.5M union == one reference unit
    # per U(33.5M) stages
    from tpu_radix_join.planner.profile import (SORT_REF_ELEMS,
                                                sort_stage_units)
    expect = 10.0 / sort_stage_units(SORT_REF_ELEMS)
    assert prof.value("sort_stage_unit_ms") == pytest.approx(expect,
                                                             rel=1e-3)
    assert "artifact:" in prof.source("sort_stage_unit_ms")
    # untouched constants keep their committed citations
    assert prof.source("hbm_gbps") == PROF.source("hbm_gbps")

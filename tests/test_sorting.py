"""The sort wrappers (ops/sorting.py) and the planner's sort term.

Parity contract with NumPy: keys come out non-decreasing and the
(key, *values) row multiset is preserved.  The sorts are *unstable* as
advertised, so equal-key runs may order their value lanes differently
from NumPy; parity is therefore asserted on canonicalized rows (sorted
lexicographically), not element-by-element.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_radix_join.data.tuples import effective_key_bits
from tpu_radix_join.ops.sorting import (segmented_xor_fold, sort_kv_unstable,
                                        sort_lex_unstable, sort_unstable)

N = 4096


def _u32(a):
    return jnp.asarray(np.asarray(a, dtype=np.uint32))


def _assert_sorted_parity(out, raw):
    """Keys equal NumPy's sort and the row multiset is preserved."""
    got = [np.asarray(o) for o in out]
    np.testing.assert_array_equal(got[0], np.sort(np.asarray(raw[0])))
    perm_in = np.lexsort(tuple(reversed([np.asarray(r) for r in raw])))
    perm_out = np.lexsort(tuple(reversed(got)))
    for r, g in zip(raw, got):
        np.testing.assert_array_equal(np.asarray(r)[perm_in], g[perm_out])


def test_effective_key_bits():
    assert effective_key_bits(None) == 32
    assert effective_key_bits(1 << 16) == 16
    assert effective_key_bits(1 << 16, fanout_bits=5) == 11
    assert effective_key_bits(2) == 1
    assert effective_key_bits(1) == 1          # degenerate: single key value
    assert effective_key_bits(None, key_bits=64) == 64
    assert effective_key_bits(1 << 40, key_bits=64) == 40
    with pytest.raises(ValueError):
        effective_key_bits(0)


# ------------------------------------------------------------ the wrappers

@pytest.mark.parametrize("case", ["random", "sentinel_saturated",
                                  "duplicate_heavy", "presorted",
                                  "reverse_sorted"])
@pytest.mark.parametrize("value_lanes", [0, 1, 2])
def test_sweep_parity_with_numpy(case, value_lanes):
    rng = np.random.default_rng(sum(map(ord, case)))
    keys = {
        "random": rng.integers(0, 1 << 32, N, dtype=np.uint32),
        # every uint32 is a valid key, the pad sentinels included
        "sentinel_saturated": rng.choice(
            np.array([0, 1, 0xFFFFFFFE, 0xFFFFFFFF], np.uint32), N),
        "duplicate_heavy": (rng.integers(0, 1 << 32, N) % 7
                            ).astype(np.uint32),
        "presorted": np.sort(rng.integers(0, 1 << 32, N, dtype=np.uint32)),
        "reverse_sorted": np.sort(
            rng.integers(0, 1 << 32, N, dtype=np.uint32))[::-1].copy(),
    }[case]
    vals = [np.arange(N, dtype=np.uint32),
            rng.integers(0, 1 << 32, N, dtype=np.uint32)]
    raw = [keys] + vals[:value_lanes]
    if value_lanes:
        out = sort_kv_unstable(*(_u32(a) for a in raw))
    else:
        out = [sort_unstable(_u32(keys))]
    _assert_sorted_parity(out, raw)


def test_64bit_split_lane_lex_sort_matches_numpy():
    rng = np.random.default_rng(9)
    hi = rng.integers(0, 1 << 8, N, dtype=np.uint32)
    lo = rng.integers(0, 1 << 32, N, dtype=np.uint32)
    rid = np.arange(N, dtype=np.uint32)
    out = sort_lex_unstable(_u32(hi), _u32(lo), _u32(rid), num_keys=2)
    order = np.lexsort((rid, lo, hi))
    np.testing.assert_array_equal(np.asarray(out[0]), hi[order])
    np.testing.assert_array_equal(np.asarray(out[1]), lo[order])
    _assert_sorted_parity(out, [hi, lo, rid])


def test_all_sentinel_keys_with_padding_lose_nothing():
    # an odd length and every key 0xFFFFFFFF (the pad sentinel's
    # neighbourhood): no real row may be taken for padding
    n = N - 3
    keys = np.full(n, 0xFFFFFFFF, np.uint32)
    rid = np.arange(n, dtype=np.uint32)
    out = sort_kv_unstable(_u32(keys), _u32(rid))
    np.testing.assert_array_equal(np.asarray(out[0]), keys)
    np.testing.assert_array_equal(np.sort(np.asarray(out[1])), rid)


def test_tiny_and_empty_inputs():
    out = sort_kv_unstable(_u32([5]), _u32([7]))
    assert np.asarray(out[0]).tolist() == [5]
    assert np.asarray(out[1]).tolist() == [7]
    out = sort_kv_unstable(_u32([]), _u32([]))
    assert np.asarray(out[0]).size == 0


def test_batched_sort_matches_numpy():
    x = np.random.default_rng(3).integers(0, 99, (4, 64), dtype=np.uint32)
    out = np.asarray(sort_unstable(_u32(x)))
    np.testing.assert_array_equal(out, np.sort(x, axis=-1))


def test_wrappers_match_numpy():
    rng = np.random.default_rng(21)
    keys = rng.integers(0, 1 << 32, N, dtype=np.uint32)
    rid = np.arange(N, dtype=np.uint32)
    _assert_sorted_parity([sort_unstable(_u32(keys))], [keys])
    _assert_sorted_parity(sort_kv_unstable(_u32(keys), _u32(rid)),
                          [keys, rid])
    _assert_sorted_parity(
        sort_lex_unstable(_u32(keys % 7), _u32(rid), num_keys=1),
        [keys % 7, rid])


def test_xor_fold_exact():
    rng = np.random.default_rng(5)
    seg = rng.integers(0, 16, N, dtype=np.uint32)
    vals = rng.integers(0, 1 << 32, N, dtype=np.uint32)
    expect = np.zeros(16, np.uint32)
    for q in range(16):
        expect[q] = np.bitwise_xor.reduce(vals[seg == q]) \
            if (seg == q).any() else 0
    got = np.asarray(segmented_xor_fold(_u32(seg), _u32(vals), 16))
    np.testing.assert_array_equal(got, expect)


_WRAPPERS = {
    "sort_unstable": (1, lambda k: sort_unstable(k)),
    "sort_kv_unstable": (2, lambda k, v: sort_kv_unstable(k, v)),
    "sort_lex_unstable": (3, lambda hi, lo, v: sort_lex_unstable(
        hi, lo, v, num_keys=2)),
}


@pytest.mark.parametrize("elems", [1 << 10, 1 << 18, 1 << 25, 40_000_000])
@pytest.mark.parametrize("wrapper", sorted(_WRAPPERS))
def test_wrapper_lowers_to_a_sort_named_trj_sort(wrapper, elems):
    # the per-stage sort time finds the sort's device work by this name
    lanes, fn = _WRAPPERS[wrapper]
    x = jax.ShapeDtypeStruct((elems,), jnp.uint32)
    text = jax.jit(fn).lower(*[x] * lanes).as_text(dialect="hlo",
                                                   debug_info=True)
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert len(sorts) == 1, sorts
    assert re.search(r'op_name="[^"]*trj\.sort[^"]*"', sorts[0]), sorts[0]
    assert "custom-call" not in text


# ------------------------------------------------------------- planner

def test_old_plan_files_with_sort_impl_still_load():
    from tpu_radix_join.planner.cost_model import Workload
    from tpu_radix_join.planner.plan import JoinPlan, plan_join
    from tpu_radix_join.planner.profile import load_profile
    plan, _ = plan_join(load_profile(),
                        Workload(r_tuples=1 << 22, s_tuples=1 << 22,
                                 num_nodes=8))
    assert "sort_impl" not in plan.to_dict()
    assert "sort_impl" not in plan.config_kwargs()
    for impl in ("xla", "pallas", "auto"):
        doc = {**plan.to_dict(), "sort_impl": impl, "schema_version": 5}
        assert JoinPlan.from_dict(doc).to_dict() == {**plan.to_dict(),
                                                     "schema_version": 5}


def test_profile_with_retired_radix_constant_loads(tmp_path):
    from tpu_radix_join.planner.profile import load_profile
    prof = load_profile()
    doc = prof.to_dict()
    doc["schema_version"] = 5
    doc["constants"]["radix_sort_pass_unit_ms"] = {
        "value": 8.79609, "source": "calibrate:radix_slot_pass impl=pallas"}
    path = tmp_path / "v5.json"
    path.write_text(json.dumps(doc))
    old = load_profile(str(path))
    assert all(old.value(k) == prof.value(k) for k in prof.constants)


def test_fit_profile_ignores_radix_sort_rows():
    from tpu_radix_join.planner.calibrate import (collect_samples,
                                                  fit_profile)
    radix = [{"kind": "bench", "run_id": f"r{i}",
              "metric": "radix_sort_speedup", "size": 1 << 20,
              "sort_passes": 4, "sort_kernel_ms": 2.0} for i in range(2)]
    obs = [{"kind": "obs", "run_id": f"o{i}",
            "constant": "dispatch_floor_ms", "value": 0.5}
           for i in range(2)]
    assert "radix_sort_pass_unit_ms" not in collect_samples(radix)
    prof, fits = fit_profile(radix + obs, fitted_at=1000.0)
    assert set(fits) == {"dispatch_floor_ms"}
    assert "radix_sort_pass_unit_ms" not in prof.constants


#: (workload, the strategy row whose sort term is checked, the term's
#: sort_ms arguments after the profile: elements, lane factor, rows)
_SORT_ROWS = {
    "narrow32": (dict(key_bound=1 << 20), "incore_fused_sort_narrow",
                 lambda prof, u: (u, 1.0, 1)),
    "full32": (dict(key_bound=1 << 31), "incore_fused_sort_full",
               lambda prof, u: (u, prof.value("full_range_sort_factor"), 1)),
    "full64": (dict(key_bits=64), "incore_fused_sort_full",
               lambda prof, u: (u, 1.0 + 2.0 * (
                   prof.value("full_range_sort_factor") - 1.0), 1)),
    "batched": (dict(key_bound=1 << 20), "incore_fused_twolevel",
                lambda prof, u: (u, 1.0, 32)),
}


@pytest.mark.parametrize("row", sorted(_SORT_ROWS))
def test_planner_sort_term_is_sort_ms(row):
    from tpu_radix_join.planner.cost_model import (Workload,
                                                   enumerate_strategies,
                                                   sort_ms)
    from tpu_radix_join.planner.profile import load_profile
    prof = load_profile()
    kw, strategy, args = _SORT_ROWS[row]
    w = Workload(r_tuples=1 << 22, s_tuples=1 << 22, num_nodes=8, **kw)
    got = next(r for r in enumerate_strategies(prof, w)
               if r.strategy == strategy)
    assert got.terms["sort"] == round(
        sort_ms(prof, *args(prof, w.union_per_node)), 3) > 0


#: each cell's shape and the strategy and cost the parent commit's
#: planner chose for it (v5e_lite profile)
_CELLS = {
    "uniform_1c": (dict(r_tuples=20_000_000, s_tuples=20_000_000,
                        key_bound=20_000_000, num_nodes=1),
                   "incore_fused_sort_narrow", 46.51),
    "uniform_4c": (dict(r_tuples=80_000_000, s_tuples=80_000_000,
                        key_bound=80_000_000, num_nodes=4),
                   "incore_fused_sort_narrow", 51.866),
    "serve_1c": (dict(r_tuples=45_000_000, s_tuples=180_000_000,
                      key_bound=180_000_001, num_nodes=1),
                 "incore_fused_sort_narrow", 298.663),
}


@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_plan_join_picks_the_cells_strategy(cell):
    from tpu_radix_join.planner.cost_model import Workload
    from tpu_radix_join.planner.plan import plan_join
    from tpu_radix_join.planner.profile import load_profile
    kw, strategy, predicted_ms = _CELLS[cell]
    plan, _ = plan_join(load_profile(), Workload(**kw))
    assert plan.strategy == strategy
    assert plan.predicted_ms == predicted_ms


# -------------------------------------------------------- engine wiring

def test_join_oracle_exact():
    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.data.relation import Relation

    n = 8
    inner = Relation(n << 10, n, "unique", seed=31)
    outer = Relation(n << 10, n, "unique", seed=32)
    res = HashJoin(JoinConfig(num_nodes=n, verify="check")).join(inner, outer)
    assert res.ok and res.matches == inner.expected_matches(outer)


def test_config_rejects_unknown_sort_impl():
    from tpu_radix_join import JoinConfig
    for impl in ("pallas", "pallas_interpret", "bogus"):
        with pytest.raises(ValueError, match="sort impl"):
            JoinConfig(sort_impl=impl)
    for impl in ("auto", "xla"):
        assert JoinConfig(sort_impl=impl).sort_impl == impl

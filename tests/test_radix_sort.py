"""Pallas LSD radix sort (ops/pallas/radix_sort.py) and its wiring
(ops/sorting impl switch, planner sort arm, radix-sort telemetry).

Parity contract with lax.sort: keys come out non-decreasing and the
(key, *values) row multiset is preserved.  Both engines are *unstable*
as advertised, so equal-key runs may order their value lanes differently
between arms; parity is therefore asserted on canonicalized rows (sorted
lexicographically), not element-by-element.  The radix kernel itself is
additionally STABLE (the partition pass's first-in-input-order contract,
chained across digit passes), which the duplicate-heavy sweep pins
directly — the 64-bit split-lane path depends on it.

Everything runs the interpret kernel on host CPU (tier-1); the shapes
are kept to a handful of (n, shift) combos because each distinct combo
costs a fresh trace of the pass kernel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_radix_join.ops.pallas.radix_sort as rsmod
import tpu_radix_join.ops.sorting as sorting
from tpu_radix_join.data.tuples import effective_key_bits
from tpu_radix_join.ops.pallas.radix_sort import (num_radix_passes,
                                                  radix_sort_pallas)
from tpu_radix_join.ops.sorting import (resolve_sort_impl,
                                        segmented_xor_fold,
                                        set_default_sort_impl,
                                        sort_kv_unstable, sort_lex_unstable,
                                        sort_unstable)
from tpu_radix_join.performance.measurements import (SORTFALLBACK, SORTPASS,
                                                     Measurements)

INTERP = "pallas_interpret"
N = 4096          # one shared shape -> the pass kernel traces once per shift


def _u32(a):
    return jnp.asarray(np.asarray(a, dtype=np.uint32))


def _assert_sorted_parity(out, raw):
    """Keys non-decreasing + row multiset preserved (both arms' contract)."""
    got = [np.asarray(o) for o in out]
    assert (np.diff(got[0].astype(np.int64)) >= 0).all()
    perm_in = np.lexsort(tuple(reversed([np.asarray(r) for r in raw])))
    perm_out = np.lexsort(tuple(reversed(got)))
    for r, g in zip(raw, got):
        np.testing.assert_array_equal(np.asarray(r)[perm_in], g[perm_out])


# ------------------------------------------------------------ pass counting

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


def test_num_radix_passes_bound_mapping():
    # the ISSUE's pin: a 16-bit bound buys exactly 2 of the 4 passes back
    assert num_radix_passes(None) == 4
    assert num_radix_passes(1 << 16) == 2
    assert num_radix_passes(1 << 8) == 1
    assert num_radix_passes(257) == 2
    assert num_radix_passes(None, key_bits=64) == 8


# --------------------------------------------------------------- the kernel

@pytest.mark.parametrize("case", ["random", "sentinel_saturated",
                                  "duplicate_heavy", "presorted",
                                  "reverse_sorted"])
@pytest.mark.parametrize("value_lanes", [0, 1, 2])
def test_sweep_parity_with_lax_sort(case, value_lanes):
    rng = np.random.default_rng(hash(case) % (1 << 16))
    keys = {
        "random": rng.integers(0, 1 << 32, N, dtype=np.uint32),
        # every uint32 is a valid key — the pad discipline is positional,
        # so even an input saturated with would-be sentinels must survive
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
    out = radix_sort_pallas(tuple(_u32(a) for a in raw), num_keys=1,
                            interpret=True)
    _assert_sorted_parity(out, raw)
    if value_lanes >= 1:
        # stability: first value lane is input position — within an
        # equal-key run it must come out strictly increasing
        k, v = np.asarray(out[0]), np.asarray(out[1])
        run_starts = np.flatnonzero(np.diff(k) == 0)
        assert (v[run_starts + 1] > v[run_starts]).all()


def test_64bit_split_lane_lex_sort_matches_numpy():
    rng = np.random.default_rng(9)
    hi = rng.integers(0, 1 << 8, N, dtype=np.uint32)
    lo = rng.integers(0, 1 << 32, N, dtype=np.uint32)
    rid = np.arange(N, dtype=np.uint32)
    out = radix_sort_pallas((_u32(hi), _u32(lo), _u32(rid)), num_keys=2,
                            key_bounds=(1 << 8, None), interpret=True)
    order = np.lexsort((rid, lo, hi))    # stable -> unique expected order
    np.testing.assert_array_equal(np.asarray(out[0]), hi[order])
    np.testing.assert_array_equal(np.asarray(out[1]), lo[order])
    np.testing.assert_array_equal(np.asarray(out[2]), rid[order])


def test_bounded_keys_skip_passes(monkeypatch):
    rng = np.random.default_rng(11)
    keys = rng.integers(0, 1 << 16, N, dtype=np.uint32)
    rid = np.arange(N, dtype=np.uint32)
    calls = []
    real = rsmod.radix_pass_slots_pallas

    def counting(k, *, shift, interpret=False):
        calls.append(shift)
        return real(k, shift=shift, interpret=interpret)

    monkeypatch.setattr(rsmod, "radix_pass_slots_pallas", counting)
    out = radix_sort_pallas((_u32(keys), _u32(rid)), num_keys=1,
                            key_bounds=(1 << 16,), interpret=True)
    assert calls == [0, 8]               # 2 passes, not 4
    _assert_sorted_parity(out, [keys, rid])
    calls.clear()
    radix_sort_pallas((_u32(keys), _u32(rid)), num_keys=1, interpret=True)
    assert calls == [0, 8, 16, 24]       # unbounded worst case


def test_all_sentinel_keys_with_padding_lose_nothing():
    # n not a multiple of the tile width forces pad rows; every key is
    # 0xFFFFFFFF (= the dropped-slot marker's neighborhood), so only the
    # positional pad rule keeps real rows apart from padding
    n = N - 3
    keys = np.full(n, 0xFFFFFFFF, np.uint32)
    rid = np.arange(n, dtype=np.uint32)
    out = radix_sort_pallas((_u32(keys), _u32(rid)), num_keys=1,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(out[0]), keys)
    np.testing.assert_array_equal(np.asarray(out[1]), rid)  # stable identity


def test_tiny_and_empty_inputs():
    out = radix_sort_pallas((_u32([5]), _u32([7])), num_keys=1,
                            interpret=True)
    assert np.asarray(out[0]).tolist() == [5]
    out = radix_sort_pallas((_u32([]), _u32([])), num_keys=1, interpret=True)
    assert np.asarray(out[0]).size == 0


# ------------------------------------------------------- the sorting switch

def test_switch_wrappers_route_and_match(monkeypatch):
    rng = np.random.default_rng(21)
    keys = rng.integers(0, 1 << 32, N, dtype=np.uint32)
    rid = np.arange(N, dtype=np.uint32)
    for impl in ("xla", INTERP):
        _assert_sorted_parity([sort_unstable(_u32(keys), impl=impl)], [keys])
        _assert_sorted_parity(
            sort_kv_unstable(_u32(keys), _u32(rid), impl=impl), [keys, rid])
        _assert_sorted_parity(
            sort_lex_unstable(_u32(keys % 7), _u32(rid), num_keys=1,
                              impl=impl), [keys % 7, rid])


def test_batched_sort_quietly_ineligible_even_when_forced(capsys):
    # 2-D sorts are outside the kernel's shapes: a forced impl routes to
    # lax.sort with no fallback noise (forcing selects the impl for the
    # sorts the kernel can express, it does not redefine what it expresses)
    x = jnp.asarray(np.random.default_rng(3).integers(
        0, 99, (4, 64), dtype=np.uint32))
    out = np.asarray(sort_unstable(x, impl=INTERP))
    np.testing.assert_array_equal(out, np.sort(np.asarray(x), axis=-1))
    assert "fell back" not in capsys.readouterr().err


def test_xor_fold_exact_under_forced_radix_arm():
    rng = np.random.default_rng(5)
    seg = rng.integers(0, 16, N, dtype=np.uint32)
    vals = rng.integers(0, 1 << 32, N, dtype=np.uint32)
    expect = np.zeros(16, np.uint32)
    for q in range(16):
        expect[q] = np.bitwise_xor.reduce(vals[seg == q]) \
            if (seg == q).any() else 0
    set_default_sort_impl(INTERP)
    try:
        got = np.asarray(segmented_xor_fold(_u32(seg), _u32(vals), 16))
    finally:
        set_default_sort_impl("auto")
    np.testing.assert_array_equal(got, expect)


# ------------------------------------------------------- auto is lax.sort

@pytest.mark.parametrize("elems", [1 << 10, 1 << 18, 1 << 25, 40_000_000])
@pytest.mark.parametrize("shape", ["eligible", "batched", "int32"])
def test_auto_resolves_to_xla_at_every_size(monkeypatch, capsys, elems,
                                            shape):
    # stand for a TPU backend: the radix kernel could compile, and auto
    # still takes lax.sort, quietly, at every size and for every shape
    from tpu_radix_join.ops.pallas import merge_scan
    monkeypatch.setattr(merge_scan, "pallas_available", lambda: True)
    m = Measurements()
    sorting.install_sort_observer(m)
    eligible = shape == "eligible"
    try:
        assert resolve_sort_impl("auto", eligible) == "xla"
        assert resolve_sort_impl(None, eligible) == "xla"
        # what traces: one lax.sort and no radix pass, with no allocation
        dims = {"eligible": ((elems,), jnp.uint32),
                "batched": ((8, elems // 8), jnp.uint32),
                "int32": ((elems,), jnp.int32)}[shape]
        x = jax.ShapeDtypeStruct(*dims)
        jaxpr = str(jax.make_jaxpr(lambda k: sort_kv_unstable(k, k))(x))
        assert "sort[" in jaxpr and "pallas_call" not in jaxpr
        assert m.counters[SORTPASS] == 0
        assert m.counters[SORTFALLBACK] == 0
        # forced arms are honoured wherever the kernel can express them
        assert resolve_sort_impl("xla", eligible) == "xla"
        for forced in ("pallas", INTERP):
            assert resolve_sort_impl(forced, eligible) == (
                forced if eligible else "xla")
    finally:
        sorting.install_sort_observer(None)
    assert capsys.readouterr().err == ""


def test_pallas_path_ticks_sortpass_span():
    m = Measurements()
    sorting.install_sort_observer(m)
    try:
        keys = _u32(np.arange(N)[::-1].copy())
        sort_kv_unstable(keys, _u32(np.arange(N)), impl=INTERP)
        assert m.counters[SORTPASS] == 1
        spans = [r for r in m.flightrec.records()
                 if r["name"] == "radix_sort" and r["kind"] == "span"]
        assert spans and spans[0]["impl"] == INTERP
        assert spans[0]["site"] == "sort_kv_unstable"
    finally:
        sorting.install_sort_observer(None)


# ------------------------------------------------------------- planner

def test_plan_sort_prices_both_arms():
    from tpu_radix_join.planner.cost_model import plan_sort
    from tpu_radix_join.planner.profile import load_profile
    prof = load_profile()
    plan = plan_sort(prof, 1 << 25)
    # auto binds lax.sort, the arm the runtime's auto picks, while the
    # radix arm stays priced for the explain table
    assert plan.impl == "xla" and plan.sort_ms == plan.xla_ms
    assert plan.pallas_ms > 0 and plan.passes == 4
    # a bound halves the radix arm's passes and its price with them
    bounded = plan_sort(prof, 1 << 25, key_bound=1 << 16)
    assert bounded.impl == "xla"
    assert bounded.passes == 2 < plan.passes
    assert bounded.pallas_ms < plan.pallas_ms
    # the radix arm prices off the schema-v5 constant
    bumped = prof.replace_constants(radix_sort_pass_unit_ms={
        "value": prof.value("radix_sort_pass_unit_ms") * 10,
        "source": "test"})
    assert plan_sort(bumped, 1 << 25).pallas_ms > plan.pallas_ms
    # small and batched sorts stay xla too
    assert plan_sort(prof, 1 << 10).impl == "xla"
    assert plan_sort(prof, 1 << 25, rows=32).impl == "xla"


def test_strategy_rows_carry_the_sort_arm():
    from tpu_radix_join.planner.cost_model import (Workload,
                                                   enumerate_strategies)
    from tpu_radix_join.planner.profile import load_profile
    rows = enumerate_strategies(load_profile(),
                                Workload(r_tuples=1 << 22, s_tuples=1 << 22,
                                         key_bound=1 << 20, num_nodes=8))
    fused = next(r for r in rows if r.strategy == "incore_fused_sort_narrow")
    assert fused.terms["sort"] > 0
    assert "sort arm:" in fused.note


def test_plan_binds_sort_impl_and_v4_plans_still_load():
    from tpu_radix_join.planner.cost_model import Workload
    from tpu_radix_join.planner.plan import (PLAN_SCHEMA_VERSION, JoinPlan,
                                             plan_join)
    from tpu_radix_join.planner.profile import load_profile
    plan, _ = plan_join(load_profile(),
                        Workload(r_tuples=1 << 22, s_tuples=1 << 22,
                                 num_nodes=8))
    assert PLAN_SCHEMA_VERSION == 5
    assert plan.sort_impl == "xla"
    assert plan.config_kwargs()["sort_impl"] == plan.sort_impl
    doc = plan.to_dict()
    doc.pop("sort_impl")
    doc["schema_version"] = 4
    assert JoinPlan.from_dict(doc).sort_impl == "auto"


def test_profile_v4_shims_the_sort_unit():
    from tpu_radix_join.planner.profile import load_profile
    prof = load_profile()
    doc = {"name": "old", "schema_version": 4,
           "constants": {k: dict(v) for k, v in prof.constants.items()}}
    doc["constants"].pop("radix_sort_pass_unit_ms")
    import json
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(doc, f)
        path = f.name
    shimmed = load_profile(path)
    assert shimmed.value("radix_sort_pass_unit_ms") == pytest.approx(
        12.0 / prof.value("hbm_gbps"), rel=1e-3)
    assert "shim" in shimmed.constants["radix_sort_pass_unit_ms"]["source"]


def test_calibrate_inverts_sort_bench_rows():
    from tpu_radix_join.planner.calibrate import collect_samples
    rows = [{"kind": "bench", "run_id": "r1",
             "metric": "radix_sort_speedup", "size": 1 << 20,
             "sort_passes": 4, "sort_kernel_ms": 2.0},
            {"kind": "bench", "run_id": "r2",
             "metric": "radix_sort_speedup", "size": 1 << 19,
             "sort_passes": 2, "sort_kernel_ms": 0.5}]
    got = collect_samples(rows)["radix_sort_pass_unit_ms"]
    assert got[0].value == pytest.approx(2.0 / (4 * (1 << 20) / 1e6))
    assert got[1].value == pytest.approx(0.5 / (2 * (1 << 19) / 1e6))


# -------------------------------------------------------- engine wiring

def _oracle_join(**cfg_kw):
    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.performance import Measurements

    n = 8
    inner = Relation(n << 10, n, "unique", seed=31)
    outer = Relation(n << 10, n, "unique", seed=32)
    m = Measurements(node_id=0, num_nodes=n)
    eng = HashJoin(JoinConfig(num_nodes=n, verify="check", **cfg_kw),
                   measurements=m)
    res = eng.join(inner, outer)
    assert res.ok and res.matches == inner.expected_matches(outer)
    return m


def test_join_forced_radix_sort_oracle_exact():
    try:
        m = _oracle_join(sort_impl=INTERP)
    finally:
        # the engine binds its impl process-wide; don't leak the forced
        # interpret arm (or the join's observer) into later test files
        set_default_sort_impl("auto")
        sorting.install_sort_observer(None)
    assert m.counters[SORTPASS] > 0
    spans = [r for r in m.flightrec.records()
             if r["name"] == "radix_sort" and r["kind"] == "span"]
    assert spans and all(s["impl"] == INTERP for s in spans)


def test_config_rejects_unknown_sort_impl():
    from tpu_radix_join import JoinConfig
    with pytest.raises(ValueError, match="sort impl"):
        JoinConfig(sort_impl="bogus")

"""Fused Pallas radix-partition kernel (ops/pallas/partition.py) and its
wiring (ops/radix impl selection, planner pricing, fallback telemetry).

Parity contract with the sort path: histograms / counts / group_counts /
overflow are byte-equal on every input; block *membership* is multiset-
equal per (block, sub) group when overflow == 0.  Under overflow the two
paths may keep different tuples of the clipped boundary group (the
unstable sort keeps an arbitrary subset, the fused kernel keeps
first-in-input-order) — both are contract-valid because overflow != 0
already voids the result (Window retries at doubled capacity), so those
tests assert membership (every kept row is a genuine tuple of its group)
plus the byte-equal accounting, not tuple identity."""

import jax.numpy as jnp
import numpy as np
import pytest

import tpu_radix_join.ops.radix as radix
from tpu_radix_join.data.tuples import CompressedBatch
from tpu_radix_join.ops.pallas.partition import (MAX_PARTITIONS,
                                                 partition_slots_pallas)
from tpu_radix_join.ops.radix import (local_histogram, reorder_by_partition,
                                      scatter_to_blocks,
                                      scatter_to_blocks_grouped)
from tpu_radix_join.performance.measurements import (PARTFALLBACK, PARTPASS,
                                                     Measurements)

INTERP = "pallas_interpret"


def _comp(keys, rids):
    return CompressedBatch(key_rem=jnp.asarray(keys, jnp.uint32),
                           rid=jnp.asarray(rids, jnp.uint32))


def _rand(n, num_blocks, num_sub=1, seed=0, valid_p=None):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 1 << 20, n).astype(np.uint32)
    batch = _comp(keys, np.arange(n))
    dest = jnp.asarray(rng.integers(0, num_blocks, n).astype(np.uint32))
    sub = jnp.asarray(rng.integers(0, num_sub, n).astype(np.uint32))
    valid = (None if valid_p is None else
             jnp.asarray(rng.random(n) < valid_p))
    return batch, dest, sub, valid


# ----------------------------------------------------------------- kernel

def test_kernel_dense_mode_is_grouping_permutation():
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 7, 5000).astype(np.uint32)
    slots, hist = partition_slots_pallas(jnp.asarray(ids), num_groups=7,
                                         interpret=True)
    slots = np.asarray(slots)
    # a permutation: every tuple lands, each slot once
    assert sorted(slots.tolist()) == list(range(5000))
    np.testing.assert_array_equal(np.asarray(hist),
                                  np.bincount(ids, minlength=7))
    # grouped by id in id order, input order within a group
    base = np.concatenate([[0], np.cumsum(np.bincount(ids, minlength=7))])
    for g in range(7):
        mine = np.flatnonzero(ids == g)
        np.testing.assert_array_equal(np.sort(slots[mine]),
                                      np.arange(base[g], base[g + 1]))
        # input order preserved within the group
        assert (np.diff(slots[mine]) > 0).all()


def test_kernel_blocked_mode_matches_numpy_reference():
    rng = np.random.default_rng(3)
    num_groups, group_size, cap = 12, 3, 40
    ids = rng.integers(0, num_groups + 2, 700).astype(np.uint32)  # some invalid
    slots, hist = partition_slots_pallas(
        jnp.asarray(ids), num_groups=num_groups, group_size=group_size,
        capacity=cap, interpret=True)
    slots = np.asarray(slots)
    np.testing.assert_array_equal(np.asarray(hist),
                                  np.bincount(ids, minlength=num_groups
                                              )[:num_groups])
    # reference: per-destination unclipped prefix in (group, input) order
    base = np.concatenate([[0], np.cumsum(np.bincount(
        np.minimum(ids, num_groups), minlength=num_groups + 1))])[:-1]
    pos_in_group = np.zeros_like(ids)
    seen = {}
    for i, g in enumerate(ids):
        seen[g] = seen.get(g, 0) + 1
        pos_in_group[i] = seen[g] - 1
    for i, g in enumerate(ids):
        if g >= num_groups:
            assert slots[i] == 0xFFFFFFFF          # invalid -> sentinel
            continue
        blk = g // group_size
        within = base[g] - base[(g // group_size) * group_size] \
            + pos_in_group[i]
        if within >= cap:
            assert slots[i] == 0xFFFFFFFF          # overflow -> dropped
        else:
            assert slots[i] == blk * cap + within


def test_kernel_multi_tile_carry():
    # > 1 grid tile (262144 ids per tile at the max block): the SMEM
    # cursors must carry across sequential grid steps
    n = 600_000
    rng = np.random.default_rng(4)
    ids = rng.integers(0, 5, n).astype(np.uint32)
    slots, hist = partition_slots_pallas(jnp.asarray(ids), num_groups=5,
                                         interpret=True)
    slots = np.asarray(slots)
    assert sorted(slots.tolist()) == list(range(n))
    np.testing.assert_array_equal(np.asarray(hist),
                                  np.bincount(ids, minlength=5))


@pytest.fixture
def mosaic_branch_interpreted(monkeypatch):
    """Run the kernels' Mosaic branches (``interpret=False`` in the kernel
    body) under the Pallas interpreter: the CPU's only view of the code
    the chip compiles, which interpret mode otherwise never traces."""
    from jax.experimental import pallas as pl

    import tpu_radix_join.ops.pallas.partition as part
    real = pl.pallas_call
    monkeypatch.setattr(part.pl, "pallas_call",
                        lambda *a, **kw: real(*a, **{**kw,
                                                     "interpret": True}))


@pytest.mark.parametrize("num_groups,group_size,capacity", [
    (5, 1, None), (257, 1, None), (8, 2, 30000)])
def test_mosaic_branch_matches_interpret_branch(mosaic_branch_interpreted,
                                                num_groups, group_size,
                                                capacity):
    import jax
    ids = jnp.asarray(np.random.default_rng(num_groups).integers(
        0, num_groups + 1, 100_003).astype(np.uint32))
    kw = dict(num_groups=num_groups, group_size=group_size,
              capacity=capacity)
    # the jitted wrapper caches per static args: jit a fresh lambda so the
    # interpret=False trace really takes the Mosaic branch
    mosaic = jax.jit(lambda x: partition_slots_pallas(x, **kw))(ids)
    interp = partition_slots_pallas(ids, **kw, interpret=True)
    for a, b in zip(mosaic, interp):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_rejects_bad_geometry():
    ids = jnp.zeros((16,), jnp.uint32)
    with pytest.raises(ValueError, match=f"> {MAX_PARTITIONS + 1}"):
        partition_slots_pallas(ids, num_groups=MAX_PARTITIONS + 2,
                               interpret=True)
    with pytest.raises(ValueError, match="multiple"):
        partition_slots_pallas(ids, num_groups=10, group_size=4,
                               capacity=8, interpret=True)


# ------------------------------------------------- flat scatter parity

def _valid_rows(blocks, counts, cap, b):
    """The occupied prefix of block ``b`` (both impls fill contiguously)."""
    k = int(min(int(counts[b]), cap))
    lo = b * cap
    return (np.asarray(blocks.key_rem)[lo:lo + k],
            np.asarray(blocks.rid)[lo:lo + k])


@pytest.mark.parametrize("valid_p", [None, 0.7])
def test_scatter_parity_no_overflow(valid_p):
    n, nb, cap = 4000, 8, 1000
    batch, dest, _, valid = _rand(n, nb, seed=5, valid_p=valid_p)
    bs, cs, os_ = scatter_to_blocks(batch, dest, nb, cap, "inner",
                                    valid=valid, impl="sort")
    bp, cp, op = scatter_to_blocks(batch, dest, nb, cap, "inner",
                                   valid=valid, impl=INTERP)
    np.testing.assert_array_equal(np.asarray(cs), np.asarray(cp))
    assert int(os_) == int(op) == 0
    for b in range(nb):
        ks, rs = _valid_rows(bs, np.asarray(cs), cap, b)
        kp, rp = _valid_rows(bp, np.asarray(cp), cap, b)
        # same multiset of tuples per block (within-block order is free)
        np.testing.assert_array_equal(np.sort(rs), np.sort(rp))
        np.testing.assert_array_equal(np.sort(ks), np.sort(kp))
    # sentinel padding past the count on both routes
    np.testing.assert_array_equal(
        np.asarray(bs.key_rem)[int(np.asarray(cs)[0]):cap],
        np.asarray(bp.key_rem)[int(np.asarray(cp)[0]):cap])


def test_scatter_parity_under_overflow():
    n, nb, cap = 4000, 4, 500                       # demand ~1000 > cap
    batch, dest, _, _ = _rand(n, nb, seed=6)
    bs, cs, os_ = scatter_to_blocks(batch, dest, nb, cap, "inner",
                                    impl="sort")
    bp, cp, op = scatter_to_blocks(batch, dest, nb, cap, "inner",
                                   impl=INTERP)
    # the accounting is byte-equal even when the kept subsets differ
    np.testing.assert_array_equal(np.asarray(cs), np.asarray(cp))
    assert int(os_) == int(op) > 0
    dest_np, rid_np = np.asarray(dest), np.arange(n)
    for b in range(nb):
        for blocks, counts in ((bs, cs), (bp, cp)):
            _, rids = _valid_rows(blocks, np.asarray(counts), cap, b)
            # membership: every kept row is a genuine tuple of this block
            assert set(rids) <= set(rid_np[dest_np == b])
            assert len(set(rids)) == len(rids) == cap


# ----------------------------------------------- grouped scatter parity

def _group_rows(blocks, group_counts, cap, b, s):
    gc = np.asarray(group_counts)
    lo = b * cap + int(gc[b, :s].sum())
    return np.asarray(blocks.rid)[lo:lo + int(gc[b, s])]


@pytest.mark.parametrize("valid_p", [None, 0.8])
def test_grouped_parity_no_overflow(valid_p):
    n, nb, ns, cap = 3000, 4, 8, 1200
    batch, dest, sub, valid = _rand(n, nb, num_sub=ns, seed=7,
                                    valid_p=valid_p)
    ss = scatter_to_blocks_grouped(batch, dest, sub, nb, ns, cap, "inner",
                                   valid=valid, impl="sort")
    pp = scatter_to_blocks_grouped(batch, dest, sub, nb, ns, cap, "inner",
                                   valid=valid, impl=INTERP)
    np.testing.assert_array_equal(np.asarray(ss[1]), np.asarray(pp[1]))
    np.testing.assert_array_equal(np.asarray(ss[2]), np.asarray(pp[2]))
    assert int(ss[3]) == int(pp[3]) == 0
    for b in range(nb):
        for s in range(ns):
            np.testing.assert_array_equal(
                np.sort(_group_rows(ss[0], ss[2], cap, b, s)),
                np.sort(_group_rows(pp[0], pp[2], cap, b, s)))


def test_grouped_parity_under_overflow_accounting():
    n, nb, ns, cap = 3000, 4, 8, 400                # demand ~750 > cap
    batch, dest, sub, _ = _rand(n, nb, num_sub=ns, seed=8)
    ss = scatter_to_blocks_grouped(batch, dest, sub, nb, ns, cap, "inner",
                                   impl="sort")
    pp = scatter_to_blocks_grouped(batch, dest, sub, nb, ns, cap, "inner",
                                   impl=INTERP)
    np.testing.assert_array_equal(np.asarray(ss[1]), np.asarray(pp[1]))
    np.testing.assert_array_equal(np.asarray(ss[2]), np.asarray(pp[2]))
    assert int(ss[3]) == int(pp[3]) > 0
    dest_np, sub_np = np.asarray(dest), np.asarray(sub)
    for b in range(nb):
        for s in range(ns):
            for res in (ss, pp):
                rids = _group_rows(res[0], res[2], cap, b, s)
                mine = set(np.flatnonzero((dest_np == b) & (sub_np == s)))
                assert set(rids) <= mine            # membership only


# ---------------------------------------------------------- reorder parity

@pytest.mark.parametrize("valid_p", [None, 0.6])
def test_reorder_parity(valid_p):
    n, p = 5000, 16
    rng = np.random.default_rng(9)
    keys = rng.integers(0, 1 << 16, n).astype(np.uint32)
    pid = jnp.asarray(rng.integers(0, p, n).astype(np.uint32))
    valid = (None if valid_p is None else
             jnp.asarray(rng.random(n) < valid_p))
    batch = _comp(keys, np.arange(n))
    outs, pids, hs, offs = reorder_by_partition(batch, pid, p, valid=valid,
                                                impl="sort")
    outp, pidp, hp, offp = reorder_by_partition(batch, pid, p, valid=valid,
                                                impl=INTERP)
    np.testing.assert_array_equal(np.asarray(hs), np.asarray(hp))
    np.testing.assert_array_equal(np.asarray(offs), np.asarray(offp))
    total = int(np.asarray(hs).sum())
    # both are grouped ascending over the valid prefix...
    for pids_ in (np.asarray(pids), np.asarray(pidp)):
        assert (np.diff(pids_[:total]) >= 0).all()
    # ...with the same per-partition multiset of rows
    off = np.concatenate([np.asarray(offs), [total]])
    for g in range(p):
        lo, hi = int(off[g]), int(off[g + 1])
        np.testing.assert_array_equal(
            np.sort(np.asarray(outs.rid)[lo:hi]),
            np.sort(np.asarray(outp.rid)[lo:hi]))


def test_reorder_sort_hist_matches_local_histogram():
    # satellite: the sort fallback derives its histogram from searchsorted
    # run bounds instead of a separate local_histogram pass — byte-identical
    n, p = 7000, 32
    rng = np.random.default_rng(10)
    pid = jnp.asarray(rng.integers(0, p, n).astype(np.uint32))
    valid = jnp.asarray(rng.random(n) < 0.5)
    batch = _comp(rng.integers(0, 99, n), np.arange(n))
    for v in (None, valid):
        _, _, hist, _ = reorder_by_partition(batch, pid, p, valid=v,
                                             impl="sort")
        np.testing.assert_array_equal(
            np.asarray(hist), np.asarray(local_histogram(pid, p, v,
                                                         impl="xla")))


# ------------------------------------------- grouped clip property test

def test_grouped_clip_eats_highest_pid_tail_property():
    """group_counts sums to the tuples actually present per block, and the
    clip keeps the lowest pids: kept[b, s] follows the cum-min formula, so
    every group below the clip point keeps its full demand and everything
    past it is eaten — the contract pack_blocks builds headers from."""
    rng = np.random.default_rng(11)
    for trial in range(8):
        nb = int(rng.integers(2, 6))
        ns = int(rng.integers(2, 9))
        n = int(rng.integers(200, 2500))
        cap = int(rng.integers(8, max(9, 2 * n // nb)))
        batch, dest, sub, valid = _rand(n, nb, num_sub=ns,
                                        seed=100 + trial,
                                        valid_p=0.9 if trial % 2 else None)
        blocks, counts, gc, overflow = scatter_to_blocks_grouped(
            batch, dest, sub, nb, ns, cap, "inner", valid=valid,
            impl="sort")
        gc = np.asarray(gc).astype(np.int64)
        d, s = np.asarray(dest).astype(np.int64), np.asarray(sub)
        ok = np.ones(n, bool) if valid is None else np.asarray(valid)
        raw = np.zeros((nb, ns), np.int64)
        np.add.at(raw, (d[ok], s[ok].astype(np.int64)), 1)
        # kept = clipped cum-min of the raw demand, low pids first
        cum = np.minimum(np.cumsum(raw, axis=1), cap)
        kept = np.concatenate([cum[:, :1], np.diff(cum, axis=1)], axis=1)
        np.testing.assert_array_equal(gc, kept)
        # sums to the tuples actually present per block (occupied prefix)
        key_np = np.asarray(blocks.key_rem).reshape(nb, cap)
        rid_np = np.asarray(blocks.rid).reshape(nb, cap)
        for b in range(nb):
            present = int(gc[b].sum())
            assert present == min(int(np.asarray(counts)[b]), cap)
            # the present rows really are this block's tuples, pid-sorted
            rids = rid_np[b, :present]
            assert set(rids) <= set(np.flatnonzero(ok & (d == b)))
            assert (np.diff(s[rids].astype(np.int64)) >= 0).all()
            del key_np  # membership checked via rid; keys ride along
            key_np = np.asarray(blocks.key_rem).reshape(nb, cap)
        assert int(overflow) == int(np.maximum(
            raw.sum(axis=1) - cap, 0).sum())


# ------------------------------------------------------- fallback telemetry

def test_auto_fallback_ticks_counter_and_logs_once(monkeypatch, capsys):
    m = Measurements()
    radix.install_partition_observer(m)
    monkeypatch.setattr(radix, "_fallback_logged", False)
    try:
        # CPU backend: auto must degrade to the sort path, loudly once
        assert radix.resolve_partition_impl(None, 8, "scatter_to_blocks") \
            == "loop"
        assert radix.resolve_partition_impl("auto", 8, "reorder") == "loop"
        err = capsys.readouterr().err
        assert err.count("fell back to the XLA sort path") == 1
        assert m.counters[PARTFALLBACK] == 2
        # explicit impls never tick the fallback
        assert radix.resolve_partition_impl("sort", 8, "x") == "loop"
        assert radix.resolve_partition_impl(INTERP, 8, "x") == INTERP
        assert m.counters[PARTFALLBACK] == 2
    finally:
        radix.install_partition_observer(None)


def test_pallas_path_ticks_partpass_span():
    """The fused pass ticks PARTPASS as it is traced, and its device work
    is named ``trj.partition`` (where the trace-time span used to be)."""
    import jax

    from tpu_radix_join.observability import stages

    m = Measurements()
    radix.install_partition_observer(m)
    try:
        batch, dest, _, _ = _rand(512, 4, seed=12)
        compiled = jax.jit(lambda b, d: scatter_to_blocks(
            b, d, 4, 256, "inner", impl=INTERP)).lower(batch, dest).compile()
        assert m.counters[PARTPASS] == 1
        program = stages.program_stages(compiled.as_text())
        # every instruction that does device work is partition work
        assert {st for name, st in program.stages.items()
                if program.opcodes[name] not in stages.TRIVIAL} == {
                    stages.PARTITION}
        assert not [r for r in m.flightrec.records()
                    if r["name"] == "partition_pass"]
    finally:
        radix.install_partition_observer(None)


# ------------------------------------------------------------- planner

def test_plan_partition_prices_both_arms():
    from tpu_radix_join.planner.cost_model import plan_partition
    from tpu_radix_join.planner.profile import load_profile
    prof = load_profile()
    on = plan_partition(prof, 1 << 25, pallas_ok=True)
    off = plan_partition(prof, 1 << 25, pallas_ok=False)
    assert on.impl == "pallas" and off.impl == "sort"
    assert on.partition_ms == on.fused_ms < off.partition_ms == off.sort_ms
    # the fused arm prices off the schema-v4 constant: doubling the unit
    # moves the estimate
    bumped = prof.replace_constants(partition_pass_unit_ms={
        "value": prof.value("partition_pass_unit_ms") * 10,
        "source": "test"})
    assert plan_partition(bumped, 1 << 25, pallas_ok=True).fused_ms \
        > on.fused_ms


def test_twolevel_strategy_carries_partition_term():
    from tpu_radix_join.planner.calibrate import TERM_TO_CONSTANT
    from tpu_radix_join.planner.cost_model import (Workload,
                                                   enumerate_strategies)
    from tpu_radix_join.planner.profile import load_profile
    rows = enumerate_strategies(load_profile(),
                                Workload(r_tuples=1 << 22,
                                         s_tuples=1 << 22, num_nodes=8))
    tl = next(r for r in rows if r.strategy == "incore_fused_twolevel")
    assert "partition" in tl.terms and tl.terms["partition"] > 0
    assert "scatter" not in tl.terms
    assert TERM_TO_CONSTANT["partition"] == "partition_pass_unit_ms"


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


def test_join_fused_partition_flat_mesh_oracle_exact(monkeypatch):
    from tpu_radix_join.observability import stages

    tables = []
    monkeypatch.setattr(stages, "record", lambda compiled: tables.append(
        stages.program_stages(compiled.as_text())) or True)
    m = _oracle_join(partition_impl=INTERP, exchange_codec="pack")
    # any PARTFALLBACK here is the histogram auto-select degrading on the
    # CPU backend; the forced scatter impl itself never falls back
    assert m.counters[PARTPASS] > 0
    join, = [p for p in tables if p.module.startswith("jit_trj_join")]
    assert [n for n, st in join.stages.items()
            if st is None and join.opcodes[n] not in stages.TRIVIAL] == []
    assert {stages.PARTITION, stages.EXCHANGE} <= set(join.stages.values())


def test_join_fused_partition_hierarchical_mesh_oracle_exact():
    m = _oracle_join(partition_impl=INTERP, num_hosts=2,
                     exchange_codec="pack")
    assert m.counters[PARTPASS] > 0


def test_join_fused_partition_two_level_oracle_exact():
    # two_level adds the local second radix pass (local_partitioning.py),
    # which must route through the same forced impl
    m = _oracle_join(partition_impl=INTERP, two_level=True,
                     allocation_factor=2.0)
    assert m.counters[PARTPASS] > 2   # exchange scatters + local passes


def test_config_rejects_unknown_partition_impl():
    from tpu_radix_join import JoinConfig
    with pytest.raises(ValueError, match="partition impl"):
        JoinConfig(partition_impl="bogus")

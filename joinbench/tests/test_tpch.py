"""The TPC-H key generator keeps the spec's key rules, its device and
NumPy twins agree, and the reference counts through membership."""

import numpy as np
import pytest

from joinbench import tpch, tpch_reference

ORDERS = 3000                    # SF 0.002


@pytest.mark.parametrize("seed", [0, 987654321987, 2**31 + 11])
def test_orders_take_8_of_every_32_keys_once(seed):
    keys = tpch.orders_np(ORDERS, tpch.Seeds(seed))
    assert np.unique(keys).size == ORDERS
    assert (keys & np.uint32(0b11000)).max() == 0
    expect = tpch.sparse_key(np.arange(1, ORDERS + 1, dtype=np.uint32))
    assert np.array_equal(np.sort(keys), expect)
    assert keys.max() == tpch.sparse_key(np.uint32(ORDERS))


def test_thirty_scale_factors_reach_180m():
    n = tpch.orders_rows(30)
    assert n == 45_000_000
    assert tpch.sparse_key(np.uint32(n)) == 180_000_000


@pytest.mark.parametrize("seed", [5, 2**33 + 1])
def test_each_order_has_one_to_seven_lineitems(seed):
    o = tpch.orders_np(ORDERS, tpch.Seeds(seed))
    li = tpch.lineitem_np(ORDERS, tpch.Seeds(seed))
    per_order = np.bincount(np.searchsorted(np.sort(o), li),
                            minlength=ORDERS)
    assert per_order.min() == 1 and per_order.max() == 7
    assert np.isin(li, o).all()
    # uniform over 1..7: each count near a seventh of the orders
    shares = np.bincount(per_order, minlength=8)[1:] / ORDERS
    assert np.abs(shares - 1 / 7).max() < 0.03
    assert abs(li.size / ORDERS - 4) < 0.15


def test_counts_and_rows_come_from_the_seed():
    a, b = tpch.Seeds(11), tpch.Seeds(12)
    assert np.array_equal(tpch.lineitem_np(ORDERS, a),
                          tpch.lineitem_np(ORDERS, tpch.Seeds(11)))
    assert tpch.lineitem_np(ORDERS, a).size != tpch.lineitem_np(
        ORDERS, b).size or not np.array_equal(
        tpch.lineitem_np(ORDERS, a), tpch.lineitem_np(ORDERS, b))
    assert not np.array_equal(tpch.orders_np(ORDERS, a),
                              tpch.orders_np(ORDERS, b))


def test_device_twin_matches_the_numpy_twin():
    seeds = tpch.Seeds(2**40 + 3)
    o_key, o_rid, l_key, l_rid = tpch.device_tables(ORDERS, seeds)
    li = tpch.lineitem_np(ORDERS, seeds)
    assert np.array_equal(np.asarray(o_key), tpch.orders_np(ORDERS, seeds))
    assert np.array_equal(np.asarray(l_key), li)
    assert np.array_equal(np.asarray(o_rid), np.arange(ORDERS))
    assert np.array_equal(np.asarray(l_rid), np.arange(li.size))


def test_gap_keys_miss_every_order():
    rng = np.random.default_rng(1)
    gaps = tpch.gap_keys(rng, 5000, ORDERS)
    assert not np.isin(gaps, tpch.orders_np(ORDERS, tpch.Seeds(1))).any()
    assert gaps.max() < tpch.sparse_key(np.uint32(ORDERS)) + 32


def test_reference_counts_moved_rows_by_membership():
    seeds = tpch.Seeds(9)
    o, li = tpch.orders_np(ORDERS, seeds), tpch.lineitem_np(ORDERS, seeds)
    ref = tpch_reference.FKJoin(o, li)
    assert ref.count() == li.size
    rng = np.random.default_rng(2)
    pos = rng.choice(li.size, 50, replace=False)
    new = tpch.gap_keys(rng, 50, ORDERS)
    new[:10] = o[:10]                     # some land on real orders
    moved = li.copy()
    moved[pos] = new
    hist = np.bincount(o, minlength=int(moved.max()) + 1)
    assert ref.count(pos, new) == int(hist[moved].sum()) == li.size - 40
    with pytest.raises(ValueError):
        ref.count(np.array([1, 1]), new[:2])

"""Registered, refreshable tables in ``JoinSession`` against a plain NumPy
count, on TPC-H's orders ⋈ lineitem key rules at a small scale factor:
ORDERS' keys take the first 8 of every 32 orderkeys (sparse), each order
has 1 to 7 lineitems, and rows are held in a seeded random order.
"""

import numpy as np
import pytest

from tpu_radix_join.core.config import JoinConfig, ServiceConfig
from tpu_radix_join.data.tuples import TupleBatch
from tpu_radix_join.performance.measurements import (JHIST, JTOTAL,
                                                     QFINISH,
                                                     QSERVE, QTABLE, QUPDATE,
                                                     QWAIT, Measurements)
from tpu_radix_join.robustness.retry import REQUEST_ERROR
from tpu_radix_join.service import JoinSession, QueryRequest
from tpu_radix_join.service.session import STALE_VERSION

ORDERS = 1500                    # SF 0.001


def _sparse(i):
    return ((i >> 3) << 5) | (i & 7)


def _tpch(seed, orders=ORDERS):
    """ORDERS and LINEITEM order-key lanes, rows shuffled."""
    rng = np.random.default_rng(seed)
    o = _sparse(np.arange(1, orders + 1, dtype=np.uint32))
    lines = np.repeat(o, rng.integers(1, 8, orders))
    return rng.permutation(o), rng.permutation(lines)


def _batch(keys):
    return TupleBatch(key=np.asarray(keys, np.uint32),
                      rid=np.arange(len(keys), dtype=np.uint32))


def _count(o_keys, l_keys):
    """|orders ⋈ lineitem| by membership of each lineitem key."""
    member = np.zeros(int(max(o_keys.max(), l_keys.max())) + 1, bool)
    member[o_keys] = True
    return int(member[l_keys].sum())


def _session(nodes=1, **service):
    m = Measurements()
    return JoinSession(JoinConfig(num_nodes=nodes),
                       ServiceConfig(**service), measurements=m), m


def _query(session, qid, tenant="default", **kw):
    session.submit(QueryRequest(qid, tenant=tenant, inner="orders",
                                outer="lineitem", **kw))
    return session.run_next()


def _gap_keys(rng, k, orders=ORDERS):
    """``k`` orderkeys in the sparse gaps, which ORDERS never holds."""
    i = rng.integers(1, orders + 1, k).astype(np.uint32)
    upd = rng.integers(1, 4, k).astype(np.uint32)
    return ((i >> 3) << 5) | (upd << 3) | (i & 7)


@pytest.mark.parametrize("nodes", [1, 4])
def test_registered_fk_join_matches_the_reference(nodes):
    o, li = _tpch(1)
    li = li[: li.size - li.size % nodes]
    session, _ = _session(nodes)
    session.register_table("orders", _batch(o[: o.size - o.size % nodes]))
    session.register_table("lineitem", _batch(li))
    out = _query(session, "q0")
    assert out.status == "ok", out.detail
    assert out.matches == _count(o[: o.size - o.size % nodes], li)
    assert out.expected is None and out.served_by == "execute"


def test_runs_of_one_to_seven_and_sparse_keys_are_exercised():
    o, li = _tpch(2)
    assert o.size != li.size
    runs = np.bincount(np.searchsorted(np.sort(o), li))
    assert runs.min() == 1 and runs.max() == 7
    assert (o & np.uint32(0b11000)).max() == 0
    session, _ = _session()
    session.register_table("orders", _batch(o))
    session.register_table("lineitem", _batch(li))
    assert _query(session, "q").matches == li.size


def test_an_update_moves_the_count_and_bumps_the_version():
    o, li = _tpch(3)
    session, _ = _session()
    session.register_table("orders", _batch(o))
    v0 = session.register_table("lineitem", _batch(li))
    first = _query(session, "q0")
    rng = np.random.default_rng(7)
    pos = rng.choice(li.size, 37, replace=False)
    new = _gap_keys(rng, 37)
    v1, previous = session.update_table("lineitem", pos, new)
    assert v1 > v0 and session.table_version("lineitem") == v1
    np.testing.assert_array_equal(np.asarray(previous), li[pos])
    second = _query(session, "q1")
    moved = li.copy()
    moved[pos] = new
    assert second.matches == _count(o, moved) == first.matches - 37
    assert second.table_versions == {"orders": first.table_versions["orders"],
                                     "lineitem": v1}
    # putting the keys back restores the count under yet another version
    v2, _ = session.update_table("lineitem", pos, previous)
    third = _query(session, "q2")
    assert v2 > v1 and third.matches == first.matches
    assert session.summary()["table_updates"] == 2


def test_update_skips_positions_past_the_end_and_refuses_repeats():
    o, li = _tpch(4)
    session, _ = _session()
    session.register_table("orders", _batch(o))
    session.register_table("lineitem", _batch(li))
    pos = np.array([0, li.size, li.size + 5])
    _, previous = session.update_table("lineitem", pos,
                                       np.array([1, 2, 3], np.uint32))
    assert list(np.asarray(previous)[1:]) == [0, 0]
    got = np.asarray(session._tables["lineitem"].batch.key)
    assert got[0] == 1 and np.array_equal(got[1:], li[1:])
    with pytest.raises(ValueError, match="distinct rows"):
        session.update_table("lineitem", [3, 3], [1, 2])


def test_a_table_swapped_under_its_name_serves_nothing_stale():
    """The fault test: a relation replaced under the same name must reach
    no query through a placed lane, a plan or a cached result."""
    o, li = _tpch(5)
    _, other = _tpch(6)
    other = other[: li.size]
    session, _ = _session(result_cache_max=16)
    session.register_table("orders", _batch(o))
    v_old = session.register_table("lineitem", _batch(li))
    assert _query(session, "a").matches == li.size
    hit = _query(session, "b")                     # same versions: cached
    assert hit.served_by == "cache_hit" and hit.matches == li.size
    swapped = other.copy()
    swapped[: 100] = _gap_keys(np.random.default_rng(0), 100)
    v_new = session.register_table("lineitem", _batch(swapped))
    assert v_new > v_old
    assert not [k for k in session._place_cache if k[2:] == (
        "lineitem", v_old)]
    after = _query(session, "c")
    assert after.served_by == "execute"
    assert after.table_versions["lineitem"] == v_new
    assert after.matches == _count(o, swapped) == li.size - 100


def test_a_plan_sized_on_one_version_stays_exact_on_the_next():
    """The plan cache keys on shapes, not versions: on several nodes a
    query after an update warm-starts from capacities sized on the table
    before it.  When the update piles rows onto one partition those fall
    short, and the engine measures the join's own, so the count stays
    exact; the next query starts warm from them."""
    o, li = _tpch(13)
    o, li = o[: o.size - o.size % 4], li[: li.size - li.size % 4]
    session, m = _session(4)
    session.register_table("orders", _batch(o))
    session.register_table("lineitem", _batch(li))
    assert not _query(session, "q0").warm
    assert _query(session, "q1").warm
    hist = m.times_us[JHIST]
    pos = np.arange(0, li.size, 2)
    session.update_table("lineitem", pos,
                         np.full(pos.size, o[0], np.uint32))
    moved = li.copy()
    moved[pos] = o[0]
    resized = _query(session, "q2")
    assert resized.status == "ok" and not resized.warm
    assert m.times_us[JHIST] > hist and m.counters["RETRIES"] == 1
    assert resized.matches == _count(o, moved) == li.size
    again = _query(session, "q3")
    assert again.warm and again.matches == li.size


def test_a_registered_table_survives_its_queries():
    o, li = _tpch(7)
    session, _ = _session()
    session.register_table("orders", _batch(o))
    session.register_table("lineitem", _batch(li))
    for i in range(2):
        assert _query(session, f"q{i}").status == "ok"
    for name, keys in (("orders", o), ("lineitem", li)):
        batch = session._tables[name].batch
        assert not batch.key.is_deleted() and not batch.rid.is_deleted()
        np.testing.assert_array_equal(np.asarray(batch.key), keys)
        np.testing.assert_array_equal(np.asarray(batch.rid),
                                      np.arange(keys.size))


def test_streams_are_accounted_per_tenant():
    o, li = _tpch(8)
    session, _ = _session()
    session.register_table("orders", _batch(o))
    session.register_table("lineitem", _batch(li))
    streams = [f"stream{i}" for i in range(4)]
    for round_ in range(2):
        for t in streams:
            session.submit(QueryRequest(f"{t}-{round_}", tenant=t,
                                        inner="orders", outer="lineitem"))
    outs = session.drain()
    assert [o_.tenant for o_ in outs] == streams * 2
    assert all(o_.matches == li.size for o_ in outs)
    summary = session.summary()
    assert summary["tenant_queries"] == dict.fromkeys(streams, 2)
    assert summary["served_by"]["execute"] == 8
    assert summary["table_hits"] == 16


def test_a_query_older_than_its_admission_is_refused():
    o, li = _tpch(9)
    session, _ = _session()
    session.register_table("orders", _batch(o))
    v = session.register_table("lineitem", _batch(li))
    request = QueryRequest("q", inner="orders", outer="lineitem")
    session.submit(request)
    # admission records the versions current then
    assert session._admitted[id(request)] == {"orders": v - 1,
                                              "lineitem": v}
    # the fault: the query was admitted under a version the table lacks
    session._admitted[id(request)]["lineitem"] = v + 1
    out = session.run_next()
    assert out.status == "failed" and out.failure_class == STALE_VERSION
    assert session.summary()["stale_rejections"] == 1
    assert session._admitted == {}
    assert _query(session, "r").status == "ok"


def test_an_unknown_table_fails_the_query_alone():
    o, li = _tpch(10)
    session, _ = _session()
    session.register_table("orders", _batch(o))
    out = _query(session, "q")
    assert out.status == "failed" and out.failure_class == REQUEST_ERROR
    session.register_table("lineitem", _batch(li))
    assert _query(session, "r").status == "ok"
    with pytest.raises(ValueError, match="both"):
        QueryRequest("x", inner="orders")


def test_seeded_spec_queries_run_beside_tables():
    o, li = _tpch(11)
    session, _ = _session()
    session.register_table("orders", _batch(o))
    session.register_table("lineitem", _batch(li))
    session.submit(QueryRequest("spec", tuples_per_node=1 << 10, seed=3))
    spec = session.run_next()
    assert spec.status == "ok" and spec.matches == spec.expected == 1 << 10
    assert spec.table_versions is None
    assert "table_versions" not in spec.to_json()
    assert _query(session, "t").matches == li.size


def test_spans_counters_bytes_and_heartbeat_see_the_tables():
    o, li = _tpch(12)
    session, m = _session()
    session.register_table("orders", _batch(o))
    session.register_table("lineitem", _batch(li))
    assert session.placed_bytes() == 8 * (o.size + li.size)
    session.update_table("lineitem", [0], [li[0]])
    _query(session, "q")
    for tag in (QWAIT, QSERVE, QTABLE, QUPDATE, QFINISH):
        assert m.times_us[tag] > 0, tag
    assert m.times_us[QSERVE] >= m.times_us[JTOTAL]
    tables = session._heartbeat_extra()["tables"]
    assert tables["lineitem"]["version"] == session.table_version("lineitem")
    assert tables["orders"]["rows"] == o.size
    assert session.placed_bytes() == 8 * (o.size + li.size)


def test_a_spec_placed_again_does_not_retrace_its_generator():
    """With no placed-relation reuse every spec query places anew; the
    generator's program is kept by spec, so only the first one traces."""
    import jax

    traces, counting = [], [False]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, *_, **__: counting[0] and "trace" in event
        and traces.append(event))
    session, _ = _session(2, place_cache_max=0)
    for i in range(3):
        counting[0] = i == 2
        session.submit(QueryRequest(f"q{i}", tuples_per_node=1 << 10,
                                    seed=5))
        out = session.run_next()
        assert out.status == "ok" and out.matches == 2 << 10
    counting[0] = False
    assert traces == []

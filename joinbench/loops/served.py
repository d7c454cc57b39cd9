"""TPC-H's throughput test served through ``JoinSession``.

ORDERS and LINEITEM (``joinbench.tpch``) are made once in set-up, from the
seed, on the device, and registered with the session as tables.  Each of
``streams`` query streams is a tenant that keeps one query outstanding: as
soon as its query is answered it submits the next (a closed loop with no
think time).  Every query is the count of orders ⋈ lineitem.  Before each
one a refresh, through the session's table update, puts back the keys the
previous refresh moved, then moves 1 to ``refresh_slots`` lineitem rows
(count and rows from the seed) onto keys in ORDERS' unused gaps, so every
query's count differs and only a join that reads lineitem's current keys
gives it.  The session answers its oldest query first, one at a time.

Each stream makes one query in set-up, so that everything the window runs
is compiled.  Queries still queued when the window ends are answered and
checked after it, and not counted.  Besides the batch cells' checks,
``stale`` counts answers not computed by the engine or computed on a
LINEITEM older than the version their query was admitted under.

Traffic parameters:

* ``streams``: the concurrent query streams, ``stream0`` and so on;
* ``refresh_slots``: the most lineitem rows one refresh moves.
"""

from __future__ import annotations

import time

import numpy as np

from joinbench import tpch, tpch_reference
from joinbench import window as win
from joinbench.spec import LoopResult


def refresh(seed: int, query: int, rows: int, orders: int, slots: int):
    """The refresh before query number ``query``: ``slots`` rows to move
    (those past the ``k`` real ones point past the table and are skipped)
    and their new keys, and the ``k`` real ones alone."""
    rng = np.random.default_rng(win.seed_sequence(seed) + [2, query + 1])
    k = int(rng.integers(1, slots + 1))
    pos = np.full(slots, rows, np.int32)
    new = np.zeros(slots, np.uint32)
    pos[:k] = rng.choice(rows, size=k, replace=False)
    new[:k] = tpch.gap_keys(rng, k, orders)
    return pos, new, pos[:k].copy(), new[:k].copy()


def run(ctx) -> LoopResult:
    from tpu_radix_join.service import JoinSession, QueryRequest

    # a program whose session holds no tables cannot run this cell: say
    # so before any data is made
    if not hasattr(JoinSession, "register_table"):
        raise RuntimeError("this program's JoinSession has no registered "
                           "tables (JoinSession.register_table)")
    from tpu_radix_join.core.config import JoinConfig, ServiceConfig
    from tpu_radix_join.data.tuples import TupleBatch
    from tpu_radix_join.performance.measurements import (JTOTAL, QSERVE,
                                                         QWAIT, Measurements)
    conf, chips = ctx.config, ctx.chips
    if int(conf["nodes"]) != chips:
        raise ValueError(f"{conf['name']} runs {conf['nodes']} nodes, the "
                         f"cell has {chips} chips")
    orders = tpch.orders_rows(float(conf["scale_factor"]))
    streams = [f"stream{i}" for i in range(int(ctx.traffic["streams"]))]
    slots = int(ctx.traffic["refresh_slots"])
    meas = Measurements()
    session = JoinSession(JoinConfig(num_nodes=chips,
                                     **conf.get("join_config", {})),
                          ServiceConfig(), measurements=meas)
    if ctx.substitute is not None:
        session = ctx.substitute(session)
    seeds = tpch.Seeds(ctx.seed)
    with win.span("place"):
        o_key, o_rid, l_key, l_rid = tpch.device_tables(orders, seeds)
        session.register_table("orders", TupleBatch(key=o_key, rid=o_rid))
        session.register_table("lineitem", TupleBatch(key=l_key, rid=l_rid))
    rows = int(l_key.shape[0])
    del o_key, o_rid, l_key, l_rid          # the session owns the tables
    tuples = orders + rows
    # what the last refresh moved: nothing yet (every row past the end)
    undo = [np.full(slots, rows, np.int32), np.zeros(slots, np.uint32)]
    #: LINEITEM version -> the query whose refresh it holds (None: as made)
    moved_at = {session.table_version("lineitem"): None}
    moves = {}
    pending = {}
    issued = [0]

    def submit(stream: str) -> None:
        query = issued[0]
        issued[0] += 1
        pos, new, moved, keys = refresh(ctx.seed, query, rows, orders,
                                        slots)
        moves[query] = moved, keys
        with win.span("refresh"):
            version, _ = session.update_table("lineitem", *undo)
            moved_at[version] = None
            version, previous = session.update_table("lineitem", pos, new)
            moved_at[version] = query
            undo[:] = pos, previous
        qid = f"{stream}-{query}"
        pending[qid] = {"stream": stream, "query": query,
                        "admitted": version, "tuples": tuples}
        session.submit(QueryRequest(qid, tenant=stream, inner="orders",
                                    outer="lineitem"))

    def serve() -> dict:
        before = {tag: meas.times_us.get(tag, 0.0)
                  for tag in (QWAIT, QSERVE, JTOTAL)}
        with win.span("query"):
            out = session.run_next()
        spent = {tag: (meas.times_us.get(tag, 0.0) - t0) / 1e3
                 for tag, t0 in before.items()}
        rec = pending.pop(out.query_id)
        rec.update(t1=time.perf_counter(), matches=out.matches,
                   ok=out.status == "ok", served_by=out.served_by,
                   read=(out.table_versions or {}).get("lineitem"),
                   wait_ms=spent[QWAIT], serve_ms=spent[QSERVE],
                   engine_ms=spent[JTOTAL])
        return rec

    warm = []
    for stream in streams:
        submit(stream)
        warm.append(serve())
    setup_s = time.perf_counter() - ctx.t0
    records = []
    with win.Window(ctx) as w:
        for stream in streams:
            submit(stream)
        while w.open():
            records.append(serve())
            if w.open():
                submit(records[-1]["stream"])
        w.close()
    tail = [serve() for _ in range(len(pending))]
    peak = win.memory_peak_bytes(list(session.engine.mesh.devices.flat))
    fallbacks = win.fallbacks(meas)
    session.close()
    del session, undo

    ref = tpch_reference.FKJoin(tpch.orders_np(orders, seeds),
                                tpch.lineitem_np(orders, seeds))
    checked = warm + records + tail
    for rec in checked:
        known = rec["read"] in moved_at
        rec["expected"] = (ref.count(*moves.get(moved_at[rec["read"]], ()))
                           if known else None)
        rec["stale"] = (rec["served_by"] != "execute" or not known
                        or rec["read"] < rec["admitted"])

    def gap(rec) -> int:
        if rec["matches"] is None or rec["expected"] is None:
            return 0
        return abs(rec["matches"] - rec["expected"])

    def wrong(rec) -> bool:
        return gap(rec) > 0 or not rec["ok"] or rec["stale"]

    checks = {
        "count_gap": (max(map(gap, checked)), 0),
        "not_ok": (sum(not x["ok"] for x in checked), 0),
        "fallbacks": (fallbacks, 0),
        "stale": (sum(x["stale"] for x in checked), 0),
    }
    return LoopResult(
        setup_s=setup_s, window_s=w.seconds, records=records, checks=checks,
        attempted=len(records), failed=sum(map(wrong, records)),
        memory_peak_bytes=peak, window_programs=w.programs)

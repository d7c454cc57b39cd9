"""The served TPC-H cell at a tiny scale on the CPU: a sound run is
correct, and the controls, a join that skips lineitem and a session that
answers from the previous version, are not."""

import json
import os
import time

import jax.numpy as jnp
import pytest

from joinbench import run, spec
from joinbench.loops import served

CELL = "serve_1c"


@pytest.fixture
def tiny_tpch(tiny_root):
    path = os.path.join(tiny_root, spec.PACKAGE, "configs",
                        "tpch_sf30_served.json")
    with open(path) as f:
        conf = json.load(f)
    conf["scale_factor"] = 0.002                  # 3,000 orders
    with open(path, "w") as f:
        json.dump(conf, f)
    return tiny_root


def _run(root, substitute=None, trace=False):
    line, result = run.run_cell(CELL, 987654321987, 1.0, trace, root=root,
                                require_tpu=False, t0=time.perf_counter(),
                                substitute=substitute)
    return line, result


def test_a_tiny_served_run_is_correct(tiny_tpch):
    line, result = _run(tiny_tpch)
    assert line["correct"], line["checks"]
    assert set(line["checks"]) == {"count_gap", "not_ok", "fallbacks",
                                   "stale"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"tuples_per_s", "setup_s"}
    assert {r["stream"] for r in result.records} == {
        f"stream{i}" for i in range(4)}
    assert len({r["expected"] for r in result.records}) > 1
    assert result.window_programs.get("compiled", 0) == 0


def test_a_traced_served_run_reads_the_serving_layers(tiny_tpch, tmp_path):
    from jax.profiler import ProfileData

    from joinbench import trace

    line, _ = run.run_cell(CELL, 987654321987, 1.0, True, root=tiny_tpch,
                           require_tpu=False, t0=time.perf_counter(),
                           keep_trace=str(tmp_path))
    assert line["correct"]
    got = set(line["metrics"])
    assert {"session_host_ms", "queue_wait_ms", "engine_ms.served"} <= got
    assert "device_idle_pct.served" not in got     # no device plane here
    assert line["metrics"]["queue_wait_ms"]["value"] > 0
    spans = {e.name for plane in ProfileData.from_file(
                 trace.find_xplane(str(tmp_path))).planes
             for trace_line in plane.lines for e in trace_line.events}
    assert {"trj.QWAIT", "trj.QSERVE", "trj.QTABLE", "trj.QUPDATE",
            "trj.QFINISH", "trj.JTOTAL"} <= spans


def skip_lineitem(session):
    """A join that never reads lineitem: it answers |LINEITEM|, as if
    every row still named an order."""
    join = session.engine.join_arrays

    def broken(r, s, **kw):
        return join(r, r, **kw)._replace(matches=int(s.size))
    session.engine.join_arrays = broken
    return session


def previous_version(session):
    """A session that answers from the table as it was before the last
    update, and says so."""
    update, resolve = session.update_table, session._resolve_tables
    before = {}

    def remember(name, positions, keys):
        table = session._tables[name]
        before[name] = (table.batch._replace(key=jnp.array(table.batch.key)),
                        table.version)
        return update(name, positions, keys)

    def stale(engine, request):
        r, s, versions = resolve(engine, request)
        if request.outer in before:
            s, versions[request.outer] = before[request.outer]
        return r, s, versions
    session.update_table, session._resolve_tables = remember, stale
    return session


def test_a_join_that_skips_lineitem_is_not_correct(tiny_tpch):
    line, _ = _run(tiny_tpch, skip_lineitem)
    assert not line["correct"]
    assert line["checks"]["count_gap"]["value"] > 0


def test_a_session_answering_from_the_previous_version_is_not_correct(
        tiny_tpch):
    line, _ = _run(tiny_tpch, previous_version)
    assert not line["correct"]
    assert line["checks"]["stale"]["value"] > 0


def test_a_program_without_tables_fails_at_once(tiny_tpch, monkeypatch):
    from tpu_radix_join.service import JoinSession

    monkeypatch.delattr(JoinSession, "register_table")
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="registered"):
        _run(tiny_tpch)
    assert time.perf_counter() - t0 < 5


def test_refreshes_come_from_the_seed_and_stay_in_the_gaps():
    a = served.refresh(7, 3, 10_000, 3000, 1024)
    b = served.refresh(7, 3, 10_000, 3000, 1024)
    assert all((x == y).all() for x, y in zip(a, b))
    pos, new, moved, keys = a
    k = moved.size
    assert 1 <= k <= 1024 and (pos[k:] == 10_000).all()
    assert len(set(moved.tolist())) == k
    assert (keys & 0b11000).min() > 0

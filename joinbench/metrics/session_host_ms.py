"""session_host_ms: median per query of the session's own host time: its
QSERVE timer (dequeue to outcome) less the engine's JTOTAL for that query.
Table resolution, the finish and the outcome's accounting are in it.
Nothing where the records carry no such times."""

from joinbench.stats import median


def read(run):
    return median([r["serve_ms"] - r["engine_ms"] for r in run.records
                   if r.get("serve_ms") and r.get("engine_ms")])

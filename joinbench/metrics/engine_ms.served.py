"""engine_ms.served: median per query of the engine's JTOTAL timer (the
key probe, the fused join program, the flags readback and the finish) as
the session calls it."""

from joinbench.stats import median


def read(run):
    return median([r["engine_ms"] for r in run.records
                   if r.get("engine_ms")])

"""jproc_ms: median per join of the engine's fenced JPROC timer (the fused
join program)."""

from joinbench.stats import median


def read(run):
    return median([r["jproc_ms"] for r in run.records if r["jproc_ms"] > 0])

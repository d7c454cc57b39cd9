"""jhist_ms: median per join of the engine's fenced JHIST timer (the sizing
pre-pass).  The one-node join skips the pre-pass: nothing to read there."""

from joinbench.stats import median


def read(run):
    return median([r["jhist_ms"] for r in run.records if r["jhist_ms"] > 0])

"""tuples_per_s: (|R| + |S|) of every join of the window, over the window's
whole time, from its start to the end of its last join."""

from joinbench.stats import rate


def read(run):
    return rate(sum(r["tuples"] for r in run.records), run.result.window_s)

"""queue_wait_ms: median per query of the session's QWAIT interval, from
submit to dequeue: the time a stream's query waits behind the others."""

from joinbench.stats import median


def read(run):
    return median([r["wait_ms"] for r in run.records if "wait_ms" in r])

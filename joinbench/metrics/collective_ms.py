"""collective_ms: device time of the collective operations in the traced
window per join, averaged over the chips; nothing where none ran."""

from joinbench import trace


def read(run):
    if run.trace is None or not run.records:
        return None
    seconds = trace.collective_s(run.trace)
    return None if seconds is None else seconds * 1e3 / len(run.records)

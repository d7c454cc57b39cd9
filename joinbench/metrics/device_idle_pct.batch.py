"""device_idle_pct.batch: the share of the traced window in which no
operation ran on the device, averaged over the chips."""

from joinbench import trace


def read(run):
    return None if run.trace is None else trace.idle_pct(run.trace)

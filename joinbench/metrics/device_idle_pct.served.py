"""device_idle_pct.served: the share of the traced window in which no
operation ran on the device, averaged over the chips; read as
device_idle_pct.batch, over a served window."""

from joinbench import trace


def read(run):
    return None if run.trace is None else trace.idle_pct(run.trace)

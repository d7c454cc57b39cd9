"""join_roofline: the least time a chip needs to read its share of both
relations once at peak HBM bandwidth, over the device-busy time per join
in the traced window.  HBM bounds it: the count does no arithmetic worth
counting."""

from joinbench import trace, work
from joinbench.peaks import peaks_for


def read(run):
    if run.trace is None or not run.records:
        return None
    busy = trace.busy_s(run.trace)
    if not busy:
        return None
    tuples = int(run.config["tuples_per_node"]) * run.chips
    least = work.least_join_seconds(tuples, tuples, run.chips,
                                    peaks_for(run.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least / (busy / len(run.records))

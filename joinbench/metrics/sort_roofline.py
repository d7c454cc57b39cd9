"""sort_roofline: the least time a chip needs to read and write, once, at
peak HBM bandwidth, every (key, rid) element of both relations it sorts,
over sort_ms.  The bytes come from the configuration's shapes alone:
2 (read, write) x 8 B x 2 relations x tuples_per_node, whatever sorts."""

from joinbench import work
from joinbench.metrics.sort_ms import stage_ms
from joinbench.peaks import peaks_for


def read(run):
    ms = stage_ms(run, "trj.sort")
    if not ms:
        return None
    least_bytes = 2 * work.TUPLE_BYTES * 2 * int(run.config["tuples_per_node"])
    least_s = least_bytes / peaks_for(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)

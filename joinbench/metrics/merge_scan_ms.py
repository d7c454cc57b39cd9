"""merge_scan_ms: device time per join, in the traced window, of the
operations that the program's ``trj.merge_scan`` scope owns (the merge
count and its Pallas scan), averaged over the chips; read as sort_ms."""

from joinbench.metrics.sort_ms import stage_ms


def read(run):
    return stage_ms(run, "trj.merge_scan")

"""unattributed_ms: device time per join, in the traced window, of the
operations that no ``trj.*`` stage is credited with, averaged over the
chips: names the program's programs give to different stages (the
benchmark's trace keeps an operation's name, not its program), names no
scope owns, and names of programs the engine did not compile (the
benchmark's own key rewrite).  Time that leaves sort_ms, merge_scan_ms
or partition_ms because another program came to share a name shows up
here.  Nothing where the program keeps no stage table."""

from joinbench.metrics.sort_ms import per_join_ms


def read(run):
    return per_join_ms(run, lambda st: not (st or "").startswith("trj."))

"""partition_ms: device time per join, in the traced window, of the
operations that the program's ``trj.partition`` scope owns (histograms,
partition ids, the partition kernels), averaged over the chips; read as
sort_ms.  One node partitions nothing.  An operation whose name another
program gives to another stage counts in unattributed_ms instead: in
``uniform_4c`` the two block scatters of R and S share their names with
the sizing program's key maxima, so this reads the rest of the stage."""

from joinbench.metrics.sort_ms import stage_ms


def read(run):
    return stage_ms(run, "trj.partition")

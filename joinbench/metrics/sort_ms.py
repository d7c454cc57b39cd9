"""sort_ms: device time per join, in the traced window, of the operations
that the program's ``trj.sort`` scope owns, averaged over the chips.

The program records, once per compile, which ``trj.*`` scope owns each
instruction of its programs (``tpu_radix_join/observability/stages.py``);
an operation whose name its programs give to different stages counts for
none (unattributed_ms counts it).  Nothing where the program keeps no
such table or no operation of the stage ran."""

from joinbench import trace

STAGE = "trj.sort"


def per_join_ms(run, counts):
    """Milliseconds per join, averaged over the chips, of the device
    operations whose stage ``counts(stage)`` accepts; None where there is
    no trace, or the program names no stage of any operation in it."""
    if run.trace is None or not run.records:
        return None
    try:
        from tpu_radix_join.observability.stages import SPAN_PREFIX, stage_of
    except ImportError:
        return None
    names = {name for ops in run.trace.device_ops.values()
             for name, _, _, _ in ops}
    if not any((stage_of(n) or "").startswith(SPAN_PREFIX) for n in names):
        return None
    per_device = [
        sum(e - s for s, e in trace.union(
            [(s, e) for name, _, s, e in ops if counts(stage_of(name))],
            run.trace.window))
        for ops in run.trace.device_ops.values()]
    return sum(per_device) / len(per_device) * 1e3 / len(run.records)


def stage_ms(run, stage: str):
    """Milliseconds per join of the device operations ``stage`` owns;
    None where none ran."""
    return per_join_ms(run, lambda st: st == stage) or None


def read(run):
    return stage_ms(run, STAGE)

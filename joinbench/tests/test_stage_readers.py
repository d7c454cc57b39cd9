"""The per-stage readers (sort_ms, merge_scan_ms, partition_ms,
sort_roofline, unattributed_ms) on synthetic traces, with the program's
stage table filled from a small HLO text."""

import sys

import pytest

from joinbench import spec, trace

_HLO = """HloModule jit_trj_join

ENTRY %main (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  %partition_slots_pallas.2 = u32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(trj_join)/trj.partition/jit(partition_slots_pallas)/pallas_call"}
  %radix_pass_slots_pallas.4 = u32[8]{0} custom-call(%partition_slots_pallas.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(trj_join)/trj.merge_scan/trj.sort/jit(radix_pass_slots_pallas)/pallas_call"}
  %fusion.1 = u32[8]{0} fusion(%radix_pass_slots_pallas.4), kind=kLoop, calls=%fused, metadata={op_name="jit(trj_join)/trj.merge_scan/trj.sort/scatter"}
  ROOT %merge_scan_partitions.1 = u32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(trj_join)/trj.merge_scan/jit(merge_scan_partitions)/pallas_call"}
}
"""

_OTHER = """HloModule jit_trj_sizing

ENTRY %main (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  ROOT %fusion.1 = u32[8]{0} negate(%p), metadata={op_name="jit(trj_sizing)/trj.partition/neg"}
}
"""


class _Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


@pytest.fixture
def table():
    from tpu_radix_join.observability import stages

    stages.reset()
    stages.record(_Compiled(_HLO))
    yield stages
    stages.reset()


def _op(name, start, end):
    return (name, "custom-call", start, end)


def _run(device_ops, joins=2, kind="TPU v5 lite", tuples=1000):
    t = trace.from_events(
        {d: [_op(*o) for o in ops] for d, ops in device_ops.items()},
        [("joinbench.window", 0.0, 10.0)])
    result = spec.LoopResult(setup_s=1.0, window_s=10.0,
                             records=[{}] * joins, checks={}, attempted=joins,
                             failed=0, memory_peak_bytes=0)
    return spec.Run(cell={"chips": len(device_ops)},
                    config={"tuples_per_node": tuples}, traffic={},
                    device_kind=kind, result=result, trace=t)


_OPS = [("partition_slots_pallas.2", 0.0, 0.5),
        ("radix_pass_slots_pallas.4", 1.0, 3.0),
        ("fusion.1", 3.0, 4.0),
        ("merge_scan_partitions.1", 4.0, 4.25),
        ("rewrite_fusion", 9.0, 9.5)]   # a program the stages never saw


def test_each_stage_sums_its_own_ops_per_join(table):
    run = _run({"/device:TPU:0": _OPS})
    assert spec.reader("sort_ms")(run) == pytest.approx(1500.0)
    assert spec.reader("merge_scan_ms")(run) == pytest.approx(125.0)
    assert spec.reader("partition_ms")(run) == pytest.approx(250.0)
    # only the program the stages never saw is left over
    assert spec.reader("unattributed_ms")(run) == pytest.approx(250.0)


def test_chips_are_averaged_and_the_window_clips(table):
    run = _run({"/device:TPU:0": _OPS,
                "/device:TPU:1": [("radix_pass_slots_pallas.4", 9.0, 11.0)]},
               joins=1)
    # chip 0: 3 s of sort; chip 1: 1 s inside the window
    assert spec.reader("sort_ms")(run) == pytest.approx(2000.0)


def test_a_name_the_programs_disagree_on_counts_for_no_stage(table):
    table.record(_Compiled(_OTHER))   # fusion.1 is partition work there
    run = _run({"/device:TPU:0": _OPS})
    assert table.stage_of("fusion.1") == table.AMBIGUOUS
    assert spec.reader("sort_ms")(run) == pytest.approx(1000.0)
    assert spec.reader("partition_ms")(run) == pytest.approx(250.0)
    # what left sort_ms shows up, beside the unrecorded program
    assert spec.reader("unattributed_ms")(run) == pytest.approx(750.0)


def test_every_op_owned_reads_zero_unattributed(table):
    run = _run({"/device:TPU:0": _OPS[:-1]})
    assert spec.reader("unattributed_ms")(run) == 0.0


def test_the_sort_roofline_is_the_least_hbm_time_over_sort_ms(table):
    run = _run({"/device:TPU:0": _OPS}, tuples=20_000_000)
    least = 2 * 8 * 2 * 20_000_000 / 819e9
    assert spec.reader("sort_roofline")(run) == pytest.approx(
        100.0 * least / 1.5)


def test_nothing_to_read_gives_nothing(table, monkeypatch):
    run = _run({"/device:TPU:0": [("rewrite_fusion", 1.0, 2.0)]})
    for name in ("sort_ms", "merge_scan_ms", "partition_ms",
                 "sort_roofline", "unattributed_ms"):
        assert spec.reader(name)(run) is None
    assert spec.reader("sort_ms")(_run({})) is None
    assert spec.reader("unattributed_ms")(_run({})) is None
    # a program that names no stages (no stage module at all)
    monkeypatch.setitem(sys.modules, "tpu_radix_join.observability.stages",
                        None)
    run = _run({"/device:TPU:0": _OPS})
    for name in ("sort_ms", "merge_scan_ms", "partition_ms",
                 "sort_roofline", "unattributed_ms"):
        assert spec.reader(name)(run) is None

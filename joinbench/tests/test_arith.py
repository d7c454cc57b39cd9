"""The arithmetic of the numbers: rates, medians, the trace reduction,
the peaks table and the bytes a join must move."""

import os

import pytest

from joinbench import peaks, stats, trace, work


def test_rate_is_all_the_work_over_all_the_time():
    assert stats.rate(40e6 * 4, 10.8) == pytest.approx(14814814.81)
    assert stats.rate(0, 1.0) is None and stats.rate(1, 0) is None


def test_median_of_the_timers():
    assert stats.median([3, 1, 2]) == 2
    assert stats.median([4.0, 1.0]) == 2.5
    assert stats.median([]) is None


def _op(name, start, end):
    """A device op whose opcode is its name's stem."""
    return (name, name.rsplit(".", 1)[0].replace("_", "-"), start, end)


def _trace(device_ops, spans=()):
    host = [("joinbench.window", 0.0, 10.0)] + list(spans)
    return trace.from_events({d: [_op(*o) for o in ops]
                              for d, ops in device_ops.items()}, host)


def test_busy_is_a_union_of_intervals_inside_the_window():
    t = _trace({"/device:TPU:0": [("fusion.1", 1.0, 3.0),
                                  ("fusion.2", 2.0, 4.0),   # overlaps
                                  ("copy.3", 9.5, 12.0)]})  # clipped
    assert trace.busy_s(t) == pytest.approx(3.5)
    assert trace.idle_pct(t) == pytest.approx(65.0)


def test_busy_and_collectives_average_over_devices():
    t = _trace({"/device:TPU:0": [("all_to_all.1", 0.0, 1.0),
                                  ("fusion.4", 1.0, 5.0)],
                "/device:TPU:1": [("all_to_all.1", 0.0, 3.0),
                                  ("all-gather-start.2", 2.0, 4.0),
                                  ("fusion.4", 4.0, 5.0)]})
    assert trace.busy_s(t) == pytest.approx(5.0)
    assert trace.collective_s(t) == pytest.approx((1.0 + 4.0) / 2)


def test_collectives_are_found_by_opcode_not_by_name():
    text = ("%all_to_all.21 = u32[4,1,8388608]{2,1,0:T(1,128)} all-to-all("
            "u32[4,1,8388608]{2,1,0:T(1,128)} %bitcast.3), replica_groups={}")
    assert trace.parse_op(text) == ("all_to_all.21", "all-to-all")
    kernel = ("%radix_pass_slots_pallas.5 = (u32[312576,128]{1,0:T(8,128)}, "
              "s32[256]{0:T(256)}) custom-call(u32[312576,128]{1,0} %p), "
              "custom_call_target=\"tpu_custom_call\"")
    assert trace.parse_op(kernel) == ("radix_pass_slots_pallas.5",
                                      "custom-call")
    assert trace.parse_op("jit_body(123)") == ("jit_body(123)", "")
    for kind in ("all-to-all", "all-reduce-start", "all-gather",
                 "reduce-scatter", "collective-permute-done"):
        assert trace.is_collective(kind), kind
    for kind in ("fusion", "sort", "custom-call", "copy-start", ""):
        assert not trace.is_collective(kind), kind
    t = _trace({"/device:TPU:0": [("fusion.1", 0.0, 1.0)]})
    assert trace.collective_s(t) is None


def test_breakdown_names_ops_and_gaps_by_host_span():
    t = _trace({"/device:TPU:0": [("sort.1", 0.0, 4.0),
                                  ("fusion.2", 6.0, 9.0)]},
               [("joinbench.join", 0.0, 9.5),
                ("joinbench.rewrite", 4.0, 5.9)])
    b = trace.breakdown(t)
    assert b["device_ops"] == [["sort.1", 4.0], ["fusion.2", 3.0]]
    assert b["idle_gaps"][0][0] == "joinbench.rewrite"
    assert b["idle_gaps"][0][1] == pytest.approx(2.0)
    assert b["idle_gaps"][1] == ["joinbench.join", pytest.approx(1.0)]


def test_devices_beyond_the_cell_are_left_out():
    ops = {f"/device:TPU:{i}": [_op("fusion.1", 0.0, 1.0 + i)]
           for i in range(4)}
    t = trace.from_events(ops, [("joinbench.window", 0.0, 10.0)], devices=1)
    assert list(t.device_ops) == ["/device:TPU:0"]


def test_one_window_span_is_required():
    with pytest.raises(ValueError):
        trace.from_events({}, [])


def test_collectives_in_flight_count_from_the_async_line():
    t = trace.from_events(
        {"/device:TPU:0": [_op("fusion.1", 0.0, 4.0)]},
        [("joinbench.window", 0.0, 10.0)], None,
        {"/device:TPU:0": [_op("all-gather-start.1", 1.0, 2.5),
                           _op("copy-start.2", 0.0, 9.0)]})
    assert trace.collective_s(t) == pytest.approx(1.5)
    assert trace.busy_s(t) == pytest.approx(4.0)


RECORDED = os.path.join(os.path.dirname(__file__), "data")


def test_a_recorded_four_chip_trace():
    """uniform_4c on a v5e 2x2, --trace 1, 3 joins in a 14 s window (the
    source paths in its metadata were rewritten to ``./checkout/``)."""
    t = trace.load(RECORDED, devices=4)
    assert len(t.device_ops) == 4
    assert t.window_s == pytest.approx(13.999502783)
    assert trace.busy_s(t) == pytest.approx(13.9600746575)
    assert 0.2 < trace.idle_pct(t) < 0.4
    # two all-to-alls (inner, outer) and a small all-reduce per join
    kinds = {k for _, k, _, _ in t.device_ops["/device:TPU:0"]}
    assert {"all-to-all", "all-reduce", "custom-call", "sort"} <= kinds
    assert 0.009 < trace.collective_s(t) < 0.011
    b = trace.breakdown(t)
    assert b["device_ops"][0][0].startswith("radix_pass_slots_pallas")
    assert {g[0] for g in b["idle_gaps"]} <= {"joinbench.join",
                                            "joinbench.rewrite",
                                            "no benchmark span"}


def test_peaks_are_keyed_by_device_kind_with_a_source():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops"] == 197e12
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_join_bytes_from_shapes():
    # 20M x 20M on one chip: 40M tuples of 8 B
    assert work.join_bytes_per_chip(20_000_000, 20_000_000, 1) == 320_000_000
    # 80M x 80M over four chips: each reads its quarter
    assert work.join_bytes_per_chip(80_000_000, 80_000_000, 4) == 320_000_000
    assert work.least_join_seconds(20_000_000, 20_000_000, 1, 819e9) == \
        pytest.approx(3.907e-4, rel=1e-3)

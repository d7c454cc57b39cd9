"""The join's stage scopes and host spans (observability/stages.py and the
profiler sink of performance/measurements.py).

Every non-trivial instruction of the compiled join program is owned by a
``trj.*`` stage; the table is read once per compile; a name two programs
give to different stages is reported as ambiguous; and the program's host
timers show up in a profiler trace, nested as they run.
"""

import glob

import jax
import pytest

from tpu_radix_join import HashJoin, JoinConfig, Relation
from tpu_radix_join.observability import stages
from tpu_radix_join.performance import Measurements


@pytest.fixture
def fresh_stages():
    stages.reset()
    yield
    stages.reset()


@pytest.fixture
def recorded(fresh_stages, monkeypatch):
    """The stage table of every program the engine records, in order."""
    tables = []
    record = stages.record

    def capture(compiled):
        tables.append(stages.program_stages(compiled.as_text()))
        return record(compiled)

    monkeypatch.setattr(stages, "record", capture)
    return tables


def _join(nodes: int, meas=None, joins: int = 1):
    engine = HashJoin(JoinConfig(num_nodes=nodes), measurements=meas)
    r = Relation(nodes << 11, nodes, "unique", seed=3)
    s = Relation(nodes << 11, nodes, "unique", seed=4)
    for _ in range(joins):
        res = engine.join(r, s)
        assert res.ok and res.matches == r.expected_matches(s)
    return engine


def _program(tables, prefix: str) -> stages.Program:
    found = [p for p in tables if p.module.startswith(prefix)]
    assert len(found) == 1, [p.module for p in tables]
    return found[0]


def _unowned(program: stages.Program):
    """Instructions that do device work and that no stage owns."""
    return sorted(n for n, st in program.stages.items()
                  if st is None and program.opcodes[n] not in stages.TRIVIAL)


def test_one_node_join_every_instruction_has_a_stage(recorded):
    _join(1)
    join = _program(recorded, "jit_trj_join")
    assert _unowned(join) == []
    owned = set(join.stages.values())
    assert {stages.SORT, stages.MERGE_SCAN, stages.CHECKS} <= owned
    # one node partitions nothing and exchanges nothing
    assert stages.EXCHANGE not in owned


def test_four_node_join_every_instruction_has_a_stage(recorded):
    _join(4)
    join = _program(recorded, "jit_trj_join")
    assert _unowned(join) == []
    assert {stages.SORT, stages.MERGE_SCAN, stages.PARTITION,
            stages.EXCHANGE, stages.CHECKS} <= set(join.stages.values())
    assert [n for n, op in join.opcodes.items() if op == "all-to-all"
            and join.stages[n] != stages.EXCHANGE] == []
    sizing = _program(recorded, "jit_trj_sizing")
    assert _unowned(sizing) == []
    assert stages.KEY_PROBE in set(sizing.stages.values())


def test_stage_table_is_recorded_once_per_compile(recorded):
    engine = _join(4, joins=3)
    assert len(recorded) == len(engine._compiled) >= 2
    # the raw-array path probes the key range with its own program
    engine.join_arrays(engine.place(Relation(1 << 13, 4, "unique", seed=5)),
                       engine.place(Relation(1 << 13, 4, "unique", seed=6)))
    assert len(recorded) == len(engine._compiled)
    assert any(p.module.startswith("jit_trj_key_max") for p in recorded)


def _hlo(module: str, fusion_stage: str) -> str:
    return f"""HloModule {module}, is_scheduled=true

%fused_computation (param_0: u32[8]) -> u32[8] {{
  %param_0 = u32[8]{{0}} parameter(0)
  ROOT %add.1 = u32[8]{{0}} add(%param_0, %param_0), metadata={{op_name="jit(x)/{fusion_stage}/add"}}
}}

ENTRY %main (p: u32[8]) -> u32[8] {{
  %p = u32[8]{{0}} parameter(0)
  %fusion = u32[8]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation
  %sort.1 = u32[8]{{0}} sort(%fusion), dimensions={{0}}, metadata={{op_name="jit(x)/trj.partition/trj.sort/sort"}}
  ROOT %wrapped_reduce-window = u32[8]{{0}} fusion(%sort.1), kind=kLoop, calls=%fused_computation
}}
"""


class _Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def test_innermost_scope_owns_and_fusions_fall_back(fresh_stages):
    p = stages.program_stages(_hlo("jit_a", "trj.merge_scan"))
    assert p.module == "jit_a"
    assert p.stages["sort.1"] == stages.SORT          # innermost scope
    assert p.stages["fusion"] == stages.MERGE_SCAN    # from its fused root
    assert "add.1" not in p.stages                    # fused: never runs alone
    assert _unowned(p) == []


def test_instructions_no_scope_names_take_their_consumers_stage():
    text = """HloModule jit_b

ENTRY %main (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  %reduce-window.3 = u32[8]{0} reduce-window(%p), window={size=8}
  %reduce-window.4 = u32[8]{0} reduce-window(%reduce-window.3), window={size=8}
  ROOT %sort.2 = u32[8]{0} sort(%reduce-window.4), dimensions={0}, metadata={op_name="jit(b)/trj.sort/sort"}
}
"""
    p = stages.program_stages(text)
    assert p.stages["reduce-window.3"] == stages.SORT
    assert p.stages["reduce-window.4"] == stages.SORT
    assert p.stages["sort.2"] == stages.SORT


def test_a_name_two_programs_disagree_on_is_ambiguous(fresh_stages):
    assert stages.record(_Compiled(_hlo("jit_a", "trj.merge_scan")))
    assert stages.stage_of("fusion") == stages.MERGE_SCAN
    # the same program again agrees with itself
    assert stages.record(_Compiled(_hlo("jit_a", "trj.merge_scan")))
    assert stages.stage_of("fusion") == stages.MERGE_SCAN
    stages.record(_Compiled(_hlo("jit_c", "trj.exchange")))
    assert stages.stage_of("fusion") == stages.AMBIGUOUS
    assert stages.stage_of("sort.1") == stages.SORT   # still agreed on
    assert stages.stage_of("no.such.op") is None


def test_a_program_without_text_records_nothing(fresh_stages):
    class NoText:
        def as_text(self):
            return None   # what jax.stages.Compiled gives where unavailable

    assert stages.record(NoText()) is False
    assert stages.stage_of("fusion") is None


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[-1]
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("trj."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return spans


def test_profiler_trace_holds_the_programs_host_spans(tmp_path):
    meas = Measurements()
    engine = HashJoin(JoinConfig(num_nodes=1), measurements=meas)
    r = engine.place(Relation(1 << 12, 1, "unique", seed=7))
    s = engine.place(Relation(1 << 12, 1, "unique", seed=8))
    engine.join_arrays(r, s)   # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        res = engine.join_arrays(r, s)
    assert res.ok
    spans = _host_spans(str(tmp_path))
    for name in ("trj.JTOTAL", "trj.JPROC", "trj.key_probe",
                 "trj.readback", "trj.finish"):
        assert len(spans.get(name, ())) == 1, (name, sorted(spans))
    (t0, t1), = spans["trj.JTOTAL"]
    (p0, p1), = spans["trj.JPROC"]
    assert t0 <= p0 < p1 <= t1
    (k0, k1), = spans["trj.key_probe"]
    assert t0 <= k0 < k1 <= p0
    assert "trj.JCOMPILE" not in spans   # nothing compiled in the trace


def test_overlapping_timers_close_their_own_spans(tmp_path):
    meas = Measurements()
    with jax.profiler.trace(str(tmp_path)):
        meas.start("JTOTAL")
        meas.start("JPROC")
        meas.stop("JTOTAL")   # out of order: spans are keyed, not stacked
        meas.start("JPROC")   # a restarted timer ends its first span
        meas.stop("JPROC")
        with meas.span("finish"):
            pass
    spans = _host_spans(str(tmp_path))
    assert len(spans["trj.JTOTAL"]) == 1
    assert len(spans["trj.JPROC"]) == 2
    assert len(spans["trj.finish"]) == 1
    assert meas._annotations == {}

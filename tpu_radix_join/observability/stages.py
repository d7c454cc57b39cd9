"""Which stage of the join each compiled operation belongs to.

Every stage of the join names its device work with a ``jax.named_scope``
inside the function that implements it, so every caller inherits the
name:

* ``trj.sort``: the ``lax.sort`` calls of ``ops/sorting.py``;
* ``trj.merge_scan``: the merge count of ``ops/merge_count.py`` and its
  Pallas scan;
* ``trj.partition``: histograms, partition ids, the radix partitioning
  of ``ops/radix.py`` and its Pallas kernels, network partitioning;
* ``trj.exchange``: block assembly and the ``all_to_all`` of
  ``parallel/window.py``;
* ``trj.key_probe``: the max-key probes;
* ``trj.checks``: the engine's input-contract, conservation and
  count-overflow checks.

The scope lands in each HLO instruction's ``metadata.op_name``
(``jit(trj_join)/trj.partition/trj.sort/...``); the innermost ``trj.*``
scope owns the instruction.  :func:`record` reads a compiled program's
HLO text once per compile and keeps, per process, which stage owns each
instruction that runs on its own: those of the entry computation and of
the computations control flow calls, not the bodies of fusions or
reducers.  A fusion whose own metadata names no stage takes the stage of
its fused root, else the most common one among its fused instructions.
An instruction that still names none (XLA's own rewrites, and lowerings
JAX outlines, such as ``cumsum``) takes the one stage its consumers
agree on, else the one its operands agree on.

A profiler trace names a device operation by its instruction name alone,
and two programs may use one name: :func:`stage_of` answers
:data:`AMBIGUOUS` for a name that the process's programs give to
different stages, rather than guess.

This module imports nothing of the package: it owns the ``trj.`` prefix
that the stage scopes and the host spans of ``Measurements`` share.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, List, NamedTuple, Optional, Set, Tuple

#: the prefix of every scope and host span the program names
#: (``trj.sort``, ``trj.JTOTAL``)
SPAN_PREFIX = "trj."

SORT = "trj.sort"
MERGE_SCAN = "trj.merge_scan"
PARTITION = "trj.partition"
EXCHANGE = "trj.exchange"
KEY_PROBE = "trj.key_probe"
CHECKS = "trj.checks"

#: what :func:`stage_of` answers for a name different programs disagree on
AMBIGUOUS = "ambiguous"
#: opcodes that need no stage: bookkeeping, and the copies XLA inserts
#: to change layouts
TRIVIAL = frozenset({"parameter", "tuple", "get-tuple-element", "bitcast",
                     "copy", "constant"})

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
#: the opcode: the first lower-case word after a blank and before "("
_OPCODE = re.compile(r"(?<=\s)([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def innermost(op_name: str) -> Optional[str]:
    """The innermost ``trj.*`` scope of an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part.startswith(SPAN_PREFIX):
            return part
    return None


class Program(NamedTuple):
    """The stage table of one compiled program."""

    module: str
    #: instruction -> the stage that owns it, or None
    stages: Dict[str, Optional[str]]
    #: instruction -> its opcode
    opcodes: Dict[str, str]


class _Instr:
    __slots__ = ("name", "opcode", "text", "root")

    def __init__(self, name: str, opcode: str, text: str, root: bool):
        self.name, self.opcode, self.text, self.root = (name, opcode, text,
                                                        root)


def _parse(hlo_text: str) -> Tuple[str, Dict[str, List[_Instr]]]:
    """(module name, computation name -> its instructions)."""
    module = ""
    comps: Dict[str, List[_Instr]] = {}
    current = None
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _COMPUTATION.match(line)
        if m and not line.startswith(" "):
            current = comps.setdefault(m.group(2), [])
            continue
        if current is None:
            continue
        m = _INSTRUCTION.match(line)
        if m:
            op = _OPCODE.search(" " + m.group(3))
            current.append(_Instr(m.group(2), op.group(1) if op else "",
                                  m.group(3), bool(m.group(1))))
    return module, comps


def program_stages(hlo_text: str) -> Program:
    """The stage table of the instructions of one compiled program's HLO
    text that run on their own."""
    module, comps = _parse(hlo_text)
    fused: Set[str] = set()
    for instrs in comps.values():
        for ins in instrs:
            if ins.opcode == "fusion":
                fused.update(_CALLS.findall(ins.text))
            fused.update(_TO_APPLY.findall(ins.text))

    memo: Dict[str, Optional[str]] = {}

    def fused_stage(comp: str) -> Optional[str]:
        if comp not in memo:
            memo[comp] = None
            counts: collections.Counter = collections.Counter()
            root = None
            for ins in comps.get(comp, ()):
                st = own_stage(ins)
                if st is not None:
                    counts[st] += 1
                    if ins.root:
                        root = st
            memo[comp] = root or (counts.most_common(1)[0][0]
                                  if counts else None)
        return memo[comp]

    def own_stage(ins: _Instr) -> Optional[str]:
        m = _OP_NAME.search(ins.text)
        st = innermost(m.group(1)) if m else None
        if st is None and ins.opcode == "fusion":
            for comp in _CALLS.findall(ins.text):
                st = fused_stage(comp)
                if st is not None:
                    break
        return st

    table: Dict[str, Optional[str]] = {}
    opcodes: Dict[str, str] = {}
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        opcodes.update((ins.name, ins.opcode) for ins in instrs)
        stage = {ins.name: own_stage(ins) for ins in instrs}
        names = list(stage)   # program order: the inference is repeatable
        operands = {ins.name: [r for r in _REF.findall(ins.text)
                               if r in stage and r != ins.name]
                    for ins in instrs}
        users: Dict[str, List[str]] = {n: [] for n in names}
        for n, refs in operands.items():
            for r in refs:
                users[r].append(n)
        for neighbours in (users, operands):
            changed = True
            while changed:
                changed = False
                for n in names:
                    if stage[n] is not None:
                        continue
                    found = {stage[x] for x in neighbours[n]} - {None}
                    if len(found) == 1:
                        stage[n] = found.pop()
                        changed = True
        table.update(stage)
    return Program(module, table, opcodes)


#: instruction -> stage, None, or AMBIGUOUS, over every recorded program
_names: Dict[str, Optional[str]] = {}


def record(compiled) -> bool:
    """Record which stage owns each instruction of ``compiled`` (a
    ``jax.stages.Compiled``); False where the executable gives no HLO
    text (``as_text`` returns None there)."""
    text = compiled.as_text()
    if not text:
        return False
    for name, stage in program_stages(text).stages.items():
        if name in _names and _names[name] != stage:
            _names[name] = AMBIGUOUS
        else:
            _names[name] = stage
    return True


def stage_of(instruction: str) -> Optional[str]:
    """The stage that owns ``instruction`` in every recorded program that
    has it; None where no program has it or none names a stage;
    :data:`AMBIGUOUS` where the programs disagree."""
    return _names.get(instruction)


def reset() -> None:
    """Forget every recorded program."""
    _names.clear()

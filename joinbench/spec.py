"""Finding the parts of a cell by name, and the records a run passes
between them.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
configuration's file is the one its entry names; the traffic mix is
``joinbench/traffic/<mix>.json``, whose ``loop`` key names the generator
``joinbench/loops/<loop>.py``; a metric is read by
``joinbench/metrics/<metric>.py``.  Every lookup starts from a checkout
root, so a test can point it at a tree of its own.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "joinbench"
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _entry(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _entry(bench["workloads"], _checked(name), "workload")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    entry = _entry(bench["configs"], _checked(name), "config")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def traffic(name: str, root: str = ROOT) -> dict:
    path = os.path.join(root, PACKAGE, "traffic", _checked(name) + ".json")
    with open(path) as f:
        return json.load(f)


def _load_file(kind: str, name: str, root: str):
    path = os.path.join(root, PACKAGE, kind, _checked(name) + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        f"{PACKAGE}.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    if mod_spec is None or not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def loop(name: str, root: str = ROOT):
    """The traffic generator module ``loops/<name>.py``: ``run(ctx)``."""
    return _load_file("loops", name, root)


def reader(name: str, root: str = ROOT):
    """The reader ``metrics/<name>.py``: ``read(run) -> float | None``."""
    return _load_file("metrics", name, root).read


def _reports(metric: dict, cell: str, default: bool) -> bool:
    cells = metric.get("workloads")
    return default if cells is None else cell in cells


def end_to_end(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["end_to_end"] if _reports(m, cell, True)]


def per_layer(bench: dict, cell: str) -> List[dict]:
    """Per-layer metrics of ``cell``: those listing it, and those with no
    list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end(bench, cell)}
    return [m for m in bench["per_layer"]
            if _reports(m, cell, m["moves"] in e2e)]


@dataclasses.dataclass
class Context:
    """What a traffic generator is given."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    #: directory to write the profiler trace of the window to, or None
    trace_dir: Optional[str]
    #: ``time.perf_counter()`` at process start: set-up is measured from it
    t0: float
    #: ``fn(join_arrays) -> join_arrays``
    #: applied to the path under test (controls and fault tests)
    substitute: Optional[Any] = None
    #: ``run.CompileCounter``: programs compiled, loaded and traced so far
    compile_counter: Optional[Any] = None

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])


@dataclasses.dataclass
class LoopResult:
    """What a traffic generator hands back."""

    setup_s: float
    #: host-clock seconds from the window's start to the end of its last
    #: join
    window_s: float
    #: one dict per join of the window
    records: List[Dict[str, Any]]
    #: name -> (value, limit): the numbers that decide ``correct``
    checks: Dict[str, tuple]
    attempted: int
    failed: int
    memory_peak_bytes: int
    #: ``CompileCounter.counts`` over the window: there should be none
    window_programs: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Run:
    """What a metric reader is given."""

    cell: dict
    config: dict
    traffic: dict
    device_kind: str
    result: LoopResult
    #: the reduced trace of the window (``--trace 1``), else None
    trace: Any = None

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    @property
    def records(self) -> List[Dict[str, Any]]:
        return self.result.records

"""The reduction from a profiler trace to device numbers.

``jax.profiler.trace`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes (``/device:TPU:<n>``) hold one event per XLA
operation on their ``XLA Ops`` line; the host plane holds the benchmark's
own ``TraceAnnotation`` spans (``joinbench.*``), on the same clock.  The
window is the span named ``joinbench.window``.  An event's name is the
HLO instruction's text; the reduction keeps the instruction's name
(``fusion.3``, ``radix_pass_slots_pallas.5``) and its opcode (``fusion``,
``custom-call``, ``all-to-all``).
Asynchronous operations (``*-start`` of copies, slices and collectives)
sit on the ``Async XLA Ops`` line.

* busy time: the union of a device's operation intervals inside the
  window; the idle share is one minus busy over the window's length;
* collective time: the union of the intervals of operations whose kind is
  a collective (all-to-all, all-gather, all-reduce, reduce-scatter,
  collective-permute), found by the HLO opcode in the event's text, never
  by a name the program chose (shard_map names its all-to-all
  ``all_to_all.21``);
* the breakdown: the operations that took most device time, and the
  longest idle gaps, each named by the benchmark span the host was in.

Numbers of several devices are averaged over the devices used.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "joinbench.window"
SPAN_PREFIX = "joinbench."
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
_COLLECTIVE = re.compile(
    r"^(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)")
#: the opcode: the first lower-case word after a blank and before "(" in
#: the instruction's text (``u32[4,8]{1,0:T(1,128)} all-to-all(...)``)
_OPCODE = re.compile(r"(?<=\s)([a-z][a-z0-9-]*)\(")

Interval = Tuple[float, float]
#: (instruction name, opcode, start, end)
Op = Tuple[str, str, float, float]


@dataclasses.dataclass
class Trace:
    """Intervals in seconds on the profiler's clock."""

    window: Interval
    #: device plane name -> its operations
    device_ops: Dict[str, List[Op]]
    #: [(span name, start, end)] of the benchmark's host spans
    host_spans: List[Tuple[str, float, float]]
    #: device plane name -> its asynchronous operations
    async_ops: Dict[str, List[Op]] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def parse_op(event_name: str) -> Tuple[str, str]:
    """``%fusion.3 = u32[...] fusion(...)`` -> ``("fusion.3", "fusion")``."""
    name, _, text = event_name.partition(" = ")
    m = _OPCODE.search(text)
    return name.lstrip("%"), m.group(1) if m else ""


def _ops(line) -> List[Op]:
    return [(*parse_op(e.name), e.start_ns * 1e-9,
             (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def load(trace_dir: str, devices: Optional[int] = None) -> Trace:
    """Read the trace under ``trace_dir``; keep the first ``devices``
    device planes by ordinal (all when None)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    device_ops: Dict[str, list] = {}
    async_ops: Dict[str, list] = {}
    host_spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = _ops(line)
                elif line.name == ASYNC_LINE:
                    async_ops[plane.name] = _ops(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return from_events(device_ops, host_spans, devices, async_ops)


def _ordinal(plane_name: str) -> int:
    m = re.search(r"(\d+)$", plane_name)
    return int(m.group(1)) if m else 0


def from_events(device_ops: Dict[str, list], host_spans: list,
                devices: Optional[int] = None,
                async_ops: Optional[Dict[str, list]] = None) -> Trace:
    windows = [(s, e) for name, s, e in host_spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    names = sorted((n for n, ops in device_ops.items() if ops), key=_ordinal)
    if devices is not None:
        names = names[:devices]
    async_ops = async_ops or {}
    return Trace(windows[0], {n: device_ops[n] for n in names},
                 sorted(host_spans, key=lambda t: t[1]),
                 {n: async_ops.get(n, []) for n in names})


def union(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    """The intervals clipped to ``window`` and merged where they overlap."""
    lo, hi = window
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if e > lo and s < hi)
    out: List[List[float]] = []
    for s, e in clipped:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def _mean(values: Sequence[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def busy_s(trace: Trace) -> Optional[float]:
    """Seconds in which some operation ran, averaged over the devices."""
    return _mean([_length(union([(s, e) for _, _, s, e in ops],
                                trace.window))
                  for ops in trace.device_ops.values()])


def idle_pct(trace: Trace) -> Optional[float]:
    busy = busy_s(trace)
    if busy is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / trace.window_s)


def is_collective(opcode: str) -> bool:
    return bool(_COLLECTIVE.match(opcode))


def collective_s(trace: Trace) -> Optional[float]:
    """Seconds in which a collective ran, synchronous or in flight,
    averaged over the devices; None where no device ran one."""
    per_device = [
        _length(union([(s, e) for _, kind, s, e in
                       ops + trace.async_ops.get(plane, [])
                       if is_collective(kind)], trace.window))
        for plane, ops in trace.device_ops.items()]
    if not any(per_device):
        return None
    return _mean(per_device)


def top_ops(trace: Trace, k: int = 10) -> List[list]:
    """``[[op name, seconds]]``: the ``k`` operations with the most device
    time in the window, averaged over the devices."""
    totals: Dict[str, float] = {}
    for ops in trace.device_ops.values():
        for name, _, s, e in ops:
            for cs, ce in union([(s, e)], trace.window):
                totals[name] = totals.get(name, 0.0) + ce - cs
    ndev = max(1, len(trace.device_ops))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[name, t / ndev] for name, t in ranked]


def _host_label(trace: Trace, gap: Interval) -> str:
    """The innermost benchmark span around the middle of ``gap``, else the
    one that overlaps it most."""
    mid = (gap[0] + gap[1]) / 2
    best, best_key = "no benchmark span", None
    for name, s, e in trace.host_spans:
        if name == WINDOW_SPAN:
            continue
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap <= 0:
            continue
        key = (s <= mid <= e, -(e - s) if s <= mid <= e else overlap)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def idle_gaps(trace: Trace, k: int = 10) -> List[list]:
    """``[[host span, seconds]]``: the ``k`` longest gaps in which the first
    device ran nothing, each named by what the host was doing."""
    if not trace.device_ops:
        return []
    ops = next(iter(trace.device_ops.values()))
    busy = union([(s, e) for _, _, s, e in ops], trace.window)
    edges = [trace.window[0]] + [t for iv in busy for t in iv] \
        + [trace.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_host_label(trace, g), g[1] - g[0]] for g in gaps[:k]]


def breakdown(trace: Trace) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}

"""Run one cell of the benchmark once.

    python3 -m joinbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run makes its data from ``--seed``, warms up every program the cell's
traffic uses (set-up), drives the path under test for ``--seconds``
(the window), then compares every answer of the window with the plain
reference.  Its last line on standard output is one JSON object::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  ``checks`` gives each number compared with its limit; the same
lines close standard error.  A process that finds no TPU, or fewer chips
than the cell asks for, prints no result and exits 3.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # set-up is measured from here, before JAX loads

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

from joinbench import spec  # noqa: E402

#: exit code of a run that found no accelerator, or too few chips
NO_ACCELERATOR = 3


class NoAccelerator(RuntimeError):
    pass


def require_accelerator(devices, chips: int) -> None:
    """Refuse anything but ``chips`` or more TPU devices."""
    if not devices or devices[0].platform != "tpu":
        platform = devices[0].platform if devices else "none"
        raise NoAccelerator(f"no TPU: JAX's first device is on {platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devices)}")


def use_compile_cache(root: str) -> Optional[str]:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    the program reads the same directory from the environment.  Not on
    the CPU, whose cached programs are tied to the host's features."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """From JAX's monitoring events: ``counts["compiled"]`` programs that
    missed the persistent cache and were compiled, ``counts["loaded"]``
    programs read back from it, ``counts["traced"]`` functions traced."""

    _EVENTS = {"/jax/compilation_cache/cache_misses": "compiled",
               "/jax/compilation_cache/cache_hits": "loaded",
               "/jax/core/compile/jaxpr_trace_duration": "traced"}

    def __init__(self):
        import jax

        self.counts = dict.fromkeys(self._EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, *_, **__) -> None:
        key = self._EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1


def _metrics(entries, run: spec.Run, root: str) -> dict:
    out = {}
    for m in entries:
        value = spec.reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = spec.ROOT, t0: Optional[float] = None,
             require_tpu: bool = True, keep_trace: Optional[str] = None,
             substitute=None) -> tuple:
    """One run of one cell: ``(result line as a dict, spec.LoopResult)``."""
    from joinbench import trace as tr

    bench = spec.load_benchmark(root)
    cell = spec.workload(bench, workload)
    import jax

    devices = jax.devices()
    if require_tpu:
        require_accelerator(devices, int(cell["chips"]))
    use_compile_cache(root)
    counter = CompileCounter()
    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="joinbench-trace-")
    try:
        ctx = spec.Context(
            cell=cell, config=spec.config(bench, cell["config"], root),
            traffic=spec.traffic(cell["traffic"], root), seed=seed,
            seconds=seconds, trace_dir=trace_dir,
            t0=_T0 if t0 is None else t0, substitute=substitute,
            compile_counter=counter)
        result = spec.loop(ctx.traffic["loop"], root).run(ctx)
        reduced = tr.load(trace_dir, ctx.chips) if trace else None
    finally:
        if trace_dir and not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run = spec.Run(cell=cell, config=ctx.config, traffic=ctx.traffic,
                   device_kind=devices[0].device_kind, result=result,
                   trace=reduced)
    entries = (spec.per_layer(bench, workload) if trace
               else spec.end_to_end(bench, workload))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": result.memory_peak_bytes}
    line = {"correct": all(v <= lim for v, lim in result.checks.values())
            and result.attempted > 0,
            "attempted": result.attempted, "failed": result.failed,
            "metrics": _metrics(entries, run, root), "device": device}
    if reduced is not None:
        device["busy_s"] = tr.busy_s(reduced) or 0.0
        device["window_s"] = reduced.window_s
        line["breakdown"] = tr.breakdown(reduced)
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, (v, lim) in result.checks.items()}
    return line, result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", metavar="DIR",
                   help="write the profiler trace to DIR and keep it")
    args = p.parse_args(argv)
    try:
        line, result = run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), keep_trace=args.keep_trace)
    except NoAccelerator as e:
        print(f"[joinbench] {e}", file=sys.stderr)
        return NO_ACCELERATOR
    programs = result.window_programs
    print(f"[joinbench] window: {programs.get('compiled', 0)} programs "
          f"compiled, {programs.get('loaded', 0)} loaded from the compile "
          f"cache, {programs.get('traced', 0)} traced; setup_s "
          f"{result.setup_s}, window_s {result.window_s}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"[joinbench] check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"[joinbench] correct {str(line['correct']).lower()}",
          file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

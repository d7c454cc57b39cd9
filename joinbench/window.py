"""What every traffic generator shares: the measured window, the
benchmark's host spans, and the program's fallback counters."""

from __future__ import annotations

import contextlib
import time

from joinbench.trace import SPAN_PREFIX, WINDOW_SPAN


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class Window:
    """``with Window(ctx) as w:`` measures from entry; ``w.open()`` says
    whether new work may start; ``w.close()`` stamps the end of the last
    piece of work.  Under ``ctx.trace_dir`` the profiler records it."""

    def __init__(self, ctx):
        self.ctx = ctx
        self._stack = contextlib.ExitStack()
        self.start = self.end = self.deadline = None
        self._counts0 = {}
        #: the compile counter's counts over the window
        self.programs = {"compiled": 0, "loaded": 0, "traced": 0}

    def __enter__(self):
        import jax

        if self.ctx.trace_dir:
            # no Python tracer: the benchmark's spans are TraceMe events,
            # and tracing every Python call would slow the host path
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            self._stack.enter_context(jax.profiler.trace(
                self.ctx.trace_dir, profiler_options=options))
        self._stack.enter_context(
            jax.profiler.TraceAnnotation(WINDOW_SPAN))
        counter = self.ctx.compile_counter
        if counter is not None:
            self._counts0 = dict(counter.counts)
        self.start = time.perf_counter()
        self.deadline = self.start + self.ctx.seconds
        return self

    def open(self) -> bool:
        return time.perf_counter() < self.deadline

    def close(self) -> None:
        self.end = time.perf_counter()
        counter = self.ctx.compile_counter
        if counter is not None:
            self.programs = {k: v - self._counts0.get(k, 0)
                             for k, v in counter.counts.items()}

    def __exit__(self, *exc):
        if self.end is None:
            self.close()
        self._stack.close()
        return False

    @property
    def seconds(self) -> float:
        return self.end - self.start


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def seed_sequence(seed: int) -> list:
    """``--seed`` as NumPy seed material: any whole number, sign kept."""
    return [abs(int(seed)), int(seed < 0)]


#: the program's counters that say ``auto`` left the Pallas kernels
FALLBACK_COUNTERS = ("PARTFALLBACK", "SORTFALLBACK")


def fallbacks(measurements) -> int:
    return sum(int(measurements.counters.get(c, 0))
               for c in FALLBACK_COUNTERS)

"""Compile-time telemetry: jax.monitoring events -> NCOMPILE/COMPILEMS.

XLA compilation is the one cost the reference has no analog for
(Measurements.cpp keeps none because C++ has no runtime compile), and
here it is both large (seconds to tens of seconds per program on the
chip, PERF.md) and
*recurring* when shapes churn: a resident serve session that recompiles
after warmup is leaking its amortization win.  JCOMPILE only times the
window-allocation compile the engine brackets explicitly; this monitor
hears EVERY backend compile via ``jax.monitoring``'s
``/jax/core/compile/backend_compile_duration`` event and mirrors it into
the registry's counters:

  * ``NCOMPILE``  — backend compiles observed (count);
  * ``COMPILEMS`` — total backend-compile wall milliseconds.

Because they are ordinary counters they ride everywhere counters already
go: heartbeat ticks (MetricsSampler snapshots ``m.counters``), the
run-end ledger row, forensics bundles, and the regress gate (pinned
lower-is-better).  service/session.py watches the per-query NCOMPILE
delta to warn on recompile storms after warmup.

jax.monitoring offers no per-listener deregistration (only a global
clear), so ONE module-level listener is registered on first install and
dispatches to the currently-installed registries; ``uninstall`` removes
a registry from that set, after which the listener is inert for it.
"""

from __future__ import annotations

from typing import List

# the module, not its names: measurements imports this package's stage
# names, so either may be the first of the two to load
from tpu_radix_join.performance import measurements as _tags

#: the duration event XLA fires once per backend compile (jax 0.4.x)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_registered = False
_active: List[object] = []      # installed Measurements registries


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event != BACKEND_COMPILE_EVENT:
        return
    ms = max(0, int(round(duration_secs * 1e3)))
    for m in list(_active):
        try:
            m.incr(_tags.NCOMPILE)
            m.incr(_tags.COMPILEMS, by=ms)
        except Exception:   # noqa: BLE001 — telemetry must not fail a compile
            pass


def install_compile_monitor(measurements):
    """Start mirroring backend-compile events into ``measurements``'
    NCOMPILE/COMPILEMS counters.  Idempotent per registry; returns the
    registry for chaining."""
    global _registered
    if not _registered:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _registered = True
    if measurements not in _active:
        _active.append(measurements)
    return measurements


def uninstall_compile_monitor(measurements) -> None:
    """Stop mirroring into ``measurements`` (the global listener stays
    registered but becomes a no-op for it — jax.monitoring cannot drop a
    single listener)."""
    try:
        _active.remove(measurements)
    except ValueError:
        pass

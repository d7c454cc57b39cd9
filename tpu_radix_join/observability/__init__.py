"""Unified observability layer: span timelines, live metrics, regression gate.

Three pillars over the ``performance`` registry (ISSUE 3):

  * :mod:`spans` — hierarchical cross-rank span tracer; every
    ``Measurements.start/stop`` mirrors into a Chrome-trace span, every
    ``Measurements.event`` into an instant event; per-rank export.
  * :mod:`metrics` — opt-in background heartbeat (``--metrics-interval``)
    sampling host RSS, device HBM, and the counter registry to JSONL.
  * :mod:`regress` — baseline-vs-fresh per-tag comparison behind
    ``tools_check_regress.py`` and bench.py's ``--check-regress``.

Merging per-rank span files onto one aligned clock lives in
:mod:`timeline` (driven by ``tools_make_report.py --emit-timeline``).
:mod:`stages` names the join's device work: one ``trj.*`` named scope
per stage, and per process the table of which stage owns each compiled
instruction, read once per compile.

The cross-run memory layer (ISSUE 9) adds two:

  * :mod:`ledger` — append-only schema-versioned JSONL store of per-run
    observations (phase spans, counters, plan-vs-actual tables, bench
    lines, query outcomes, stack fingerprints), written at run end and
    backfillable from committed artifacts; feeds the profile
    auto-calibration loop in ``planner/calibrate.py``;
  * :mod:`compilemon` — jax.monitoring listener mirroring every backend
    compile into the NCOMPILE/COMPILEMS counters (recompile-storm canary
    for ``--serve``).

The always-on black-box layer (ISSUE 8) adds three more:

  * :mod:`flightrec` — bounded ring of recent spans/counter deltas/events
    wired into every Measurements registry with no opt-in flag;
  * :mod:`watchdog` — phase-progress monitor that converts a hung
    collective into a classified ``backend_unavailable`` outcome through
    the engine's cancel hook, dumping stacks + ring on the way;
  * :mod:`postmortem` — self-contained forensics bundles on any terminal
    failure, rendered/merged by ``tools_postmortem.py``.

The attribution layer (ISSUE 18) adds two more:

  * :mod:`critpath` — cross-rank critical-path reconstruction over
    exported span streams: which rank's which phase bounded the wall
    clock, decomposed into compute / collective-wait / straggle, with
    hedge-claim shortening estimates (``[CRITPATH]`` driver line,
    ``tools_critical_path.py``, the ``--plan explain`` measured column);
  * :mod:`statusz` — read-only live JSON introspection endpoint for the
    resident service (``--serve --statusz PORT``).
"""

from tpu_radix_join.observability.compilemon import (install_compile_monitor,
                                                     uninstall_compile_monitor)
from tpu_radix_join.observability.critpath import (compute_critical_path,
                                                   critical_path_for_dir,
                                                   critical_path_from_tracer,
                                                   format_summary,
                                                   render_report)
from tpu_radix_join.observability.flightrec import (FlightRecorder,
                                                    dump_all_stacks)
from tpu_radix_join.observability.ledger import (Ledger, bench_payload,
                                                 default_ledger_dir,
                                                 ingest_artifacts, load_rows,
                                                 run_payload)
from tpu_radix_join.observability.metrics import MetricsSampler, load_samples
from tpu_radix_join.observability.postmortem import (build_bundle,
                                                     list_bundles,
                                                     load_bundle,
                                                     merge_bundles,
                                                     render_bundle,
                                                     write_bundle)
from tpu_radix_join.observability.regress import (check_files, check_result,
                                                  compare_tags, extract_tags,
                                                  format_table,
                                                  parse_tag_thresholds)
from tpu_radix_join.observability.spans import SpanTracer
from tpu_radix_join.observability.statusz import (StatuszServer,
                                                  measurements_sections)
from tpu_radix_join.observability.timeline import (find_span_files,
                                                   merge_timeline)
from tpu_radix_join.observability.watchdog import (HangDetected, Watchdog,
                                                   engine_killer)

__all__ = [
    "FlightRecorder", "HangDetected", "Ledger", "MetricsSampler",
    "SpanTracer", "StatuszServer", "Watchdog", "bench_payload",
    "build_bundle", "check_files", "check_result", "compare_tags",
    "compute_critical_path", "critical_path_for_dir",
    "critical_path_from_tracer", "default_ledger_dir", "dump_all_stacks",
    "engine_killer", "extract_tags", "find_span_files", "format_summary",
    "format_table", "ingest_artifacts", "install_compile_monitor",
    "list_bundles", "load_bundle", "load_rows", "load_samples",
    "measurements_sections", "merge_bundles", "merge_timeline",
    "parse_tag_thresholds", "render_bundle", "render_report",
    "run_payload", "uninstall_compile_monitor", "write_bundle",
]

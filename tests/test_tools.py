"""Regression tests for the artifact-analysis tools: the evidence-summary
generator (tools_make_report.py) and the net-of-dispatch phase table
(experiments/exp_phase_net.py) parse perf dirs written by
``Measurements.store`` to known values, so a refactor of the perf format or
the tools cannot silently corrupt the numbers they report."""

import os
import subprocess
import sys

from tpu_radix_join.performance.measurements import Measurements

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv):
    out = subprocess.run([sys.executable, *argv], capture_output=True,
                         text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout


def _perf_dir(path, repeat, per_join_ms, counters=()):
    """A driver perf dir for ``repeat`` joins whose phases took
    ``per_join_ms`` each (the registry holds the cumulative times)."""
    m = Measurements(node_id=0, num_nodes=1)
    for tag, ms in per_join_ms.items():
        m.add_time_us(tag, ms * 1e3 * repeat)
    for tag, v in counters:
        m.counters[tag] = v
    m.meta.update(config={"repeat": repeat})
    m.store(str(path))
    return str(path)


def test_make_report_tabulates_perf_dirs(tmp_path):
    _perf_dir(tmp_path / "perf_16m_sort", 3, {"JPROC": 108.5,
                                              "JTOTAL": 309.4})
    _perf_dir(tmp_path / "perf_20m_phases", 3,
              {"JHIST": 83.2, "JMPI": 317.1, "SLOCPREP": 366.3,
               "JPROC": 507.4}, counters=[("JPROCRATE", 78_800_000)])
    out = _run("tools_make_report.py", str(tmp_path))
    assert "| dir | repeat | key_range | JHIST | JMPI | SLOCPREP | JPROC " \
           "| JPROCRATE_M/s |" in out
    assert "| perf_16m_sort | 3 |  |  |  |  | 108.5 |  |" in out
    assert "| perf_20m_phases | 3 |  | 83.2 | 317.1 | 366.3 | 507.4 " \
           "| 78.8 |" in out


def test_make_report_empty_dir(tmp_path):
    out = _run("tools_make_report.py", str(tmp_path))
    assert "Evidence summary" in out      # no artifacts -> no tables, no crash
    assert "Perf artifacts" not in out


def test_phase_net_table(tmp_path):
    phases = _perf_dir(tmp_path / "perf_16m_phases", 3,
                       {"JHIST": 80.0, "JMPI": 300.0, "JPROC": 150.0})
    fused = _perf_dir(tmp_path / "perf_16m_sort", 3, {"JPROC": 108.5})
    out = _run("experiments/exp_phase_net.py", phases, fused)
    # no SDISPATCH in these dirs: net == gross, flagged loudly
    assert "no SDISPATCH tag" in out
    assert "JPROC" in out and "fused dir" in out
    assert "JPROC gross 108.5 ms/join" in out
    assert "split-vs-fused gap: 341.5 ms/join gross" in out

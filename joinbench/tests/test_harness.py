"""Discovery by name, extension by new files, the run's last line, and the
refusal to run without a TPU."""

import json
import os
import subprocess
import sys
import time

import pytest

from joinbench import run, spec


def test_every_cell_finds_its_parts():
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        conf = spec.config(bench, cell["config"])
        assert conf["name"] == cell["config"]
        assert int(conf["nodes"]) == cell["chips"]
        traffic = spec.traffic(cell["traffic"])
        assert hasattr(spec.loop(traffic["loop"]), "run")
        names = {m["name"] for m in spec.end_to_end(bench, cell["name"])}
        assert "setup_s" in names and len(names) >= 2
        assert spec.per_layer(bench, cell["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_per_layer_metrics_follow_their_cells():
    bench = spec.load_benchmark()
    across = {"jhist_ms", "collective_ms"}
    assert across <= {m["name"] for m in spec.per_layer(bench, "uniform_4c")}
    assert not across & {m["name"]
                         for m in spec.per_layer(bench, "uniform_1c")}
    for cell in ("uniform_1c", "uniform_4c"):
        e2e = {m["name"] for m in spec.end_to_end(bench, cell)}
        assert all(m["moves"] in e2e for m in spec.per_layer(bench, cell))


def test_names_are_checked():
    with pytest.raises(ValueError):
        spec.traffic("../BENCHMARK")


def _run(root, cell, trace=False, **kw):
    line, _ = run.run_cell(cell, 987654321987, 0.5, trace, root=root,
                           require_tpu=False, t0=time.perf_counter(), **kw)
    return line


@pytest.mark.parametrize("cell", ["uniform_1c", "uniform_4c"])
def test_a_tiny_run_is_correct(tiny_root, cell):
    line = _run(tiny_root, cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    bench = spec.load_benchmark(tiny_root)
    assert set(line["metrics"]) == {m["name"]
                                    for m in spec.end_to_end(bench, cell)}
    assert list(line)[-1] == "checks"


def test_a_traced_run_reads_per_layer_metrics(tiny_root):
    line = _run(tiny_root, "uniform_1c", trace=True)
    assert line["correct"]
    assert "jproc_ms" in line["metrics"]
    # the CPU has no device plane: no device metric is made up
    assert not {"device_idle_pct.batch", "join_roofline"} & set(
        line["metrics"])
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] > 0


def test_a_cell_config_traffic_and_metric_are_added_as_new_files(tiny_root):
    """A later PR adds files and entries, and edits no existing file."""
    pkg = os.path.join(tiny_root, spec.PACKAGE)
    with open(os.path.join(pkg, "configs", "hpcjoin_20m_uniform.json")) as f:
        conf = json.load(f)
    conf.update(name="hpcjoin_half", tuples_per_node=2048)
    with open(os.path.join(pkg, "configs", "hpcjoin_half.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(pkg, "traffic", "few_rewrites.json"), "w") as f:
        json.dump({"loop": "batch", "rewrite_slots": 4}, f)
    with open(os.path.join(pkg, "metrics", "joins_done.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.records))\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "hpcjoin_half", "source": "test",
                             "file": "joinbench/configs/hpcjoin_half.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "half_1c", "config": "hpcjoin_half",
                               "traffic": "few_rewrites", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "joins_done", "unit": "joins",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["half_1c"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    line = _run(tiny_root, "half_1c")
    assert line["correct"], line["checks"]
    assert line["metrics"]["joins_done"]["value"] == line["attempted"]
    assert "tuples_per_s" not in line["metrics"]


class _Device:
    def __init__(self, platform):
        self.platform = platform


def test_refuses_without_a_tpu():
    with pytest.raises(run.NoAccelerator):
        run.require_accelerator([_Device("cpu")], 1)
    with pytest.raises(run.NoAccelerator):
        run.require_accelerator([_Device("tpu")], 4)
    with pytest.raises(run.NoAccelerator):
        run.require_accelerator([], 1)
    run.require_accelerator([_Device("tpu")] * 4, 4)


def test_the_command_on_a_cpu_exits_3_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "joinbench.run", "--workload", "uniform_1c",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == run.NO_ACCELERATOR
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr

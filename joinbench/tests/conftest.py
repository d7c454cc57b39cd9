"""CPU tests of the harness: four virtual CPU devices, tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest joinbench/tests -q
"""

import json
import os
import shutil

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                  ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")

from joinbench import spec  # noqa: E402

#: per-node tuples of every configuration in the tiny tree
TINY_TUPLES = 4096


def make_tiny_root(dst: str) -> str:
    """A checkout-like tree: the real BENCHMARK.json, traffic, loops and
    metrics, with every configuration cut to ``TINY_TUPLES`` per node."""
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), dst)
    for sub in ("configs", "traffic", "loops", "metrics"):
        shutil.copytree(os.path.join(spec.ROOT, spec.PACKAGE, sub),
                        os.path.join(dst, spec.PACKAGE, sub))
    for name in os.listdir(os.path.join(dst, spec.PACKAGE, "configs")):
        path = os.path.join(dst, spec.PACKAGE, "configs", name)
        with open(path) as f:
            conf = json.load(f)
        conf["tuples_per_node"] = TINY_TUPLES
        with open(path, "w") as f:
            json.dump(conf, f)
    return dst


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """The tiny tree; the CPU has no Pallas kernels, so the program's
    ``auto`` falls back off them and the fallback check is left out."""
    from joinbench import window

    monkeypatch.setattr(window, "FALLBACK_COUNTERS", ())
    return make_tiny_root(str(tmp_path))

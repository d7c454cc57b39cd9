"""chip_smoke.py's checks, driven on the virtual CPU mesh at a small size.

The smoke itself refuses the CPU; these tests call its phases directly so
its oracles (unique-key count, NumPy reference, output placement) are
exercised where the tier-1 suite runs.  On the CPU ``auto`` takes the XLA
paths, so the last check, no fallback off the Pallas kernels, is the one
that fails: reaching it means every earlier check passed."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import chip_smoke  # noqa: E402

N = 4096


@pytest.fixture
def small(monkeypatch):
    monkeypatch.setattr(chip_smoke, "TUPLES_PER_NODE", N)


def run_phase(name, nodes, outer_kind, expected):
    report = {}
    with pytest.raises(chip_smoke.SmokeFailure, match="fell back"):
        chip_smoke.batch_phase(name, nodes, outer_kind, expected, report)
    return report[name]


def test_refuses_the_cpu():
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.run(four_chips=False)


@pytest.mark.parametrize("nodes", [1, 4])
def test_batch_phase_checks_output_devices(small, nodes):
    got = run_phase("batch", nodes, "unique", N * nodes)
    assert got["matches"] == N * nodes
    assert got["output_devices"] == nodes


def test_skewed_phase_matches_numpy_reference(small):
    ref = chip_smoke.numpy_join_count(N * 4, chip_smoke.SEED, "zipf")
    got = run_phase("skewed", 4, "zipf", ref)
    assert got["matches"] == ref and got["output_devices"] == 4


def test_batch_phase_fails_on_a_wrong_oracle(small):
    with pytest.raises(chip_smoke.SmokeFailure, match="oracle"):
        chip_smoke.batch_phase("batch", 1, "unique", N + 1, {})

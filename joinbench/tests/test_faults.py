"""With the path under test broken underneath, a run is not correct.

Each fault is planted in the program's answer as the window receives it;
the rest of the run, the reference and the comparison are the harness's
own.  The controls (``joinbench.control``) are checked the same way.
"""

import time

import numpy as np
import pytest

from joinbench import control, reference, run


def _run(root, cell, substitute):
    line, _ = run.run_cell(cell, 424242, 0.3, False, root=root,
                           require_tpu=False, t0=time.perf_counter(),
                           substitute=substitute)
    return line


def _host_result(res, matches):
    return type(res)(matches=int(matches), ok=res.ok,
                     partition_counts=res.partition_counts,
                     diagnostics=res.diagnostics)


def altered(join):
    """An answer altered where it is produced."""
    def broken(r, s, **kw):
        res = join(r, s, **kw)
        return _host_result(res, res.matches + 1)
    return broken


def stale(join):
    """A step that returns its state unchanged: each join answers with the
    first join's result."""
    first = []

    def broken(r, s, **kw):
        if not first:
            first.append(join(r, s, **kw))
        return first[0]
    return broken


def half_batch(join):
    """Half of S left out, the count scaled back up over the rest."""
    def broken(r, s, **kw):
        res = join(r, s, **kw)
        keys_r, keys_s = np.asarray(r.key), np.asarray(s.key)
        half = reference.join_count(keys_r, keys_s[: keys_s.size // 2])
        return _host_result(res, 2 * half)
    return broken


def no_exchange(join):
    """The exchange between chips left out: each chip joins only the
    tuples it already holds."""
    def broken(r, s, **kw):
        res = join(r, s, **kw)
        local = 0
        for rs, ss in zip(r.key.addressable_shards, s.key.addressable_shards):
            local += reference.join_count(np.asarray(rs.data),
                                          np.asarray(ss.data))
        return _host_result(res, local)
    return broken


@pytest.mark.parametrize("fault", [altered, stale, half_batch])
@pytest.mark.parametrize("cell", ["uniform_1c", "uniform_4c"])
def test_batch_faults_are_not_correct(tiny_root, cell, fault):
    line = _run(tiny_root, cell, fault)
    assert not line["correct"]
    assert line["checks"]["count_gap"]["value"] > 0


def test_the_missing_exchange_is_not_correct(tiny_root):
    line = _run(tiny_root, "uniform_4c", no_exchange)
    assert not line["correct"]
    assert line["checks"]["count_gap"]["value"] > 0


@pytest.mark.parametrize("name", ["closed_form", "skip_partition"])
@pytest.mark.parametrize("cell", ["uniform_1c", "uniform_4c"])
def test_controls_are_not_correct(tiny_root, cell, name):
    line = _run(tiny_root, cell, control.substitute(name))
    assert not line["correct"]
    assert line["checks"]["count_gap"]["value"] > 0

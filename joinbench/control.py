"""The controls: the plain reference put in the program's place with one
guarantee of the configuration broken.  Each must come out not correct.

    python3 -m joinbench.control --workload <cell> --seeds <n,n,...> --seconds <s> \
        --controls closed_form,skip_partition

* ``closed_form``: answers ``|S|`` without reading the data, the count a
  join that did not run gives for dense unique keys (breaks: the count is
  of the tuples given);
* ``skip_partition``: the reference's count with S's tuples of radix
  partition 0 (key mod 16 == 0) left out, as a join that drops one
  partition would give (breaks: the count is exact).

Each run is the benchmark's own, with ``HashJoin.join_arrays`` replaced,
all in one process; one JSON line per run.  The benchmark's runs never
use this module.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from joinbench import reference

SKIPPED_PARTITION_BITS = 4


def _count(r_keys: np.ndarray, s_keys: np.ndarray, control: str) -> int:
    if control == "closed_form":
        return int(s_keys.size)
    if control == "skip_partition":
        mask = np.uint32((1 << SKIPPED_PARTITION_BITS) - 1)
        return reference.join_count(r_keys, s_keys[(s_keys & mask) != 0])
    raise ValueError(f"unknown control {control!r}")


class _Result:
    def __init__(self, matches: int):
        self.matches, self.ok = matches, True


def substitute(control: str):
    """A substitute for ``HashJoin.join_arrays``."""
    def wrap(join):
        def controlled(r, s, **_):
            return _Result(_count(np.asarray(r.key), np.asarray(s.key),
                                  control))
        return controlled
    return wrap


def main(argv=None) -> int:
    from joinbench import run

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--controls", default="closed_form,skip_partition")
    args = p.parse_args(argv)
    for name in args.controls.split(","):
        for seed in map(int, args.seeds.split(",")):
            try:
                line, _ = run.run_cell(args.workload, seed, args.seconds, False,
                                    t0=time.perf_counter(),
                                    substitute=substitute(name))
            except run.NoAccelerator as e:
                print(f"[joinbench] {e}", file=sys.stderr)
                return run.NO_ACCELERATOR
            print(json.dumps({"control": name, "workload": args.workload,
                              "seed": seed, "correct": line["correct"],
                              "attempted": line["attempted"],
                              "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

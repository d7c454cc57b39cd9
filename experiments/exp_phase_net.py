"""Net-of-dispatch phase breakdown (VERDICT r4 #7): how much of a split
pipeline's phase columns is real device work vs the per-program host
dispatch round trip (recorded as SDISPATCH by
``Measurements.measure_dispatch_floor``).

    python experiments/exp_phase_net.py PHASES_DIR [FUSED_DIR]

``PHASES_DIR``: a ``--measure-phases`` experiment dir (e.g.
``artifacts/chip_r5/perf_16m_phases``).  Each split phase column runs as its
own program per repeat, so its gross host-clock time includes one dispatch
floor per repeat; the table prints gross, dispatches charged, and net.
With ``FUSED_DIR`` (the same workload's fused run) it also answers the
round-4 question directly: of the bucket path's gross JPROC-vs-fused gap,
how many ms are dispatch accounting vs real extra work.

The reference needs no such correction — its phases share one process and
PAPI brackets them without re-dispatch (Measurements.cpp:90-134); here the
split is the price of host-visible JMPI/JPROC columns (config.measure_phases).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json

from tpu_radix_join.performance.measurements import Measurements

# one host-dispatched program per repeat per column (hash_join._run_split:
# shuffle -> JMPI; bucket LP -> SLOCPREP; probe/BP chain -> JPROC; the
# sizing pre-pass -> JHIST).  BPBUILD/BPPROBE are sub-spans of the bucket
# JPROC chain's two programs.
_PROGRAMS_PER_REPEAT = {
    "JHIST": 1, "JMPI": 1, "SLOCPREP": 1, "JPROC": 1,
    "BPBUILD": 1, "BPPROBE": 1,
}


def _load(d):
    ms = Measurements.load(d)
    if not ms:
        raise SystemExit(f"no .perf files in {d}")
    m = ms[0]
    info_path = os.path.join(d, f"{m.node_id}.info")
    repeat = 1
    if os.path.exists(info_path):
        with open(info_path) as f:
            meta = json.load(f)
        repeat = int(meta.get("config", {}).get("repeat") or 1)
    return m, repeat


def main() -> int:
    if len(sys.argv) < 2:
        print(__doc__)
        return 2
    m, repeat = _load(sys.argv[1])
    floor = m.times_us.get("SDISPATCH", 0.0)
    if not floor:
        print("WARNING: no SDISPATCH tag in this perf dir; net == gross")
    print(f"dir: {sys.argv[1]}  repeats: {repeat}  "
          f"dispatch floor: {floor / 1e3:.1f} ms/program")
    print(f"{'phase':10s} {'gross ms':>10s} {'dispatches':>11s} "
          f"{'net ms':>10s} {'net ms/join':>12s}")
    nets = {}
    for tag, per_rep in _PROGRAMS_PER_REPEAT.items():
        gross = m.times_us.get(tag)
        if gross is None:
            continue
        charged = per_rep * repeat if tag not in ("BPBUILD", "BPPROBE") else 0
        net = max(0.0, gross - charged * floor)
        nets[tag] = net
        print(f"{tag:10s} {gross / 1e3:10.1f} {charged:11d} "
              f"{net / 1e3:10.1f} {net / repeat / 1e3:12.1f}")

    if len(sys.argv) > 2:
        f, f_rep = _load(sys.argv[2])
        f_gross = f.times_us.get("JPROC", 0.0)
        f_floor = f.times_us.get("SDISPATCH", floor)
        f_net = max(0.0, f_gross - f_rep * f_floor)
        split_work = sum(nets.get(t, 0.0)
                         for t in ("JMPI", "SLOCPREP", "JPROC"))
        split_gross = sum(m.times_us.get(t, 0.0)
                          for t in ("JMPI", "SLOCPREP", "JPROC"))
        print(f"\nfused dir: {sys.argv[2]}  JPROC gross "
              f"{f_gross / f_rep / 1e3:.1f} ms/join, net "
              f"{f_net / f_rep / 1e3:.1f} ms/join")
        gap_gross = split_gross / repeat - f_gross / f_rep
        gap_net = split_work / repeat - f_net / f_rep
        if gap_gross > 0:
            print(f"split-vs-fused gap: {gap_gross / 1e3:.1f} ms/join gross, "
                  f"{gap_net / 1e3:.1f} ms/join net of dispatch — "
                  f"{100 * (1 - gap_net / gap_gross):.0f}% of the gap is "
                  f"dispatch accounting")
    return 0


if __name__ == "__main__":
    sys.exit(main())

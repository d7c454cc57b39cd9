"""Subprocess worker for the real multi-process plumbing test
(test_multihost.py::test_two_process_plumbing): one rank of an N-process CPU
world — 4 virtual devices per process, ``jax.distributed`` over a localhost
coordinator (the ``mpirun`` analog, main.cpp:36-48), hierarchical
(dcn=N, ici=4) mesh join, and the rank-0 measurement gather
(Measurements.cpp:548-590).  Not a pytest module (no ``test_`` prefix)."""

import sys


def main(port: str, rank: str, nproc: str) -> None:
    # must precede any JAX backend use, and must NOT itself touch
    # jax.devices() — distributed.initialize comes first
    from tpu_radix_join.utils.platform import force_host_cpu_devices
    force_host_cpu_devices(4, defer_check=True)

    import jax
    from tpu_radix_join.parallel.multihost import initialize, process_info

    nproc = int(nproc)
    assert initialize(coordinator_address=f"127.0.0.1:{port}",
                      num_processes=nproc, process_id=int(rank))
    pid, pcount = process_info()
    assert pcount == nproc, (pid, pcount)
    assert jax.local_device_count() == 4
    assert jax.device_count() == 4 * nproc

    from tpu_radix_join import HashJoin, JoinConfig, Relation
    from tpu_radix_join.performance import Measurements, print_results

    n = jax.device_count()
    # measure_phases: the shuffle (JMPI, with cross-process collectives) and
    # the probe run as separate programs even in a real multi-process world
    cfg = JoinConfig(num_nodes=n, num_hosts=nproc, measure_phases=True)
    size = 1 << 12
    r = Relation(size, n, "unique", seed=1)
    s = Relation(size, n, "unique", seed=9)
    m = Measurements(node_id=pid, num_nodes=nproc)
    res = HashJoin(cfg, measurements=m).join(r, s)
    assert res.ok, res.diagnostics
    assert res.matches == size, res.matches
    assert m.times_us.get("JMPI", 0) > 0 and m.times_us.get("JPROC", 0) > 0

    # materializing pipeline across processes: exercises the single-
    # collective stacked result gather (hash_join.join_materialize_arrays)
    mat = HashJoin(JoinConfig(num_nodes=n, num_hosts=nproc,
                              match_rate_cap=4)).join_materialize(r, s)
    assert mat.ok, mat.diagnostics
    assert mat.matches == size, mat.matches

    # full-range auto routing across processes: the device max-key probe's
    # readback must ride the multi-host gather (_to_host), and the 2-key
    # lexicographic count must stay exact through the cross-process shuffle
    import jax.numpy as jnp
    import numpy as np
    from tpu_radix_join.data.tuples import TupleBatch
    big = ((1 << 31) + 11 * np.arange(size, dtype=np.uint64)).astype(np.uint32)
    shuffled = np.random.default_rng(0).permutation(big)
    shuffled[: size // 4] = 5
    fr = HashJoin(JoinConfig(num_nodes=n, num_hosts=nproc)).join_arrays(
        TupleBatch(key=jnp.asarray(big),
                   rid=jnp.arange(size, dtype=jnp.uint32)),
        TupleBatch(key=jnp.asarray(shuffled),
                   rid=jnp.arange(size, dtype=jnp.uint32)))
    assert fr.ok, fr.diagnostics
    assert fr.matches == size - size // 4, fr.matches

    all_m = m.gather_all()
    assert len(all_m) == nproc, len(all_m)
    assert sorted(mm.node_id for mm in all_m) == list(range(nproc))
    if pid == 0:
        assert all(mm.times_us.get("JTOTAL", 0) > 0 for mm in all_m)
        print_results(all_m)
        print(f"MULTIPROC_OK matches={res.matches} ranks={len(all_m)}")
    print(f"RANK_DONE {pid}")


if __name__ == "__main__":
    main(*sys.argv[1:4])

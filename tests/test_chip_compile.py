"""The main path's Pallas kernels compile for a TPU v5e that is described,
not attached (on-chip-measurement guide, section 2).

Interpret mode never traces the kernels' Mosaic branches, so only these
compiles show here what the chip's compiler refuses: tiles that overrun
scoped VMEM, or a kernel body that takes minutes to compile.  Sizes are the
chip smoke's (20M tuples per side, 40M in the combined sort) and group
counts the ones the main path uses: 32 network partitions, 256 radix
digits, 4 destinations.  The topology is described inside a fixture, never
at import: only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N = 20_000_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *sizes):
    args = [jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=sharding)
            for n in sizes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("num_partitions", [32, 128])
def test_histogram_compiles(one_chip, num_partitions):
    from tpu_radix_join.ops.pallas.histogram import histogram_pallas
    _compile(lambda ids: histogram_pallas(ids, None,
                                          num_partitions=num_partitions),
             one_chip, N)


@pytest.mark.parametrize("num_groups,capacity", [
    (257, None),            # reorder_by_partition at the 256-way cap
    (4, N // 4 + 4096),     # scatter_to_blocks over a 4-chip mesh
])
def test_partition_slots_compiles(one_chip, num_groups, capacity):
    from tpu_radix_join.ops.pallas.partition import partition_slots_pallas
    _compile(lambda ids: partition_slots_pallas(
        ids, num_groups=num_groups, capacity=capacity), one_chip, N)


@pytest.mark.parametrize("shift", [0, 24])
def test_radix_pass_compiles(one_chip, shift):
    from tpu_radix_join.ops.pallas.radix_sort import radix_pass_slots_pallas
    _compile(lambda keys: radix_pass_slots_pallas(keys, shift=shift),
             one_chip, 2 * N)


def test_merge_scan_partitions_compiles(one_chip):
    from tpu_radix_join.ops.pallas.merge_scan import (TILE,
                                                      merge_scan_partitions)
    _compile(lambda packed: merge_scan_partitions(packed, num_partitions=32),
             one_chip, 2 * N // TILE * TILE)


def test_merge_scan_wide_compiles(one_chip):
    from tpu_radix_join.ops.pallas.merge_scan import (
        TILE, merge_scan_partitions_wide)
    n = 2 * N // TILE * TILE
    _compile(lambda lo, hi, tag: merge_scan_partitions_wide(
        lo, hi, tag, num_partitions=32), one_chip, n, n, n)


def test_fused_pipeline_names_its_stages(topo, monkeypatch):
    """The one-node fused join at the real tuple width (uint32 key and rid
    lanes), compiled for the described chip with ``auto`` choosing the
    kernels as a TPU backend does: ``trj.sort`` owns the ``lax.sort`` and
    no radix pass is compiled."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.data.tuples import TupleBatch
    from tpu_radix_join.observability import stages
    from tpu_radix_join.ops.pallas import merge_scan

    monkeypatch.setattr(merge_scan, "pallas_available", lambda: True)
    cfg = JoinConfig(num_nodes=1)
    mesh = Mesh(np.array(topo.devices[:1]), (cfg.mesh_axis,))
    engine = HashJoin(cfg, mesh=mesh)
    n = 1 << 20
    lane = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=NamedSharding(
        mesh, P(cfg.mesh_axis)))
    batch = TupleBatch(key=lane, rid=lane)
    compiled = engine._pipeline_fn(n, n, 8, 8).lower(batch, batch).compile()
    program = stages.program_stages(compiled.as_text())
    assert program.module.startswith("jit_trj_join")
    assert not [name for name, op in program.opcodes.items()
                if op == "custom-call"
                and name.startswith("radix_pass_slots_pallas")]
    sorts = [name for name, op in program.opcodes.items() if op == "sort"]
    assert sorts and {program.stages[s] for s in sorts} == {stages.SORT}
    scans = [name for name, op in program.opcodes.items()
             if op == "custom-call" and name.startswith("merge_scan")]
    assert scans and {program.stages[s] for s in scans} == {
        stages.MERGE_SCAN}

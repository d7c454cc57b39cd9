"""Pallas TPU kernel: partition histogram at stream bandwidth.

The histogram is the pipeline's first hot pass (LocalHistogram.cpp:44-47;
GPU ``histogram_build_L1/L2``, kernels.cu:19-185).  XLA's options are both
bandwidth-catastrophes on TPU for this shape: ``jnp.bincount`` lowers to a
serialized scatter-add (~58 ms at 16M keys measured on v5e) and a broadcast
compare-reduce streams an [n, P] intermediate (~24 ms).  This kernel reads
the ids exactly once and keeps the P accumulators in registers/SMEM:
per tile, P masked reductions on the VPU — ~1 ms at 16M for P = 32.

Grid steps run sequentially on a TPU core, so accumulating into one SMEM
output block across steps needs no atomics (the same freedom the GPU kernels
buy with shared-memory atomics).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_radix_join.ops.pallas.merge_scan import out_struct

ROWS = 2048          # tile = ROWS x 128 uint32 = 1MB VMEM
LANES = 128
MAX_PARTITIONS = 128  # unrolled per-partition reductions; keep the loop sane


def _kernel(pid_ref, w_ref, out_ref, num_partitions: int, weighted: bool):
    """int32 arithmetic throughout: Mosaic does not legalize unsigned
    reductions (see merge_scan.py); counts/weight sums fit int32 by the
    n < 2**31 contract."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        for p in range(num_partitions):
            out_ref[p] = jnp.int32(0)

    pid = pid_ref[:]
    w = w_ref[:].astype(jnp.int32) if weighted else None
    for p in range(num_partitions):
        hit = pid == jnp.uint32(p)
        if weighted:
            contrib = jnp.where(hit, w, jnp.int32(0))
        else:
            contrib = hit.astype(jnp.int32)
        # staged reduction (sublane sum, then lane sum) vectorizes on the
        # VPU where a flat jnp.sum lowers row-serially
        c = jnp.sum(jnp.sum(contrib, axis=0))
        out_ref[p] = out_ref[p] + c


@functools.partial(jax.jit,
                   static_argnames=("num_partitions", "interpret"))
def histogram_pallas(pid: jnp.ndarray,
                     weights: jnp.ndarray | None = None,
                     *, num_partitions: int,
                     interpret: bool = False) -> jnp.ndarray:
    """uint32 [num_partitions] counts (or weight sums) of ``pid`` uint32 [n].

    ``n`` is padded internally to a tile multiple; padding ids are routed to
    ``num_partitions`` (out of range, counted nowhere).  Ids >=
    ``num_partitions`` in the input are likewise ignored — callers route
    invalid slots to an out-of-range id (radix.local_histogram).
    """
    if num_partitions > MAX_PARTITIONS:
        raise ValueError(f"num_partitions {num_partitions} > {MAX_PARTITIONS}")
    n = pid.shape[0]
    tile = ROWS * LANES
    pad = (-n) % tile
    weighted = weights is not None
    if pad:
        pid = jnp.concatenate(
            [pid, jnp.full((pad,), num_partitions, jnp.uint32)])
    w = weights if weighted else pid   # dummy ref keeps one kernel signature
    if weighted and pad:
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)])
    num_tiles = (n + pad) // tile

    kernel = functools.partial(_kernel, num_partitions=num_partitions,
                               weighted=weighted)
    return pl.pallas_call(
        kernel,
        grid=(num_tiles,),
        in_specs=[pl.BlockSpec((ROWS, LANES), lambda t: (t, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((ROWS, LANES), lambda t: (t, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((num_partitions,), lambda t: (0,),
                               memory_space=pltpu.SMEM),
        out_shape=out_struct((num_partitions,), jnp.int32, pid),
        interpret=interpret,
    )(pid.reshape(num_tiles * ROWS, LANES),
      w.astype(jnp.uint32).reshape(num_tiles * ROWS, LANES)
      ).astype(jnp.uint32)


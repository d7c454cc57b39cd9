"""The benchmark's own data generator: dense unique keys from a seed.

A relation of ``G`` tuples holds every key of ``[0, G)`` exactly once, in
the order of a seeded permutation, and its rids are the global positions
(the reference's ``Relation::fillUniqueValues``, ``Relation.cpp:63-73``).
The permutation is a 6-round Feistel network over ``2**(2*half)`` values
with cycle-walking back into ``[0, G)``.

The arithmetic is a copy of ``tpu_radix_join/data/relation.py``
(``feistel_permutation_np``, ``_feistel_keys``, ``unique_keys_device``),
kept here so that the yardstick does not move when the program does.  The
cells generate with the device twin below, whose round keys are an
argument and not a constant, so one compiled program serves every seed;
the reference reads the NumPy twin, which gives the same keys bit for bit.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

FEISTEL_ROUNDS = 6
_ROUND_MUL = 0x9E3779B1


def round_keys(seed: int) -> np.ndarray:
    """The six uint32 round keys of the permutation named by ``seed``."""
    return np.random.default_rng(seed).integers(
        0, 1 << 31, size=FEISTEL_ROUNDS, dtype=np.uint32)


def half_bits(global_size: int) -> int:
    return (max(2, (global_size - 1).bit_length()) + 1) // 2


def _feistel_np(v: np.ndarray, rk: np.ndarray, half: int) -> np.ndarray:
    """One pass of the network over uint32 values below ``2**(2*half)``.
    uint32 wrap-around leaves the low ``half`` bits as in 64-bit
    arithmetic, and only those survive the mask."""
    mask = np.uint32((1 << half) - 1)
    l, r = v >> np.uint32(half), v & mask
    with np.errstate(over="ignore"):
        for k in rk:
            f = ((r * np.uint32(_ROUND_MUL) + np.uint32(k))
                 ^ (r >> np.uint32(7))) & mask
            l, r = r, (l ^ f) & mask
    return (l << np.uint32(half)) | r


_CHUNK = 1 << 20


def _walk_np(v: np.ndarray, rk: np.ndarray, half: int,
             global_size: int) -> None:
    """Cycle-walk, in place, the values of ``v`` that lie past the size."""
    walk = np.flatnonzero(v >= global_size)
    while walk.size:
        v[walk] = _feistel_np(v[walk], rk, half)
        walk = walk[v[walk] >= global_size]


def unique_keys_np(start: int, count: int, global_size: int,
                   seed: int) -> np.ndarray:
    """Keys of global positions ``[start, start+count)``: uint32.  Large
    ranges go in chunks over a few threads; NumPy's loops release the
    interpreter's lock."""
    rk = round_keys(seed)
    half = half_bits(global_size)
    out = np.arange(start, start + count, dtype=np.uint32)

    def chunk(lo: int) -> None:
        v = _feistel_np(out[lo:lo + _CHUNK], rk, half)
        _walk_np(v, rk, half, global_size)
        out[lo:lo + _CHUNK] = v

    lows = range(0, count, _CHUNK)
    if len(lows) == 1:
        chunk(0)
        return out
    threads = min(16, os.cpu_count() or 1, len(lows))
    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        for f in [pool.submit(chunk, lo) for lo in lows]:
            f.result()
    return out


def _feistel_jax(v, rk, half: int):
    import jax.numpy as jnp

    mask = jnp.uint32((1 << half) - 1)
    l, r = v >> half, v & mask
    for i in range(FEISTEL_ROUNDS):
        f = ((r * jnp.uint32(_ROUND_MUL) + rk[i]) ^ (r >> 7)) & mask
        l, r = r, (l ^ f) & mask
    return (l << half) | r


def _unique_keys_jax(idx, rk, global_size: int):
    """Device twin of :func:`unique_keys_np` over the positions ``idx``."""
    import jax
    import jax.numpy as jnp

    half = half_bits(global_size)
    gs = jnp.uint32(global_size)
    v = _feistel_jax(idx, rk, half)
    return jax.lax.while_loop(
        lambda v: jnp.any(v >= gs),
        lambda v: jnp.where(v < gs, v, _feistel_jax(v, rk, half)), v)


def pair_generator(mesh, axis, local: int):
    """A jitted ``gen(rk_r, rk_s) -> (r_key, r_rid, s_key, s_rid)``: both
    relations of ``local`` tuples per device, laid out over ``mesh`` along
    ``axis`` (device ``i`` holds positions ``[i*local, (i+1)*local)``), in
    one device program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    global_size = local * int(mesh.devices.size)

    def body(rk_r, rk_s):
        lo = jax.lax.axis_index(axis).astype(jnp.uint32) * jnp.uint32(local)
        idx = jnp.arange(local, dtype=jnp.uint32) + lo
        return (_unique_keys_jax(idx, rk_r, global_size), idx,
                _unique_keys_jax(idx, rk_s, global_size), idx)

    spec = P(axis)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=(spec,) * 4))


def key_rewriter(mesh, axis):
    """A jitted ``rewrite(key, undo_pos, undo_key, pos, new) -> (key',
    saved)`` that changes the sharded key lane in place (``key`` is
    donated, so only the positions named are touched): it first puts
    ``undo_key`` back at ``undo_pos``, then saves the keys at ``pos`` and
    writes ``new`` there.  Fed the previous call's ``pos`` and ``saved``,
    it leaves the lane as it was made except at this call's positions.
    Every argument holds one equal block per device, with local positions;
    a position past the device's shard drops its write."""
    import jax
    from jax.sharding import PartitionSpec as P

    def body(key, undo_pos, undo_key, pos, new):
        key = key.at[undo_pos].set(undo_key, mode="drop")
        saved = key.at[pos].get(mode="fill", fill_value=0)
        return key.at[pos].set(new, mode="drop"), saved

    spec = P(axis)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 5,
                                 out_specs=(spec, spec)), donate_argnums=0)

"""Relations and seeded data generation with closed-form correctness oracles.

Replaces ``data/Relation.{h,cpp}``:

  * ``fill_unique``  -> ``Relation::fillUniqueValues`` (Relation.cpp:63-73,87-97):
    every key in ``0..global_size-1`` appears exactly once across all shards, so
    the exact expected match count of R ⋈ S (both unique over the same range) is
    ``global_size`` — the oracle the reference checks manually via the
    ``[RESULTS] Tuples:`` line (Measurements.cpp:599-606, main.cpp:94-98).
  * ``fill_modulo``  -> ``Relation::fillModuloValues`` (Relation.cpp:75-85):
    key = rid % modulo, giving closed-form match-rate control.
  * ``fill_zipf``    -> the Zipf ``zFactor`` capability of the GPU data model
    (data/data.hpp:88) exercised by the skew benchmark config.
  * ``Relation::distribute`` (Relation.cpp:99-141): the reference
    pairwise-exchanges random blocks so each rank holds a random slice of the
    key space; here the generator IS globally shuffled (a seeded permutation
    sharded contiguously), so the join pipeline needs no network pre-step.
    For shards that DO arrive with locality, ``parallel/distribute.py``
    provides the explicit all_to_all + local-reshuffle equivalent.

TPU-first scale path: host-side ``np.random.permutation`` caps out around a
few hundred million tuples, so ``fill_unique`` can also run **on device** via a
seeded Feistel-network bijection over the key domain with vectorized
cycle-walking (``feistel_permutation``) — each shard computes its own slice of
the global permutation with no host materialization (SURVEY.md §7.4 item 5).
"""

from __future__ import annotations

import ctypes
import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_radix_join.data.tuples import TupleBatch
from tpu_radix_join.native.build import load as _load_native
from tpu_radix_join.utils.hashing import mix32, mix32_np

_FEISTEL_ROUNDS = 6
_ZIPF_TABLE_MAX = 65536

# 64-bit key spread (key_bits=64): the upper lane is a fixed mix of the
# 32-bit logical key, shared by every relation (NOT seeded) so equal logical
# keys always map to equal wide keys — every closed-form oracle carries over
# unchanged, and the hi lane is a deterministic function of the lo lane, so
# the streaming loader can derive it per chunk.  The mix lands in
# [2**30, 2**31): every generated wide key exceeds 2**62 (a genuinely >32-bit
# domain, like the reference's uint64 keys, Tuple.h:19-20) and the sentinel
# lane (tuples.py: key_hi for wide batches) can never collide with the
# 0xFFFFFFFE/0xFFFFFFFF padding sentinels.  Injectivity is by the lo lane:
# the logical-key generators already guarantee it for the "unique" kind.
_HI_LANE_LOW = np.uint32(0x40000000)
_HI_LANE_MASK = np.uint32(0x3FFFFFFF)


def key_hi_lane_np(key: np.ndarray) -> np.ndarray:
    """uint32 hi lane for wide keys — numpy twin of :func:`key_hi_lane`."""
    return (mix32_np(key) & _HI_LANE_MASK) | _HI_LANE_LOW


@jax.jit
def key_hi_lane(key: jnp.ndarray) -> jnp.ndarray:
    """Device twin of :func:`key_hi_lane_np` (bit-identical)."""
    return ((mix32(key) & jnp.uint32(_HI_LANE_MASK))
            | jnp.uint32(_HI_LANE_LOW))


ZIPF_TAIL_POINTS = 4096
_ZIPF_V_SALT = 0x9E3779B9   # second-draw salt for the tail interpolation


def zipf_tables(theta: float, domain: int):
    """Integer-scaled Zipf(1+theta) sampling tables, shared VERBATIM by the
    numpy, native (datagen.cc), and device samplers — after this point every
    sampler runs identical uint32 arithmetic, so all three are bit-identical
    (including on TPU, which has no f64: the f64 below runs once, on host,
    at table-build time).

      head_cdf: uint32 [min(domain, 65536)] — rank CDF scaled to 2**32
        (head-rank probabilities exact to 2**-32).
      tail_keys: uint32 [4097] — piecewise-linear inverse CDF of the
        continuous power-law tail for ranks past the head table (the same
        tail the r3 f64 sampler inverted exactly; the 4096-segment linear
        approximation error is < one segment width, on ranks whose
        individual probabilities are < 65536**-(1+theta)).
    """
    table = min(domain, _ZIPF_TABLE_MAX)
    ranks = np.arange(1, table + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / np.power(ranks, 1.0 + theta))
    head = cdf[-1]
    t_pow = float(table) ** -theta
    d_pow = float(domain) ** -theta
    tail = (t_pow - d_pow) / theta if domain > table else 0.0
    total = head + tail
    head_cdf = np.minimum(np.floor(cdf / total * 4294967296.0),
                          4294967295.0).astype(np.uint32)
    if domain > table:
        f = (np.arange(ZIPF_TAIL_POINTS + 1, dtype=np.float64)
             / ZIPF_TAIL_POINTS)
        x = np.power(t_pow - f * (t_pow - d_pow), -1.0 / theta)
        tail_keys = np.clip(np.floor(x), table, domain - 1).astype(np.uint32)
    else:
        # unused (no tail); a constant table keeps every sampler shape-stable
        tail_keys = np.full(ZIPF_TAIL_POINTS + 1, table - 1, np.uint32)
    return head_cdf, tail_keys


def zipf_keys_np(start: int, count: int, head_cdf: np.ndarray,
                 tail_keys: np.ndarray, domain: int, seed: int) -> np.ndarray:
    """numpy Zipf sampler twin (of datagen.cc fill_zipf and
    :func:`_zipf_range`): pure uint32 ops on the shared tables.

    Draw: u = mix32(index ^ mix32(seed)); head ranks by upper-bound search
    of the scaled CDF; tail ranks by linear interpolation of ``tail_keys``
    with a second mixed draw supplying (segment, fraction) bits."""
    table = len(head_cdf)
    idx = np.arange(start, start + count, dtype=np.uint32)
    with np.errstate(over="ignore"):
        u = mix32_np(idx ^ mix32_np(np.uint32(seed & 0xFFFFFFFF)))
        key = np.minimum(
            np.searchsorted(head_cdf, u, side="right"),
            table - 1).astype(np.uint32)
        if domain > table:
            v = mix32_np(u ^ np.uint32(_ZIPF_V_SALT))
            j = (v >> np.uint32(20)).astype(np.int64)
            frac = (v >> np.uint32(8)) & np.uint32(0xFFF)
            tk = tail_keys[j]
            d = tail_keys[j + 1] - tk
            interp = ((d >> np.uint32(12)) * frac
                      + (((d & np.uint32(0xFFF)) * frac) >> np.uint32(12)))
            s = tk + interp
            # uint32-wrap clamp (domain may sit within 4093 of 2**32):
            # a wrapped sum is detectable as s < tk — same test on device
            k_tail = np.where(s < tk, np.uint32(domain - 1),
                              np.minimum(s, np.uint32(domain - 1)))
            key = np.where(u >= head_cdf[-1], k_tail, key)
    return key


@functools.partial(jax.jit,
                   static_argnames=("n", "domain", "seed", "wide"))
def _zipf_range(start, n: int, head_cdf: jnp.ndarray, tail_keys: jnp.ndarray,
                domain: int, seed: int, wide: bool):
    """Device Zipf sampler twin — bit-identical to :func:`zipf_keys_np`
    (same tables, same uint32 ops; ``searchsorted`` results are
    method-independent).  ``start`` may be a Python int or traced uint32.
    Returns ``(key[, key_hi], rid)`` like ``_device_range``."""
    rid = jnp.arange(n, dtype=jnp.uint32) + jnp.uint32(start)
    table = head_cdf.shape[0]
    u = mix32(rid ^ mix32(jnp.uint32(seed & 0xFFFFFFFF)))
    # method="sort": one combined sort instead of per-element binary-search
    # gathers — the TPU-friendly lowering (result is exact either way)
    key = jnp.minimum(
        jnp.searchsorted(head_cdf, u, side="right", method="sort"),
        table - 1).astype(jnp.uint32)
    if domain > table:
        v = mix32(u ^ jnp.uint32(_ZIPF_V_SALT))
        j = (v >> jnp.uint32(20)).astype(jnp.int32)
        frac = (v >> jnp.uint32(8)) & jnp.uint32(0xFFF)
        tk = tail_keys[j]
        d = tail_keys[j + 1] - tk
        interp = ((d >> jnp.uint32(12)) * frac
                  + (((d & jnp.uint32(0xFFF)) * frac) >> jnp.uint32(12)))
        s = tk + interp
        # uint32-wrap clamp, twin of the numpy sampler's
        k_tail = jnp.where(s < tk, jnp.uint32(domain - 1),
                           jnp.minimum(s, jnp.uint32(domain - 1)))
        key = jnp.where(u >= head_cdf[table - 1], k_tail, key)
    return (key, key_hi_lane(key), rid) if wide else (key, rid)


def _feistel_round_np(l, r, k, half_bits):
    mask = (1 << half_bits) - 1
    # Simple multiplicative hash round function (xxhash-style constants).
    f = ((r * 0x9E3779B1 + k) ^ (r >> 7)) & mask
    return r, (l ^ f) & mask


def feistel_permutation_np(idx: np.ndarray, domain_bits: int, seed: int) -> np.ndarray:
    """Seeded bijection on [0, 2**domain_bits) — numpy reference implementation."""
    half = (domain_bits + 1) // 2
    mask = (1 << half) - 1
    l = (idx >> half).astype(np.uint64)
    r = (idx & mask).astype(np.uint64)
    keys = np.random.default_rng(seed).integers(0, 1 << 31, size=_FEISTEL_ROUNDS, dtype=np.uint64)
    for i in range(_FEISTEL_ROUNDS):
        l, r = _feistel_round_np(l, r, keys[i], half)
    out = (l << half) | r
    return out & ((1 << (2 * half)) - 1)


def _feistel_keys(seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 1 << 31, size=_FEISTEL_ROUNDS, dtype=np.uint32)


@functools.partial(jax.jit, static_argnames=("domain_bits",))
def _feistel_jax(idx: jnp.ndarray, round_keys: jnp.ndarray, domain_bits: int) -> jnp.ndarray:
    half = (domain_bits + 1) // 2
    mask = jnp.uint32((1 << half) - 1)
    l = (idx >> half).astype(jnp.uint32)
    r = (idx & mask).astype(jnp.uint32)
    for i in range(_FEISTEL_ROUNDS):
        f = ((r * jnp.uint32(0x9E3779B1) + round_keys[i]) ^ (r >> 7)) & mask
        l, r = r, (l ^ f) & mask
    return (l.astype(jnp.uint32) << half) | r


def unique_keys_device(start, count: int, global_size: int, seed: int) -> jnp.ndarray:
    """Shard [start, start+count) of a seeded permutation of [0, global_size),
    computed entirely on device via Feistel + cycle-walking.  ``start`` may be
    a Python int or a traced uint32 scalar (generate_sharded passes the
    per-device ``axis_index``-derived offset).

    Requires domain 2**b >= global_size; indices mapping outside
    [0, global_size) are re-walked until they land inside (expected <= 2 steps
    since the pow2 domain is < 2x the target)."""
    domain_bits = max(2, (global_size - 1).bit_length())
    rk = jnp.asarray(_feistel_keys(seed))
    idx = (jnp.arange(count, dtype=jnp.uint32) + jnp.uint32(start))
    # bind as uint32: a bare Python int >= 2**31 (global_size caps at
    # 2**32 - 1) would overflow JAX's weak-int32 scalar promotion
    gs = jnp.uint32(global_size)

    def body(v):
        out = _feistel_jax(v, rk, domain_bits)
        return jnp.where(v < gs, v, out)  # only walk still-outside values

    def cond(v):
        return jnp.any(v >= gs)

    v = _feistel_jax(idx, rk, domain_bits)
    v = jax.lax.while_loop(cond, body, v)
    return v


def _device_range(start, n: int, global_size: int, seed: int,
                  modulo: Optional[int], wide: bool):
    """Core on-device generator for the global index range
    [start, start+n): ``(key[, key_hi], rid)`` uint32 lanes.  ``modulo=None``
    selects the unique Feistel walk; a value selects dense-rid residues.
    ``start`` may be a Python int or a traced uint32 scalar.  The single
    source of truth for on-device generation — ``Relation.shard``,
    ``Relation.generate_sharded`` and ``streaming.stream_chunks_device`` all
    call it, so the bit-identity contract with the host generators lives in
    one place."""
    rid = jnp.arange(n, dtype=jnp.uint32) + jnp.uint32(start)
    if modulo is None:
        key = unique_keys_device(start, n, global_size, seed)
    else:
        key = rid % jnp.uint32(modulo)
    return (key, key_hi_lane(key), rid) if wide else (key, rid)


# NOTE: every distinct (n, global_size, seed, modulo, wide) tuple — i.e.
# every relation spec and every ragged tail-chunk size — compiles its own
# XLA program (the Feistel round-key table is baked in at trace time, which
# is what makes the device twin bit-identical to the host path).  Expected
# and acceptable: sweeps over many tiny relation specs pay a per-spec
# compile; production-shape runs reuse one or two entries (ADVICE r3).
_device_range_jit = jax.jit(
    _device_range,
    static_argnames=("n", "global_size", "seed", "modulo", "wide"))


def device_range(start, n: int, global_size: int, seed: int,
                 modulo: Optional[int], wide: bool):
    """Jitted :func:`_device_range`.  ``start`` is coerced to uint32 before
    the jit boundary: a bare Python int above 2**31 - 1 (reachable — node
    offsets run up to ``global_size``, capped at 2**32 - 1) would otherwise
    overflow JAX's default int32 argument parsing."""
    return _device_range_jit(np.uint32(start), n, global_size, seed,
                             modulo, wide)


@functools.lru_cache(maxsize=32)
def _sharded_program(mesh, axes, kind: str, local: int, global_size: int,
                     seed: int, wide: bool, modulo: Optional[int],
                     zipf_theta: Optional[float], key_domain: Optional[int]):
    """The jitted on-device generator of one relation spec over ``mesh``
    (:meth:`Relation.generate_sharded`), kept per spec: a spec generated
    again, as a session's placed-relation LRU does after an eviction, is
    not traced again."""
    from jax.sharding import PartitionSpec

    if kind == "zipf":
        head_cdf, tail_keys = zipf_tables(zipf_theta, key_domain)
        c_dev = jnp.asarray(head_cdf)
        tk_dev = jnp.asarray(tail_keys)

    def gen():
        i = jax.lax.axis_index(axes)   # flat rank over the (maybe
        lo = i.astype(jnp.uint32) * jnp.uint32(local)   # hierarchical) mesh
        if kind == "zipf":
            return _zipf_range(lo, local, c_dev, tk_dev, key_domain, seed,
                               wide)
        return _device_range(lo, local, global_size, seed, modulo, wide)

    spec = PartitionSpec(axes)
    out_specs = (spec, spec, spec) if wide else (spec, spec)
    return jax.jit(jax.shard_map(
        gen, mesh=mesh, in_specs=(), out_specs=out_specs))


class Relation:
    """A logical relation: a global keyspace spec + per-shard generators.

    The reference's ``Relation`` owns one rank's tuple shard backed by ``Pool``
    memory (Relation.cpp:26-37); here the object is a *spec* and ``shard_np`` /
    ``shard`` materialize a given node's slice (host numpy / device jax).
    ``rid`` is the global tuple index, as in the reference where rid is dense
    (Relation.cpp:63-73).
    """

    def __init__(
        self,
        global_size: int,
        num_nodes: int = 1,
        kind: str = "unique",
        seed: int = 1234,
        key_bits: int = 32,
        modulo: Optional[int] = None,
        zipf_theta: Optional[float] = None,
        key_domain: Optional[int] = None,
    ):
        if global_size % num_nodes != 0:
            raise ValueError("global_size must divide evenly across nodes")
        if kind not in ("unique", "modulo", "zipf"):
            raise ValueError(f"unknown relation kind {kind!r}")
        if kind == "modulo" and not modulo:
            raise ValueError("modulo kind requires modulo=")
        if kind == "zipf" and (zipf_theta is None or zipf_theta <= 0):
            raise ValueError("zipf kind requires zipf_theta= > 0")
        if key_bits not in (32, 64):
            raise ValueError("key_bits must be 32 or 64")
        # Deliberate contract: benchmark relations stay within the merge-probe
        # key range so every probe discipline accepts them interchangeably.
        if key_bits == 32 and global_size > (1 << 31) - 2:
            raise ValueError(
                "32-bit keys cap global_size at 2**31 - 2 (31-bit merge-count "
                "packing + sentinel headroom); use key_bits=64 beyond that")
        if key_bits == 64 and global_size > (1 << 32) - 1:
            raise ValueError(
                "global_size caps at 2**32 - 1 (dense uint32 rids)")
        self.global_size = int(global_size)
        self.num_nodes = int(num_nodes)
        self.kind = kind
        self.seed = int(seed)
        self.key_bits = int(key_bits)
        self.modulo = modulo
        self.zipf_theta = zipf_theta
        self.key_domain = int(key_domain) if key_domain else self.global_size
        self._zipf_cache = None   # (head_cdf, tail_keys), built on first use

    def _zipf_tables_cached(self):
        if self._zipf_cache is None:
            self._zipf_cache = zipf_tables(self.zipf_theta, self.key_domain)
        return self._zipf_cache

    @property
    def local_size(self) -> int:
        return self.global_size // self.num_nodes

    def key_bound(self) -> int:
        """Exclusive static upper bound on generated key values — the input
        to the engine's automatic key-range routing (config.key_range
        "auto": bounds <= 2**31-2 keep the packed 31-bit count path).
        unique: a permutation of [0, global_size); modulo: residues below
        min(modulo, global_size); zipf: draws over [0, key_domain).  Wide
        (64-bit) relations report 2**64: they never use the 32-bit packing."""
        if self.key_bits == 64:
            return 1 << 64
        if self.kind == "unique":
            return self.global_size
        if self.kind == "modulo":
            return min(self.modulo, self.global_size)
        return self.key_domain

    # ------------------------------------------------------------------ host
    def fill_np(self, start: int, count: int, num_threads: int = 0,
                out_key: Optional[np.ndarray] = None,
                out_rid: Optional[np.ndarray] = None,
                ) -> Tuple[np.ndarray, np.ndarray]:
        """(keys, rids) for the global index range [start, start+count).

        Uses the native multithreaded generators (native/datagen.cc) when the
        toolchain produced the shared library; the numpy fallbacks are
        bit-identical (same Feistel rounds / same Zipf table + hashing).
        ``out_key``/``out_rid`` (uint32 [count], e.g. memory-pool views from
        ``memory.Pool.get_array``) are filled in place when given — the
        streaming loader reuses two such buffer pairs for arbitrarily large
        relations (data/streaming.py)."""
        lo, n = int(start), int(count)
        lib = _load_native()
        if num_threads <= 0:
            num_threads = min(16, os.cpu_count() or 1)

        def buf(out):
            if out is None:
                return np.empty(n, dtype=np.uint32)
            if (out.shape != (n,) or out.dtype != np.uint32
                    or not out.flags.c_contiguous):
                raise ValueError(f"out buffer must be contiguous uint32 [{n}]")
            return out

        key, rid = buf(out_key), buf(out_rid)
        if lib is not None:
            kp = key.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))
            lib.fill_rids(rid.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                          lo, n, num_threads)
        else:
            rid[:] = np.arange(lo, lo + n, dtype=np.uint32)

        if self.kind == "unique":
            domain_bits = max(2, (self.global_size - 1).bit_length())
            if lib is not None:
                rk = np.ascontiguousarray(_feistel_keys(self.seed))
                lib.fill_unique(
                    kp, lo, n, self.global_size, (domain_bits + 1) // 2,
                    rk.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                    num_threads)
                return key, rid
            idx = np.arange(lo, lo + n, dtype=np.uint64)
            k = feistel_permutation_np(idx, domain_bits, self.seed)
            while (k >= self.global_size).any():
                out = k >= self.global_size
                k[out] = feistel_permutation_np(k[out], domain_bits, self.seed)
            key[:] = k.astype(np.uint32)
            return key, rid

        if self.kind == "modulo":
            if lib is not None:
                lib.fill_modulo(kp, lo, n, self.modulo, num_threads)
                return key, rid
            key[:] = rid % np.uint32(self.modulo)
            return key, rid

        # zipf: skewed draw over [0, key_domain) — integer tables shared
        # verbatim with the native and device samplers (zipf_tables)
        head_cdf, tail_keys = self._zipf_tables_cached()
        if lib is not None:
            p_u32 = ctypes.POINTER(ctypes.c_uint32)
            lib.fill_zipf(
                kp, lo, n, head_cdf.ctypes.data_as(p_u32), len(head_cdf),
                tail_keys.ctypes.data_as(p_u32), self.key_domain,
                self.seed, num_threads)
            return key, rid
        key[:] = zipf_keys_np(lo, n, head_cdf, tail_keys, self.key_domain,
                              self.seed)
        return key, rid

    def shard_np(self, node: int, num_threads: int = 0) -> Tuple[np.ndarray, ...]:
        """One node's shard as numpy uint32 arrays.

        Contract (the driver's ``HashJoin._place`` consumes this): a 2-tuple
        ``(keys, rids)`` when ``key_bits == 32``; a 3-tuple
        ``(keys_lo, keys_hi, rids)`` when ``key_bits == 64`` — the wide analog
        of the reference's uint64 keys (Tuple.h:19-20) as two uint32 lanes.
        """
        key, rid = self.fill_np(node * self.local_size, self.local_size,
                                num_threads)
        if self.key_bits == 64:
            return key, key_hi_lane_np(key), rid
        return key, rid

    # ---------------------------------------------------------------- device
    def zipf_range_device(self, start, n: int):
        """Device Zipf lanes for the global index range [start, start+n)
        (``(key[, key_hi], rid)``), bit-identical to the host sampler —
        the tables are host-built once (cached) and shipped as uint32
        constants; all sampling arithmetic runs on device."""
        head_cdf, tail_keys = self._zipf_tables_cached()
        return _zipf_range(np.uint32(start), n, jnp.asarray(head_cdf),
                           jnp.asarray(tail_keys), self.key_domain,
                           self.seed, self.key_bits == 64)

    def shard(self, node: int) -> TupleBatch:
        """One node's shard as a device TupleBatch — every kind generates on
        device (unique/modulo: Feistel walk / residues; zipf since r4: the
        integer-table sampler)."""
        lo = node * self.local_size
        if self.kind == "zipf":
            out = self.zipf_range_device(lo, self.local_size)
        else:
            out = device_range(
                lo, self.local_size, self.global_size, self.seed,
                self.modulo if self.kind == "modulo" else None,
                self.key_bits == 64)
        if self.key_bits == 64:
            key, hi, rid = out
            return TupleBatch(key=key, rid=rid, key_hi=hi)
        key, rid = out
        return TupleBatch(key=key, rid=rid, key_hi=None)

    def generate_sharded(self, mesh, axes) -> Optional[TupleBatch]:
        """The whole relation generated **on device**, sharded over ``mesh``
        along ``axes`` (device i holds node i's slice) — no host
        materialization and no host->device transfer (SURVEY.md §7.4 item 5:
        "generate sharded on-device rather than host-side like
        Relation::fillUniqueValues").

        Bit-identical to the ``shard_np`` host path for every kind
        ("unique": same Feistel rounds + cycle walk; "modulo": same
        dense-rid residues; "zipf" since r4: the integer-table sampler —
        host-built uint32 tables, device uint32 arithmetic).  Returns
        ``None`` only for kinds without a device generator (none today;
        the hook remains for future kinds)."""
        if self.kind not in ("unique", "modulo", "zipf"):
            return None
        n = int(np.prod(mesh.devices.shape))
        if n != self.num_nodes:
            raise ValueError(
                f"mesh has {n} devices, relation expects {self.num_nodes}")
        wide = self.key_bits == 64
        zipf = self.kind == "zipf"
        out = _sharded_program(
            mesh, axes, self.kind, self.local_size, self.global_size,
            self.seed, wide, self.modulo if self.kind == "modulo" else None,
            self.zipf_theta if zipf else None,
            self.key_domain if zipf else None)()
        if wide:
            key, hi, rid = out
            return TupleBatch(key=key, rid=rid, key_hi=hi)
        key, rid = out
        return TupleBatch(key=key, rid=rid, key_hi=None)

    # ---------------------------------------------------------------- oracle
    def expected_matches(self, outer: "Relation") -> Optional[int]:
        """Closed-form expected |self ⋈ outer| where derivable (SURVEY.md §4.1).

        unique ⋈ unique over the same range -> global_size (the reference's
        oracle, main.cpp:95-98); unique ⋈ modulo/zipf with outer key domain
        covered by the unique range -> outer.global_size.  Returns None when no
        closed form applies (caller should fall back to a host join)."""
        if self.kind != "unique":
            return None
        if outer.kind == "unique" and outer.global_size == self.global_size:
            return self.global_size
        if outer.kind == "modulo" and outer.modulo <= self.global_size:
            return outer.global_size
        if outer.kind == "zipf" and outer.key_domain <= self.global_size:
            return outer.global_size
        return None


def host_join_count(r_keys: np.ndarray, s_keys: np.ndarray) -> int:
    """O((n+m) log) host oracle join count for tests without a closed form."""
    r_sorted = np.sort(r_keys)
    lo = np.searchsorted(r_sorted, s_keys, side="left")
    hi = np.searchsorted(r_sorted, s_keys, side="right")
    return int((hi - lo).sum())

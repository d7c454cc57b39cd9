"""Native C++ runtime: pool allocator + multithreaded generators.

Skipped when no toolchain is available (the package falls back to numpy)."""

import os

import numpy as np
import pytest

from tpu_radix_join.native.build import load

lib = load()
pytestmark = pytest.mark.skipif(lib is None, reason="no native toolchain")


def test_pool_bump_and_reset():
    from tpu_radix_join.memory import Pool
    pool = Pool(1 << 16)
    assert pool.native
    a = pool.get_array((100,), np.uint32)
    b = pool.get_array((100,), np.uint32)
    a[:] = 1
    b[:] = 2
    assert a.sum() == 100 and b.sum() == 200   # disjoint regions
    used = pool.used()
    assert used >= 800 and used % 64 == 0       # 64B-aligned bumps
    # overflow fallback past capacity must still hand out valid memory
    big = pool.get_array((1 << 15,), np.uint32)
    big[:] = 3
    assert big.sum() == 3 * (1 << 15)
    pool.reset()
    assert pool.used() == 0
    pool.close()


def test_native_unique_matches_numpy():
    from tpu_radix_join.data.relation import Relation, feistel_permutation_np
    rel = Relation(1 << 12, 4, "unique", seed=17)
    for node in (0, 3):
        native_keys, _ = rel.shard_np(node)             # native path
        lo = node * rel.local_size
        idx = np.arange(lo, lo + rel.local_size, dtype=np.uint64)
        bits = max(2, (rel.global_size - 1).bit_length())
        ref = feistel_permutation_np(idx, bits, rel.seed)
        while (ref >= rel.global_size).any():
            out = ref >= rel.global_size
            ref[out] = feistel_permutation_np(ref[out], bits, rel.seed)
        np.testing.assert_array_equal(native_keys, ref.astype(np.uint32))


def test_native_unique_is_permutation():
    from tpu_radix_join.data.relation import Relation
    rel = Relation(3000, 3, "unique", seed=5)
    keys = np.concatenate([rel.shard_np(i)[0] for i in range(3)])
    np.testing.assert_array_equal(np.sort(keys), np.arange(3000))


def test_native_zipf_matches_numpy_twin():
    from tpu_radix_join.data.relation import (Relation, zipf_keys_np,
                                              zipf_tables)
    rel = Relation(4096, 2, "zipf", zipf_theta=0.75, key_domain=1024, seed=9)
    for node in (0, 1):
        native_keys, _ = rel.shard_np(node)
        head_cdf, tail_keys = zipf_tables(0.75, 1024)
        twin = zipf_keys_np(node * rel.local_size, rel.local_size, head_cdf,
                            tail_keys, 1024, 9)
        np.testing.assert_array_equal(native_keys, twin)
    # skew sanity: rank 0 must dominate
    keys = np.concatenate([rel.shard_np(i)[0] for i in range(2)])
    assert (keys == 0).mean() > 0.2


def test_native_zipf_covers_large_domains():
    # domains beyond the 65536-rank table must still be reachable via the
    # interpolated power-law tail (and match the numpy twin bit-for-bit)
    from tpu_radix_join.data.relation import (Relation, zipf_keys_np,
                                              zipf_tables)
    domain = 1 << 20
    rel = Relation(1 << 16, 1, "zipf", zipf_theta=0.75, key_domain=domain, seed=4)
    keys, _ = rel.shard_np(0)
    assert keys.max() > 65536          # tail ranks appear
    assert keys.max() < domain
    head_cdf, tail_keys = zipf_tables(0.75, domain)
    twin = zipf_keys_np(0, 1 << 16, head_cdf, tail_keys, domain, 4)
    np.testing.assert_array_equal(keys, twin)


def test_pool_survives_gc():
    # arrays returned by a temporary Pool must keep the region alive
    import gc
    from tpu_radix_join.memory import Pool
    arr = Pool(1 << 16).get_array((1000,), np.uint32)
    gc.collect()
    arr[:] = 0xABCD
    assert int(arr.sum()) == 1000 * 0xABCD


def test_native_modulo():
    from tpu_radix_join.data.relation import Relation
    rel = Relation(1 << 10, 2, "modulo", modulo=17)
    k, rid = rel.shard_np(1)
    np.testing.assert_array_equal(k, rid % 17)


def test_library_is_keyed_by_source_hash(tmp_path, monkeypatch):
    """A stale library in the checkout never loads: the built library's
    name is a hash of the sources and flags, so editing a source (or
    dropping a foreign .so beside them) selects a different file."""
    import shutil

    from tpu_radix_join.native import build

    for src in build._SOURCES:
        shutil.copy(os.path.join(build._DIR, src), tmp_path / src)
    monkeypatch.setattr(build, "_DIR", str(tmp_path))
    first = build.library_path()
    assert os.path.dirname(first) == str(tmp_path)
    (tmp_path / os.path.basename(first)).write_bytes(b"stale")
    with open(tmp_path / "datagen.cc", "a") as f:
        f.write("\n// edited\n")
    edited = build.library_path()
    assert edited != first
    monkeypatch.setattr(build, "_FLAGS", build._FLAGS + ["-g"])
    assert build.library_path() not in (first, edited)

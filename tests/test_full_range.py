"""Full-range uint32 key discipline (VERDICT r4 weak #4 / next #8): the
31-bit packed fast path's ceiling must not silently reject — or worse,
silently undercount — any sub-sentinel uint32 workload.  Covers the
full-range lexicographic count op, the config routing (narrow/full/auto),
the Relation static bound, and the out-of-core chunked path."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_radix_join import HashJoin, JoinConfig, Relation
from tpu_radix_join.data.tuples import TupleBatch
from tpu_radix_join.ops.merge_count import (
    MAX_MERGE_KEY,
    merge_count_per_partition,
    merge_count_per_partition_full,
)


def _oracle_counts(r_keys, s_keys, fanout_bits):
    """Per-partition duplicate-aware match counts via numpy."""
    num_p = 1 << fanout_bits
    out = np.zeros(num_p, dtype=np.uint64)
    common, r_idx, s_idx = np.intersect1d(
        *(np.unique(k) for k in (r_keys, s_keys)), return_indices=True)
    rc = dict(zip(*np.unique(r_keys, return_counts=True)))
    sc = dict(zip(*np.unique(s_keys, return_counts=True)))
    for k in common:
        out[int(k) & (num_p - 1)] += int(rc[k]) * int(sc[k])
    return out


@pytest.mark.parametrize("fanout", [0, 3, 5])
def test_merge_full_oracle_full_range(fanout):
    rng = np.random.default_rng(7 + fanout)
    # keys straddling 2**31 with duplicates, right up to the sentinel floor
    r = rng.integers(0, 0xFFFFFFFE, size=4096, dtype=np.uint32)
    s = rng.integers(0, 0xFFFFFFFE, size=4096, dtype=np.uint32)
    dup = rng.integers(1 << 31, 0xFFFFFFFD, size=64, dtype=np.uint32)
    r = np.concatenate([r, np.repeat(dup, 3)])
    s = np.concatenate([s, np.repeat(dup, 2)])
    counts, maxw = merge_count_per_partition_full(
        jnp.asarray(r), jnp.asarray(s), fanout, return_max_weight=True)
    got = np.asarray(counts).astype(np.uint64)
    want = _oracle_counts(r, s, fanout)
    np.testing.assert_array_equal(got, want)
    # max single-outer-tuple weight == max inner multiplicity among matched keys
    assert int(np.asarray(maxw)) == 3


def test_merge_full_matches_packed_on_low_keys():
    rng = np.random.default_rng(3)
    r = jnp.asarray(rng.integers(0, 1 << 20, size=8192, dtype=np.uint32))
    s = jnp.asarray(rng.integers(0, 1 << 20, size=8192, dtype=np.uint32))
    full, mw_full = merge_count_per_partition_full(
        r, s, 5, return_max_weight=True)
    packed, mw_packed = merge_count_per_partition(
        r, s, 5, impl="xla", return_max_weight=True)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(packed))
    assert int(np.asarray(mw_full)) == int(np.asarray(mw_packed))


def _big_key_batches(n, num_nodes, seed=0):
    """TupleBatch pair with keys above 2**31 and a known match count."""
    rng = np.random.default_rng(seed)
    base = (1 << 31) + 17
    r_keys = base + np.arange(n, dtype=np.uint64) * 7      # distinct
    s_keys = rng.permutation(r_keys)
    s_keys[: n // 2] = 3                                   # half never match
    mk = lambda k: TupleBatch(key=jnp.asarray(k.astype(np.uint32)),
                              rid=jnp.arange(n, dtype=jnp.uint32))
    return mk(r_keys), mk(s_keys), n - n // 2


@pytest.mark.parametrize("nodes,phases", [(1, False), (8, False), (8, True)])
def test_join_arrays_full_routes_and_counts(nodes, phases):
    """key_range='full' joins keys >= 2**31 exactly, on the n==1
    specialization, the fused distributed path, and the split-phase path."""
    r, s, want = _big_key_batches(1 << 12, nodes)
    cfg = JoinConfig(num_nodes=nodes, key_range="full",
                     measure_phases=phases)
    res = HashJoin(cfg).join_arrays(r, s)
    assert res.ok, res.diagnostics
    assert res.matches == want


def test_join_arrays_auto_probes_and_routes():
    """Default key_range='auto' on raw arrays detects big keys via the
    device max probe and still produces the exact count."""
    r, s, want = _big_key_batches(1 << 12, 8, seed=1)
    res = HashJoin(JoinConfig(num_nodes=8)).join_arrays(r, s)
    assert res.ok, res.diagnostics
    assert res.matches == want


def test_join_arrays_narrow_flags_big_keys():
    """Explicit key_range='narrow' keeps the packed fast path and flags —
    never silently drops — out-of-range keys."""
    r, s, _ = _big_key_batches(1 << 10, 1)
    res = HashJoin(JoinConfig(num_nodes=1, key_range="narrow")).join_arrays(r, s)
    assert not res.ok
    assert res.diagnostics["key_contract_violations"] > 0


def test_join_relation_static_bound_routes():
    """join(Relation, Relation) resolves 'auto' statically: a zipf outer
    drawn over a > 2**31 key domain rides the full-range discipline (no
    contract flag), oracle-checked against the host-generated shards."""
    n, nodes = 1 << 12, 8
    inner = Relation(n, nodes, "unique", seed=2)
    outer = Relation(n, nodes, "zipf", seed=5, zipf_theta=0.75,
                     key_domain=(1 << 32) - 64)
    assert outer.key_bound() == (1 << 32) - 64
    assert inner.key_bound() == n
    res = HashJoin(JoinConfig(num_nodes=nodes)).join(inner, outer)
    assert res.ok, res.diagnostics
    o_keys = outer.fill_np(0, n)[0]
    want = int(np.sum(o_keys < n))   # inner is a permutation of [0, n)
    assert res.matches == want


def test_chunked_join_count_full_range():
    """Out-of-core chunked count must route big keys to the full-range
    discipline instead of silently zeroing them on the pack-pads."""
    from tpu_radix_join.ops.chunked import chunked_join_count
    rng = np.random.default_rng(11)
    n = 1 << 12
    r_keys = ((1 << 31) + np.arange(n, dtype=np.uint64) * 5).astype(np.uint32)
    s_keys = rng.permutation(r_keys)
    s_keys[: n // 4] = 1
    mk = lambda k: TupleBatch(key=jnp.asarray(k),
                              rid=jnp.arange(n, dtype=jnp.uint32))
    got = chunked_join_count(mk(r_keys), mk(s_keys), slab_size=1 << 10)
    assert got == n - n // 4


def test_chunked_join_count_sentinel_keys_raise():
    from tpu_radix_join.ops.chunked import chunked_join_count
    n = 256
    keys = np.arange(n, dtype=np.uint32)
    keys[3] = 0xFFFFFFFE
    mk = lambda k: TupleBatch(key=jnp.asarray(k),
                              rid=jnp.arange(n, dtype=jnp.uint32))
    with pytest.raises(ValueError, match="sentinel"):
        chunked_join_count(mk(keys), mk(np.arange(n, dtype=np.uint32)),
                           slab_size=128)


def test_key_range_config_validation():
    with pytest.raises(ValueError, match="key range"):
        JoinConfig(key_range="wat")
    with pytest.raises(ValueError, match="wide"):
        JoinConfig(key_bits=64, key_range="full")


def test_cli_key_range_flag(capsys):
    from tpu_radix_join.main import main
    rc = main(["--tuples-per-node", "1024", "--nodes", "4",
               "--key-range", "full"])
    assert rc == 0
    assert "[RESULTS] Tuples: 4096" in capsys.readouterr().out


@pytest.mark.parametrize("fanout", [0, 1, 5])
def test_merge_full_pallas_matches_xla(fanout):
    """The fused Pallas realization (wide kernel with a zero hi lane) must
    agree exactly with the XLA scan fallback on full-range keys."""
    rng = np.random.default_rng(21 + fanout)
    r = rng.integers(0, 0xFFFFFFFE, size=5000, dtype=np.uint32)
    s = rng.integers(0, 0xFFFFFFFE, size=5000, dtype=np.uint32)
    dup = rng.integers(1 << 31, 0xFFFFFFFD, size=32, dtype=np.uint32)
    r = jnp.asarray(np.concatenate([r, np.repeat(dup, 4)]))
    s = jnp.asarray(np.concatenate([s, np.repeat(dup, 2)]))
    cx, mx = merge_count_per_partition_full(
        r, s, fanout, impl="xla", return_max_weight=True)
    cp, mp = merge_count_per_partition_full(
        r, s, fanout, impl="pallas_interpret", return_max_weight=True)
    np.testing.assert_array_equal(np.asarray(cx), np.asarray(cp))
    assert int(np.asarray(mx)) == int(np.asarray(mp))


def test_full_range_composes_with_skew_split():
    """The skew split (replicated hot inner riding the local probe) must
    stay exact when the probe runs the full-range discipline."""
    n = 1 << 12
    half = n // 2
    big = lambda a: ((1 << 31) + a.astype(np.uint64) * 3).astype(np.uint32)
    r = TupleBatch(key=jnp.asarray(big(np.arange(n))),
                   rid=jnp.arange(n, dtype=jnp.uint32))
    hot = np.concatenate([np.full(half, big(np.array([3]))[0], np.uint32),
                          big(np.arange(half))])
    s = TupleBatch(key=jnp.asarray(hot), rid=jnp.arange(n, dtype=jnp.uint32))
    cfg = JoinConfig(num_nodes=8, skew_threshold=4.0, allocation_factor=4.0,
                     key_range="full")
    res = HashJoin(cfg).join_arrays(r, s)
    assert res.ok, res.diagnostics
    # key 2**31+9 (= big(3)) matches half+1 outer tuples; the other half-1
    # distinct outer keys match once each
    assert res.matches == (half + 1) + (half - 1)


def test_merge_full_inside_shard_map():
    """The full-range count must trace inside a shard_map body — the chip
    pipeline's exact shape (hash_join._local_process).  The portable XLA
    realization is asserted here.  The interpret-mode merge-scan kernel
    cannot run under shard_map: the HLO interpreter evaluates its top-level
    ops on the tile's varying mesh axes and rejects mixing them with
    constants (asserted below so a JAX upgrade that lifts it is noticed;
    the workaround is to read the tile inside the ``pl.when`` phase
    bodies).  Compiled
    Pallas traces its kernel outside the mesh."""
    import jax
    from jax.sharding import PartitionSpec as P
    from tpu_radix_join.parallel.mesh import make_mesh

    n_dev, n = 4, 4096
    rng = np.random.default_rng(2)
    r = ((1 << 31) + 3 * np.arange(n, dtype=np.uint64)).astype(np.uint32)
    s = rng.permutation(r)
    mesh = make_mesh(n_dev)

    def body(impl):
        def run(rk, sk):
            c, mw = merge_count_per_partition_full(
                rk, sk, 3, impl=impl, return_max_weight=True)
            return jax.lax.psum(c, "nodes"), jax.lax.pmax(mw, "nodes")
        return jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(P("nodes"), P("nodes")),
            out_specs=(P(), P())))

    counts, mw = body("xla")(jnp.asarray(r), jnp.asarray(s))
    # keys are globally distinct and both sides shard identically, so each
    # shard-local count sees only its own slice's permuted intersection;
    # the psum total is exactly the number of keys co-resident on a shard
    total = int(np.asarray(counts).astype(np.uint64).sum())
    shard = n // n_dev
    want = sum(
        len(np.intersect1d(r[i * shard:(i + 1) * shard],
                           s[i * shard:(i + 1) * shard]))
        for i in range(n_dev))
    assert total == want, (total, want)
    assert int(np.asarray(mw)) == 1
    with pytest.raises(ValueError, match="varying manual axes"):
        body("pallas_interpret")(jnp.asarray(r), jnp.asarray(s))


def test_key_boundary_values_exact():
    """Boundary keys around the packing cap and the sentinel floor: every
    sub-sentinel value joins exactly on the full path; the narrow path is
    exact up to MAX_MERGE_KEY inclusive."""
    from tpu_radix_join.ops.merge_count import merge_count_chunks

    edge = np.array([0, 1, MAX_MERGE_KEY - 1, MAX_MERGE_KEY,
                     MAX_MERGE_KEY + 1, 1 << 31, 0xFFFFFFFC, 0xFFFFFFFD],
                    dtype=np.uint32)
    pad = np.arange(100, 100 + 120, dtype=np.uint32)     # fill to size
    keys = np.concatenate([edge, pad])
    # full path: every key matches itself exactly once, in its partition
    c = merge_count_per_partition_full(
        jnp.asarray(keys), jnp.asarray(keys), 3)
    np.testing.assert_array_equal(
        np.asarray(c).astype(np.uint64), _oracle_counts(keys, keys, 3))
    # narrow path on the in-contract prefix only
    ok = keys[keys <= MAX_MERGE_KEY]
    cn = merge_count_chunks(jnp.asarray(ok), jnp.asarray(ok))
    assert int(np.asarray(cn).astype(np.uint64).sum()) == ok.size

"""The copied generator and the plain reference agree with the program's
generator and join count at a small size."""

import numpy as np
import pytest

from joinbench import datagen, reference
from joinbench.loops import batch


@pytest.mark.parametrize("size", [5, 4096, 20_000, 3 << 20])
def test_numpy_twin_matches_the_programs_generator(size):
    from tpu_radix_join.data.relation import Relation

    for seed in (0, 1234, 2**31 + 11):
        ours = datagen.unique_keys_np(0, size, size, seed)
        rel = Relation(size, 1, "unique", seed=seed)
        assert np.array_equal(ours, rel.fill_np(0, size)[0])
        assert np.array_equal(np.sort(ours), np.arange(size))


def test_device_twin_matches_the_numpy_twin_on_four_devices():
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), ("nodes",))
    local = 3000
    rk, rr, sk, sr = datagen.pair_generator(mesh, "nodes", local)(
        datagen.round_keys(7), datagen.round_keys(8))
    n = 4 * local
    assert np.array_equal(np.asarray(rk), datagen.unique_keys_np(0, n, n, 7))
    assert np.array_equal(np.asarray(sk), datagen.unique_keys_np(0, n, n, 8))
    assert np.array_equal(np.asarray(rr), np.arange(n))
    assert np.array_equal(np.asarray(sr), np.arange(n))


def test_key_rewriter_rewrites_each_devices_own_positions_in_place():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("nodes",))
    lanes = NamedSharding(mesh, P("nodes"))
    made = np.arange(40, dtype=np.uint32)
    keys = jax.device_put(made, lanes)
    rewrite = datagen.key_rewriter(mesh, "nodes")
    undo = (jax.device_put(np.full(8, 10, np.int32), lanes),
            jax.device_put(np.zeros(8, np.uint32), lanes))
    pos = np.array([0, 9, 10, 10, 3, 10, 10, 10], np.int32)   # 2 slots each
    new = np.array([100, 101, 0, 0, 102, 0, 0, 0], np.uint32)
    out, saved = rewrite(keys, *undo, jax.device_put(pos, lanes),
                         jax.device_put(new, lanes))
    assert keys.is_deleted()                 # donated: written in place
    expect = made.copy()
    expect[[0, 9, 23]] = [100, 101, 102]
    assert np.array_equal(np.asarray(out), expect)
    assert np.array_equal(np.asarray(saved)[[0, 1, 4]], [0, 9, 23])
    # the next rewrite puts the last one's keys back first
    pos2 = np.array([9, 10, 5, 10, 10, 10, 10, 10], np.int32)
    new2 = np.array([200, 0, 201, 0, 0, 0, 0, 0], np.uint32)
    out2, _ = rewrite(out, jax.device_put(pos, lanes), saved,
                      jax.device_put(pos2, lanes), jax.device_put(new2, lanes))
    expect = made.copy()
    expect[[9, 15]] = [200, 201]
    assert np.array_equal(np.asarray(out2), expect)


def test_rewritten_join_counts_like_a_plain_join():
    n, local, slots = 8192, 2048, 16
    ref = reference.RewrittenJoin(n, 3, 4)
    assert ref.total == n
    for i in range(5):
        _, _, gpos, gnew = batch.rewrites(99, i, 4, local, slots, n)
        s = ref.s.copy()
        s[gpos] = gnew
        assert ref.count(gpos, gnew) == reference.join_count(ref.r_keys, s)
        assert ref.count(gpos, gnew) == n - gpos.size
        assert gpos.size == np.unique(gpos).size


def test_rewrites_come_from_the_seed():
    a = batch.rewrites(2**31 + 5, 3, 4, 1000, 64, 4000)
    b = batch.rewrites(2**31 + 5, 3, 4, 1000, 64, 4000)
    c = batch.rewrites(2**31 + 5, 4, 4, 1000, 64, 4000)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[2], c[2])


def test_reference_count_matches_the_programs_join():
    import jax
    from jax.sharding import Mesh

    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.data.tuples import TupleBatch

    engine = HashJoin(JoinConfig(num_nodes=4, sort_impl="xla",
                                 partition_impl="sort"),
                      mesh=Mesh(np.array(jax.devices()[:4]), ("nodes",)))
    local, slots = 2048, 32
    n = 4 * local
    rk, rr, sk, sr = datagen.pair_generator(engine.mesh, "nodes", local)(
        datagen.round_keys(5), datagen.round_keys(6))
    pos, new, gpos, gnew = batch.rewrites(1, 0, 4, local, slots, n)
    s_key, _ = datagen.key_rewriter(engine.mesh, "nodes")(
        sk, np.full_like(pos, local), np.zeros_like(new), pos, new)
    res = engine.join_arrays(TupleBatch(rk, rr), TupleBatch(s_key, sr))
    assert res.ok
    assert res.matches == reference.RewrittenJoin(n, 5, 6).count(gpos, gnew)

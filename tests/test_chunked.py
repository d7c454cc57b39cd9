import jax.numpy as jnp
import numpy as np

from tpu_radix_join.data.relation import Relation, host_join_count
from tpu_radix_join.data.tuples import TupleBatch
from tpu_radix_join.ops.chunked import chunked_join_count, chunked_join_grid


def _batch(keys):
    keys = np.asarray(keys, np.uint32)
    return TupleBatch(key=jnp.asarray(keys),
                      rid=jnp.arange(len(keys), dtype=jnp.uint32))


def test_chunked_matches_monolithic():
    rng = np.random.default_rng(0)
    r = rng.integers(0, 4096, 1 << 14).astype(np.uint32)
    s = rng.integers(0, 4096, 1 << 14).astype(np.uint32)
    expect = host_join_count(r, s)
    for slab in (1 << 14, 1 << 12, 1 << 10):
        assert chunked_join_count(_batch(r), _batch(s), slab) == expect


def test_chunked_grid_both_sides():
    rng = np.random.default_rng(1)
    r = rng.integers(0, 1024, 1 << 12).astype(np.uint32)
    s = rng.integers(0, 1024, 1 << 12).astype(np.uint32)
    expect = host_join_count(r, s)
    r_chunks = [_batch(r[:1 << 11]), _batch(r[1 << 11:])]
    s_chunks = [_batch(s[:1 << 11]), _batch(s[1 << 11:])]
    assert chunked_join_grid(r_chunks, s_chunks, 1 << 10) == expect


def test_chunked_indivisible_slab_padded():
    # ragged outer sizes are sentinel-padded to a slab multiple, not rejected
    assert chunked_join_count(_batch([1, 2, 3]), _batch([1, 2, 3]), 2) == 3


def test_chunked_unique_oracle():
    rel_r = Relation(1 << 14, 1, "unique", seed=1)
    rel_s = Relation(1 << 14, 1, "unique", seed=2)
    r, s = rel_r.shard(0), rel_s.shard(0)
    assert chunked_join_count(r, s, 1 << 11) == 1 << 14


def test_grid_checkpoint_resume(tmp_path):
    """Interrupt after two chunk pairs; the rerun must skip completed work
    and land on the exact total (SURVEY.md §5.4 — resume is new capability,
    the reference is single-shot)."""
    import json

    rel_r = Relation(1 << 12, 1, "unique", seed=1)
    rel_s = Relation(1 << 12, 1, "unique", seed=2)
    r, s = rel_r.shard(0), rel_s.shard(0)

    def halves(batch):
        n = batch.key.shape[0] // 2
        return [TupleBatch(key=batch.key[:n], rid=batch.rid[:n]),
                TupleBatch(key=batch.key[n:], rid=batch.rid[n:])]

    ckpt = str(tmp_path / "grid.ckpt")
    calls = {"n": 0}
    real = chunked_join_count

    def failing(rb, sb, slab, **kw):
        calls["n"] += 1
        if calls["n"] > 2:
            raise RuntimeError("simulated preemption")
        return real(rb, sb, slab, **kw)

    import tpu_radix_join.ops.chunked as C
    C.chunked_join_count, orig = failing, C.chunked_join_count
    try:
        import pytest
        with pytest.raises(RuntimeError):
            chunked_join_grid(halves(r), halves(s), 1 << 10,
                              checkpoint_path=ckpt, checkpoint_tag="t")
    finally:
        C.chunked_join_count = orig
    state = json.load(open(ckpt))
    assert not state["done"] and state["total"] > 0

    total = chunked_join_grid(halves(r), halves(s), 1 << 10,
                              checkpoint_path=ckpt, checkpoint_tag="t")
    assert total == 1 << 12
    assert json.load(open(ckpt))["done"]
    # a third run short-circuits on the done marker (same fingerprint)
    assert chunked_join_grid(halves(r), halves(s), 1 << 10,
                             checkpoint_path=ckpt, checkpoint_tag="t") == total
    # different geometry, tag, or an untagged call must refuse the file
    import pytest
    with pytest.raises(ValueError):
        chunked_join_grid(halves(r), halves(s), 1 << 9,
                          checkpoint_path=ckpt, checkpoint_tag="t")
    with pytest.raises(ValueError):
        chunked_join_grid(halves(r), halves(s), 1 << 10,
                          checkpoint_path=ckpt, checkpoint_tag="other-data")
    with pytest.raises(ValueError):
        chunked_join_grid(halves(r), halves(s), 1 << 10,
                          checkpoint_path=ckpt)
    # corrupt checkpoint: restart from zero, exact result
    with open(ckpt, "w") as f:
        f.write("{trunca")
    assert chunked_join_grid(halves(r), halves(s), 1 << 10,
                             checkpoint_path=ckpt, checkpoint_tag="t") == total


def test_grid_join_wide_streamed_chunks():
    """A Relation(key_bits=64) stream through chunked_join_grid counts on
    the full (hi, lo) key — the streaming/out-of-core path must not quietly
    drop the hi lane the way round 2's driver path did."""
    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.data.streaming import stream_chunks
    from tpu_radix_join.ops.chunked import chunked_join_count, chunked_join_grid

    r_rel = Relation(1 << 11, 1, "unique", seed=41, key_bits=64)
    s_rel = Relation(1 << 11, 1, "modulo", modulo=1 << 10, seed=42,
                     key_bits=64)
    total = chunked_join_grid(
        list(stream_chunks(r_rel, 0, 600)),
        lambda: stream_chunks(s_rel, 0, 700),
        slab_size=256)
    # oracle: every modulo key < 2**10 matches exactly one unique key
    assert total == s_rel.global_size

    # mixed widths must raise, not truncate
    import pytest
    narrow = Relation(1 << 10, 1, "unique", seed=1)
    wide = Relation(1 << 10, 1, "unique", seed=2, key_bits=64)
    nb = next(iter(stream_chunks(narrow, 0, 1 << 10)))
    wb = next(iter(stream_chunks(wide, 0, 1 << 10)))
    with pytest.raises(ValueError, match="mixed key widths"):
        chunked_join_count(wb, nb, 128)

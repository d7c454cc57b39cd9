"""Exchange wire codec + staged all_to_all (ISSUE 7).

Three layers under test, bottom-up:

  * the bit-packed wire format itself (data/tuples.py pack/unpack_blocks):
    property round-trip over key width x fanout x bound tightness, with
    pad-slot garbage that must not leak and sentinels that must survive
    bit-exactly;
  * the staged exchange (parallel/window.py block_all_to_all): every mode
    must deliver the byte-identical ordering of the fused route, on the
    flat and the hierarchical mesh;
  * the engine + planner wiring: an 8-node join under ``exchange_codec=
    pack, exchange_stages=4`` is oracle-exact with verification on, the
    regress gate pins the footprint tags lower-is-better, ``--plan``
    surfaces the codec choice, and schema-v1 profiles load through the
    ici_bytes_per_s shim.
"""

import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpu_radix_join.data import tuples as T
from tpu_radix_join.parallel import window as W
from tpu_radix_join.parallel.mesh import make_hierarchical_mesh, make_mesh

N = 8


# ------------------------------------------------------------ codec core

def _contract_blocks(rng, spec, key_space, nb):
    """Blocks honoring the scatter_to_blocks_grouped contract — each block's
    valid tuples contiguous at the front and sorted by partition id — with
    every pad slot filled with all-ones garbage the codec must mask out.
    Returns (lanes dict, group_counts, per-tuple expected arrays)."""
    cap = spec.capacity
    mask = spec.num_sub - 1
    # one full block, one empty block, the rest partial
    counts = [cap, 0] + list(rng.integers(1, cap, nb - 2))
    keys = np.full(nb * cap, (1 << 64) - 1, np.uint64)
    rids = np.full(nb * cap, 0xFFFFFFFF, np.uint64)
    group_counts = np.zeros((nb, spec.num_sub), np.uint32)
    for b, cnt in enumerate(counts):
        k = rng.integers(0, key_space, cnt, dtype=np.uint64)
        if cnt:
            k[0] = key_space - 1          # exercise the exact bound edge
        pid = (k & np.uint64(mask)).astype(np.uint32)
        order = np.argsort(pid, kind="stable")
        keys[b * cap:b * cap + cnt] = k[order]
        rids[b * cap:b * cap + cnt] = rng.integers(
            0, 1 << 20, cnt, dtype=np.uint64)
        group_counts[b] = np.bincount(pid, minlength=spec.num_sub)
    return keys, rids, np.asarray(counts), group_counts


def _roundtrip(spec, keys, rids, group_counts, side):
    lo = jnp.asarray(keys & np.uint64(0xFFFFFFFF), jnp.uint32)
    hi = (jnp.asarray(keys >> np.uint64(32), jnp.uint32)
          if spec.wide else None)
    blocks = T.TupleBatch(key=lo, rid=jnp.asarray(rids, jnp.uint32),
                          key_hi=hi)
    words = T.pack_blocks(spec, blocks, jnp.asarray(group_counts))
    assert words.shape == (group_counts.shape[0] * spec.block_words,)
    return T.unpack_blocks(spec, words, side)


@pytest.mark.parametrize("wide", [False, True], ids=["key32", "key64"])
@pytest.mark.parametrize("fanout_bits", [0, 1, 2, 3, 4, 5])
@pytest.mark.parametrize("bound", ["tight", "loose", "none"])
def test_codec_roundtrip_bit_exact(wide, fanout_bits, bound):
    rng = np.random.default_rng(fanout_bits * 7 + (13 if wide else 0))
    nb, cap = 4, 64
    key_space = (1 << 44) if wide else (1 << 20)
    # spec bounds: tight hugs the data, loose wastes headroom, none falls
    # back to full lane width — all must stay exact
    key_bound = {"tight": key_space, "loose": key_space << 7,
                 "none": None}[bound]
    rid_bound = {"tight": 1 << 20, "loose": 1 << 29, "none": None}[bound]
    spec = T.make_wire_spec(cap, fanout_bits, wide=wide,
                            key_bound=key_bound, rid_bound=rid_bound)
    if bound == "tight":
        # the tight spec actually shrinks the tuple vs the no-bound layout
        free = T.make_wire_spec(cap, fanout_bits, wide=wide)
        assert spec.tuple_bits < free.tuple_bits
    keys, rids, counts, gc = _contract_blocks(rng, spec, key_space, nb)
    got, got_counts = _roundtrip(spec, keys, rids, gc, "inner")
    np.testing.assert_array_equal(np.asarray(got_counts), counts)
    valid = (np.arange(nb * cap) % cap) < counts[np.arange(nb * cap) // cap]
    got_key = np.asarray(got.key).astype(np.uint64)
    if wide:
        got_key |= np.asarray(got.key_hi).astype(np.uint64) << np.uint64(32)
    np.testing.assert_array_equal(got_key[valid], keys[valid])
    np.testing.assert_array_equal(
        np.asarray(got.rid)[valid].astype(np.uint64), rids[valid])
    # pad slots are the side's exact sentinels — garbage never leaks
    assert (np.asarray(got.key)[~valid] == T.R_PAD_KEY).all()
    assert (np.asarray(got.rid)[~valid] == np.asarray(T.PAD_RID)).all()
    assert not np.asarray(T.valid_mask(got, "inner"))[~valid].any()


def test_codec_outer_side_sentinels():
    spec = T.make_wire_spec(16, 2, key_bound=1 << 10, rid_bound=1 << 10)
    rng = np.random.default_rng(3)
    keys, rids, counts, gc = _contract_blocks(rng, spec, 1 << 10, 3)
    got, _ = _roundtrip(spec, keys, rids, gc, "outer")
    valid = (np.arange(3 * 16) % 16) < counts[np.arange(3 * 16) // 16]
    assert (np.asarray(got.key)[~valid] == T.S_PAD_KEY).all()
    assert not np.asarray(T.valid_mask(got, "outer"))[~valid].any()


def test_wire_spec_geometry_and_errors():
    spec = T.make_wire_spec(1024, 5, key_bound=1 << 20, rid_bound=1 << 20)
    # 15 kept key bits + 20 rid bits = 35-bit tuples, 32 header words
    assert spec.tuple_bits == 35 and spec.header_words == 32
    assert spec.bytes_per_tuple < 8.0
    assert spec.bytes_per_block == 4 * spec.block_words
    with pytest.raises(ValueError, match="capacity"):
        T.make_wire_spec(0, 5)
    with pytest.raises(ValueError, match="fanout_bits"):
        T.make_wire_spec(8, 32)
    with pytest.raises(ValueError, match="key_bound"):
        T.make_wire_spec(8, 0, key_bound=0)
    with pytest.raises(ValueError, match="multiple"):
        T.unpack_blocks(spec, jnp.zeros((spec.block_words + 1,),
                                        jnp.uint32), "inner")


# ------------------------------------------------- staged exchange parity

BLOCK = 96          # not divisible by 5: exercises uneven column groups


def _all_to_all(x, mode, hierarchical=False):
    if hierarchical:
        mesh = make_hierarchical_mesh(2, N)
        spec, axis = P(("dcn", "ici")), ("dcn", "ici")
    else:
        mesh = make_mesh(N)
        spec, axis = P("nodes"), "nodes"
    fn = jax.shard_map(
        lambda v: W.block_all_to_all(v, N, BLOCK, axis, mode=mode),
        mesh=mesh, in_specs=spec, out_specs=spec)
    return np.asarray(jax.jit(fn)(x))


def test_staged_orderings_match_fused():
    x = jnp.arange(N * N * BLOCK, dtype=jnp.uint32)
    fused = _all_to_all(x, "fused")
    for mode in ("staged:2", "staged:4", "staged:5", "auto", 3):
        np.testing.assert_array_equal(_all_to_all(x, mode), fused, str(mode))


def test_hierarchical_route_matches_flat_fused_and_staged():
    x = jnp.arange(N * N * BLOCK, dtype=jnp.uint32)
    fused = _all_to_all(x, "fused")
    np.testing.assert_array_equal(_all_to_all(x, "fused", True), fused)
    np.testing.assert_array_equal(_all_to_all(x, "staged:3", True), fused)


def test_parse_exchange_mode():
    assert W.parse_exchange_mode("fused", 1 << 20) == 1
    assert W.parse_exchange_mode("staged:4", 1 << 20) == 4
    assert W.parse_exchange_mode("auto", 4096) == 4
    assert W.parse_exchange_mode("auto", 4095) == 1
    assert W.parse_exchange_mode(6, 1 << 20) == 6
    assert W.parse_exchange_mode("staged:100", 3) == 3   # clamps to block
    with pytest.raises(ValueError, match="must be an integer"):
        W.parse_exchange_mode("staged:x", 8)
    with pytest.raises(ValueError, match="exchange mode"):
        W.parse_exchange_mode("bogus", 8)
    with pytest.raises(ValueError, match=">= 1"):
        W.parse_exchange_mode(0, 8)


def test_block_all_to_all_validates_length():
    with pytest.raises(ValueError, match="leading axis"):
        W.block_all_to_all(jnp.zeros((10,), jnp.uint32), N, 2, "nodes")


def test_hierarchical_validates_mesh_factorization():
    mesh = make_hierarchical_mesh(2, N)
    fn = jax.shard_map(
        lambda v: W.hierarchical_block_all_to_all(v, 6, 2, "dcn", "ici"),
        mesh=mesh, in_specs=P(("dcn", "ici")), out_specs=P(("dcn", "ici")))
    with pytest.raises(ValueError, match="factor the node count"):
        jax.jit(fn)(jnp.zeros((N * 12,), jnp.uint32))


def test_window_rejects_unresolved_auto_codec():
    with pytest.raises(ValueError, match="resolved by the caller"):
        W.Window(N, 64, "nodes", "inner", codec="auto")


# ------------------------------------------------ packed window exchange

def test_window_pack_matches_off_exchange():
    """Same tuples through the raw and the packed+staged window: identical
    per-sender receive counts, zero overflow, identical per-block tuple
    multisets (the packed route pid-sorts within blocks, so ordering inside
    one block may legally differ)."""
    mesh = make_mesh(N)
    cap, per = 256, 1000
    rng = np.random.default_rng(9)
    key = jnp.asarray(rng.integers(0, 1 << 18, N * per, dtype=np.uint64),
                      jnp.uint32)
    rid = jnp.arange(N * per, dtype=jnp.uint32)

    def run(codec, mode):
        def body(k, r):
            pid = k & jnp.uint32(7)
            win = W.Window(N, cap, "nodes", "inner", codec=codec, mode=mode,
                           fanout_bits=3, key_bound=1 << 18,
                           rid_bound=N * per)
            res = win.exchange(T.TupleBatch(key=k, rid=r), pid, pid=pid)
            return (res.batch.key, res.batch.rid, res.recv_counts,
                    res.send_overflow[None])
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P("nodes"), P("nodes")),
                           out_specs=(P("nodes"),) * 4)
        k, r, cnt, ovf = jax.jit(fn)(key, rid)
        return (np.asarray(k), np.asarray(r), np.asarray(cnt),
                np.asarray(ovf))

    k_off, r_off, c_off, o_off = run("off", "fused")
    k_pk, r_pk, c_pk, o_pk = run("pack", "staged:4")
    assert not o_off.any() and not o_pk.any()
    np.testing.assert_array_equal(c_pk, c_off)
    cnt = c_off.reshape(-1)
    for b in range(N * N):      # per-(receiver, sender) block multisets
        lo, hi = b * cap, b * cap + cnt[b]
        off_pairs = sorted(zip(k_off[lo:hi], r_off[lo:hi]))
        pk_pairs = sorted(zip(k_pk[lo:hi], r_pk[lo:hi]))
        assert off_pairs == pk_pairs, f"block {b}"
        # pad slots carry the inner sentinel on both routes
        assert (k_pk[b * cap + cnt[b]:(b + 1) * cap] == T.R_PAD_KEY).all()


# ------------------------------------------------------ engine + planner

def test_join_pack_staged_is_oracle_exact():
    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.data.relation import Relation
    from tpu_radix_join.performance import Measurements
    from tpu_radix_join.performance.measurements import (PACKRATIO,
                                                         WIREBYTES, XSTAGES)

    inner = Relation(N << 10, N, "unique", seed=41)
    outer = Relation(N << 10, N, "unique", seed=42)
    expected = inner.expected_matches(outer)
    m = Measurements(node_id=0, num_nodes=N)
    eng = HashJoin(JoinConfig(num_nodes=N, exchange_codec="pack",
                              exchange_stages=4, verify="check"),
                   measurements=m)
    res = eng.join(inner, outer)
    assert res.ok and res.matches == expected
    xs = m.meta["exchange_plan"]
    assert xs["codec"] == "pack" and xs["stages"] == 4
    assert xs["bytes_per_tuple"] < 8.0
    assert xs["peak_exchange_bytes"] < xs["raw_bytes"]
    assert m.counters[WIREBYTES] == xs["wire_bytes"]
    assert m.counters[PACKRATIO] < 100
    assert m.counters[XSTAGES] == 4


def test_config_validates_exchange_knobs():
    from tpu_radix_join import JoinConfig
    with pytest.raises(ValueError, match="exchange codec"):
        JoinConfig(exchange_codec="bogus")
    with pytest.raises(ValueError, match="exchange_stages"):
        JoinConfig(exchange_stages=-1)


def test_regress_pins_exchange_tags_lower_is_better():
    from tpu_radix_join.observability.regress import higher_is_better
    assert not higher_is_better("WIREBYTES")
    assert not higher_is_better("peak_exchange_bytes")
    assert not higher_is_better("peak_exchange_bytes_raw")
    assert not higher_is_better("bytes_per_tuple")
    assert higher_is_better("value")            # the reduction headline
    assert higher_is_better("peak_speedup")


def test_planner_prices_codec_and_explains_choice():
    from tpu_radix_join import JoinConfig
    from tpu_radix_join.planner import (Workload, explain_table, load_profile,
                                        plan_join)
    from tpu_radix_join.planner.cost_model import (incore_resident_bytes,
                                                   plan_exchange)

    # pack's two extra HBM passes must cost more than the wire bytes they
    # save for the loose case to stay raw: pin a slow HBM against the
    # modeled ICI, whatever the committed profile measured
    prof = load_profile().replace_constants(
        hbm_gbps={"value": 50.0, "source": "test: slow HBM"})
    loose = Workload(r_tuples=N << 17, s_tuples=N << 17, key_bound=N << 17,
                     num_nodes=N)
    assert plan_exchange(prof, loose).codec == "off"
    # near the residency envelope the packed wire buys the headroom back
    tight = Workload(r_tuples=N << 17, s_tuples=N << 17, key_bound=N << 17,
                     num_nodes=N, memory_budget_bytes=int(
                         incore_resident_bytes(loose) * 1.5))
    xp = plan_exchange(prof, tight)
    assert xp.codec == "pack" and xp.bytes_per_tuple < 8.0
    plan, costs = plan_join(prof, tight)
    assert plan.exchange_codec == "pack" and plan.exchange_stages >= 1
    assert "exchange: codec=pack" in explain_table(costs, plan)
    # the plan's knobs bind directly onto JoinConfig
    cfg = JoinConfig(num_nodes=N, **plan.config_kwargs())
    assert cfg.exchange_codec == "pack"


def test_plan_schema_v4_and_older_back_compat():
    from tpu_radix_join.planner.plan import PLAN_SCHEMA_VERSION, JoinPlan
    assert PLAN_SCHEMA_VERSION == 6
    doc = JoinPlan(engine="incore", exchange_codec="pack",
                   exchange_stages=4,
                   predicted_terms={"shuffle": 1.5}).to_dict()
    again = JoinPlan.from_dict(doc)
    assert again.exchange_codec == "pack" and again.exchange_stages == 4
    assert again.predicted_terms == {"shuffle": 1.5}
    # a v5 file carries the retired sort_impl: ignored on load
    v5 = {**doc, "sort_impl": "xla", "schema_version": 5}
    assert JoinPlan.from_dict(v5).to_dict() == {**doc, "schema_version": 5}
    # a v3 file (pre-audit) has no predicted_terms: empty table on load
    v3 = {k: v for k, v in doc.items() if k != "predicted_terms"}
    v3["schema_version"] = 3
    assert JoinPlan.from_dict(v3).predicted_terms == {}
    assert JoinPlan.from_dict(v3).exchange_codec == "pack"
    old = {k: v for k, v in v3.items()
           if k not in ("exchange_codec", "exchange_stages")}
    old["schema_version"] = 2
    assert JoinPlan.from_dict(old).exchange_codec == "off"
    assert JoinPlan.from_dict(old).exchange_stages == 1


def test_profile_v1_shim_derives_ici_bytes_per_s(tmp_path):
    from tpu_radix_join.planner import load_profile
    prof = load_profile()
    doc = copy.deepcopy(prof.to_dict())
    doc["schema_version"] = 1
    del doc["constants"]["ici_bytes_per_s"]
    path = tmp_path / "v1.json"
    path.write_text(json.dumps(doc))
    old = load_profile(str(path))
    assert old.value("ici_bytes_per_s") == prof.value("ici_gbps") * 1e9
    assert old.source("ici_bytes_per_s").startswith("shim:derived")
    # a v2 file with the constant present loads untouched
    assert prof.source("ici_bytes_per_s").startswith("not measured")

"""Back-to-back batch joins through ``HashJoin.join_arrays``.

R and S are made once in set-up from the seed, on the device, laid out
over the engine's mesh.  Before each join a few of S's keys are rewritten
to keys that R does not hold, a different set for every join, drawn from
the seed; so every join's count differs and only a join that reads all
of S can give it.  The rewrite is made in place and puts back the keys
the previous one changed, so it touches a few thousand keys, not the
lane.  The window runs joins back to back, one at a time.

Traffic parameters:

* ``rewrite_slots``: the most keys rewritten per device before one join;
  each device rewrites between 1 and this many, at distinct positions.
"""

from __future__ import annotations

import time

import numpy as np

from joinbench import datagen, reference
from joinbench import window as win
from joinbench.spec import LoopResult


def rewrites(seed: int, join: int, chips: int, local: int, slots: int,
             global_size: int):
    """The key rewrite before join number ``join``: ``(pos, new)`` as one
    block of ``slots`` per device (local positions; unused slots point
    past the shard), and the same as global positions and keys."""
    rng = np.random.default_rng(win.seed_sequence(seed) + [1, join + 1])
    pos = np.full((chips, slots), local, np.int32)
    new = np.zeros((chips, slots), np.uint32)
    gpos, gnew = [], []
    for d, k in enumerate(rng.integers(1, slots + 1, size=chips)):
        p = rng.choice(local, size=int(k), replace=False)
        keys = global_size + d * slots + np.arange(k, dtype=np.int64)
        pos[d, :k], new[d, :k] = p, keys
        gpos.append(d * local + p)
        gnew.append(keys)
    return (pos.ravel(), new.ravel(), np.concatenate(gpos),
            np.concatenate(gnew))


def run(ctx) -> LoopResult:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_radix_join import HashJoin, JoinConfig
    from tpu_radix_join.data.tuples import TupleBatch
    from tpu_radix_join.performance.measurements import (JHIST, JPROC,
                                                         Measurements)

    conf, chips = ctx.config, ctx.chips
    if int(conf["nodes"]) != chips:
        raise ValueError(f"{conf['name']} runs {conf['nodes']} nodes, the "
                         f"cell has {chips} chips")
    local = int(conf["tuples_per_node"])
    global_size = local * chips
    slots = int(ctx.traffic["rewrite_slots"])
    meas = Measurements()
    engine = HashJoin(JoinConfig(num_nodes=chips,
                                 **conf.get("join_config", {})),
                      measurements=meas)
    join = engine.join_arrays
    if ctx.substitute is not None:
        join = ctx.substitute(join)
    mesh, axis = engine.mesh, engine.config.mesh_axes
    lanes = NamedSharding(mesh, P(axis))
    seed_r, seed_s = (int(s) for s in np.random.default_rng(
        win.seed_sequence(ctx.seed)).integers(0, 1 << 62, size=2))
    with win.span("place"):
        r_key, r_rid, s_key, s_rid = jax.block_until_ready(
            datagen.pair_generator(mesh, axis, local)(
                datagen.round_keys(seed_r), datagen.round_keys(seed_s)))
    r = TupleBatch(key=r_key, rid=r_rid)
    rewrite = datagen.key_rewriter(mesh, axis)
    # what the last rewrite changed: nothing yet (every position is past
    # its shard, so the first undo drops)
    undo = [jax.device_put(np.full(chips * slots, local, np.int32), lanes),
            jax.device_put(np.zeros(chips * slots, np.uint32), lanes)]

    def one(i: int) -> dict:
        nonlocal s_key
        pos, new, gpos, gnew = rewrites(ctx.seed, i, chips, local, slots,
                                        global_size)
        jproc0 = meas.times_us.get(JPROC, 0.0)
        jhist0 = meas.times_us.get(JHIST, 0.0)
        meas.meta.pop("output_devices", None)
        t0 = time.perf_counter()
        with win.span("rewrite"):
            pos = jax.device_put(pos, lanes)
            s_key, saved = rewrite(s_key, *undo, pos,
                                   jax.device_put(new, lanes))
            undo[:] = pos, saved
        with win.span("join"):
            res = join(r, TupleBatch(key=s_key, rid=s_rid))
        t1 = time.perf_counter()
        return {"t0": t0, "t1": t1, "tuples": 2 * global_size,
                "matches": int(res.matches), "ok": bool(res.ok),
                "devices": len(meas.meta.get("output_devices", ())),
                "jproc_ms": (meas.times_us.get(JPROC, 0.0) - jproc0) / 1e3,
                "jhist_ms": (meas.times_us.get(JHIST, 0.0) - jhist0) / 1e3,
                "positions": gpos, "new_keys": gnew}

    warm = one(-1)
    setup_s = time.perf_counter() - ctx.t0
    records = []
    with win.Window(ctx) as w:
        while w.open():
            records.append(one(len(records)))
        w.close()
    peak = win.memory_peak_bytes(list(mesh.devices.flat))
    fallbacks = win.fallbacks(meas)
    del r, r_key, r_rid, s_key, s_rid, undo, engine, join

    ref = reference.RewrittenJoin(global_size, seed_r, seed_s)
    for rec in [warm] + records:
        rec["expected"] = ref.count(rec.pop("positions"),
                                    rec.pop("new_keys"))

    def wrong(rec) -> bool:
        return (rec["matches"] != rec["expected"] or not rec["ok"]
                or rec["devices"] < chips)

    checked = [warm] + records
    checks = {
        "count_gap": (max(abs(x["matches"] - x["expected"])
                          for x in checked), 0),
        "not_ok": (sum(not x["ok"] for x in checked), 0),
        "devices_short": (max(chips - x["devices"] for x in checked), 0),
        "fallbacks": (fallbacks, 0),
    }
    return LoopResult(
        setup_s=setup_s, window_s=w.seconds, records=records, checks=checks,
        attempted=len(records), failed=sum(map(wrong, records)),
        memory_peak_bytes=peak, window_programs=w.programs)

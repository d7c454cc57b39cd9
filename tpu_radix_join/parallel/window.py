"""The communication data plane: fixed-capacity blocks + ICI all_to_all.

Replaces ``data/Window.{h,cpp}`` — the MPI one-sided RMA window that backs the
reference's shuffle (``MPI_Alloc_mem``/``Win_create`` Window.cpp:35-46, epoch
``Win_lock_all/unlock_all`` :65-84, ``MPI_Put`` at OffsetMap-computed offsets
:86-144, conservation check ``assertAllTuplesWritten`` :180-191).

TPU-native design (SURVEY.md §7.2): instead of exactly-sized windows and
one-sided Puts, every node owns a statically-shaped [N, C] block buffer per
relation; senders scatter their tuples into per-destination blocks
(ops/radix.scatter_to_blocks) and one dense ``jax.lax.all_to_all`` over the
ICI mesh axis delivers block j of every sender to node j.  Padding slots carry
side sentinels; per-sender valid counts ride along in a second (tiny)
all_to_all — the moral equivalent of OffsetMap's exactly-written guarantee.
Epochs/barriers are implicit in XLA program order.

Two orthogonal levers reshape the wire (ISSUE 7):

* ``mode="staged:<k>"`` slices the [N, C] block buffer into k column groups
  exchanged by a *sequence* of smaller collectives chained with
  ``optimization_barrier`` — live exchange memory drops to ~1/k of the fused
  peak (the portable-redistribution decomposition of arXiv 2112.01075) while
  the received ordering stays bit-identical to the fused route.
* ``codec="pack"`` bit-packs tuples to their measured key/rid bounds before
  the collective (data/tuples.pack_blocks) and unpacks exactly on receipt;
  the packed block's header region carries the per-partition valid counts,
  so the separate count collective disappears.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_radix_join.data.tuples import (WireSpec, make_wire_spec, pack_blocks,
                                        unpack_blocks)
from tpu_radix_join.observability import stages
from tpu_radix_join.ops.radix import (scatter_to_blocks,
                                      scatter_to_blocks_grouped)
from tpu_radix_join.parallel.mesh import AxisName


def parse_exchange_mode(mode, block: int) -> int:
    """Resolve an exchange mode to a stage count k >= 1.

    ``"fused"``/1 = one collective; ``"staged:<k>"``/k = k column-group
    collectives; ``"auto"`` stages 4-ways once the block is large enough
    that the ~1/k live-memory bound matters (>= 4096 slots per block —
    below that the whole buffer is smaller than the staging bookkeeping
    is worth)."""
    if isinstance(mode, int):
        k = mode
    elif mode == "fused":
        k = 1
    elif mode == "auto":
        k = 4 if block >= 4096 else 1
    elif isinstance(mode, str) and mode.startswith("staged:"):
        try:
            k = int(mode.split(":", 1)[1])
        except ValueError:
            raise ValueError(
                f"exchange mode {mode!r}: the stage count after 'staged:' "
                f"must be an integer") from None
    else:
        raise ValueError(
            f"exchange mode must be 'fused', 'staged:<k>', 'auto', or an "
            f"int stage count, got {mode!r}")
    if k < 1:
        raise ValueError(f"exchange stage count must be >= 1, got {k}")
    return min(k, block) if block else k


@jax.named_scope(stages.EXCHANGE)
def block_all_to_all(x: jnp.ndarray, num_nodes: int, block: int,
                     axis_name: AxisName, mode="fused") -> jnp.ndarray:
    """Dense block exchange: slice ``x``'s leading [num_nodes * block] axis
    into per-destination blocks and deliver block j to node j.  The single
    collective that replaces the reference's windowed ``MPI_Put`` schedule
    (Window.cpp:86-144) and pairwise ``MPI_Send/Recv`` exchange
    (Relation.cpp:104-136).  Runs inside shard_map over ``axis_name``; a
    ``(dcn, ici)`` axis pair selects the hierarchical route.

    ``mode`` ("fused" | "staged:<k>" | "auto" | int) splits the block
    dimension into k column groups exchanged sequentially (chained with
    ``optimization_barrier`` so XLA cannot re-fuse them): peak live exchange
    memory drops to ~1/k while the received ordering stays identical to the
    fused route — group i of sender s lands in the same rows either way,
    and concatenating the groups along the block axis restores the exact
    fused layout."""
    if x.shape[0] != num_nodes * block:
        raise ValueError(
            f"block_all_to_all: leading axis of {x.shape[0]} must equal "
            f"num_nodes * block = {num_nodes} * {block} = "
            f"{num_nodes * block} (one fixed-capacity block per "
            f"destination)")
    n_stages = parse_exchange_mode(mode, block)
    if n_stages == 1:
        return _one_exchange(x, num_nodes, block, axis_name)
    rest = x.shape[1:]
    v = x.reshape((num_nodes, block) + rest)
    base, extra = divmod(block, n_stages)
    sizes = [base + (1 if i < extra else 0) for i in range(n_stages)]
    outs = []
    prev = None
    off = 0
    for g in sizes:
        part = v[:, off:off + g]
        if prev is not None:
            # tie group i+1's send to group i's arrival: the collectives
            # run as a sequence, so only ~1/k of the buffer is in flight
            part, _ = jax.lax.optimization_barrier((part, prev))
        out = _one_exchange(
            part.reshape((num_nodes * g,) + rest), num_nodes, g, axis_name
        ).reshape((num_nodes, g) + rest)
        outs.append(out)
        prev = out
        off += g
    return jnp.concatenate(outs, axis=1).reshape(
        (num_nodes * block,) + rest)


def _one_exchange(x: jnp.ndarray, num_nodes: int, block: int,
                  axis_name: AxisName) -> jnp.ndarray:
    """One fused block exchange (flat or hierarchical by axis type)."""
    if not isinstance(axis_name, str):
        dcn_axis, ici_axis = axis_name
        return hierarchical_block_all_to_all(x, num_nodes, block,
                                             dcn_axis, ici_axis)
    return jax.lax.all_to_all(
        x.reshape((num_nodes, block) + x.shape[1:]), axis_name,
        split_axis=0, concat_axis=0, tiled=False,
    ).reshape((num_nodes * block,) + x.shape[1:])


@jax.named_scope(stages.EXCHANGE)
def hierarchical_block_all_to_all(x: jnp.ndarray, num_nodes: int, block: int,
                                  dcn_axis: str, ici_axis: str) -> jnp.ndarray:
    """Two-stage exchange over a ``[num_hosts, per_host]`` mesh.

    Destination flat id ``d = host(d) * per_host + local(d)``.  Stage 1 rides
    ICI: within each host, blocks are exchanged so the device at local index
    ``l`` aggregates everything (from all its host's devices) destined for
    *any* host's local-``l`` device.  Stage 2 crosses DCN once, between
    same-local-index peers, shipping per-host-aggregated slabs — N² small
    messages become H² aggregated ones, which is the point of routing the
    bulk hops over ICI (SURVEY.md §2.4 TPU mapping; the reference leans on
    foMPI/DMAPP for the same reason on Cray fabrics, Window.h:64-68).

    Result ordering matches the flat exchange: received blocks are stacked by
    source flat id (source-host major), so callers cannot tell the routes
    apart (tested against ``block_all_to_all`` on a flat mesh).
    """
    num_hosts = jax.lax.axis_size(dcn_axis)
    per_host = jax.lax.axis_size(ici_axis)
    if num_hosts * per_host != num_nodes:
        raise ValueError(
            f"hierarchical exchange: mesh axes ({dcn_axis!r}={num_hosts}) x "
            f"({ici_axis!r}={per_host}) = {num_hosts * per_host} devices, "
            f"but num_nodes={num_nodes} — the (dcn, ici) mesh must factor "
            f"the node count exactly")
    v = x.reshape((num_hosts, per_host, block) + x.shape[1:])
    # Stage 1 (ICI): deliver column l of every destination host to local peer l.
    v = jax.lax.all_to_all(v, ici_axis, split_axis=1, concat_axis=1,
                           tiled=False)          # [H_dest, L_src, block]
    # Stage 2 (DCN): deliver row h (aggregated over the host) to host peer h.
    v = jax.lax.all_to_all(v, dcn_axis, split_axis=0, concat_axis=0,
                           tiled=False)          # [H_src, L_src, block]
    return v.reshape((num_nodes * block,) + x.shape[1:])


class ExchangeResult(NamedTuple):
    batch: object            # received batch, arrays shaped [N * C]
    recv_counts: jnp.ndarray  # uint32 [N] — valid tuples from each sender
    send_overflow: jnp.ndarray  # uint32 — local tuples dropped for lack of capacity


class Window:
    """Per-relation shuffle plane bound to a mesh axis.

    ``capacity`` is the static per-(sender, destination) block size — the
    analog of ``computeWindowSize`` (Window.cpp:168-177) except sized ahead of
    the data with ``allocation_factor`` slack (overflow is reported, never
    silently dropped from the accounting).

    ``codec="pack"`` + a :class:`~tpu_radix_join.data.tuples.WireSpec`
    switches the wire to the bounds-aware bit-packed format: tuples travel at
    ``spec.tuple_bits`` bits each and the packed header replaces the count
    side channel (one collective per exchange instead of lanes + counts).
    ``mode`` is the staged-exchange knob forwarded to every collective this
    window dispatches.
    """

    def __init__(self, num_nodes: int, capacity: int, axis_name: AxisName,
                 side: str, codec: str = "off", mode="fused",
                 fanout_bits: int = 0,
                 key_bound: Optional[int] = None,
                 rid_bound: Optional[int] = None,
                 partition_impl: Optional[str] = None,
                 epoch: int = 0):
        if codec not in ("off", "pack"):
            raise ValueError(
                f"window codec must be 'off' or 'pack', got {codec!r} "
                f"('auto' must be resolved by the caller)")
        self.num_nodes = num_nodes
        self.capacity = capacity
        self.axis_name = axis_name
        self.side = side
        self.codec = codec
        self.mode = mode
        self.fanout_bits = fanout_bits
        self.key_bound = key_bound
        self.rid_bound = rid_bound
        self.partition_impl = partition_impl
        #: membership-epoch stamp (robustness/membership.py): the mesh
        #: shape this window's collectives were laid out against.  A
        #: window is mesh-shape-specific — after a rank loss bumps the
        #: epoch, dispatching it would address a dead peer, so callers
        #: guard dispatch with :meth:`fence`.
        self.epoch = epoch

    def fence(self, view) -> None:
        """Host-side dispatch guard: raise ``StaleEpoch`` (via
        ``view.fence``, robustness/membership.MembershipView) when the
        membership epoch moved past the one this window was built at —
        a stale exchange dies loudly instead of deadlocking against a
        peer that no longer exists.  No-op when ``view`` is None."""
        if view is not None:
            view.fence(self.epoch)

    def wire_spec(self, wide: bool) -> WireSpec:
        """The packed-wire geometry for this window's bounds (static)."""
        return make_wire_spec(self.capacity, self.fanout_bits, wide=wide,
                              key_bound=self.key_bound,
                              rid_bound=self.rid_bound)

    @jax.named_scope(stages.EXCHANGE)
    def exchange(self, batch, dest: jnp.ndarray,
                 valid: jnp.ndarray | None = None,
                 pid: jnp.ndarray | None = None) -> ExchangeResult:
        """Scatter into destination blocks and all_to_all them.

        ``batch``: TupleBatch/CompressedBatch with [n] lanes; ``dest``: uint32
        [n] destination node per tuple (= assignment[pid], Window.cpp:110).
        ``pid``: the tuple partition ids — required by the packed codec
        (the dropped key bits are reconstructed from partition membership).
        Runs inside shard_map over ``axis_name``.
        """
        n, c = self.num_nodes, self.capacity
        if self.codec == "pack":
            if pid is None:
                raise ValueError(
                    "codec='pack' needs the per-tuple partition ids: the "
                    "wire drops the fanout bits and restores them from "
                    "partition membership — pass pid= to exchange()")
            spec = self.wire_spec(wide=batch[2] is not None)
            blocks, counts, group_counts, overflow = scatter_to_blocks_grouped(
                batch, dest, pid, n, spec.num_sub, c, self.side, valid=valid,
                impl=self.partition_impl)
            words = pack_blocks(spec, blocks, group_counts)
            recv_words = block_all_to_all(words, n, spec.block_words,
                                          self.axis_name, mode=self.mode)
            recv_batch, recv_counts = unpack_blocks(spec, recv_words,
                                                    self.side)
            return ExchangeResult(recv_batch, recv_counts, overflow)
        blocks, counts, overflow = scatter_to_blocks(
            batch, dest, n, c, self.side, valid=valid,
            impl=self.partition_impl)

        received = jax.tree.map(
            lambda x: block_all_to_all(x, n, c, self.axis_name,
                                       mode=self.mode), blocks)
        sent_counts = jnp.minimum(counts, jnp.uint32(c))
        recv_counts = block_all_to_all(sent_counts, n, 1, self.axis_name)
        return ExchangeResult(received, recv_counts, overflow)

    @jax.named_scope(stages.CHECKS)
    def diagnostics(
        self, result: ExchangeResult, global_hist: jnp.ndarray,
        assignment: jnp.ndarray,
    ):
        """(overflow_tuples, conservation_bad) — the two failure modes of the
        shuffle, separated so callers can tell "blocks too small" (retryable
        with bigger capacity) from "tuples misrouted" (a real bug).

        ``overflow_tuples``: psum of tuples senders dropped for lack of block
        capacity.  ``conservation_bad``: True iff the receive total differs
        from the global histogram over this node's assigned partitions
        (Window.cpp:180-191) *beyond what the overflow explains* — when
        tuples overflowed, the exact equality is unevaluable, so it is only
        asserted when overflow is zero."""
        me = jax.lax.axis_index(self.axis_name).astype(jnp.uint32)
        expected = jnp.sum(jnp.where(assignment == me, global_hist, 0))
        lost = jax.lax.psum(result.send_overflow, self.axis_name)
        conserve_bad = (jnp.sum(result.recv_counts) != expected) & (lost == 0)
        return lost, conserve_bad

    def assert_all_tuples_written(
        self, result: ExchangeResult, global_hist: jnp.ndarray,
        assignment: jnp.ndarray,
    ) -> jnp.ndarray:
        """Combined invariant (conservation AND zero overflow) — the exact
        contract of the reference's assert (SURVEY.md §4.3)."""
        lost, bad = self.diagnostics(result, global_hist, assignment)
        return (lost == 0) & ~bad

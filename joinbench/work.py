"""The least work a join must do, computed from the cell's shapes alone.

An equi-join has to read every key and rid of both relations at least once,
whatever implements it.  So the least time a chip can take for its share
of one join is the bytes of its shards over the chip's peak HBM bandwidth.
The count does no floating-point work, so HBM bounds it, not compute.
"""

from __future__ import annotations

#: the tuple's lanes: a 4-byte key and a 4-byte rid
TUPLE_BYTES = 8


def join_bytes_per_chip(tuples_r: int, tuples_s: int, chips: int) -> int:
    """Bytes of R and S one chip holds and so must read once per join."""
    return (tuples_r + tuples_s) * TUPLE_BYTES // chips


def least_join_seconds(tuples_r: int, tuples_s: int, chips: int,
                       hbm_bytes_per_s: float) -> float:
    return join_bytes_per_chip(tuples_r, tuples_s, chips) / hbm_bytes_per_s

"""TPC-H's order keys from a seed: ORDERS.O_ORDERKEY and LINEITEM.L_ORDERKEY.

The key rules of TPC-H v3 (§4.2.3), as dbgen applies them:

* ORDERS has SF x 1,500,000 rows.  Order ``i`` (``i = 1..N``) has the key
  ``((i >> 3) << 5) | (i & 7)``: only the first 8 of every 32 keys are
  used, up to SF x 6,000,000.  The refresh functions insert new orders into
  the unused ones; :func:`gap_keys` draws such keys.
* Each order has 1 to 7 lineitems, drawn uniformly; each lineitem carries
  its order's key.

Not dbgen: the lineitem counts come from a seeded hash, so LINEITEM's size
differs from dbgen's, and both tables hold their rows in a seeded random
order (``datagen``'s Feistel permutations), not dbgen's clustered one.
Each table is a key lane and a rid lane, the rid being the row number.

The benchmark makes the tables on the device (:func:`device_tables`) and
its reference reads the NumPy twins (:func:`orders_np`,
:func:`lineitem_np`), which give the same keys bit for bit.
"""

from __future__ import annotations

import numpy as np

from joinbench import datagen
from joinbench.window import seed_sequence

ORDERS_PER_SF = 1_500_000
MAX_LINES = 7
#: order numbers take 3 low bits, then 2 bits the key space leaves unused
_KEEP_BITS, _GAP_BITS = 3, 2


def orders_rows(scale_factor: float) -> int:
    return int(round(scale_factor * ORDERS_PER_SF))


def sparse_key(i, xp=np):
    """O_ORDERKEY of order number ``i`` (uint32, NumPy or ``jax.numpy``)."""
    keep = xp.uint32((1 << _KEEP_BITS) - 1)
    return (((i >> xp.uint32(_KEEP_BITS)) << xp.uint32(_KEEP_BITS + _GAP_BITS))
            | (i & keep))


def gap_keys(rng: np.random.Generator, count: int, orders: int) -> np.ndarray:
    """``count`` keys of the unused 24 of every 32 among ORDERS' keys:
    no ORDERS row holds one."""
    i = rng.integers(1, orders + 1, count).astype(np.uint32)
    gap = rng.integers(1, 1 << _GAP_BITS, count).astype(np.uint32)
    return sparse_key(i) | (gap << np.uint32(_KEEP_BITS))


def _mix32(x, xp):
    """The murmur3 finalizer: a bijection of uint32 that scatters bits."""
    x = x ^ (x >> xp.uint32(16))
    x = x * xp.uint32(0x85EBCA6B)
    x = x ^ (x >> xp.uint32(13))
    x = x * xp.uint32(0xC2B2AE35)
    return x ^ (x >> xp.uint32(16))


def lines_per_order(i, salt: int, xp=np):
    """1 to 7 lineitems for each order number in ``i`` (uint32)."""
    h = _mix32(_mix32(i, xp) ^ xp.uint32(salt), xp)
    return xp.uint32(1) + h % xp.uint32(MAX_LINES)


class Seeds:
    """What ``--seed`` decides: the two tables' row orders and the
    lineitem counts."""

    def __init__(self, seed: int):
        orders, lines, salt = np.random.default_rng(
            seed_sequence(seed) + [3]).integers(0, 1 << 62, size=3)
        self.orders, self.lineitem = int(orders), int(lines)
        self.salt = int(salt) & 0xFFFFFFFF


def _order_numbers(n: int) -> np.ndarray:
    return np.arange(1, n + 1, dtype=np.uint32)


def orders_np(n: int, seeds: Seeds) -> np.ndarray:
    """ORDERS' key lane: row ``p`` holds order ``perm(p) + 1``."""
    with np.errstate(over="ignore"):
        return sparse_key(datagen.unique_keys_np(0, n, n, seeds.orders)
                          + np.uint32(1))


def lineitem_np(n: int, seeds: Seeds) -> np.ndarray:
    """LINEITEM's key lane: the lines laid out order by order, row ``q``
    holding line ``perm(q)``."""
    numbers = _order_numbers(n)
    with np.errstate(over="ignore"):
        per_line = np.repeat(numbers, lines_per_order(numbers, seeds.salt))
    total = per_line.size
    return sparse_key(per_line[datagen.unique_keys_np(0, total, total,
                                                      seeds.lineitem)])


def device_tables(n: int, seeds: Seeds):
    """Both tables on the default device, in two device programs:
    ``(o_key, o_rid, l_key, l_rid)``.  LINEITEM's size is read back
    between them, since it sets the second program's shapes."""
    import jax
    import jax.numpy as jnp

    def orders(rk):
        rows = jnp.arange(n, dtype=jnp.uint32)
        number = datagen._unique_keys_jax(rows, rk, n) + jnp.uint32(1)
        return sparse_key(number, jnp), rows

    def counts():
        return lines_per_order(jnp.arange(1, n + 1, dtype=jnp.uint32),
                               seeds.salt, jnp)

    total = int(jax.jit(lambda: jnp.sum(counts(), dtype=jnp.uint32))())

    def lineitem(rk):
        per_line = jnp.repeat(jnp.arange(1, n + 1, dtype=jnp.uint32),
                              counts(), total_repeat_length=total)
        rows = jnp.arange(total, dtype=jnp.uint32)
        return (sparse_key(per_line[datagen._unique_keys_jax(rows, rk, total)],
                           jnp), rows)

    o_key, o_rid = jax.jit(orders)(datagen.round_keys(seeds.orders))
    l_key, l_rid = jax.jit(lineitem)(datagen.round_keys(seeds.lineitem))
    return o_key, o_rid, l_key, l_rid

"""The plain reference of the served TPC-H join: ``count(*)`` of
orders ⋈ lineitem on the order key, in NumPy.

It imports nothing of the program.  ORDERS' keys are unique, so the count
is the number of lineitem rows whose key some order holds: membership in
ORDERS' key set, built once.  A refresh moves a few lineitem rows to new
keys; the count after it is the base count less the moved rows' old
memberships plus their new ones, so no query rebuilds anything over the
whole table.
"""

from __future__ import annotations

import numpy as np


class FKJoin:
    """|ORDERS ⋈ LINEITEM| for the tables as made and after a refresh."""

    def __init__(self, orders_keys: np.ndarray, lineitem_keys: np.ndarray):
        self.member = np.zeros(int(orders_keys.max()) + 1, bool)
        self.member[orders_keys] = True
        self.lineitem = lineitem_keys
        self.total = self.members(lineitem_keys)

    def members(self, keys: np.ndarray) -> int:
        """How many of ``keys`` some ORDERS row holds."""
        keys = np.asarray(keys)
        inside = keys[keys < self.member.size]
        return int(self.member[inside].sum())

    def count(self, positions=None, new_keys=None) -> int:
        """The count with LINEITEM's rows at ``positions`` (distinct) moved
        to ``new_keys``; the tables as made when none are given."""
        if positions is None:
            return self.total
        if np.unique(positions).size != len(positions):
            raise ValueError("moved rows must be distinct")
        return (self.total - self.members(self.lineitem[positions])
                + self.members(new_keys))

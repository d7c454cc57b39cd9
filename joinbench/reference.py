"""The plain reference: an equi-join count in NumPy.

It imports nothing of the program.  ``|R ⋈ S|`` is the sum over the
tuples of S of how many tuples of R carry the same key: a histogram of R's
keys, looked up at S's keys.
"""

from __future__ import annotations

import numpy as np

from joinbench.datagen import unique_keys_np


def multiplicity(r_keys: np.ndarray, s_keys: np.ndarray) -> np.ndarray:
    """For each tuple of S, the number of tuples of R with its key."""
    per_key = np.bincount(r_keys)
    inside = s_keys < per_key.size
    out = np.zeros(s_keys.shape, np.int64)
    out[inside] = per_key[s_keys[inside]]
    return out


def join_count(r_keys: np.ndarray, s_keys: np.ndarray) -> int:
    return int(multiplicity(r_keys, s_keys).sum())


class RewrittenJoin:
    """R ⋈ S where each join rewrites a few of S's keys.

    R and S are the benchmark's seeded relations of ``global_size`` tuples.
    :meth:`count` gives the count after S's keys at ``positions`` become
    ``new_keys``: the join of the unchanged tuples plus that of the
    rewritten ones."""

    def __init__(self, global_size: int, seed_r: int, seed_s: int):
        r = unique_keys_np(0, global_size, global_size, seed_r)
        self.s = unique_keys_np(0, global_size, global_size, seed_s)
        self.r_keys = r
        self.per_tuple = multiplicity(r, self.s)
        self.total = int(self.per_tuple.sum())

    def count(self, positions: np.ndarray, new_keys: np.ndarray) -> int:
        if np.unique(positions).size != positions.size:
            raise ValueError("rewritten positions must be distinct")
        kept = self.total - int(self.per_tuple[positions].sum())
        return kept + join_count(self.r_keys, new_keys)

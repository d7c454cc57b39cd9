"""Rule ``sort-bypass``: hot sorts must route through ``ops/sorting.py``.

Every hot reorder goes through ``sort_unstable`` / ``sort_kv_unstable``
/ ``sort_lex_unstable``, which run ``lax.sort`` under the ``trj.sort``
scope.  A direct ``jax.lax.sort`` / ``jnp.sort`` / ``jnp.argsort`` call
anywhere else sits outside that scope, so its device time escapes the
per-stage sort time that reads it (and ``jnp.sort``/``argsort`` are
stable sorts, ~2x the cost).

Host-side ``np.sort``/``np.argsort`` are NOT flagged — numpy on host
arrays is the oracle/verification idiom, not a device sort.  The
wrappers' own module and the Pallas kernels are the allowed homes.
"""

from __future__ import annotations

import ast
from typing import List

from tpu_radix_join.analysis.core import (Finding, Repo, dotted_name, rule)

ALLOWED_FILES = ("tpu_radix_join/ops/sorting.py",)
ALLOWED_PREFIXES = ("tpu_radix_join/ops/pallas/",)

#: dotted call spellings that bypass the wrappers
SORT_CALLS = {
    "jax.lax.sort", "lax.sort",
    "jnp.sort", "jnp.argsort", "jnp.lexsort",
    "jax.numpy.sort", "jax.numpy.argsort", "jax.numpy.lexsort",
}
#: method receivers that mark a *host* array (never flagged)
HOST_ROOTS = {"np", "numpy"}


@rule("sort-bypass",
      "direct lax.sort/jnp.sort/argsort outside ops/sorting.py "
      "escapes the trj.sort scope",
      token="sort")
def check(repo: Repo) -> List[Finding]:
    out: List[Finding] = []
    for src in repo.files:
        if (src.rel in ALLOWED_FILES
                or src.rel.startswith(ALLOWED_PREFIXES)):
            continue
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name in SORT_CALLS:
                out.append(Finding(
                    rule="sort-bypass", path=src.rel, line=node.lineno,
                    key=name,
                    message=(f"direct {name} call escapes the trj.sort "
                             f"scope of ops/sorting.py — use "
                             f"sort_unstable/sort_kv_unstable (or add a "
                             f"baseline entry with a reason)")))
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in ("argsort", "lexsort")):
                # method-call spelling: x.argsort() — flagged unless the
                # receiver is rooted at np/numpy (host oracle arrays);
                # call-chain receivers (np.abs(h).argsort()) root at the
                # innermost callee
                recv = node.func.value
                while isinstance(recv, ast.Call):
                    recv = recv.func
                root = (dotted_name(recv) or "").split(".")[0]
                if root not in HOST_ROOTS:
                    out.append(Finding(
                        rule="sort-bypass", path=src.rel, line=node.lineno,
                        key=f".{node.func.attr}()",
                        message=(f".{node.func.attr}() reorder escapes "
                                 f"the trj.sort scope of ops/sorting.py — "
                                 f"use sort_kv_unstable")))
    return out

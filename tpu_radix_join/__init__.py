"""tpu_radix_join — a TPU-native distributed radix hash join framework.

A from-scratch JAX/XLA rebuild of the capabilities of
lushl9301/Distributed-Radix-Hash-Join-on-GPUs (ETH hpcjoin lineage, C++/MPI/CUDA):
the full histogram -> window allocation -> network partitioning (all-to-all) ->
local partitioning -> build-probe pipeline runs as a single pjit/shard_map SPMD
program over a TPU mesh.  See SURVEY.md at the repo root for the component-level
mapping to the reference (file:line citations throughout the code).
"""

from tpu_radix_join.core.config import JoinConfig
from tpu_radix_join.data.relation import Relation
from tpu_radix_join.operators.hash_join import HashJoin

__version__ = "0.1.0"

__all__ = ["JoinConfig", "Relation", "HashJoin", "__version__"]

"""Distributed training over a jax.sharding.Mesh.

Replaces the reference's entire src/network/ layer (socket/MPI linkers,
Bruck allgather, recursive-halving reduce-scatter) with XLA collectives
inside shard_map; see comm.py for the per-learner communication patterns.
"""

from .comm import (DataParallelComm, FeatureParallelComm,  # noqa: F401
                   HistExchange, VotingParallelComm)
from .grow import make_comm, make_parallel_grow  # noqa: F401

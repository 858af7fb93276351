"""Device-mesh sharding for the batched consensus engine.

Two scaling axes (SURVEY.md §2.2):

- ``groups``: shard the group batch across chips when G exceeds one chip
  (the reference's "many resources over one log" multiplexing axis,
  ``ResourceManager.java:56``, turned into a data-parallel dimension);
- ``peers``: place each Raft replica on its own chip — real distributed
  consensus where quorum tallies (sums over the peer axis) become XLA
  collectives over ICI, replacing the reference's Netty server↔server
  traffic (``AtomixReplica.java:358-363``).
"""

from .mesh import (  # noqa: F401
    make_mesh,
    raft_shardings,
    raft_specs,
    shard_state,
    shard_step_inputs,
)
from . import multihost  # noqa: F401  (multi-process: one SPMD step over DCN)

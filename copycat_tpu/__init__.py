"""copycat_tpu — a TPU-native distributed coordination framework.

A from-scratch rebuild of the capabilities of Atomix/Copycat (reference:
``/root/reference``, Atomix 0.1.0-SNAPSHOT on Copycat Raft): Raft-replicated,
session-based distributed resources — atomic values/counters, maps, multimaps,
sets, queues, locks, leader elections, group membership, topics, a message bus —
behind an async client API with per-operation consistency levels.

Architecture (see SURVEY.md in the repo root):

- ``utils/ io/`` — the Catalyst-equivalent substrate: serialization with a
  type-id registry, pluggable async transports (in-memory Local + TCP),
  lifecycle/listener utilities.
- ``protocol/ server/ client/`` — the Copycat-equivalent Raft core, written as
  a pure-Python CPU oracle: leader election, log replication, commitment,
  linearizable sessions with server-push events, log cleaning/compaction.
- ``resource/ manager/`` — the Atomix-equivalent resource layer: many logical
  state machines multiplexed over one replicated log.
- ``atomic/ collections/ coordination/`` — the resource library.
- ``ops/ models/ parallel/`` — the TPU-native consensus engine: all Raft groups
  batched into fixed-shape ``[num_groups, num_peers]`` tensors, stepped as one
  XLA program (quorum tallies via sums/psums over the peer axis, state-machine
  apply via vectorized kernels), sharded over a ``jax.sharding.Mesh``.
"""

__version__ = "0.4.0"

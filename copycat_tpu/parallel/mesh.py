"""Mesh construction and sharding specs for ``RaftState``.

The step function in ``ops.consensus`` is written as pure array ops over
``[G, P, ...]`` tensors; sharding is applied by *placement only* —
``jax.device_put`` with ``NamedSharding`` on the inputs — and XLA inserts
the ICI collectives (all-gathers for the ``[G,P,P]`` vote/ack contractions,
reductions for quorum tallies) from the annotations. No hand-written
collectives: the compiler owns the schedule.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.consensus import RaftState, Submits


def make_mesh(groups: int | None = None, peers: int | None = None,
              devices: list | None = None) -> Mesh:
    """Build a 1D ``('groups',)`` or 2D ``('groups','peers')`` mesh."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if peers is None:
        groups = groups or n
        return Mesh(np.asarray(devices[:groups]), ("groups",))
    groups = groups or n // peers
    if groups * peers > n:
        raise ValueError(f"mesh {groups}x{peers} needs {groups * peers} devices, have {n}")
    dev = np.asarray(devices[: groups * peers]).reshape(groups, peers)
    return Mesh(dev, ("groups", "peers"))


def raft_specs(mesh: Mesh, state: RaftState) -> RaftState:
    """Per-leaf PartitionSpecs: group axis sharded, peer axis sharded when
    the mesh has a ``peers`` axis, log/ring/pool axes replicated.

    Every ``RaftState`` leaf (including all resource pools and the event
    ring) is laid out ``[G, P, ...]``, so one rule covers the whole tree."""
    g = "groups" if "groups" in mesh.axis_names else None
    p = "peers" if "peers" in mesh.axis_names else None
    return jax.tree.map(
        lambda x: P(g, p, *([None] * (x.ndim - 2))), state)


def raft_shardings(mesh: Mesh, state: RaftState) -> tuple[RaftState, Any]:
    """``NamedSharding``s for every ``state`` leaf (arrays or shapes) and
    for the ``[G,P,P]`` deliver mask — what a program that builds them
    already placed takes as ``out_shardings``."""
    g = "groups" if "groups" in mesh.axis_names else None
    p = "peers" if "peers" in mesh.axis_names else None
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            raft_specs(mesh, state),
                            is_leaf=lambda x: isinstance(x, P))
    return state_sh, NamedSharding(mesh, P(g, p, None))


def shard_state(state: RaftState, mesh: Mesh) -> RaftState:
    return jax.tree.map(jax.device_put, state,
                        raft_shardings(mesh, state)[0])


def shard_step_inputs(submits: Submits, deliver: Any, mesh: Mesh
                      ) -> tuple[Submits, Any]:
    g = "groups" if "groups" in mesh.axis_names else None
    p = "peers" if "peers" in mesh.axis_names else None
    sub = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P(g, None))), submits)
    dl = jax.device_put(deliver, NamedSharding(mesh, P(g, p, None)))
    return sub, dl

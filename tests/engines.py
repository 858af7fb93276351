"""What size a test engine is.

Every ``RaftGroups`` compiles its round, query and deep-drive programs for
its ``(groups, peers, log_slots, submit_slots)`` and its ``Config``, and
the driver runs tier-1 from an empty compile cache: a shape of its own
costs a test file seconds of XLA:CPU, a shared one nothing. A test whose
subject is not the shape takes one of these (a test of two groups drives
two of the eight). A test that needs its own shape constructs it with a
``# shape:`` comment saying why; ``test_platform_guard.py`` holds that
line. Seeds stay per test: a seed is data and costs no compile.
"""

from copycat_tpu.manager.device_executor import DeviceEngineConfig
from copycat_tpu.models import RaftGroups
from copycat_tpu.ops.apply import ResourceConfig
from copycat_tpu.ops.consensus import Config

#: the deep drive's engines (``models/bulk.py``)
MONOTONE = Config(monotone_tag_accept=True)

#: groups of the device-plane shape, for the tests' own index arithmetic
G = 8


def device_plane(config: Config | None = None, seed: int = 0,
                 **kw) -> RaftGroups:
    """Eight groups of three, a 32-slot ring, four submit slots: the
    device plane's tests, and a mesh's multiple of eight."""
    return RaftGroups(G, 3, log_slots=32, submit_slots=4, config=config,
                      seed=seed, **kw)


def wide_window(config: Config, seed: int = 0) -> RaftGroups:
    """The device plane with eight submit slots, so that one round can
    fill ``applies_per_round=8``: the conflict-partitioned apply window."""
    return RaftGroups(G, 3, log_slots=32, submit_slots=8, config=config,
                      seed=seed)


def five_peer(config: Config | None = None, seed: int = 0,
              **kw) -> RaftGroups:
    """The device plane with five peer lanes: quorums of three,
    membership change (``voters=3`` leaves two standby lanes) and lease
    churn."""
    return RaftGroups(G, 5, log_slots=32, submit_slots=4, config=config,
                      seed=seed, **kw)


def short_ring(config: Config | None = None, seed: int = 0,
               **kw) -> RaftGroups:
    """The device plane with a 16-slot ring: at four appends a round a
    follower cut off for eight rounds has fallen behind it, so the
    snapshot install runs within a short schedule."""
    return RaftGroups(G, 3, log_slots=16, submit_slots=4, config=config,
                      seed=seed, **kw)


#: a map table of two buckets (``ops.apply.map_buckets``), the other pools
#: as they are everywhere
WIDE_MAP = Config(resource=ResourceConfig(map_slots=512))


def wide_map(seed: int = 0, **kw) -> RaftGroups:
    """The device plane with a map table of two buckets: a keyed map op
    fetches its key's bucket, and 257 keys of one bucket fill it."""
    return device_plane(WIDE_MAP, seed=seed, **kw)


#: the served stacks' device executor (``AtomixServer(executor="tpu")``):
#: the device plane's shape, so a served engine runs its programs
SERVED = DeviceEngineConfig(capacity=G, num_peers=3, log_slots=32)

#: the same with room for 32 device resources, for the tests that hold
#: more than eight at once
SERVED_WIDE = SERVED._replace(capacity=32)

#: the served shape over :data:`WIDE_MAP`'s pools
SERVED_MAP = SERVED._replace(resource=WIDE_MAP.resource)

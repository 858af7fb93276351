"""Lease soundness across membership change (VERDICT r3 #6).

The leader lease certifies BOUNDED_LINEARIZABLE reads without a log
append (``ops/consensus.py`` ``RaftState.lease``). Its soundness hinge
under dynamic membership: the lease quorum must be evaluated against the
leader's ACTIVE (latest-in-log) config — an implementation that kept
counting acks against the config the lease was first acquired under
would let a partitioned ex-leader serve stale atomic reads after config
changes replaced its ack voters (old-config quorums need not intersect
late-config quorums; only ADJACENT single-server configs must).

Scenario driven here: voters grow {0,1,2} → {0,1,2,3,4}, then the leader
is partitioned WITH one companion — a 2-node island that IS a quorum of
the original 3-voter config but is NOT a quorum of the active 5-voter
config. The unsound lease holds; the sound one drops. Meanwhile the
majority side elects, removes both islanders from the config
(single-server steps), commits new writes, and serves atomic reads of
the new value.

Reference obligation: ``Consistency.java:157-176`` BOUNDED_LINEARIZABLE;
membership change per ``AtomixServerTest.testServerJoin/Leave``.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.ops.consensus import Config  # noqa: E402

from engines import five_peer  # noqa: E402


def _island_deliver(G: int, P: int, island: set[int]) -> jnp.ndarray:
    """Full connectivity within ``island`` and within its complement;
    nothing across."""
    deliver = np.zeros((G, P, P), bool)
    for a in range(P):
        for b in range(P):
            deliver[:, a, b] = (a in island) == (b in island)
    return jnp.asarray(deliver)


def test_partitioned_ex_leader_lease_drops_under_grown_config():
    rg = five_peer(Config(dynamic_membership=True), seed=3, voters=3)
    rg.wait_for_leaders()

    # grow the voter set to all 5 lanes (single-server steps)
    for lane in (3, 4):
        tags = [rg.add_peer(g, lane) for g in range(2)]
        rg.run_until(tags)
    assert rg.voting_members(0) == [0, 1, 2, 3, 4]

    # baseline write + lease held under full delivery
    t = rg.submit(0, ap.OP_VALUE_SET, a=111)
    rg.run_until([t])
    rg.run(2)
    leader = rg.leader(0)
    assert leader >= 0
    assert bool(np.asarray(rg.state.lease)[0].any())

    # island = old leader + one companion: a quorum of the ORIGINAL
    # 3-voter config (2 of {0,1,2}) but not of the active 5-voter one
    companion = next(p for p in (0, 1, 2) if p != leader)
    island = {leader, companion}
    rg.deliver = _island_deliver(rg.num_groups, 5, island)

    for _ in range(3):
        rg.step_round()
        lease = np.asarray(rg.state.lease)[0]
        # the sound lease (quorum vs ACTIVE config = 3 of 5) is gone on
        # the island even though the island still acks the ex-leader —
        # an old-config lease (2 of {0,1,2}) would survive here
        assert not lease[leader], \
            "partitioned ex-leader holds a lease its active config denies"
        assert not lease[companion]

    # majority side: elect, then single-server-remove both islanders
    for _ in range(60):
        rg.step_round()
        lead2 = rg.leader(0)
        if lead2 >= 0 and lead2 not in island:
            break
    else:
        raise AssertionError("majority never elected a new leader")

    for lane in sorted(island):
        t = rg.remove_peer(0, lane)
        rg.run_until([t], max_rounds=120)
    members = rg.voting_members(0)
    assert set(members) == {0, 1, 2, 3, 4} - island, members

    # new writes commit on the majority; atomic lease reads see them
    t = rg.submit(0, ap.OP_VALUE_SET, a=222)
    rg.run_until([t], max_rounds=120)
    q = rg.submit_query(0, ap.OP_VALUE_GET, consistency="atomic")
    rg.run_until([q], max_rounds=120)
    assert rg.results[q] == 222

    # the ex-leader cannot be serving anything: CheckQuorum stepped it
    # down (no quorum contact under its 5-voter active config) and its
    # term is stale relative to the majority line. (state.lease is a
    # group-level bit replicated across lanes — it now reports the NEW
    # leader's held lease, which is the sound outcome.)
    roles = np.asarray(rg.state.role)[0]
    terms = np.asarray(rg.state.term)[0]
    assert roles[leader] != 2, "partitioned ex-leader still claims leadership"
    assert terms[leader] < terms.max()

    # heal: the ex-leader steps down; no stale value resurfaces
    from copycat_tpu.ops.consensus import full_delivery
    rg.deliver = full_delivery(rg.num_groups, 5)
    rg.run(10)
    q = rg.submit_query(0, ap.OP_VALUE_GET, consistency="atomic")
    rg.run_until([q], max_rounds=120)
    assert rg.results[q] == 222


def test_lease_read_never_serves_during_config_island():
    """While the ex-leader's island holds an old-config quorum, an atomic
    query routed at it must escalate to the command path (and therefore
    only complete on the true leader's line) — never serve locally from
    the stale lane."""
    rg = five_peer(Config(dynamic_membership=True), seed=5, voters=3)
    rg.wait_for_leaders()
    for lane in (3, 4):
        rg.run_until([rg.add_peer(0, lane)])
    t = rg.submit(0, ap.OP_VALUE_SET, a=7)
    rg.run_until([t])

    leader = rg.leader(0)
    companion = next(p for p in (0, 1, 2) if p != leader)
    rg.deliver = _island_deliver(rg.num_groups, 5, {leader, companion})

    # atomic read during the partition: it must reflect the majority
    # line's state (the islanded lanes cannot serve it via lease)
    q = rg.submit_query(0, ap.OP_VALUE_GET, consistency="atomic")
    rg.run_until([q], max_rounds=200)
    assert rg.results[q] == 7

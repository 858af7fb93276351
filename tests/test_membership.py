"""Device-path cluster membership change (server join/leave).

The reference grows and shrinks a live cluster
(manager/src/test/java/io/atomix/AtomixServerTest.java testServerJoin /
testServerLeave — Raft membership change in the external Copycat core).
The device equivalent: per-group voter sets over the fixed ``P`` peer
lanes, changed by single-server OP_CFG_ADD/REMOVE entries through the
replicated log (``Config.dynamic_membership``). These tests drive the
full lifecycle — standby lanes, join, leave, leader self-removal — and
check the part that actually matters: THE QUORUM CHANGES (fault patterns
that stall the old config commit in the new one, and vice versa).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.models import RaftGroups  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.ops.consensus import LEADER, Config  # noqa: E402

from engines import device_plane, five_peer  # noqa: E402

DYN = Config(dynamic_membership=True)


def make(peers=5, voters=None, **kw):
    build = five_peer if peers == 5 else device_plane
    return build(DYN, voters=voters, **kw)


def isolate(rg: RaftGroups, lanes) -> np.ndarray:
    """Full delivery except ``lanes``, which are cut from everyone."""
    dl = np.ones((rg.num_groups, rg.num_peers, rg.num_peers), bool)
    for lane in lanes:
        dl[:, lane, :] = False
        dl[:, :, lane] = False
    return dl


def commits_under(rg: RaftGroups, deliver, rounds=25) -> bool:
    """Submit one counter op and report whether it commits while the
    given delivery mask is in force."""
    tag = rg.submit(0, ap.OP_LONG_ADD, 1)
    for _ in range(rounds):
        rg.step_round(deliver=deliver)
        if tag in rg.results:
            return True
    # drain under full connectivity so the op doesn't leak into the next
    # phase of the test
    rg.run_until([tag], max_rounds=100)
    return False


def resolve(rg: RaftGroups, tag: int, max_rounds=100) -> int:
    rg.run_until([tag], max_rounds=max_rounds)
    return rg.results[tag]


def test_standby_lanes_never_lead():
    rg = make(peers=5, voters=3)
    rg.wait_for_leaders()
    tags = [rg.submit(g, ap.OP_LONG_ADD, 1) for g in range(4)]
    for _ in range(40):
        rg.step_round()
        role = np.asarray(rg.state.role)
        assert not (role[:, 3:] == LEADER).any(), \
            "standby (non-voter) lane became leader"
    assert all(t in rg.results for t in tags)
    assert rg.voting_members(0) == [0, 1, 2]


def test_add_peer_grows_fault_tolerance():
    rg = make(peers=5, voters=3)
    rg.wait_for_leaders()

    # 3 voters {0,1,2}, quorum 2: cutting lanes 1 and 2 leaves one voter
    assert not commits_under(rg, isolate(rg, [1, 2]))

    # join lanes 3 and 4 (serialized by the one-in-flight append guard;
    # the second submit is simply rejected+requeued until the first
    # applies)
    t3 = rg.add_peer(0, 3)
    t4 = rg.add_peer(0, 4)
    rg.run_until([t3, t4], max_rounds=150)
    assert rg.voting_members(0) == [0, 1, 2, 3, 4]

    # 5 voters, quorum 3: the SAME fault now leaves {0,3,4} — commits
    assert commits_under(rg, isolate(rg, [1, 2]), rounds=60)


def test_remove_peer_shrinks_quorum():
    rg = make(peers=5)  # all 5 voting, quorum 3
    rg.wait_for_leaders()

    # cutting {1,3,4} leaves 2 of 5 — stalls
    assert not commits_under(rg, isolate(rg, [1, 3, 4]))

    t3 = rg.remove_peer(0, 3)
    t4 = rg.remove_peer(0, 4)
    rg.run_until([t3, t4], max_rounds=150)
    assert rg.voting_members(0) == [0, 1, 2]

    # same fault against 3 voters {0,1,2}, quorum 2: {0,2} — commits
    assert commits_under(rg, isolate(rg, [1, 3, 4]), rounds=60)

    # the departed lanes stay out: never lead again (in group 0: the
    # other groups of the shared shape keep their five voters)
    for _ in range(30):
        rg.step_round()
        role = np.asarray(rg.state.role)
        assert not (role[0, 3:] == LEADER).any()


def test_leader_self_removal_steps_down():
    rg = make(peers=3)
    rg.wait_for_leaders()
    old = rg.leader(0)
    tag = rg.remove_peer(0, old)
    resolve(rg, tag, max_rounds=150)
    # a new leader emerges among the remaining voters
    for _ in range(60):
        rg.step_round()
        new = rg.leader(0)
        if new >= 0 and new != old:
            break
    assert new >= 0 and new != old
    assert old not in rg.voting_members(0)
    # and the shrunk group still commits
    t = rg.submit(0, ap.OP_LONG_ADD, 7)
    assert resolve(rg, t) == 7


def test_remove_last_member_fails_fast():
    rg = make(peers=3, voters=1)  # single-voter group (lane 0)
    rg.wait_for_leaders()
    tag = rg.remove_peer(0, 0)
    for _ in range(30):
        rg.step_round()
        if tag in rg.results:
            break
    # refused outright (FAIL result) — NOT left retrying, which would
    # block every later op in the group's queue behind the FIFO gate
    assert rg.results.get(tag) == ap.FAIL
    assert rg.voting_members(0) == [0]
    # the group is still alive
    t = rg.submit(0, ap.OP_LONG_ADD, 3)
    assert resolve(rg, t) == 3


def test_removed_partitioned_lane_cannot_disrupt():
    """A lane removed WHILE partitioned never learns its removal: it
    holds an inflated term and campaigns forever, it gets no appends
    (non-member), so the ack path can't depose it either — without
    leader stickiness its RequestVote would depose the healthy leader
    every few rounds forever. With stickiness (voters ignore
    RequestVote while hearing a current leader, Raft thesis §4.2.3) the
    group must stay stable after the heal."""
    rg = make(peers=3)
    rg.wait_for_leaders()
    victim = (rg.leader(0) + 1) % 3  # a follower
    dl = isolate(rg, [victim])
    for _ in range(5):
        rg.step_round(deliver=dl)  # let the victim's term inflate
    t = rg.remove_peer(0, victim)
    for _ in range(100):
        rg.step_round(deliver=dl)
        if t in rg.results:
            break
    assert t in rg.results and victim not in rg.voting_members(0)

    # heal — the removed lane rejoins the network with a higher term
    depositions = 0
    prev = rg.leader(0)
    tags = []
    for r in range(80):
        if r % 4 == 0:
            tags.append(rg.submit(0, ap.OP_LONG_ADD, 1))
        rg.step_round()
        cur = rg.leader(0)
        if cur >= 0 and prev >= 0 and cur != prev:
            depositions += 1
        prev = cur if cur >= 0 else prev
    assert depositions <= 1, \
        f"removed lane depose-looped the leader ({depositions} changes)"
    rg.run_until(tags, max_rounds=100)


def test_exactly_once_counter_across_churn():
    """Counter increments interleaved with join/leave under nemesis:
    every committed increment applies exactly once, election safety
    holds (≤1 leader per (group, term)) across config changes."""
    rng = np.random.default_rng(7)
    rg = make(peers=5, voters=3)
    rg.wait_for_leaders()
    seen = {}  # (group, term) -> leader lane

    cfg_plan = [("add", 3), ("add", 4), ("remove", 1), ("remove", 3)]
    tags, cfg_tags = [], []
    prev_outside = set()
    for r in range(220):
        if r % 3 == 0:
            tags.append(rg.submit(0, ap.OP_LONG_ADD, 1))
        if r % 40 == 20 and cfg_plan:
            kind, lane = cfg_plan.pop(0)
            cfg_tags.append(rg.add_peer(0, lane) if kind == "add"
                            else rg.remove_peer(0, lane))
        deliver = None
        if 0 < (r % 30) < 8:  # nemesis window: cut one random lane
            deliver = isolate(rg, [int(rng.integers(0, 5))])
        rg.step_round(deliver=deliver)
        role = np.asarray(rg.state.role)
        term = np.asarray(rg.state.term)
        member = np.asarray(rg.state.member)
        outside = set()
        for g, p in zip(*np.nonzero(role == LEADER)):
            key = (int(g), int(term[g, p]))
            prev = seen.setdefault(key, int(p))
            assert prev == int(p), f"two leaders in term {key}"
            if not (member[g, p] >> p) & 1:
                # a leader that appended+applied its own removal in one
                # round steps down the NEXT round (it already tallies
                # commits under the new config meanwhile — Raft thesis
                # §4.2.2); it must never persist a second round
                outside.add((int(g), int(p)))
        assert not (outside & prev_outside), \
            f"self-removed leader persisted two rounds: {outside & prev_outside}"
        prev_outside = outside
    rg.run_until(tags + cfg_tags, max_rounds=200)
    assert rg.voting_members(0) == [0, 2, 4]
    # exactly-once: the final counter equals the number of increments
    t = rg.submit(0, ap.OP_LONG_ADD, 0)
    assert resolve(rg, t) == len(tags)


def test_added_lane_catches_up_via_snapshot_install():
    """A lane added AFTER the leader's ring has wrapped past genesis can
    never be served by AppendEntries (its needed prefix is gone): the
    stale→snapshot-install path must hand it the full state — including
    the membership view — and it must then count toward the new quorum."""
    rg = make(peers=5, voters=3)
    rg.wait_for_leaders()
    # push well past the ring's 32 entries so that it has wrapped
    tags = [rg.submit(0, ap.OP_LONG_ADD, 1) for _ in range(80)]
    rg.run_until(tags, max_rounds=200)

    t = rg.add_peer(0, 3)
    resolve(rg, t, max_rounds=150)
    for _ in range(40):  # replication/install rounds
        rg.step_round()
    member = np.asarray(rg.state.member[0])
    applied = np.asarray(rg.state.applied_index[0])
    # the added lane holds the full applied state and the 4-voter config
    assert applied[3] == applied.max(), "added lane not caught up"
    assert member[3] == 0b01111, f"installed view wrong: {member[3]:b}"
    assert rg.value(0, peer=3) == 80

    # and it genuinely votes: with original voter 0 cut, the 4-voter
    # quorum (3) is reachable ONLY if the installed lane 3 acks —
    # {1,2} alone is 2 < 3
    assert commits_under(rg, isolate(rg, [0]), rounds=60)


def test_membership_sharded_over_mesh():
    """The dynamic-membership path (latest-config view scans, masked
    rank-select quorums, population_count) compiled and stepped over a
    multi-device mesh — join, leave, and leader self-removal all work
    with the group axis sharded (XLA inserts the collectives)."""
    from copycat_tpu.parallel import make_mesh

    mesh = make_mesh(groups=8)
    rg = make(voters=3, mesh=mesh)
    rg.wait_for_leaders()
    t = rg.submit(3, ap.OP_LONG_ADD, 9)
    assert resolve(rg, t) == 9
    t3 = rg.add_peer(3, 3)
    t4 = rg.add_peer(3, 4)
    rg.run_until([t3, t4], max_rounds=200)
    assert rg.voting_members(3) == [0, 1, 2, 3, 4]
    tr = rg.remove_peer(3, rg.leader(3))
    rg.run_until([tr], max_rounds=200)
    assert len(rg.voting_members(3)) == 4
    t = rg.submit(3, ap.OP_LONG_ADD, 1)
    assert resolve(rg, t) == 10
    # untouched groups keep the initial 3-voter config
    assert rg.voting_members(0) == [0, 1, 2]


def test_api_validation():
    # raw config submits get add_peer/remove_peer's validation
    rg = make(peers=3)
    with pytest.raises(ValueError):
        rg.submit(0, ap.OP_CFG_ADD, 7)          # lane out of range
    static = device_plane()
    with pytest.raises(ValueError):
        static.submit(0, ap.OP_CFG_ADD, 1)      # static engine
    with pytest.raises(ValueError):
        static.add_peer(0, 1)
    # voters == num_peers is the all-lanes default — fine without dyn
    device_plane(voters=3)
    with pytest.raises(ValueError):
        device_plane(voters=2)


def test_static_path_unchanged():
    """dynamic_membership=False keeps today's step semantics bit-for-bit:
    identical state evolution with member carried untouched."""
    a = device_plane()
    b = device_plane(DYN)
    for _ in range(40):
        a.step_round()
        b.step_round()
    for g in range(2):
        a.submit(g, ap.OP_LONG_ADD, 2)
        b.submit(g, ap.OP_LONG_ADD, 2)
    for _ in range(10):
        a.step_round()
        b.step_round()
    for la, lb in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

"""Tests for the batched TPU consensus engine (ops/ models/ parallel/).

Mirrors the reference's "real consensus, fake network" strategy
(SURVEY.md §4): full elections, replication, commitment and apply run for
every group, with message delivery masked for partitions — all inside the
compiled step.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from copycat_tpu.models import RaftGroups  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.ops.consensus import LEADER, Config  # noqa: E402

from engines import device_plane, short_ring  # noqa: E402


class LeaderLedger:
    """Tracks (group, term) -> leader across rounds; asserts election safety."""

    def __init__(self):
        self.seen = {}

    def observe(self, rg: RaftGroups):
        role = np.asarray(rg.state.role)
        term = np.asarray(rg.state.term)
        for g, p in zip(*np.nonzero(role == LEADER)):
            key = (int(g), int(term[g, p]))
            prev = self.seen.setdefault(key, int(p))
            assert prev == int(p), f"two leaders for group {g} term {term[g, p]}"


def test_every_group_elects_one_leader():
    rg = device_plane()
    ledger = LeaderLedger()
    leaders = None
    for _ in range(100):
        out = rg.step_round()
        ledger.observe(rg)
        leaders = np.asarray(out.leader)
        if (leaders >= 0).all():
            break
    assert (leaders >= 0).all()
    # exactly one leader lane per group at max term
    role = np.asarray(rg.state.role)
    assert (np.sum(role == LEADER, axis=1) >= 1).all()


def test_counter_ops_commit_and_replicate():
    rg = device_plane()
    rg.wait_for_leaders()
    tags = [rg.submit(0, ap.OP_LONG_ADD, 1) for _ in range(10)]
    tags += [rg.submit(1, ap.OP_LONG_ADD, 5) for _ in range(4)]
    rg.run_until(tags)
    # addAndGet semantics: strictly increasing prefix sums per group
    g0 = [rg.results[t] for t in tags[:10]]
    g1 = [rg.results[t] for t in tags[10:]]
    assert g0 == list(range(1, 11))
    assert g1 == [5, 10, 15, 20]
    # replicas converge once followers learn the commit index
    rg.run(5)
    val = np.asarray(rg.state.resources.value)
    assert (val[0] == 10).all()
    assert (val[1] == 20).all()


def test_value_set_cas_get_semantics():
    rg = device_plane()
    rg.wait_for_leaders()
    t_set = rg.submit(0, ap.OP_VALUE_SET, 5)
    t_cas_hit = rg.submit(0, ap.OP_VALUE_CAS, 5, 7)
    t_cas_miss = rg.submit(0, ap.OP_VALUE_CAS, 5, 9)
    t_gas = rg.submit(0, ap.OP_VALUE_GET_AND_SET, 42)
    t_get = rg.submit(0, ap.OP_VALUE_GET)
    rg.run_until([t_set, t_cas_hit, t_cas_miss, t_gas, t_get])
    assert rg.results[t_cas_hit] == 1
    assert rg.results[t_cas_miss] == 0
    assert rg.results[t_gas] == 7
    assert rg.results[t_get] == 42


def test_leader_partition_failover_preserves_committed_writes():
    rg = device_plane()
    ledger = LeaderLedger()
    rg.wait_for_leaders()
    t1 = rg.submit(0, ap.OP_LONG_ADD, 7)
    rg.run_until([t1])
    old_leader = rg.leader(0)
    assert old_leader >= 0

    # Partition the leader from both followers.
    deliver = np.ones((rg.num_groups, 3, 3), bool)
    deliver[0, old_leader, :] = False
    deliver[0, :, old_leader] = False
    rg.deliver = jnp.asarray(deliver)
    for _ in range(60):
        rg.step_round()
        ledger.observe(rg)
        new_leader = rg.leader(0)
        if new_leader >= 0 and new_leader != old_leader:
            break
    assert rg.leader(0) != old_leader

    # The new leader must still have the committed write (leader completeness).
    t2 = rg.submit(0, ap.OP_LONG_ADD, 3)
    rg.run_until([t2], max_rounds=100)
    assert rg.results[t2] == 10

    # Heal; the deposed leader catches up and converges.
    rg.deliver = jnp.ones_like(rg.deliver)
    rg.run(20)
    ledger.observe(rg)
    val = np.asarray(rg.state.resources.value)
    assert (val[0] == 10).all()


def test_exactly_once_under_partitions():
    """The provable-loss retry protocol end to end: every queue-managed
    op submitted across random partitions eventually resolves, and the
    final counter equals the number of increments — nothing lost
    (entries overwritten by new leaders get re-submitted) and nothing
    double-applied (re-submission only on proof of loss)."""
    rng = np.random.default_rng(11)
    rg = device_plane()
    rg.wait_for_leaders()
    tags = {g: [] for g in range(3)}
    for r in range(240):
        if r % 2 == 0:
            g = int(rng.integers(3))
            tags[g].append(rg.submit(g, ap.OP_LONG_ADD, 1))
        deliver = None
        if 0 < (r % 24) < 10:  # partition window
            deliver = jnp.asarray(
                rng.random((rg.num_groups, 3, 3)) > 0.3)
        rg.step_round(deliver=deliver)
    all_tags = [t for ts in tags.values() for t in ts]
    rg.run_until(all_tags, max_rounds=300)
    for g, ts in tags.items():
        t = rg.submit(g, ap.OP_LONG_ADD, 0)
        rg.run_until([t])
        assert rg.results[t] == len(ts), \
            f"group {g}: {rg.results[t]} applied vs {len(ts)} submitted"


def test_submit_batch_matches_scalar_submits():
    """The vectorized bulk-submit path must be behaviorally identical to
    per-op submits: same per-group FIFO order, same results, tags
    aligned with the input."""
    rg = device_plane()
    rg.wait_for_leaders()
    groups = np.array([0, 0, 1, 2, 3, 3, 3])
    deltas = np.array([1, 2, 10, 5, 7, 1, 2])
    tags = rg.submit_batch(groups, ap.OP_LONG_ADD, deltas)
    assert tags.shape == (7,)
    rg.run_until(tags.tolist())
    # prefix sums per group prove FIFO within each group
    assert [rg.results[t] for t in tags.tolist()] == [1, 3, 10, 5, 7, 8, 10]
    # interleaves with scalar submits
    t = rg.submit(0, ap.OP_LONG_ADD, 4)
    more = rg.submit_batch([0], ap.OP_LONG_ADD, [5])
    rg.run_until([t, int(more[0])])
    assert rg.results[t] == 7 and rg.results[int(more[0])] == 12
    with pytest.raises(ValueError):
        rg.submit_batch([0], ap.OP_CFG_ADD, [1])


def test_checkquorum_releases_asymmetric_partition():
    """Stable ASYMMETRIC partition: the leader's outbound links to two of
    its three followers are cut, everything else stays up. The reachable
    follower is kept sticky by heartbeats (it refuses RequestVote —
    leader stickiness), so without CheckQuorum the group would wedge
    forever at 2 < 3 acks. CheckQuorum steps the quorumless leader down
    after an election timeout, heartbeats stop, and the fully-connected
    majority elects a working leader."""
    # shape: four peers, a leader that reaches one of its three followers
    rg = RaftGroups(1, 4, log_slots=32)
    rg.wait_for_leaders()
    lead = rg.leader(0)
    others = [p for p in range(4) if p != lead]
    dl = np.ones((1, 4, 4), bool)
    dl[0, lead, others[1]] = False
    dl[0, lead, others[2]] = False
    tag = rg.submit(0, ap.OP_LONG_ADD, 5)
    for _ in range(80):
        rg.step_round(deliver=jnp.asarray(dl))
        if tag in rg.results:
            break
    assert rg.results.get(tag) == 5, \
        "group wedged under asymmetric partition (CheckQuorum inactive?)"


def test_safety_under_random_partitions():
    rg = device_plane(config=Config(append_window=4, applies_per_round=4,
                            timer_min=4, timer_max=9))
    G, P = rg.num_groups, rg.num_peers
    ledger = LeaderLedger()
    rng = np.random.default_rng(7)
    submitted = {g: [] for g in range(G)}
    for round_no in range(250):
        if round_no % 10 == 0:  # reshuffle partitions
            deliver = rng.random((G, P, P)) > 0.25
            rg.deliver = jnp.asarray(deliver)
        if round_no == 180:  # heal for convergence
            rg.deliver = jnp.ones((G, P, P), bool)
        if round_no < 150 and round_no % 3 == 0:
            g = int(rng.integers(G))
            submitted[g].append(rg.submit(g, ap.OP_LONG_ADD, 1))
        rg.step_round()
        ledger.observe(rg)

    # Completed results per group are strictly increasing prefix sums.
    for g in range(G):
        res = [rg.results[t] for t in submitted[g] if t in rg.results]
        assert res == sorted(res)
        assert len(res) == len(set(res))
    # After healing, replicas of each group converge on a single value.
    rg.run(30)
    val = np.asarray(rg.state.resources.value)
    applied = np.asarray(rg.state.applied_index)
    for g in range(G):
        assert len(set(val[g].tolist())) == 1, (g, val[g], applied[g])

    # Committed-prefix log matching across replicas (within ring window).
    log_term = np.asarray(rg.state.log_term)
    log_tag = np.asarray(rg.state.log_tag)
    last = np.asarray(rg.state.last_index)
    commit = np.asarray(rg.state.commit_index)
    L = rg.log_slots
    for g in range(G):
        lo = max(1, int(last[g].max()) - L + 1)
        hi = int(commit[g].min())
        for idx in range(lo, hi + 1):
            slot = (idx - 1) % L
            terms = {int(log_term[g, p, slot]) for p in range(P)
                     if idx > last[g, p] - L and idx <= last[g, p]}
            tags = {int(log_tag[g, p, slot]) for p in range(P)
                    if idx > last[g, p] - L and idx <= last[g, p]}
            assert len(terms) <= 1, (g, idx, terms)
            assert len(tags) <= 1, (g, idx, tags)


def test_stale_follower_caught_up_by_snapshot_install():
    """A follower partitioned past the ring window reconverges via
    host-side snapshot install (``install_snapshots``)."""
    rg = short_ring()
    L = rg.log_slots
    rg.wait_for_leaders()
    leader = rg.leader(0)
    follower = next(p for p in range(3) if p != leader)

    # Fully isolate one follower; quorum of 2 keeps committing far past L.
    deliver = np.ones((rg.num_groups, 3, 3), bool)
    deliver[0, :, follower] = False
    deliver[0, follower, :] = False
    rg.deliver = jnp.asarray(deliver)
    tags = []
    for i in range(3 * L):
        tags.append(rg.submit(0, ap.OP_LONG_ADD, 1))
        rg.step_round()
    rg.run_until(tags, max_rounds=200)
    assert int(np.asarray(rg.state.commit_index)[0, leader]) > L

    # Heal: AppendEntries can no longer serve the follower (beyond the ring);
    # the stale flag must trigger snapshot install and full reconvergence.
    rg.deliver = jnp.ones_like(rg.deliver)
    rg.run(30)
    val = np.asarray(rg.state.resources.value)
    applied = np.asarray(rg.state.applied_index)
    assert (val[0] == 3 * L).all(), (val[0], applied[0])
    assert len(set(applied[0].tolist())) == 1


def test_single_peer_group_commits_immediately():
    rg = RaftGroups(1, 1, log_slots=32)  # shape: a group of one peer
    rg.wait_for_leaders()
    t = rg.submit(0, ap.OP_LONG_ADD, 9)
    rg.run_until([t], max_rounds=20)
    assert rg.results[t] == 9


@pytest.mark.parametrize("mesh_kind", ["groups", "groups_peers"])
def test_sharded_over_mesh(mesh_kind):
    from copycat_tpu.parallel import make_mesh

    if mesh_kind == "groups":
        mesh = make_mesh(groups=8)
        rg = short_ring(mesh=mesh)
    else:
        mesh = make_mesh(groups=2, peers=4)
        # shape: four peer lanes, a multiple of the mesh's peers axis
        rg = RaftGroups(8, 4, log_slots=16, mesh=mesh)
    rg.wait_for_leaders()
    tags = [rg.submit(g, ap.OP_LONG_ADD, g + 1) for g in range(4)]
    rg.run_until(tags)
    for g in range(4):
        assert rg.results[tags[g]] == g + 1


def test_out_latency_tracks_append_to_apply_lag():
    """out_latency = rounds an entry waited in the log before apply (0 when
    the synchronous round replicates+commits+applies it immediately)."""
    rg = device_plane()
    rg.wait_for_leaders()
    tags = [rg.submit(0, ap.OP_LONG_ADD, 1) for _ in range(3)]
    lats = []
    for _ in range(30):
        out = rg.step_round()
        v = np.asarray(out.out_valid)
        lats += list(np.asarray(out.out_latency)[v])
        if all(t in rg.results for t in tags):
            break
    assert all(t in rg.results for t in tags)
    assert lats, "no applied entries observed"
    L = rg.log_slots
    assert all(0 <= x <= L for x in lats), lats


def test_leader_lease_tracks_quorum_contact():
    """The lease bit must be HELD under full delivery and CLEARED within
    one round of the leader losing contact with a quorum — the
    falsifiable core of the BOUNDED_LINEARIZABLE read gate (a served
    atomic read relies on exactly this bit)."""
    rg = device_plane(seed=2)
    leaders = rg.wait_for_leaders()
    rg.run(2)
    assert bool(np.asarray(rg.state.lease).any(axis=1).all()), \
        "full delivery must hold every group's lease"

    # isolate group 0's leader from BOTH followers: next round it cannot
    # assemble a quorum of acks, so its lease must drop (groups 1..3 keep
    # theirs)
    deliver = np.ones((rg.num_groups, 3, 3), bool)
    lead0 = int(leaders[0])
    deliver[0, lead0, :] = False
    deliver[0, :, lead0] = False
    deliver[0, lead0, lead0] = True
    rg.deliver = jnp.asarray(deliver)
    rg.run(1)
    lease = np.asarray(rg.state.lease).any(axis=1)
    assert not lease[0], "isolated leader must lose the lease immediately"
    assert lease[1:].all(), "connected groups keep their leases"

    # heal: the lease returns once a quorum acks again
    rg.deliver = jnp.ones_like(rg.deliver)
    rg.run(3)
    assert np.asarray(rg.state.lease).any(axis=1).all()


def test_step_rounds_fused_matches_single_steps_and_installs_stale():
    """``step_rounds(n)`` is semantically n ``step_round()`` calls with
    empty later rounds — including the deferred snapshot-install branch:
    a follower isolated past the ring window during a FUSED block must
    reconverge the same way it does under single-round stepping
    (round-5 review finding: the stale slice-and-install path had no
    coverage)."""
    rg = short_ring()
    L = rg.log_slots
    rg.wait_for_leaders()
    leader = rg.leader(0)
    follower = next(p for p in range(3) if p != leader)

    deliver = np.ones((rg.num_groups, 3, 3), bool)
    deliver[0, :, follower] = False
    deliver[0, follower, :] = False
    rg.deliver = jnp.asarray(deliver)
    # drive the quorum side far past the ring with FUSED blocks only
    tags = []
    for _ in range(3 * L):
        tags.append(rg.submit(0, ap.OP_LONG_ADD, 1))
        rg.step_rounds(2)
    assert all(t in rg.results for t in tags)
    assert int(np.asarray(rg.state.commit_index)[0, leader]) > L

    # heal; the isolated follower is beyond AppendEntries range, so the
    # fused path's stale branch must snapshot-install it
    rg.deliver = jnp.ones_like(rg.deliver)
    for _ in range(8):
        rg.step_rounds(4)
    val = np.asarray(rg.state.resources.value)
    applied = np.asarray(rg.state.applied_index)
    assert (val[0] == 3 * L).all(), (val[0], applied[0])
    assert len(set(applied[0].tolist())) == 1

    # fused and single-round stepping agree on a fresh workload
    t2 = rg.submit_batch(np.arange(2), ap.OP_LONG_ADD, 5)
    rg.step_rounds(3)
    assert all(t in rg.results for t in t2.tolist())

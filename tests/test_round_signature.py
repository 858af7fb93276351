"""The served round's call signature against the plain programs.

``RaftGroups``' programs donate the state and the key, take the submits
as one buffer, split the key inside and return the outputs packed
(``models/raft_groups.py:_jitted_programs``). None of that may change a
result: driven over seeded nemesis schedules, the engine's outputs and
state equal, leaf for leaf, what the plain ``jax.jit(step)`` /
``query_step`` / ``install_snapshots`` give on the same inputs and keys.
The round that takes a read window's rows along (the fourth program) is
the plain step and then the plain ``query_step`` on the state it left.
"""

from functools import lru_cache, partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.models import checkpoint  # noqa: E402
from copycat_tpu.models.bulk import BulkDriver  # noqa: E402
from copycat_tpu.models.raft_groups import (  # noqa: E402
    RaftGroups, _split_slab)
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.ops.consensus import (  # noqa: E402
    Config,
    Submits,
    init_state,
    install_snapshots,
    query_step,
    step,
)
from copycat_tpu.parallel import make_mesh  # noqa: E402

from engines import G, five_peer, short_ring  # noqa: E402

P, L, S = 3, 16, 4  # engines.short_ring's: the cut of schedule() wraps it


@lru_cache(maxsize=None)
def plain_programs(config: Config) -> tuple:
    """Traced once a module: a fresh ``jax.jit`` a test lowers again."""
    return (jax.jit(partial(step, config=config)),
            jax.jit(partial(query_step, config=config)),
            jax.jit(partial(install_snapshots, config=config)))


class Plain:
    """The reference: plain jitted programs, an eager key split a round,
    a snapshot install whenever a follower fell behind the ring."""

    def __init__(self, seed: int, config: Config = Config()) -> None:
        self.key, init_key = jax.random.split(jax.random.PRNGKey(seed))
        self.state = init_state(G, P, L, init_key, config)
        self.step, self.query, self.install = plain_programs(config)
        self.installs = 0

    def round(self, submits: Submits, deliver, key=None):
        if key is None:
            self.key, key = jax.random.split(self.key)
        self.state, out = self.step(self.state, submits, deliver, key)
        return out

    def install_if_stale(self, out) -> None:
        if bool(np.asarray(out.stale).any()):
            self.state = self.install(self.state, out.stale, out.leader)
            self.installs += 1


def schedule(seed: int, rounds: int):
    """Seeded submits and delivery masks: every round a random half of the
    slots carry an add, and in every second block of 8 rounds one random
    peer of every group is cut off both ways (long enough, at L=16 and
    four appends a round, for it to fall behind the ring)."""
    rng = np.random.default_rng(seed)
    tag = 1
    for r in range(rounds):
        valid = rng.random((G, S)) < 0.5
        tags = np.zeros((G, S), np.int32)
        tags[valid] = np.arange(tag, tag + valid.sum())
        tag += int(valid.sum())
        sub = Submits(opcode=np.where(valid, ap.OP_LONG_ADD, 0).astype(np.int32),
                      a=rng.integers(1, 9, (G, S)).astype(np.int32),
                      b=np.zeros((G, S), np.int32),
                      c=np.zeros((G, S), np.int32),
                      tag=tags, valid=valid)
        deliver = np.ones((G, P, P), bool)
        if (r // 8) % 2:
            if r % 8 == 0:
                cut = rng.integers(0, P, G)
            deliver[np.arange(G), cut, :] = False
            deliver[np.arange(G), :, cut] = False
            deliver[np.arange(G), cut, cut] = True
        yield sub, deliver


def assert_same(got, want, what: str) -> None:
    got_l, want_l = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got_l) == len(want_l), what
    for i, (g, w) in enumerate(zip(got_l, want_l)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, (what, i)
        np.testing.assert_array_equal(g, w, err_msg=f"{what}, leaf {i}")


def one_read_per_group(rg: RaftGroups) -> tuple:
    """``(queries, atomic)``: slot 0 of every group reads its value at
    ATOMIC."""
    sub = rg._empty_submits()
    sub.opcode[:, 0] = ap.OP_VALUE_GET
    sub.valid[:, 0] = True
    return sub, sub.valid.copy()


def read_all(rg: RaftGroups, ref: Plain) -> None:
    """The engine's query program against the plain ``query_step`` on the
    same state."""
    sub, atomic = one_read_per_group(rg)
    assert_same(rg._run_query(sub, atomic), ref.query(ref.state, sub, atomic),
                "query")


def drive(rg: RaftGroups, ref: Plain, seed: int, rounds: int,
          ride: bool = False) -> None:
    """``ride``: every round takes a read of every group at ATOMIC along
    (``step_round(query=)``); what it brings back is what the plain query
    program reads from the state the plain step left, served or not."""
    reads = one_read_per_group(rg)
    for r, (sub, deliver) in enumerate(schedule(seed, rounds)):
        query = rg.stage_query_vector(
            np.arange(G), ap.OP_VALUE_GET, atomic=True) if ride else None
        want = ref.round(sub, deliver)
        assert_same(rg.step_round(submits=sub, deliver=deliver, query=query),
                    want, f"round {r}")
        if ride:
            assert_same(_split_slab(query.rode),
                        [x[:, :1] for x in ref.query(ref.state, *reads)],
                        f"round {r}'s reads")
        ref.install_if_stale(want)
    assert_same(rg.state, ref.state, "final state")
    assert_same(rg._key, ref.key, "carried key")
    read_all(rg, ref)


@pytest.mark.parametrize("seed", [0, 1, 2147483725])
def test_step_round_equals_the_plain_step(seed):
    rg, ref = short_ring(seed=seed), Plain(seed)
    drive(rg, ref, seed, rounds=48)
    # the schedule reached the snapshot install, which donates too
    assert ref.installs > 0
    # healed: every read serves, and the vector lane reads what the plain
    # program reads
    full = np.ones((G, P, P), bool)
    empty = rg._empty_submits()
    sub, atomic = one_read_per_group(rg)
    for _ in range(60):
        want = ref.round(empty, full)
        rg.step_round(submits=empty, deliver=full)
        ref.install_if_stale(want)
        results, served = ref.query(ref.state, sub, atomic)
        if np.asarray(served)[:, 0].all():
            break
    got = rg.drive_query_vector(np.arange(G), ap.OP_VALUE_GET, atomic=True)
    np.testing.assert_array_equal(got, np.asarray(results)[:, 0])
    assert_same(rg.state, ref.state, "state after the reads")


def test_a_round_with_reads_equals_the_plain_step_then_the_plain_query():
    """Over the nemesis schedule, snapshot installs and unserved reads
    included: one call, the two plain programs' results."""
    rg, ref = short_ring(seed=36), Plain(36)
    compiled = rg._round_query._cache_size()
    drive(rg, ref, 36, rounds=48, ride=True)
    assert ref.installs > 0
    # one width of reads: one program (none, had another test met it)
    assert rg._round_query._cache_size() - compiled <= 1


@pytest.mark.parametrize("ride", [False, True], ids=["round", "with-reads"])
def test_step_round_equals_the_plain_step_on_a_mesh(ride):
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8 virtual CPU devices (conftest)")
    mesh = make_mesh(groups=8)
    rg, ref = short_ring(seed=5, mesh=mesh), Plain(5)
    drive(rg, ref, 5, rounds=24, ride=ride)
    assert len(rg.state.term.devices()) == 8
    assert "groups" in str(rg.state.log_term.sharding.spec)


@pytest.mark.parametrize("n", [2, 4])
def test_step_rounds_equals_n_plain_steps(n):
    """The fused program: round 0 carries the queue's submits, the rest
    run empty, under the keys ``split(k, n)`` of one carried split."""
    seed = 7
    rg, ref = short_ring(seed=seed), Plain(seed)
    full = np.ones((G, P, P), bool)
    empty = rg._empty_submits()
    for _ in range(12):  # elect
        ref.round(empty, full)
        rg.step_round()
    seen = []
    harvest = rg._harvest
    rg._harvest = lambda out: (seen.append(out), harvest(out))[1]
    for burst in range(3):
        for g in range(G):
            rg.submit(g, ap.OP_LONG_ADD, g + burst + 1)
        sub = rg._build_submits()
        rg._staged_sub = sub  # what step_rounds will stage
        rg.step_rounds(n)
        ref.key, k = jax.random.split(ref.key)
        keys = jax.random.split(k, n)
        want = [ref.round(sub if i == 0 else empty, full, keys[i])
                for i in range(n)]
        assert len(seen) == n
        for i, (g, w) in enumerate(zip(seen, want)):
            assert_same(g, w, f"burst {burst}, round {i}")
        seen.clear()
    assert_same(rg.state, ref.state, "final state")
    assert_same(rg._key, ref.key, "carried key")


# -- donation --------------------------------------------------------------

def live(tree) -> list[bool]:
    return [not x.is_deleted() for x in jax.tree.leaves(tree)]


@pytest.fixture
def rg():
    groups = short_ring(seed=11)
    groups.wait_for_leaders()
    return groups


def test_a_round_donates_the_state_and_the_key(rg):
    for advance in (rg.step_round, lambda: rg.step_rounds(3)):
        old, old_key = rg.state, rg._key
        advance()
        assert not any(live(old)) and old_key.is_deleted()
        assert all(live(rg.state)) and not rg._key.is_deleted()
    # neither the delivery mask nor a query's state is donated
    assert not rg.deliver.is_deleted()
    rg.drive_query_vector(np.arange(G), ap.OP_VALUE_GET)
    assert all(live(rg.state))


def test_a_fresh_state_holds_no_buffer_twice():
    state = short_ring().state
    pointers = [x.unsafe_buffer_pointer() for x in jax.tree.leaves(state)
                if x.size]
    assert len(set(pointers)) == len(pointers)


def test_checkpoint_restore_then_a_round(rg, tmp_path):
    tags = [rg.submit(g, ap.OP_LONG_ADD, g + 1) for g in range(G)]
    rg.run_until(tags)
    checkpoint.save(rg, tmp_path / "snap.npz")
    rg.step_round()  # the saved engine goes on after its leaves were read
    back = checkpoint.load(tmp_path / "snap.npz")
    old = back.state
    more = [back.submit(g, ap.OP_LONG_ADD, 100) for g in range(G)]
    back.run_until(more)
    assert [back.results[t] for t in more] == [g + 101 for g in range(G)]
    assert not any(live(old)) and all(live(back.state))


def test_voting_members_between_rounds():
    rg = five_peer(Config(dynamic_membership=True), seed=3, voters=3)
    rg.wait_for_leaders()
    assert rg.voting_members(0) == [0, 1, 2]
    rg.run_until([rg.add_peer(0, 3)])
    rg.run(4)
    assert rg.voting_members(0) == [0, 1, 2, 3]
    assert rg.leader(0) >= 0


def test_the_shallow_bulk_drive_on_the_donated_engine(rg):
    groups = np.repeat(np.arange(G), 6)
    res = BulkDriver(rg).drive(groups, ap.OP_LONG_ADD, 1)
    np.testing.assert_array_equal(
        np.asarray(res.results).reshape(G, 6), np.tile(np.arange(1, 7), (G, 1)))
    got = BulkDriver(rg).drive_queries(np.arange(G), ap.OP_VALUE_GET,
                                       consistency="atomic")
    np.testing.assert_array_equal(got, np.full(G, 6))
    rg.step_round()
    assert all(live(rg.state))


def test_snapshot_install_after_a_stale_follower(rg):
    """A follower cut off until the ring has wrapped is caught up by the
    install program, which donates the state it is handed."""
    deliver = np.ones((G, P, P), bool)
    cut = (np.asarray(rg.wait_for_leaders()) + 1) % P
    deliver[np.arange(G), cut, :] = False
    deliver[np.arange(G), :, cut] = False
    deliver[np.arange(G), cut, cut] = True
    rg.deliver = jax.numpy.asarray(deliver)
    for _ in range(2 * L // S):  # the ring wraps past the cut follower
        for g in range(G):
            for _ in range(S):
                rg.submit(g, ap.OP_LONG_ADD, 1)
        rg.step_round()
    rg.deliver = jax.numpy.ones((G, P, P), bool)
    installed = False
    for _ in range(8):
        before = rg.state
        out = rg.step_round()
        if out.stale.any():
            installed = True
            assert not any(live(before)) and all(live(rg.state))
    assert installed
    rg.run_until(list(rg._inflight))
    applied = np.asarray(rg.state.applied_index)
    assert (applied.min(axis=1) == applied.max(axis=1)).all()

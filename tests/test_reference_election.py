"""``benchmarks/reference_election.PlainElections`` (the election cell's plain
reference) on hand-written cases from ``LeaderElectionState.java``'s
description (SURVEY.md sec. 2: a leader and a FIFO of waiting listeners, the
leader's unlisten or its session's end promotes the next, ``isLeader`` checks
the epoch), and against the CPU ``LeaderElectionState`` on a seeded history.
It imports nothing of ``copycat_tpu``.
"""

import importlib.util
import os
import random
import re

import pytest

PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "reference_election.py")


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("reference_election", PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_it_imports_nothing_of_the_program():
    text = open(PATH).read()
    assert not re.search(r"^\s*(from|import)\s+copycat_tpu", text, re.M)
    assert not re.search(r"^\s*from\s+\.", text, re.M)


def test_the_first_listen_wins_and_the_rest_wait_in_order(ref):
    plain = ref.PlainElections(2)
    assert plain.listen(0, "a", 11) == 11         # vacant: elected at once
    assert plain.listen(0, "b", 12) is None       # led: waits
    assert plain.listen(0, "c", 15) is None
    assert (plain.leader(0), plain.epoch(0), plain.waiting(0)) == (
        "a", 11, ["b", "c"])
    assert plain.leader(1) is None and plain.epoch(1) is None
    assert plain.is_leader(0, 11) and not plain.is_leader(0, 12)
    assert not plain.is_leader(1, 0)              # vacant: no epoch is current


def test_a_listen_twice_is_a_listen_once(ref):
    plain = ref.PlainElections(1)
    plain.listen(0, "a", 1)
    plain.listen(0, "b", 2)
    assert plain.listen(0, "b", 3) is None and plain.listen(0, "a", 4) is None
    assert (plain.leader(0), plain.epoch(0), plain.waiting(0)) == (
        "a", 1, ["b"])


def test_the_leaders_unlisten_promotes_the_first_in_line(ref):
    plain = ref.PlainElections(1)
    for index, who in enumerate("abc", 1):
        plain.listen(0, who, index)
    assert plain.unlisten(0, "a", 7) == ("b", 7)  # told, at the hand-over
    assert plain.is_leader(0, 7) and not plain.is_leader(0, 1)   # a's is stale
    assert plain.unlisten(0, "b", 9) == ("c", 9)
    assert plain.unlisten(0, "c", 12) is None     # nobody waits: vacant
    assert plain.leader(0) is None and not plain.is_leader(0, 9)
    assert plain.listen(0, "a", 13) == 13         # and open to the next


def test_a_waiting_candidate_that_unlistens_leaves_the_line(ref):
    plain = ref.PlainElections(1)
    for index, who in enumerate("abcd", 1):
        plain.listen(0, who, index)
    assert plain.unlisten(0, "c", 5) is None
    assert plain.unlisten(0, "z", 6) is None      # never listed: nothing
    assert (plain.leader(0), plain.epoch(0), plain.waiting(0)) == (
        "a", 1, ["b", "d"])


def test_a_sessions_end_fails_over_and_passes_over_its_own(ref):
    """``close:36-49``: the dead session's candidates are unlisted; where
    one of them led, the first waiting candidate that is alive is told; one
    of its own standing next in line is passed over."""
    plain = ref.PlainElections(2)
    for index, who in enumerate(["s1", "s2", "t", "u"], 1):
        plain.listen(0, who, index)
    dead = {"s1", "s2"}
    assert plain.session_end(0, ["s1", "s2"], 9, lambda c: c not in dead) \
        == ("t", 9)
    assert (plain.leader(0), plain.epoch(0), plain.waiting(0)) == (
        "t", 9, ["u"])
    # a dead waiter only: the leader stays, nobody is told
    plain.listen(1, "t", 20)
    plain.listen(1, "s1", 21)
    assert plain.session_end(1, ["s1"], 30, lambda c: c not in dead) is None
    assert (plain.leader(1), plain.epoch(1), plain.waiting(1)) == (
        "t", 20, [])
    # the leader dies and nobody alive waits: vacant
    assert plain.session_end(1, ["t"], 31, lambda c: False) is None
    assert plain.leader(1) is None


def test_replay_gives_an_elections_leaders_in_order(ref):
    history = [(3, ref.LISTEN, "a"), (4, ref.LISTEN, "b"),
               (6, ref.LISTEN, "c"), (8, ref.UNLISTEN, "a"),
               (9, ref.LISTEN, "a"), (12, ref.END, ["b"]),
               (15, ref.UNLISTEN, "c"), (17, ref.UNLISTEN, "a")]
    ended = {"b": 12}
    plain = ref.PlainElections(1)
    leaders = ref.replay(
        plain, 0, history,
        lambda c, index: c not in ended or index < ended[c])
    assert leaders == [("a", 3), ("b", 8), ("c", 12), ("a", 15)]
    epochs = [epoch for _, epoch in leaders]
    assert epochs == sorted(set(epochs))          # an election's epochs rise
    assert plain.leader(0) is None


def test_the_plain_elections_and_the_cpu_state_machine_agree(ref):
    """Against ``coordination/state.py``'s ``LeaderElectionState`` on a
    seeded sequence of 6,000 listens, unlistens and session ends over 12
    elections and 8 sessions: who leads and who waits after every step, and
    who was told what. (A listen of a candidate already listed is left out:
    the CPU machine lists a leader that listens again a second time.)"""
    from copycat_tpu.coordination import commands as oc
    from copycat_tpu.coordination.state import LeaderElectionState
    from copycat_tpu.server.state_machine import Commit

    class Session:
        def __init__(self, sid):
            self.id, self.events, self.is_open = sid, [], True

        def publish(self, event, message):
            assert event == "elect"
            self.events.append(message)

    class Log:
        def clean(self, index):
            pass

    n, rng = 12, random.Random(45)
    plain = ref.PlainElections(n)
    machines = [LeaderElectionState() for _ in range(n)]
    # a session's instance of an election is a candidate: one session an
    # election here, so the candidate is the session
    sessions = {s: Session(s) for s in range(8)}
    next_session = 8
    told: list[tuple[int, int]] = []       # (candidate, epoch) by the plain
    for index in range(1, 6001):
        e = rng.randrange(n)
        listed = [plain.leader(e), *plain.waiting(e)]
        listed = [c for c in listed if c is not None]
        roll = rng.random()
        if roll < 0.02 and sessions:
            # a session ends: every election it stands in, in order
            sid = rng.choice(sorted(sessions))
            session = sessions.pop(sid)
            session.is_open = False
            for k in range(n):
                got = plain.session_end(k, [sid], index,
                                        lambda c: c in sessions)
                if got is not None:
                    told.append(got)
                machines[k].close(session)
            sessions[next_session] = Session(next_session)
            next_session += 1
        elif listed and roll < 0.5:
            sid = rng.choice(listed)
            got = plain.unlisten(e, sid, index, lambda c: c in sessions)
            if got is not None:
                told.append(got)
            machines[e].unlisten(Commit(index, sessions[sid], 0.0,
                                        oc.ElectionUnlisten(), Log()))
        else:
            free = [s for s in sessions if s not in listed]
            if not free:
                continue
            sid = rng.choice(free)
            epoch = plain.listen(e, sid, index)
            if epoch is not None:
                told.append((sid, epoch))
            machines[e].listen(Commit(index, sessions[sid], 0.0,
                                      oc.ElectionListen(), Log()))
        for k in range(n):
            state = machines[k]
            leader = state._leader.session.id if state._leader else None
            assert leader == plain.leader(k)
            assert list(state._listeners) == plain.waiting(k)
    # the CPU machine's epoch is the winner's own listen; the plain one's
    # the hand-over: both name the same candidates in the same order
    assert len(told) > 1000
    counts: dict[int, int] = {}
    for c, _ in told:
        counts[c] = counts.get(c, 0) + 1
    for sid, session in sessions.items():
        assert len(session.events) == counts.get(sid, 0)

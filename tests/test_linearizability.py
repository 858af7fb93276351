"""Linearizability verification (copycat_tpu.testing).

Unit-tests the Wing & Gong checker on hand-crafted histories, then runs
Jepsen-style nemesis schedules against the batched consensus engine and
checks the recorded histories — BASELINE.md config #5's verification layer
and the in-tree replacement for the reference's external atomix-jepsen
suite (SURVEY.md §4).
"""

import math

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.testing import (  # noqa: E402
    HOp,
    HistoryRecorder,
    LockModel,
    MapModel,
    Nemesis,
    RegisterModel,
    check_linearizable,
)

from engines import device_plane  # noqa: E402


# ---------------------------------------------------------------------------
# checker unit tests
# ---------------------------------------------------------------------------

def test_checker_rejects_stale_read():
    h = [HOp(1, ("set", 1), 0, invoke=0, complete=1),
         HOp(2, ("get",), 0, invoke=2, complete=3)]  # reads 0 AFTER set(1)
    assert not check_linearizable(h, RegisterModel).ok


def test_checker_accepts_concurrent_read():
    h = [HOp(1, ("set", 1), 0, invoke=0, complete=5),
         HOp(2, ("get",), 0, invoke=1, complete=2)]  # overlaps the set
    assert check_linearizable(h, RegisterModel).ok


def test_checker_incomplete_op_may_apply():
    # a crashed set(5) explains the later read of 5
    h = [HOp(1, ("set", 5), None, invoke=0, complete=math.inf),
         HOp(2, ("get",), 5, invoke=3, complete=4)]
    assert check_linearizable(h, RegisterModel).ok


def test_checker_incomplete_op_may_never_apply():
    h = [HOp(1, ("set", 5), None, invoke=0, complete=math.inf),
         HOp(2, ("get",), 0, invoke=3, complete=4)]
    assert check_linearizable(h, RegisterModel).ok


def test_checker_cas_chain():
    h = [HOp(1, ("set", 1), 0, 0, 1),
         HOp(2, ("cas", 1, 2), 1, 2, 3),
         HOp(3, ("cas", 1, 9), 0, 4, 5),
         HOp(4, ("get",), 2, 6, 7)]
    assert check_linearizable(h, RegisterModel).ok
    # two CAS(1→x) both succeeding from one set(1) is impossible
    h_bad = [HOp(1, ("set", 1), 0, 0, 1),
             HOp(2, ("cas", 1, 2), 1, 2, 3),
             HOp(3, ("cas", 1, 9), 1, 4, 5)]
    assert not check_linearizable(h_bad, RegisterModel).ok


def test_checker_lock_model():
    good = [HOp(1, ("acquire", 7), 1, 0, 1),
            HOp(2, ("acquire", 8), 0, 2, 3),
            HOp(3, ("release", 7), 1, 4, 5),
            HOp(4, ("acquire", 8), 1, 6, 7)]
    assert check_linearizable(good, LockModel).ok
    # two non-overlapping successful acquires without a release
    bad = [HOp(1, ("acquire", 7), 1, 0, 1),
           HOp(2, ("acquire", 8), 1, 2, 3)]
    assert not check_linearizable(bad, LockModel).ok


# ---------------------------------------------------------------------------
# engine histories under nemesis
# ---------------------------------------------------------------------------

def _drain(rec, rg, max_rounds=300):
    for _ in range(max_rounds):
        if not rec._pending:
            break
        rec.tick()


REGISTER_OPS = [
    (ap.OP_VALUE_SET, ("set",)),
    (ap.OP_VALUE_GET, ("get",)),
    (ap.OP_VALUE_CAS, ("cas",)),
    (ap.OP_LONG_ADD, ("add",)),
]


def test_register_histories_linearizable_under_nemesis():
    import numpy as np
    G = 4
    rg = device_plane()
    rg.wait_for_leaders()
    rec = HistoryRecorder(rg)
    nemesis = Nemesis(rg, seed=11, period=12)
    rng = np.random.default_rng(5)

    for round_no in range(180):
        nemesis.tick()
        if round_no % 2 == 0:
            g = int(rng.integers(G))
            kind = int(rng.integers(4))
            opcode, (name,) = REGISTER_OPS[kind]
            if name == "set":
                v = int(rng.integers(1, 50))
                rec.invoke(g, opcode, ("set", v), a=v)
            elif name == "get":
                rec.invoke(g, opcode, ("get",))
            elif name == "cas":
                e, u = int(rng.integers(0, 50)), int(rng.integers(1, 50))
                rec.invoke(g, opcode, ("cas", e, u), a=e, b=u)
            else:
                d = int(rng.integers(1, 5))
                rec.invoke(g, opcode, ("add", d), a=d)
        rec.tick()
    nemesis.heal()
    _drain(rec, rg)

    for g in range(G):
        hist = rec.history(g)
        assert len(hist) > 10
        res = check_linearizable(hist, RegisterModel)
        assert res.ok, f"group {g} history not linearizable: {hist}"


def test_map_histories_linearizable_under_nemesis():
    import numpy as np
    G = 2
    rg = device_plane()
    rg.wait_for_leaders()
    rec = HistoryRecorder(rg)
    nemesis = Nemesis(rg, seed=3, period=15)
    rng = np.random.default_rng(8)

    for round_no in range(150):
        nemesis.tick()
        if round_no % 3 == 0:
            g = int(rng.integers(G))
            k = int(rng.integers(1, 4))
            kind = int(rng.integers(3))
            if kind == 0:
                v = int(rng.integers(1, 100))
                rec.invoke(g, ap.OP_MAP_PUT, ("put", k, v), a=k, b=v)
            elif kind == 1:
                rec.invoke(g, ap.OP_MAP_GET, ("get", k), a=k)
            else:
                rec.invoke(g, ap.OP_MAP_REMOVE, ("remove", k), a=k)
        rec.tick()
    nemesis.heal()
    _drain(rec, rg)

    for g in range(G):
        hist = rec.history(g)
        assert len(hist) > 10
        assert check_linearizable(hist, MapModel).ok


def test_trylock_histories_linearizable_under_nemesis():
    import numpy as np
    rg = device_plane()
    rg.wait_for_leaders()
    rec = HistoryRecorder(rg)
    nemesis = Nemesis(rg, seed=7, period=10, faults=("heal", "loss"))
    rng = np.random.default_rng(2)
    held: set[int] = set()

    for round_no in range(120):
        nemesis.tick()
        if round_no % 4 == 0:
            who = int(rng.integers(1, 5))
            if who in held and rng.random() < 0.7:
                rec.invoke(0, ap.OP_LOCK_RELEASE, ("release", who), a=who)
                held.discard(who)
            else:
                # immediate try-lock only (b=0) — synchronous result
                rec.invoke(0, ap.OP_LOCK_ACQUIRE, ("acquire", who),
                           a=who, b=0)
                held.add(who)
        rec.tick()
    nemesis.heal()
    _drain(rec, rg)

    hist = rec.history(0)
    assert len(hist) > 10
    assert check_linearizable(hist, LockModel).ok


def test_atomic_lease_reads_linearizable_under_nemesis():
    """Half the reads ride the lease-gated ATOMIC query lane (no log
    append, served only when the leader holds a quorum-acked lease);
    interleaved with writes under partitions, every history must still
    linearize — the leader-lease soundness claim (round-3 directive #8,
    reference Consistency.java:157-176 BOUNDED_LINEARIZABLE)."""
    import numpy as np
    G = 4
    rg = device_plane()
    rg.wait_for_leaders()
    rec = HistoryRecorder(rg)
    nemesis = Nemesis(rg, seed=21, period=12)
    rng = np.random.default_rng(9)

    for round_no in range(180):
        nemesis.tick()
        if round_no % 2 == 0:
            g = int(rng.integers(G))
            kind = int(rng.integers(4))
            if kind == 0:
                v = int(rng.integers(1, 50))
                rec.invoke(g, ap.OP_VALUE_SET, ("set", v), a=v)
            elif kind == 1:
                d = int(rng.integers(1, 5))
                rec.invoke(g, ap.OP_LONG_ADD, ("add", d), a=d)
            else:
                # reads: half lease-lane ATOMIC, half through the log
                query = "atomic" if kind == 2 else None
                rec.invoke(g, ap.OP_VALUE_GET, ("get",), query=query)
        rec.tick()
    nemesis.heal()
    _drain(rec, rg)

    served = rg.metrics.counter("queries_served").value
    assert served > 0, "no read was ever lease-served"
    for g in range(G):
        hist = rec.history(g)
        assert len(hist) > 10
        res = check_linearizable(hist, RegisterModel)
        assert res.ok, f"group {g} lease-read history not linearizable"

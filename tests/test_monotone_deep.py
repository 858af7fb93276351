"""Monotone-tag accept gate + deep pipelined bulk drive (round 4).

The gate (``Config.monotone_tag_accept``, ops/consensus.py) is the
device-side analogue of the reference client's session command
sequencing (Copycat client runtime — SURVEY §2.3): a submit is accepted
only when its tag is exactly (max live-ring stream tag) + 1 + its rank
among the window's valid slots. That makes per-group FIFO
device-enforced and duplicate re-sends idempotent, which is what lets
``models/bulk.py``'s deep drive dispatch blindly with ZERO blocking
fetches per round.
"""

from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from copycat_tpu.models import BulkDriver  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.ops.consensus import (  # noqa: E402
    Submits,
    full_delivery,
    step,
)

from engines import G, MONOTONE, device_plane  # noqa: E402

#: elections within a few rounds of a cut, for the failover tests
QUICK = MONOTONE._replace(timer_min=2, timer_max=4)


@pytest.fixture(scope="module")
def rg():
    groups = device_plane(MONOTONE, seed=7)
    groups.wait_for_leaders()
    return groups


def _submit_window(rg, group, tags, opcode=ap.OP_LONG_ADD, a=1):
    """Hand-build one submit window for ``group`` carrying ``tags``."""
    G, S = rg.num_groups, rg.submit_slots
    sub = rg._empty_submits()
    for s, t in enumerate(tags):
        sub.opcode[group, s] = opcode
        sub.a[group, s] = a
        sub.tag[group, s] = t
        sub.valid[group, s] = True
    return sub


def _step_raw(rg, sub):
    rg.state, rg._key, raw = rg._step(rg.state, rg._stage_round(sub),
                                      rg.deliver, rg._key)
    return rg._fetch_outputs(raw)


def test_gate_accepts_dense_stream_rejects_duplicates_and_gaps(rg):
    # fresh group 0: stream starts at tag 1
    out = _step_raw(rg, _submit_window(rg, 0, [1, 2]))
    acc = np.asarray(out.accepted)[0]
    assert acc[0] and acc[1]
    # duplicate (1,2) again: both rejected — idempotent re-send
    out = _step_raw(rg, _submit_window(rg, 0, [1, 2]))
    acc = np.asarray(out.accepted)[0]
    assert not acc.any()
    # gap (skip 3, send 4): rejected — FIFO enforced on device
    out = _step_raw(rg, _submit_window(rg, 0, [4]))
    assert not np.asarray(out.accepted)[0].any()
    # the successor (3) is accepted, and a same-window gap suffix-rejects
    out = _step_raw(rg, _submit_window(rg, 0, [3, 5]))
    acc = np.asarray(out.accepted)[0]
    assert acc[0] and not acc[1]


def test_gate_election_noop_does_not_break_the_chain():
    groups = device_plane(QUICK, seed=3)
    groups.wait_for_leaders()
    for _ in range(10):  # lease-gated accept needs a warm leader: retry
        out = _step_raw(groups, _submit_window(groups, 1, [1, 2]))
        if np.asarray(out.accepted)[1, :2].all():
            break
    else:
        pytest.fail("initial window never accepted")
    for _ in range(4):  # commit + apply everywhere (leader completeness
        _step_raw(groups, groups._empty_submits())  # preserves them)
    # force a re-election in group 1: isolate the leader for a while
    lead = int(np.asarray(jax.device_get(
        groups.state.leader_hint)).max(axis=1)[1])
    saved = groups.deliver
    groups.deliver = _isolate(groups, 1, lead)
    for _ in range(12):
        _step_raw(groups, groups._empty_submits())
    groups.deliver = saved
    groups.wait_for_leaders()
    # the new leader's log has an election no-op (tag 0) on top of the
    # stream; tag 3 must still be the next accepted
    for _ in range(20):
        out = _step_raw(groups, _submit_window(groups, 1, [3]))
        if np.asarray(out.accepted)[1].any():
            break
    else:
        pytest.fail("successor tag never accepted after re-election")
    # and the duplicate of 3 is still rejected afterwards
    out = _step_raw(groups, _submit_window(groups, 1, [3]))
    assert not np.asarray(out.accepted)[1].any()


def test_compact_leaves_match_full_arrays():
    """Scalar opcode/payload leaves and the [G,1] consecutive-tag leaf
    must behave exactly like full [G,S] arrays."""
    groups = device_plane(MONOTONE, seed=5)
    groups.wait_for_leaders()
    S = groups.submit_slots
    # compact: every group submits tags 1..4, op/a scalar
    sub = Submits(opcode=np.int32(ap.OP_LONG_ADD), a=np.int32(1),
                  b=np.int32(0), c=np.int32(0),
                  tag=np.ones((G, 1), np.int32),
                  valid=np.ones((G, S), bool))
    # the plain step: compact leaves are the deep programs' own form (the
    # served round's program takes six full planes in one buffer)
    plain = jax.jit(partial(step, config=groups.config))

    def _step_compact(rg, sub):
        rg._key, key = jax.random.split(rg._key)
        rg.state, out = plain(rg.state, sub, rg.deliver, key)
        return out

    got = np.zeros((G, S), bool)
    for _ in range(10):  # retry: leaders elected late lack the lease;
        out = _step_compact(groups, sub)  # duplicate re-sends are rejected,
        got |= np.asarray(out.accepted)  # so acceptance is once per op
        if got.all():
            break
    else:
        pytest.fail(f"compact window never fully accepted: {got}")
    for _ in range(4):
        out = _step_raw(groups, groups._empty_submits())
    applied = np.asarray(jax.device_get(
        groups.state.applied_index)).max(axis=1)
    assert (applied >= 4).all()


def test_deep_drive_fifo_across_drives(rg_deep=None):
    groups = device_plane(MONOTONE, seed=11)
    groups.wait_for_leaders()
    driver = BulkDriver(groups)
    g = np.repeat(np.arange(8), 10)
    amounts = np.tile(np.arange(1, 11), 8)
    res = driver.drive(g, ap.OP_LONG_ADD, amounts)
    want = np.tile(np.cumsum(np.arange(1, 11)), 8)
    assert (res.results == want).all()
    # second drive continues each group's stream (tags persist via
    # rg._stream_count) and stays FIFO
    res2 = driver.drive(g, ap.OP_LONG_ADD, 1)
    assert (res2.results.reshape(8, 10)
            == want[-1] + np.arange(1, 11)).all()
    assert (res2.latency_rounds() >= 1).all()


def test_deep_drive_mixed_payloads_map_roundtrip():
    groups = device_plane(MONOTONE, seed=13)
    groups.wait_for_leaders()
    driver = BulkDriver(groups)
    n = 8 * 10
    g = np.repeat(np.arange(8), 10)
    ops = np.where(np.arange(n) % 2 == 0, ap.OP_MAP_PUT, ap.OP_MAP_GET)
    keys = np.repeat(np.arange(n // 2), 2) % 5
    vals = np.where(np.arange(n) % 2 == 0, 100 + np.arange(n), 0)
    res = driver.drive(g, ops, keys, vals)
    # each GET immediately follows its PUT in group FIFO order
    assert (res.results[1::2] == 100 + np.arange(0, n, 2)).all()


def test_deep_drive_uneven_group_counts():
    groups = device_plane(MONOTONE, seed=17)
    groups.wait_for_leaders()
    driver = BulkDriver(groups)
    # ragged: group i gets i+1 ops
    g = np.concatenate([np.full(i + 1, i) for i in range(8)])
    res = driver.drive(g, ap.OP_LONG_ADD, 1)
    off = 0
    for i in range(8):
        got = res.results[off:off + i + 1]
        assert (got == np.arange(1, i + 2)).all(), (i, got)
        off += i + 1


def test_queue_managed_submit_refused_on_monotone_engine(rg):
    with pytest.raises(NotImplementedError):
        rg.submit(0, ap.OP_LONG_ADD, a=1)
    with pytest.raises(NotImplementedError):
        rg.submit_batch(np.arange(4), ap.OP_LONG_ADD, 1)


def test_query_lane_allowed_and_never_escalates_on_monotone_engine():
    """Queries don't append, so they stay allowed — and an unservable
    query must RETRY on the query lane, never escalate to the (closed)
    command path where the gate would reject its tag forever."""
    groups = device_plane(MONOTONE, seed=23)
    groups.wait_for_leaders()
    driver = BulkDriver(groups)
    driver.drive(np.array([0]), ap.OP_LONG_ADD, 7)
    # atomic reads via the queued query lane: unservable slots (cold
    # lease after an election) must RETRY as queries, not escalate
    tags = [groups.submit_query(0, ap.OP_VALUE_GET, consistency="atomic")
            for _ in range(3)]
    groups.run_until(tags, max_rounds=60)
    assert all(groups.results[t] == 7 for t in tags)
    # nothing leaked onto the command queues (the wedge the round-4
    # review flagged)
    assert not any(groups._queues.values())


def _isolate(groups, g, peer):
    """Deliver mask cutting ``peer`` off group ``g`` both directions."""
    G, P = groups.num_groups, groups.num_peers
    deliver = np.ones((G, P, P), bool)
    deliver[g, peer, :] = False
    deliver[g, :, peer] = False
    return jnp.asarray(deliver)


def test_gate_exactly_once_across_leader_change_uncommitted_tail():
    """The soundness hinge: ops accepted into a leader log that NEVER
    replicated are lost with that leader; the gate must accept the
    re-dispatch at the new leader (tags > its ring max) and each op
    applies EXACTLY once."""
    groups = device_plane(QUICK._replace(lease_gated_accept=False), seed=31)
    groups.wait_for_leaders()
    out = _step_raw(groups, groups._empty_submits())
    lead = int(np.asarray(out.leader)[0])
    # isolate the leader FIRST, then submit [1,2]: the leader accepts
    # them (no lease gate) but can never replicate them
    saved = groups.deliver
    groups.deliver = _isolate(groups, 0, lead)
    for _ in range(3):
        out = _step_raw(groups, _submit_window(groups, 0, [1, 2]))
        if np.asarray(out.accepted)[0, :2].all():
            break
    else:
        pytest.fail("doomed leader never accepted the window")
    # let a new leader rise among the connected majority
    for _ in range(20):
        out = _step_raw(groups, groups._empty_submits())
        new_lead = int(np.asarray(out.leader)[0])
        if new_lead not in (-1, lead):
            break
    else:
        pytest.fail("no new leader elected")
    # re-dispatch the lost ops at the new leader: ring max is 0 there,
    # so [1,2] must be accepted again
    for _ in range(10):
        out = _step_raw(groups, _submit_window(groups, 0, [1, 2]))
        if np.asarray(out.accepted)[0, :2].all():
            break
    else:
        pytest.fail("re-dispatch never accepted at the new leader")
    # heal; old leader rewinds and adopts the new log
    groups.deliver = saved
    for _ in range(10):
        _step_raw(groups, groups._empty_submits())
    # exactly-once: counter == 2 on the applied state of every live lane
    val = groups.value(0, peer=int(np.asarray(_step_raw(
        groups, groups._empty_submits()).leader)[0]))
    assert val == 2, f"counter {val}: an op applied twice or never"


def test_gate_dedups_committed_ops_across_leader_change():
    """Committed entries survive elections (leader completeness), so a
    duplicate re-send after failover must be rejected."""
    groups = device_plane(QUICK, seed=37)
    groups.wait_for_leaders()
    for _ in range(10):
        out = _step_raw(groups, _submit_window(groups, 0, [1, 2]))
        if np.asarray(out.accepted)[0, :2].all():
            break
    for _ in range(4):  # commit + apply on a quorum
        out = _step_raw(groups, groups._empty_submits())
    lead = int(np.asarray(out.leader)[0])
    saved = groups.deliver
    groups.deliver = _isolate(groups, 0, lead)
    for _ in range(20):
        out = _step_raw(groups, groups._empty_submits())
        if int(np.asarray(out.leader)[0]) not in (-1, lead):
            break
    # duplicate re-send at the new leader: its log CONTAINS [1,2]
    # (committed entries survive) -> ring max 2 -> rejected
    out = _step_raw(groups, _submit_window(groups, 0, [1, 2]))
    assert not np.asarray(out.accepted)[0].any()
    groups.deliver = saved
    for _ in range(8):
        _step_raw(groups, groups._empty_submits())
    val = groups.value(0, peer=int(np.asarray(_step_raw(
        groups, groups._empty_submits()).leader)[0]))
    assert val == 2


def test_timeout_resyncs_stream_cursor_engine_not_wedged():
    """A drive that times out mid-stream must leave the engine usable:
    the device consumed tags the host never saw resolve, so the cursor
    resyncs from the device ring and the NEXT drive's tags are accepted
    (round-4 review: the stale cursor wedged every later drive)."""
    groups = device_plane(MONOTONE, seed=29)
    groups.wait_for_leaders()
    driver = BulkDriver(groups)
    g = np.repeat(np.arange(G), 8)
    # max_rounds too small to even finish phase 1 + settle + harvest
    with pytest.raises(TimeoutError):
        driver.drive(g, ap.OP_LONG_ADD, 1, max_rounds=1)
    # the engine recovers: a fresh drive completes and its results account
    # for WHATEVER prefix of the abandoned drive committed (at-most-once
    # for abandoned ops — each group's counter is monotone and the new
    # ops' deltas all land exactly once)
    res = driver.drive(g, ap.OP_LONG_ADD, 1)
    vals = res.results.reshape(G, 8)
    assert (np.diff(vals, axis=1) == 1).all()  # FIFO, each delta once


def test_bulk_query_drive_all_levels():
    """Client-visible bulk READS through the no-append query lane: each
    level serves the applied value; ATOMIC additionally rides the leader
    lease (linearizable with zero log entries)."""
    groups = device_plane(MONOTONE, seed=41)
    groups.wait_for_leaders()
    driver = BulkDriver(groups)
    g = np.repeat(np.arange(8), 5)
    driver.drive(g, ap.OP_LONG_ADD, 1)   # counters now 5 everywhere
    reads = np.repeat(np.arange(8), 7)
    for level in ("sequential", "atomic", "causal", "process"):
        got = driver.drive_queries(reads, ap.OP_VALUE_GET,
                                   consistency=level)
        assert (got == 5).all(), (level, got)


def test_bulk_query_drive_map_and_errors():
    groups = device_plane(MONOTONE, seed=43)
    groups.wait_for_leaders()
    driver = BulkDriver(groups)
    n = 4 * 6
    g = np.repeat(np.arange(4), 6)
    driver.drive(g, ap.OP_MAP_PUT, np.tile(np.arange(6), 4),
                 100 + np.arange(n))
    got = driver.drive_queries(g, ap.OP_MAP_GET, np.tile(np.arange(6), 4))
    assert (got == 100 + np.arange(n)).all()
    with pytest.raises(ValueError):
        driver.drive_queries(g, ap.OP_LONG_ADD, 1)  # not read-only
    with pytest.raises(ValueError):
        driver.drive_queries(g, ap.OP_MAP_GET, 0, consistency="nope")


def test_deep_drive_session_events_ingested():
    """Lock grants ride the event ring; the deep drive's rare ev path
    must still deliver them to the host buffer."""
    groups = device_plane(MONOTONE, seed=19)
    groups.wait_for_leaders()
    driver = BulkDriver(groups)
    # acquire(1) grants synchronously; acquire(2) queues; release(1)
    # hands the lock to 2 via an EV_LOCK_GRANT outbox event
    res = driver.drive(
        np.array([0, 0, 0]),
        np.array([ap.OP_LOCK_ACQUIRE, ap.OP_LOCK_ACQUIRE,
                  ap.OP_LOCK_RELEASE]),
        np.array([1, 2, 1]), np.array([0, -1, 0]))
    assert res.results.size == 3
    assert any(code == ap.EV_LOCK_GRANT and target == 2
               for _, code, target, _ in groups.events.get(0, []))


def test_checkpoint_restore_rebuilds_stream_cursor(tmp_path):
    """Restoring a monotone engine must rebuild _stream_count from the
    log ring, or the next drive's tags collide with consumed ones and
    the gate rejects them forever (the cursor is host-side state the
    snapshot does not carry)."""
    from copycat_tpu.models import checkpoint

    groups = device_plane(MONOTONE, seed=61)
    groups.wait_for_leaders()
    driver = BulkDriver(groups)
    g = np.repeat(np.arange(G), 9)
    driver.drive(g, ap.OP_LONG_ADD, 1)

    path = tmp_path / "snap.npz"
    checkpoint.save(groups, path)
    restored = checkpoint.load(path)
    assert (restored._stream_count == 9).all(), restored._stream_count
    drv2 = BulkDriver(restored)
    res = drv2.drive(g, ap.OP_LONG_ADD, 1)
    assert (res.results.reshape(G, 9) == 9 + np.arange(1, 10)).all()

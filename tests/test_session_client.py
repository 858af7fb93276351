"""Sessioned bulk client: the unified client plane (VERDICT r4 #2).

The reference's client runtime gives every session FIFO sequencing,
exactly-once command application, response caching, event delivery and
liveness over ONE data path (Copycat client — SURVEY.md §2.3). These
tests pin that contract onto ``models.session_client.BulkSessionClient``
driving the deep (monotone-tag) pipeline — and, for the composability
claim, a classic engine too.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.models import BulkSessionClient  # noqa: E402
from copycat_tpu.models.sessions import SessionExpiredError  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402

from engines import MONOTONE, device_plane  # noqa: E402


@pytest.fixture(scope="module")
def deep_rg():
    rg = device_plane(MONOTONE, seed=11)
    rg.wait_for_leaders()
    return rg


@pytest.fixture(scope="module")
def client(deep_rg):
    return BulkSessionClient(deep_rg)


def test_exactly_once_fifo_and_result_cache(client):
    s = client.open_session()
    seqs = s.submit_batch([0] * 10, ap.OP_LONG_ADD, 1)
    extra = s.submit(0, ap.OP_VALUE_GET)
    n = client.flush()
    assert n == 11
    # FIFO: the GET queued after 10 increments sees all of them
    # (running totals 1..10 for the adds, then the read).
    adds = s.results_window(int(seqs[0]), 10)
    base = adds[0] - 1
    assert list(adds - base) == list(range(1, 11))
    assert s.result(extra) == base + 10
    # exactly-once read side: results re-correlate any number of times,
    # and a second flush with nothing pending applies nothing.
    before = s.result(extra)
    assert client.flush() == 0
    assert s.result(extra) == before
    check = s.submit(0, ap.OP_VALUE_GET)
    client.flush()
    assert s.result(check) == base + 10  # no hidden re-application


def test_sessions_interleave_on_one_group(client):
    s1 = client.open_session()
    s2 = client.open_session()
    g = 1
    a = s1.submit_batch([g] * 5, ap.OP_LONG_ADD, 10)
    b = s2.submit_batch([g] * 5, ap.OP_LONG_ADD, 1)
    client.flush()
    # both sessions' ops all applied exactly once: 5*10 + 5*1
    read = s1.submit(g, ap.OP_VALUE_GET)
    client.flush()
    assert s1.result(read) == 55
    # per-session FIFO: each session's own running results are ordered
    r1 = s1.results_window(int(a[0]), 5)
    r2 = s2.results_window(int(b[0]), 5)
    assert all(np.diff(r1) == 10)
    assert all(np.diff(r2) == 1)


def test_queries_and_atomic_reads(client):
    s = client.open_session()
    s.submit_batch([2, 2, 2], ap.OP_LONG_ADD, 7)
    client.flush()
    vals = s.query_batch([2] * 4, ap.OP_VALUE_GET, consistency="atomic")
    assert list(vals) == [21] * 4
    # the full SPI read vocabulary routes (round 9): sub-linearizable
    # levels serve from applied state, linearizable rides the lease gate
    for level in ("none", "causal", "process", "sequential",
                  "bounded_linearizable", "linearizable"):
        got = s.query_batch([2, 2], ap.OP_VALUE_GET, consistency=level)
        assert list(got) == [21, 21], (level, got)
    with pytest.raises(ValueError, match="unknown read consistency"):
        s.query_batch([2], ap.OP_VALUE_GET, consistency="nope")


def test_edge_cache_serves_causal_reads_locally(deep_rg, client):
    """The device-plane edge replica (docs/EDGE_READS.md): CAUSAL-level
    GETs of groups this client already read serve from its own
    committed post-apply state rows — zero engine rounds — and every
    write shape (ADD / SET / successful and failed CAS / GET_AND_SET)
    keeps the replica in lockstep with the engine's answer."""
    s = client.open_session()
    g = 5
    edge = client._edge
    assert edge is not None
    # cold read: drives the engine, marks interest
    s.submit(g, ap.OP_LONG_ADD, 4)
    client.flush()
    assert list(s.query_batch([g], ap.OP_VALUE_GET,
                              consistency="causal")) == [4]
    serves0 = edge._m_serves.value
    script = [
        (ap.OP_LONG_ADD, 3, 0, 7),          # add -> 7
        (ap.OP_VALUE_SET, 9, 0, 9),         # set -> 9
        (ap.OP_VALUE_CAS, 9, 12, 12),       # cas success -> 12
        (ap.OP_VALUE_CAS, 9, 99, 12),       # cas FAILURE -> still 12
        (ap.OP_VALUE_GET_AND_SET, 20, 0, 20),
    ]
    for opcode, a, b, expect in script:
        s.submit(g, opcode, a, b)
        client.flush()
        rounds_before = deep_rg.rounds
        local = s.query_batch([g] * 3, ap.OP_VALUE_GET,
                              consistency="causal")
        assert list(local) == [expect] * 3, (opcode, local)
        assert deep_rg.rounds == rounds_before, "local serve drove rounds"
        # the engine agrees (sequential drives the query lane)
        engine = s.query_batch([g], ap.OP_VALUE_GET,
                               consistency="sequential")
        assert list(engine) == [expect]
    assert edge._m_serves.value > serves0
    # sequential never serves from the cache
    assert edge._m_serves.value == serves0 + 3 * len(script)


def test_edge_cache_refuses_ttl_groups():
    """A TTL'd SET arms a device-side deadline the host cache cannot
    observe (the register later reads as unset) — the group becomes
    permanently uncacheable instead of serving the value past its
    expiry (found by review; the engine-side expiry is invisible to
    the result-row feed)."""
    from copycat_tpu.models.session_client import _EdgeValueCache
    from copycat_tpu.utils.metrics import MetricsRegistry

    cache = _EdgeValueCache(MetricsRegistry())
    cache.interest.update((0, 1))
    cache.observe(np.asarray([0, 1]),
                  np.asarray([ap.OP_VALUE_SET, ap.OP_VALUE_SET]),
                  np.asarray([5, 6]), np.asarray([0, 0]),
                  np.asarray([0, 30]),  # group 1 arms a TTL
                  np.asarray([0, 0]))
    assert cache.serve(np.asarray([0])).tolist() == [5]
    assert cache.serve(np.asarray([1])) is None
    # even a later plain write to the TTL'd group stays uncached
    cache.observe(np.asarray([1]), np.asarray([ap.OP_LONG_ADD]),
                  np.asarray([1]), np.asarray([0]), np.asarray([0]),
                  np.asarray([7]))
    assert cache.serve(np.asarray([1])) is None


def test_edge_cache_purged_on_abandoned_flush(monkeypatch):
    """An abandoned drive leaves its ops INDETERMINATE: the replica is
    purged so a later causal read cannot hide a write that may have
    applied (the correlate-a-fresh-read contract)."""
    from copycat_tpu.models.session_client import _EdgeValueCache
    from copycat_tpu.utils.metrics import MetricsRegistry

    cache = _EdgeValueCache(MetricsRegistry())
    cache.interest.add(0)
    cache.observe(np.asarray([0]), np.asarray([ap.OP_VALUE_SET]),
                  np.asarray([5]), np.asarray([0]), np.asarray([0]),
                  np.asarray([0]))
    assert cache.serve(np.asarray([0])).tolist() == [5]
    cache.purge()
    assert cache.serve(np.asarray([0])) is None
    assert cache._m_purges.value == 1


def test_edge_cache_knob_off(monkeypatch):
    monkeypatch.setenv("COPYCAT_EDGE_READS", "0")
    rg = device_plane(MONOTONE, seed=12)
    rg.wait_for_leaders()
    c = BulkSessionClient(rg)
    assert c._edge is None
    s = c.open_session()
    s.submit(0, ap.OP_LONG_ADD, 2)
    c.flush()
    assert list(s.query_batch([0], ap.OP_VALUE_GET,
                              consistency="causal")) == [2]


def test_lock_events_and_expiry_fanout(deep_rg, client):
    """A dead session's lock is released THROUGH THE LOG on a monotone
    engine (cleanup rides the next flush), and the grant event reaches
    the surviving session's listener."""
    g = 3
    holder = client.open_session()
    waiter = client.open_session()
    got = []
    waiter.on_event(g, lambda ev: got.append(ev))
    t1 = holder.lock_acquire(g)
    client.flush()
    assert holder.result(t1) == 1            # granted immediately
    t2 = waiter.lock_acquire(g)
    client.flush()
    assert waiter.result(t2) == 2            # queued behind holder
    # holder dies silently: stop keep-aliving it. Expiry is measured in
    # engine rounds; burn rounds with the OTHER session's traffic.
    client._sessions.pop(holder.id)
    reg = deep_rg.sessions
    for _ in range(40):
        waiter.submit_batch([7] * 8, ap.OP_LONG_ADD, 1)
        client.flush()
        if not reg.pending_cleanup and holder.id not in reg._sessions:
            # expiry fired on an earlier flush and cleanup committed
            q = waiter.submit(g, ap.OP_LOCK_HOLDER)
            client.flush()
            if waiter.result(q) == waiter.id:
                break
    q = waiter.submit(g, ap.OP_LOCK_HOLDER)
    client.flush()
    assert waiter.result(q) == waiter.id, \
        "dead session's lock was not released to the waiter"
    assert any(ev.code == ap.EV_LOCK_GRANT and ev.target == waiter.id
               for ev in got), "grant event not delivered to listener"
    with pytest.raises(SessionExpiredError):
        holder.submit(g, ap.OP_VALUE_GET)


def test_graceful_close_releases_lock(deep_rg, client):
    g = 4
    a = client.open_session()
    b = client.open_session()
    a.lock_acquire(g)
    b.lock_acquire(g)
    client.flush()
    a.close()
    client.flush()                            # commits the release fan-out
    q = b.submit(g, ap.OP_LOCK_HOLDER)
    client.flush()
    assert b.result(q) == b.id


def test_classic_engine_compat():
    """The same client contract runs on a CLASSIC engine (no monotone
    gate): drive is the classic bulk path, cleanup rides the queue."""
    rg = device_plane(seed=3)
    rg.wait_for_leaders()
    client = BulkSessionClient(rg)
    s = client.open_session()
    seqs = s.submit_batch([0] * 6, ap.OP_LONG_ADD, 2)
    client.flush()
    assert list(s.results_window(int(seqs[0]), 6)) == [2, 4, 6, 8, 10, 12]
    # graceful close commits lock release through the queue-managed path
    t = s.lock_acquire(1)
    client.flush()
    assert s.result(t) == 1
    s.close()
    client.flush()
    s2 = client.open_session()
    t2 = s2.lock_acquire(1)
    client.flush()
    assert s2.result(t2) == 1, "closed session's lock not released"


def test_throughput_smoke(client):
    """Mechanical throughput check (CPU): the sessioned surface commits
    a 4k-op burst in one flush with per-op numpy cost only. No benchmark
    cell drives this surface yet (its rate on the chip: not measured);
    this guards the mechanics (one drive per flush, vectorized
    correlation)."""
    s = client.open_session()
    rounds_before = client._rg.rounds
    g = np.arange(4096) % client._rg.num_groups
    seqs = s.submit_batch(g, ap.OP_LONG_ADD, 1)
    n = client.flush()
    assert n == 4096
    assert s.results_window(int(seqs[0]), 4096).min() >= 1
    # one pipelined drive: rounds grow like burst/S + settle, not per-op
    assert client._rg.rounds - rounds_before < 4096 // 2


def test_abandoned_flush_indeterminate_then_recover():
    """A flush abandoned mid-fault (liveness lost) marks its commands
    INDETERMINATE — they may or may not have applied — re-stages the
    idempotent cleanup ops, and after heal + recover() the client
    resumes with exactly-once preserved (each abandoned op applied at
    most once, verified by reading the counter)."""
    import jax.numpy as jnp

    from copycat_tpu.models.session_client import CommandIndeterminateError

    rg = device_plane(MONOTONE, seed=21)
    rg.wait_for_leaders()
    client = BulkSessionClient(rg)
    s = client.open_session()
    base = s.submit(0, ap.OP_LONG_ADD, 1)
    client.flush()
    assert s.result(base) == 1

    # cut ALL delivery: nothing can commit; the drive must lose liveness
    rg.deliver = jnp.zeros_like(rg.deliver)
    seqs = s.submit_batch([0] * 4, ap.OP_LONG_ADD, 1)
    with pytest.raises(TimeoutError):
        client.flush(max_rounds=40)
    with pytest.raises(CommandIndeterminateError):
        s.result(int(seqs[0]))

    # heal + recover, then the session keeps working with fresh seqs
    rg.deliver = jnp.ones_like(rg.deliver)
    client.recover()
    q = s.submit(0, ap.OP_VALUE_GET)
    client.flush()
    val = s.result(q)
    # exactly-once bound: the 4 abandoned adds applied AT MOST once each
    assert 1 <= val <= 5, val
    # and new commands still apply exactly once
    t = s.submit(0, ap.OP_LONG_ADD, 10)
    client.flush()
    assert s.result(t) == val + 10

"""Cluster-wide causal tracing (docs/OBSERVABILITY.md): wire
bit-identity when disabled, cross-member span propagation, assembly +
critical-path semantics, and the partition/incomplete contract.

The load-bearing contracts:

- **Tracing off is invisible**: every RPC frame is byte-identical to
  the pre-tracing wire (the committed golden bytes in
  ``tests/golden/wire_frames.json`` were captured from the plane BEFORE
  the trace fields existed — optional trailing fields omit a ``None``
  entirely), and member logs never carry trace state.
- **Tracing on is causal**: a proxied write records phases on every
  member it crossed, all under the client's id, and the assembly's
  critical path accounts for the full end-to-end wall time.
- **Partitions mark assemblies incomplete, never dropped.**
"""

from __future__ import annotations

import asyncio
import json
import pathlib
import zlib

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.client.client import PinnedConnectionStrategy, RaftClient  # noqa: E402
from copycat_tpu.io.buffer import BufferInput, BufferOutput  # noqa: E402
from copycat_tpu.io import codec as codec_mod  # noqa: E402
from copycat_tpu.io.local import LocalTransport  # noqa: E402
from copycat_tpu.io.serializer import Serializer  # noqa: E402
from copycat_tpu.io.transport import Address  # noqa: E402
from copycat_tpu.protocol import messages as msg  # noqa: E402
from copycat_tpu.server.log import CommandEntry  # noqa: E402
from copycat_tpu.server.raft import LEADER  # noqa: E402
from copycat_tpu.utils import tracing  # noqa: E402
from copycat_tpu.utils.tracing import (  # noqa: E402
    assemble_trace,
    render_waterfall,
)

from helpers import async_test  # noqa: E402
from raft_fixtures import Put  # noqa: E402
from test_sharding import (  # noqa: E402
    NotifyKey,
    close_all,
    sharded_cluster,
)

GOLDEN = pathlib.Path(__file__).parent / "golden" / "wire_frames.json"


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracing.disable()
    tracing.TRACER.clear()
    yield
    tracing.disable()
    tracing.TRACER.clear()


# ---------------------------------------------------------------------------
# the tracing-off wire differential: byte identity with the pre-tracing
# plane, via golden frames captured before the trace fields existed
# ---------------------------------------------------------------------------


def _golden_samples() -> dict:
    addr = Address("local", 5001)
    entry = CommandEntry(3, 1700000000.5, 41, 7, {"k": "v", "n": 9})
    entry.index = 12
    return {
        "vote_request": msg.VoteRequest(
            term=5, candidate=addr, last_log_index=10, last_log_term=4,
            group=None),
        "vote_request_g2": msg.VoteRequest(
            term=5, candidate=addr, last_log_index=10, last_log_term=4,
            group=2),
        "append_heartbeat": msg.AppendRequest(
            term=3, leader=addr, prev_index=12, prev_term=3, entries=[],
            commit_index=12, global_index=None, fill_to=None, group=None),
        "append_window": msg.AppendRequest(
            term=3, leader=addr, prev_index=11, prev_term=3,
            entries=[entry], commit_index=11, global_index=8, fill_to=12,
            group=1),
        "install": msg.InstallRequest(
            term=3, leader=addr, index=5, snap_term=2, total=4, offset=0,
            data=b"abcd", done=False, group=None),
        "proxy_request": msg.ProxyRequest(
            group=1, kind="commands", payload=(41, [(7, {"k": "v"})])),
        "proxy_response": msg.ProxyResponse(
            error=None, error_detail=None, leader=None,
            result=[(7, 12, "ok", None, None)]),
        "publish": msg.PublishRequest(
            session_id=41, event_index=3, prev_event_index=2,
            events=[("poked", "x")], group=None),
        "publish_g1": msg.PublishRequest(
            session_id=41, event_index=3, prev_event_index=2,
            events=[("poked", "x")], group=1),
        "command_untraced": msg.CommandRequest(
            session_id=41, seq=7, operation={"op": 1}, trace=None),
        "command_batch_untraced": msg.CommandBatchRequest(
            session_id=41, entries=[(7, {"op": 1}), (8, {"op": 2})],
            trace=None),
        "keepalive": msg.KeepAliveRequest(
            session_id=41, command_seq=6, event_index=2),
        "query": msg.QueryRequest(
            session_id=41, index=9, operation={"q": 1},
            consistency="linearizable"),
    }


def test_untraced_frames_bit_identical_to_pre_tracing_golden():
    """Every RPC with tracing off serializes to EXACTLY the bytes the
    pre-tracing plane produced (the golden hex was captured from the
    tree before ProxyRequest/ProxyResponse/AppendRequest/PublishRequest
    grew their optional trailing ``trace`` field) — on the pure-Python
    walk AND, when built, the C codec."""
    golden = json.loads(GOLDEN.read_text())
    s = Serializer()
    c = codec_mod.codec()
    for name, obj in _golden_samples().items():
        buf = BufferOutput()
        s.write_object(obj, buf)
        py = buf.to_bytes()
        assert py.hex() == golden[name], \
            f"{name}: python frame drifted from the pre-tracing wire"
        if c is not None:
            assert c.encode(obj).hex() == golden[name], \
                f"{name}: C frame drifted from the pre-tracing wire"


def test_optional_trace_field_round_trips_on_both_codecs():
    addr = Address("local", 5001)
    entry = CommandEntry(3, 1700000000.5, 41, 7, {"k": "v"})
    entry.index = 12
    traced = [
        msg.ProxyRequest(group=1, kind="commands",
                         payload=(41, [(7, {"k": "v"})]), trace=99),
        msg.ProxyResponse(result=[(7, 12, "ok", None, None)], trace=99),
        msg.AppendRequest(term=3, leader=addr, prev_index=11, prev_term=3,
                          entries=[entry], commit_index=11, global_index=8,
                          fill_to=12, group=1, trace=(99, 12)),
        msg.PublishRequest(session_id=41, event_index=3,
                           prev_event_index=2, events=[("poked", "x")],
                           group=None, trace=99),
    ]
    s = Serializer()
    c = codec_mod.codec()
    for obj in traced:
        buf = BufferOutput()
        s.write_object(obj, buf)
        py = buf.to_bytes()
        back = s.read_object(BufferInput(py))
        want = obj.trace
        assert back.trace == want, type(obj).__name__
        if c is not None:
            assert c.encode(obj) == py, type(obj).__name__
            assert c.decode(py).trace == want, type(obj).__name__
        # the untraced twin omits the field: strictly shorter frame,
        # and decoding it yields trace=None
        obj.trace = None
        buf2 = BufferOutput()
        s.write_object(obj, buf2)
        untraced = buf2.to_bytes()
        assert len(untraced) < len(py)
        assert s.read_object(BufferInput(untraced)).trace is None
        if c is not None:
            assert c.decode(untraced).trace is None


# ---------------------------------------------------------------------------
# assembly semantics (pure units)
# ---------------------------------------------------------------------------


def _span(name, member, wall, ms, trace=1, **meta):
    return {"trace": trace, "name": name, "member": member, "wall": wall,
            "duration_ms": ms, **meta}


def test_assembly_critical_path_sums_to_e2e():
    spans = [
        _span("client.submit", "client", 100.0, 10.0),
        _span("ingress.queue", "m1", 100.001, 1.0, group=0),
        _span("proxy.hop", "m1", 100.002, 7.0, group=0),
        _span("group.append", "m2", 100.003, 1.0, group=0),
        _span("quorum.wait", "m2", 100.004, 4.0, group=0),
        _span("apply", "m2", 100.008, 0.5, group=0),
    ]
    asm = assemble_trace(1, {"ring": spans})
    assert asm["incomplete"] is False, asm["incomplete_why"]
    assert asm["members"] == ["client", "m1", "m2"]
    assert asm["e2e_ms"] == pytest.approx(10.0, abs=0.01)
    # innermost-cover: segments partition the whole interval exactly
    assert asm["critical_path_ms"] == pytest.approx(asm["e2e_ms"],
                                                    abs=0.01)
    names = [c["name"] for c in asm["critical_path"]]
    assert "quorum.wait" in names and "client.submit" in names
    text = render_waterfall(asm)
    assert "INCOMPLETE" not in text
    assert "critical path" in text


def test_assembly_marks_unserved_dispatch_incomplete():
    """The partition signature: a sub-block dispatched (ingress.queue /
    a failed proxy.hop) with no group-side span for that group."""
    spans = [
        _span("client.submit", "client", 100.0, 5.0),
        _span("ingress.queue", "m1", 100.001, 0.5, group=1),
        _span("proxy.hop", "m1", 100.002, 2.0, group=1,
              error="unreachable"),
    ]
    asm = assemble_trace(1, {"ring": spans})
    assert asm["incomplete"] is True
    assert any("group 1" in why for why in asm["incomplete_why"])
    # the spans that DID land are all there, rendered with a banner
    assert len(asm["spans"]) == 3
    assert "INCOMPLETE" in render_waterfall(asm)


def test_assembly_errored_hop_with_successful_retry_is_complete():
    """A transient mid-trace failure (leader election) records an
    errored proxy.hop attempt, but the RETRY served the group — the
    assembly is complete; the failed attempt stays on the timeline."""
    spans = [
        _span("client.submit", "client", 100.0, 8.0),
        _span("ingress.queue", "m1", 100.001, 0.2, group=0),
        _span("proxy.hop", "m1", 100.001, 1.0, group=0,
              error="unreachable"),
        _span("proxy.hop", "m1", 100.003, 3.0, group=0),
        _span("group.append", "m2", 100.004, 0.5, group=0),
        _span("quorum.wait", "m2", 100.0045, 2.0, group=0),
    ]
    asm = assemble_trace(1, {"ring": spans})
    assert asm["incomplete"] is False, asm["incomplete_why"]
    assert len(asm["spans"]) == 6  # the errored attempt is rendered


def test_assembly_marks_failed_member_fetch_incomplete_and_dedups():
    span = _span("group.append", "m2", 100.0, 1.0, group=0)
    asm = assemble_trace(
        1, {"a": [span], "b": [dict(span)]},  # same ring seen twice
        failed_members=["host:9"])
    assert asm["incomplete"] is True
    assert any("host:9" in why for why in asm["incomplete_why"])
    assert len(asm["spans"]) == 1  # deduplicated


def test_assembly_of_nothing_is_incomplete_not_dropped():
    asm = assemble_trace(7, {}, failed_members=["host:1"])
    assert asm["incomplete"] is True
    assert asm["spans"] == [] and asm["critical_path_ms"] == 0.0


# ---------------------------------------------------------------------------
# the cross-member waterfall end to end (in-process sharded cluster:
# the shared ring's member tags stand in for per-member fetches)
# ---------------------------------------------------------------------------


@async_test(timeout=120)
async def test_proxied_write_produces_cross_member_waterfall():
    registry, servers = await sharded_cluster(n=3, groups=2)
    # pin the client to a member that leads NEITHER group, so every
    # sub-block pays the proxy hop (seed-spread: member g%N leads
    # group g, so the third member leads nothing at boot)
    ingress = next(s for s in servers
                   if all(g.role != LEADER for g in s.groups))
    client = RaftClient([s.address for s in servers],
                        LocalTransport(registry), session_timeout=30.0,
                        connection_strategy=PinnedConnectionStrategy(
                            ingress.address))
    try:
        await client.open()
        tracing.enable()
        # one event-loop turn, keys covering both groups -> ONE batch
        cover: dict[int, str] = {}
        i = 0
        while len(cover) < 2:
            k = f"w{i}"
            cover.setdefault(zlib.crc32(k.encode()) % 2, k)
            i += 1
        await asyncio.gather(*(
            client.submit_command_nowait(
                Put(key=k, value=1))
            for k in cover.values()))
        tracing.disable()
        traces = tracing.TRACER.traces()
        tid = next(t for t, spans in traces.items()
                   if any(s.name == "client.submit" for s in spans))
        asm = assemble_trace(tid, {"ring": traces[tid]})
        assert asm["incomplete"] is False, asm["incomplete_why"]
        server_members = [m for m in asm["members"] if m != "client"]
        assert len(server_members) >= 2, asm["members"]
        phases = {s["name"] for s in asm["spans"]}
        assert {"client.submit", "ingress.queue", "proxy.hop",
                "group.append", "quorum.wait", "apply",
                "respond"} <= phases, phases
        # acceptance bar: the critical path accounts for the measured
        # end-to-end latency within 10%
        assert abs(asm["critical_path_ms"] - asm["e2e_ms"]) \
            <= 0.1 * asm["e2e_ms"], asm
        # phase histograms fed on the members that did the work
        leader0 = next(s for s in servers
                       if s.groups[0].role == LEADER)
        for phase in ("latency.append_ms", "latency.quorum_ms",
                      "latency.apply_ms"):
            lat = leader0.groups[0].metrics.histogram(phase)
            assert lat.count > 0, phase
        assert ingress._metrics.histogram(
            "latency.ingress_queue_ms").count >= 2
        assert ingress._metrics.histogram(
            "latency.proxy_hop_ms").count >= 2
    finally:
        await close_all(servers, client)


@async_test(timeout=120)
async def test_traced_event_delivery_rides_the_publish_frame():
    """A traced command whose apply publishes session events yields
    event.push (server, under the SAME id via the entry marks) and
    client.event (client receipt) spans."""
    registry, servers = await sharded_cluster(n=3, groups=2)
    client = RaftClient([s.address for s in servers],
                        LocalTransport(registry), session_timeout=30.0)
    try:
        await client.open()
        got: list = []
        client.session().on_event("poked", got.append)
        tracing.enable()
        await client.submit(NotifyKey(key="evt-k", payload="p"))
        tracing.disable()
        # poll for the SPANS, not just the delivery: the client observes
        # the event inside _on_publish BEFORE the server's flush
        # coroutine resumes with the ack and records event.push —
        # asserting at first delivery races that resumption
        def span_names() -> set:
            return {s.name for spans in tracing.TRACER.traces().values()
                    for s in spans}

        deadline = asyncio.get_running_loop().time() + 10
        while asyncio.get_running_loop().time() < deadline and not (
                got and {"event.push", "client.event"} <= span_names()):
            await asyncio.sleep(0.02)
        assert got, "event never delivered"
        names = span_names()
        assert "event.push" in names, names
        assert "client.event" in names, names
    finally:
        await close_all(servers, client)


# ---------------------------------------------------------------------------
# nemesis: partition between ingress and owning leader mid-trace
# ---------------------------------------------------------------------------


def test_partition_mid_trace_yields_incomplete_assembly(monkeypatch):
    """ISSUE 9 satellite: a partition between the ingress and the
    owning group's leader mid-trace yields an ``incomplete=true``
    assembly carrying the spans that DID land (ingress.queue + the
    failed proxy.hop), under COPYCAT_INVARIANTS=strict."""
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")

    @async_test(timeout=240)
    async def run():
        registry, servers = await sharded_cluster(
            n=3, groups=2, session_timeout=3.0)
        ingress = next(s for s in servers
                       if all(g.role != LEADER for g in s.groups))
        client = RaftClient(
            [s.address for s in servers], LocalTransport(registry),
            session_timeout=3.0,
            connection_strategy=PinnedConnectionStrategy(ingress.address))
        try:
            await client.open()
            # a key owned by group 0, whose leader we cut off from the
            # ingress (clients bypass partitions by design, so the
            # session connection itself stays up)
            key = next(f"p{i}" for i in range(64)
                       if zlib.crc32(f"p{i}".encode()) % 2 == 0)
            leader0 = next(s for s in servers
                           if s.groups[0].role == LEADER)
            nem = registry.attach_nemesis()
            nem.partition([ingress.address],
                          [s.address for s in servers if s is not ingress])
            tracing.enable()
            fut = client.submit_command_nowait(
                Put(key=key, value=1))
            # let the ingress dispatch, try the hop, and fail it (the
            # per-try budget is the 3 s session timeout)
            await asyncio.sleep(5.0)
            tracing.disable()
            traces = tracing.TRACER.traces()
            # the trace that dispatched toward group 0 from the ingress
            tid = next(
                t for t, spans in traces.items()
                if any(s.name == "ingress.queue"
                       and (s.meta or {}).get("member")
                       == str(ingress.address) for s in spans))
            asm = assemble_trace(tid, {"ring": traces[tid]})
            assert asm["incomplete"] is True, asm
            assert any("group 0" in why for why in asm["incomplete_why"])
            landed = {s["name"] for s in asm["spans"]}
            assert "ingress.queue" in landed, landed
            # the partitioned leader recorded nothing under this id
            assert not any(
                s["name"] in ("group.append", "quorum.wait", "apply")
                and s.get("member") == str(leader0.address)
                for s in asm["spans"]), asm["spans"]
            # rendered, never dropped
            assert "INCOMPLETE" in render_waterfall(asm)
            nem.heal()
            # after the heal the in-flight write resolves one way or
            # the other (the 3 s session may legitimately have expired
            # at the group leaders while keep-alives could not fan out
            # through the partitioned ingress) — it must not hang
            try:
                await asyncio.wait_for(asyncio.shield(fut), 60)
            except (msg.ProtocolError, Exception):  # noqa: BLE001
                pass
            # strict tripwire stayed silent on every member and group
            for s in servers:
                for g in s.groups:
                    assert g.metrics.counter(
                        "repl.invariant_violations").value == 0
        finally:
            if registry.nemesis is not None:
                registry.nemesis.heal()
            await close_all(servers, client)

    run()


# ---------------------------------------------------------------------------
# member logs stay trace-free: the traced run's replicated state is
# bit-identical across members and equal to the untraced run's stream
# ---------------------------------------------------------------------------


@async_test(timeout=240)
async def test_traced_and_untraced_runs_produce_identical_logs():
    from test_sharding import _command_stream

    async def drive(traced: bool):
        registry, servers = await sharded_cluster(n=3, groups=2)
        client = RaftClient([s.address for s in servers],
                            LocalTransport(registry),
                            session_timeout=30.0)
        try:
            await client.open()
            if traced:
                tracing.enable()
            for i in range(12):
                await client.submit(
                    Put(key=f"d{i}", value=i))
            tracing.disable()
            # convergence across members, per group
            deadline = asyncio.get_running_loop().time() + 20
            while asyncio.get_running_loop().time() < deadline:
                if all(
                        s.groups[g].last_applied
                        == servers[0].groups[g].last_applied
                        and s.groups[g].log.last_index
                        == servers[0].groups[g].log.last_index
                        for s in servers for g in range(2)):
                    break
                await asyncio.sleep(0.02)
            ser = Serializer()
            slots = []
            for g in range(2):
                last = servers[0].groups[g].log.last_index
                for i in range(1, last + 1):
                    copies = {ser.write(e) for e in
                              (s.groups[g].log.get(i) for s in servers)
                              if e is not None}
                    assert len(copies) <= 1, \
                        f"group {g} slot {i} diverged"
                slots.append([_command_stream(s.groups[g])
                              for s in servers])
            return slots
        finally:
            await close_all(servers, client)

    untraced = await drive(traced=False)
    tracing.TRACER.clear()
    traced = await drive(traced=True)
    for g in range(2):
        # within each run: identical across members; across runs: the
        # same command stream — tracing left no residue in the log
        assert untraced[g][0] == untraced[g][1] == untraced[g][2]
        assert traced[g][0] == traced[g][1] == traced[g][2]
        assert untraced[g][0] == traced[g][0]


# ---------------------------------------------------------------------------
# the collection route + CLI rendering
# ---------------------------------------------------------------------------


@async_test(timeout=120)
async def test_stats_listener_serves_per_trace_spans():
    from copycat_tpu.server.stats import StatsListener, fetch_stats

    registry, servers = await sharded_cluster(n=3, groups=2)
    client = RaftClient([s.address for s in servers],
                        LocalTransport(registry), session_timeout=30.0)
    listener = StatsListener(servers[0], port=0)
    try:
        await client.open()
        await listener.open()
        tracing.enable()
        await client.submit(
            Put(key="t0", value=1))
        tracing.disable()
        addr = f"127.0.0.1:{listener.port}"
        slowest = json.loads(await fetch_stats(addr, "/traces"))
        assert slowest, "no traces on /traces"
        tid = slowest[0]["trace"]
        local = json.loads(await fetch_stats(addr, f"/traces/{tid}"))
        assert local["trace"] == tid
        assert local["member"] == str(servers[0].address)
        assert local["spans"], local
        assert all("wall" in s for s in local["spans"])
        # unknown id: empty spans, not an error (assembler marks it)
        empty = json.loads(await fetch_stats(addr, "/traces/999999"))
        assert empty["spans"] == []
    finally:
        await listener.close()
        await close_all(servers, client)


def test_traces_watch_renders_slowest_with_new_markers():
    from copycat_tpu.cli import _render_traces_watch

    body = json.dumps([
        {"trace": 2, "total_ms": 9.0, "spans": [
            {"trace": 2, "name": "group.append", "member": "m1",
             "group": 0, "duration_ms": 1.0, "wall": 1.0},
            {"trace": 2, "name": "quorum.wait", "member": "m1",
             "group": 0, "duration_ms": 8.0, "wall": 2.0}]},
        {"trace": 1, "total_ms": 3.0, "spans": [
            {"trace": 1, "name": "client.submit", "duration_ms": 3.0,
             "wall": 1.0}]},
    ]).encode()
    frame, ids = _render_traces_watch(body, None, slowest=8)
    assert ids == {1, 2}
    assert "trace 2" in frame and "quorum.wait{group=0,member=m1}" in frame
    assert "NEW" not in frame  # first poll: no delta baseline yet
    frame2, ids2 = _render_traces_watch(body, {2}, slowest=8)
    assert "NEW" in frame2  # trace 1 appeared since the last poll
    assert ids2 == {1, 2}

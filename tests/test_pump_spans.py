"""Batch-scope tracing of the served path's two pumps (utils/tracing.py
"Batch-scope spans"): one span per batch and stage from the client's flush
to the device fetch, the whole-window report with its counters, and the
off switch. A tiny served deployment on the CPU (``executor="tpu"``,
capacity 16) is driven once with bursts of writes and of ATOMIC reads, a
burst of each at once, so that every read window finds the writes' run
parked and rides its round (``tests/test_read_joins_round.py``); the tests
read what that run recorded. No number from here is a device number.
"""

import asyncio
import json

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import DistributedAtomicLong  # noqa: E402
from copycat_tpu.io.buffer import BufferOutput  # noqa: E402
from copycat_tpu.io.local import (  # noqa: E402
    LocalServerRegistry, LocalTransport)
from copycat_tpu.io.serializer import Serializer  # noqa: E402
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402
from copycat_tpu.models import RaftGroups  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.ops.consensus import Config  # noqa: E402
from copycat_tpu.protocol import messages as msg  # noqa: E402
from copycat_tpu.resource.consistency import Consistency  # noqa: E402
from copycat_tpu.utils import tracing  # noqa: E402
from copycat_tpu.utils.tracing import TRACER, Tracer  # noqa: E402

from helpers import arun, the_tick_after_the_window  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402
from test_trace_plane import GOLDEN, _golden_samples  # noqa: E402

from engines import SERVED, device_plane  # noqa: E402

COUNTERS, BURSTS = 8, 3
WRITE_PUMP = ("apply.classify", "apply.park", "apply.marshal",
              "engine.stage", "engine.wait", "engine.fetch",
              "engine.harvest", "apply.finalize")
READ_PUMP = ("read.queue", "read.gate", "read.eval", "read.drain",
             "engine.query", "read.finalize")
CLIENT = ("client.stage", "client.submit", "client.query", "client.resolve")
#: the request-scope spans the benchmark already reads
KEPT = ("group.append", "quorum.wait", "apply", "respond")


@pytest.fixture(autouse=True)
def _tracer_off_and_empty():
    tracing.disable()
    TRACER.clear()
    yield
    tracing.disable()
    TRACER.clear()


async def _deployment():
    registry = LocalServerRegistry()
    (addr,) = next_ports(1)
    server = AtomixServer(
        addr, [addr], LocalTransport(registry), election_timeout=0.5,
        heartbeat_interval=0.1, session_timeout=60.0, executor="tpu",
        engine_config=SERVED)
    await server.open()
    client = AtomixClient([addr], LocalTransport(registry),
                          session_timeout=60.0)
    await client.open()
    ctrs = [await client.get(f"ctr{i}", DistributedAtomicLong)
            for i in range(COUNTERS)]
    for c in ctrs:
        c.with_consistency(Consistency.ATOMIC)
    return server, client, ctrs


async def _burst(ctrs, delta=None):
    if delta is None:
        return await asyncio.gather(*(c.get() for c in ctrs))
    return await asyncio.gather(*(c.add_and_get(delta) for c in ctrs))


def _registries(server, client) -> dict:
    raft = server.server
    engine = raft.state_machine.device_engine._groups
    return {"engine.": engine.metrics, "group.": raft.groups[0].metrics,
            "server.": raft.metrics_server_registry(),
            "client.": client.client.metrics}


@pytest.fixture(scope="module")
def recorded():
    """One traced run: BURSTS x (a burst of writes and a burst of ATOMIC
    reads at once, the window evaluated with the writes' run still parked)
    after an untraced warm-up; what the tracer and the registries held
    when it was turned off."""
    async def drive():
        server, client, ctrs = await _deployment()
        try:
            await _burst(ctrs, 1)
            await _burst(ctrs)                   # compiles, untraced
            with the_tick_after_the_window():    # and the joint program
                await asyncio.gather(_burst(ctrs, 0), _burst(ctrs))
            regs = _registries(server, client)
            before = {p: r.counter_values() for p, r in regs.items()}
            queries = []
            real = RaftGroups._run_query

            def counted(self, sub, atomic):
                queries.append(1)
                return real(self, sub, atomic)

            RaftGroups._run_query = counted
            TRACER.clear()
            tracing.enable()
            try:
                replies = []
                with the_tick_after_the_window():
                    for k in range(BURSTS):
                        replies += await asyncio.gather(_burst(ctrs, 2),
                                                        _burst(ctrs))
            finally:
                tracing.disable()
                RaftGroups._run_query = real
            after = {p: r.counter_values() for p, r in regs.items()}
            return {"report": TRACER.report(),
                    "traces": {tid: list(spans) for tid, spans
                               in TRACER.traces().items()},
                    "before": before, "after": after,
                    "run_query_calls": len(queries), "replies": replies}
        finally:
            await client.close()
            await server.close()

    tracing.disable()
    TRACER.clear()
    try:
        return arun(drive(), timeout=240)
    finally:
        tracing.disable()
        TRACER.clear()


def _spans(recorded, name=None):
    return [s for spans in recorded["traces"].values() for s in spans
            if name is None or s.name == name]


def test_the_run_itself_answered_correctly(recorded):
    for k in range(BURSTS):
        assert recorded["replies"][2 * k] == [1 + 2 * (k + 1)] * COUNTERS
        assert recorded["replies"][2 * k + 1] == [1 + 2 * (k + 1)] * COUNTERS


@pytest.mark.parametrize("names", [WRITE_PUMP, READ_PUMP, CLIENT, KEPT],
                         ids=["write-pump", "read-pump", "client", "kept"])
def test_every_name_of_the_vocabulary_is_recorded(recorded, names):
    in_ring = {s.name for s in _spans(recorded)}
    in_report = set(recorded["report"]["spans"])
    assert set(names) <= in_ring, set(names) - in_ring
    assert set(names) <= in_report, set(names) - in_report
    for name in names:
        agg = recorded["report"]["spans"][name]
        assert agg["n"] == len(_spans(recorded, name))    # nothing evicted
        assert agg["mean_ms"] == pytest.approx(agg["total_ms"] / agg["n"])
        assert agg["max_ms"] >= agg["mean_ms"] > 0


def test_every_stage_is_per_batch_not_per_operation(recorded):
    """3 bursts of 8 writes and of 8 reads: every batch-scope name is
    recorded a few times, never 8 x 3."""
    for name in WRITE_PUMP + READ_PUMP + CLIENT:
        n = recorded["report"]["spans"][name]["n"]
        assert BURSTS <= n <= 2 * BURSTS + 1, (name, n)


def test_each_child_lies_inside_its_parent(recorded):
    by_trace = recorded["traces"]
    applies = {s.meta["batch"]: s for s in _spans(recorded, "apply")
               if s.meta and "batch" in s.meta}
    checked = set()
    for tid, spans in by_trace.items():
        for s in spans:
            if s.parent is None:
                continue
            if s.parent == "apply":
                parent = applies.get(tid)
            else:
                parent = next((p for p in spans if p.name == s.parent
                               and p.start <= s.start and s.end <= p.end),
                              None)
            assert parent is not None, (s, "has no", s.parent)
            assert parent.start - 1e-6 <= s.start, (s, parent)
            assert s.end <= parent.end + 1e-6, (s, parent)
            checked.add((s.name, s.parent))
    assert {(n, "apply") for n in WRITE_PUMP} <= checked
    assert {("read.drain", "read.eval"), ("engine.query", "read.eval"),
            ("read.finalize", "read.eval")} <= checked


def test_self_time_is_total_less_the_children_and_never_negative(recorded):
    spans = recorded["report"]["spans"]
    for name, agg in spans.items():
        assert agg["self_ms"] >= -1e-9, (name, agg)
        assert agg["self_ms"] <= agg["total_ms"] + 1e-9
    children = sum(spans[n]["total_ms"] for n in WRITE_PUMP)
    assert spans["apply"]["self_ms"] == pytest.approx(
        spans["apply"]["total_ms"] - children)
    assert spans["read.eval"]["self_ms"] == pytest.approx(
        spans["read.eval"]["total_ms"] - sum(
            spans[n]["total_ms"]
            for n in ("read.drain", "engine.query", "read.finalize")))
    assert spans["engine.wait"]["self_ms"] == spans["engine.wait"]["total_ms"]


def test_the_timeline_shares_sum_to_100(recorded):
    report = recorded["report"]
    timeline = report["timeline"]
    assert sum(timeline.values()) == pytest.approx(100.0, abs=0.01)
    assert set(timeline) - {"unspanned"} <= tracing.TIMELINE_SPANS
    assert all(v >= 0 for v in timeline.values())
    assert 0 <= timeline["unspanned"] < 100
    assert report["cut"] is False and report["window_s"] > 0
    # a closed loop of one client: a flush is open or being staged nearly
    # all the time, and the engine's wait shows under its own name
    assert timeline["engine.wait"] > 0 and timeline["engine.query"] > 0


def test_counter_deltas_equal_the_registries_own_difference(recorded):
    counters = recorded["report"]["counters"]
    for prefix in ("engine.", "group.", "server.", "client."):
        before, after = recorded["before"][prefix], recorded["after"][prefix]
        assert after, prefix
        for name, value in after.items():
            assert counters[prefix + name] == value - before.get(name, 0), \
                prefix + name
    writes, reads = BURSTS * COUNTERS, BURSTS * COUNTERS
    assert counters["client.commands_submitted"] == writes
    assert counters["client.queries_submitted"] == reads
    assert counters["group.query_ops"] == reads
    assert counters["group.query_windows"] == BURSTS
    assert counters["engine.queries_served"] == reads
    assert counters["engine.query_vector_drives"] == BURSTS
    assert counters["engine.query_joined_drives"] == BURSTS
    assert counters["server.apply.fused_dispatches"] == BURSTS
    assert counters["engine.query_settle_rounds"] == 0


def test_fetches_are_the_fetch_spans_plus_the_evaluations_that_ran_alone(
        recorded):
    """Every window's reads rode its writes' round here, so the query
    program never ran alone and a round's fetch is the window's only one;
    the reads' slab is among the bytes its ``engine.fetch`` span names."""
    counters = recorded["report"]["counters"]
    fetch_spans = recorded["report"]["spans"]["engine.fetch"]["n"]
    assert recorded["run_query_calls"] == 0
    assert counters["engine.fetches"] == \
        fetch_spans + recorded["run_query_calls"]
    assert counters["engine.rounds"] == fetch_spans
    in_spans = sum(s.meta["bytes"] for s in _spans(recorded, "engine.fetch"))
    assert 0 < in_spans == counters["engine.fetch_bytes"]


def test_a_joined_window_is_one_drain_around_the_round_and_a_marshal(
        recorded):
    """What the two spans hold where the reads ride: ``read.drain`` the
    joint call (the round's four stages lie inside it), ``engine.query``
    the marshalling of the rows before it, with no evaluation of its own
    (``attempts`` 0)."""
    drains = _spans(recorded, "read.drain")
    assert len(drains) == BURSTS
    for name in ("engine.stage", "engine.wait", "engine.fetch",
                 "engine.harvest", "apply.finalize"):
        inside = [s for s in _spans(recorded, name)
                  if any(d.start <= s.start and s.end <= d.end
                         for d in drains)]
        assert len(inside) == BURSTS, name
    queries = _spans(recorded, "engine.query")
    assert len(queries) == BURSTS
    for q in queries:
        assert q.meta == {"attempts": 0, "width": 1, "n": COUNTERS}
        drain = next(d for d in drains if d.trace_id == q.trace_id)
        assert q.end <= drain.start


def test_dispatch_leaves_count_the_buffers_of_each_program(recorded):
    """``engine.dispatch_leaves`` in the whole-window report: every round
    here took a read window along (1 buffer put, the round's 2 fresh slabs
    and the reads' 1, the 3 fetched: 7, where a round alone is 5 and a
    query evaluation alone 1 put, 1 slab out, 1 fetched: 3)."""
    counters = recorded["report"]["counters"]
    assert counters["engine.rounds"] == BURSTS
    assert counters["engine.dispatch_leaves"] == \
        7 * BURSTS + 3 * recorded["run_query_calls"]


def test_one_write_round_is_five_buffers_and_one_query_three():
    """Exactly: a round puts the six submit planes as 1 buffer, gets 2
    fresh slabs (the state and the key alias their donated inputs) and
    fetches those 2; a query evaluation puts 1, gets 1, fetches 1."""
    rg = device_plane()
    rg.wait_for_leaders()
    leaves = rg.metrics.counter("dispatch_leaves")
    tag = rg.submit(0, ap.OP_LONG_ADD, 5)
    before = leaves.value
    rg.step_round()
    assert leaves.value - before == 5
    rg.run_until([tag])
    rg.run(2)
    before = leaves.value
    tracing.enable()
    assert rg.drive_query_vector([0], ap.OP_VALUE_GET).tolist() == [5]
    tracing.disable()
    assert leaves.value - before == 3
    assert TRACER.report()["counters"]["engine.dispatch_leaves"] == 3
    before = leaves.value
    rg.step_rounds(3)
    assert leaves.value - before == 9


def test_one_append_and_one_apply_per_traced_block_as_before(recorded):
    """The spans the benchmark already reads keep their count: one
    ``group.append`` and one ``apply`` per write flush, each ``apply``
    now naming the pump turn that applied it."""
    spans = recorded["report"]["spans"]
    assert spans["group.append"]["n"] == BURSTS
    assert spans["apply"]["n"] == BURSTS
    assert spans["quorum.wait"]["n"] == BURSTS
    for s in _spans(recorded, "apply"):
        turn = {x.name for x in recorded["traces"][s.meta["batch"]]}
        assert set(WRITE_PUMP) <= turn
        assert s.meta["member"] and s.meta["group"] == 0
    for s in _spans(recorded, "group.append"):
        assert s.meta["n"] == COUNTERS and s.parent is None


def test_the_assembly_lays_the_turns_stages_inside_the_requests_apply(
        recorded):
    apply = _spans(recorded, "apply")[0]
    ring = Tracer()
    for spans in recorded["traces"].values():
        for s in spans:
            ring.span(s.trace_id, s.name, s.start, s.end, parent=s.parent,
                      **(s.meta or {}))
    linked = ring.spans_for(apply.trace_id, linked=True)
    assert len(linked) > len(ring.spans_for(apply.trace_id))
    assembly = tracing.assemble_trace(apply.trace_id, {"ring": linked})
    names = [d["name"] for d in assembly["spans"]]
    assert set(WRITE_PUMP) <= set(names) and "client.stage" in names
    assert not assembly["incomplete"]
    member = apply.meta["member"]
    assert all(d["member"] == member for d in assembly["spans"]
               if d["name"] in WRITE_PUMP)
    assert {"engine.wait", "client.stage"} <= {
        c["name"] for c in assembly["critical_path"]}
    assert assembly["critical_path_ms"] == pytest.approx(
        assembly["e2e_ms"], rel=0.01)
    other = next(s for s in _spans(recorded, "read.eval"))
    assert other.trace_id not in {d["trace"] for d in assembly["spans"]}


def test_a_read_behind_a_committed_unapplied_write_counts_settle_rounds():
    """An engine that applies one entry a round: four adds commit in one
    round, three are still unapplied when the read comes, so the query
    drive settles with engine rounds, counts them, and records their
    stages as children of ``engine.query``."""
    rg = device_plane(Config(applies_per_round=1))
    rg.wait_for_leaders()
    rg.run(3)
    for _ in range(4):
        rg.submit(0, ap.OP_LONG_ADD, 5)
    rg.step_round()
    counters = rg.metrics.counter_values()
    assert counters["query_settle_rounds"] == 0
    tracing.enable()
    value = rg.drive_query_vector([0], ap.OP_VALUE_GET)
    tracing.disable()
    assert value.tolist() == [20]
    report = TRACER.report()
    settled = report["counters"]["engine.query_settle_rounds"]
    assert settled == 3
    assert report["counters"]["engine.rounds"] == settled
    assert report["counters"]["engine.fetches"] == 2 * settled + 1
    (query,) = [s for spans in TRACER.traces().values() for s in spans
                if s.name == "engine.query"]
    assert query.meta["attempts"] == settled + 1 and query.meta["width"] == 1
    stages = TRACER.spans_for(query.trace_id)
    for name in ("engine.stage", "engine.wait", "engine.fetch",
                 "engine.harvest"):
        mine = [s for s in stages if s.name == name]
        assert len(mine) == settled
        assert all(s.parent == "engine.query" and query.start <= s.start
                   and s.end <= query.end for s in mine)
    assert report["spans"]["engine.query"]["self_ms"] >= 0


def test_fused_rounds_record_the_four_stages_once_with_their_rounds():
    rg = device_plane()
    rg.wait_for_leaders()
    rg.step_rounds(3)                            # compiles, untraced
    walls = rg.metrics.histogram("step_wall_ms").count
    tracing.enable()
    rg.step_rounds(3)
    tracing.disable()
    spans = TRACER.report()["spans"]
    for name in ("engine.stage", "engine.wait", "engine.fetch",
                 "engine.harvest"):
        assert spans[name]["n"] == 1
    (trace,) = TRACER.traces().values()
    assert all(s.meta["rounds"] == 3 for s in trace)
    assert TRACER.report()["counters"]["engine.rounds"] == 3
    # step_wall_ms is the engine.wait span's own pair of instants
    assert rg.metrics.histogram("step_wall_ms").count == walls + 1
    wait = next(s for s in trace if s.name == "engine.wait")
    assert rg.metrics.histogram("step_wall_ms").max_value >= \
        wait.duration_ms > 0


def test_the_report_is_frozen_at_disable_and_bounded():
    t = Tracer()
    assert t.report()["spans"] == {} and t.report()["counters"] == {}
    t.enable()
    t.span(1, "engine.wait", 10.0, 10.5)
    t.span(1, "group.append", 10.0, 10.25)        # not a timeline span
    t.disable()
    frozen = t.report()
    t.span(1, "engine.wait", 11.0, 12.0)          # a straggler
    assert t.report() is frozen
    assert frozen["spans"]["engine.wait"]["n"] == 1
    assert frozen["spans"]["group.append"]["total_ms"] == pytest.approx(250)
    assert len(t.spans_for(1)) == 3               # the ring still takes it
    t.clear()
    assert t.report()["spans"] == {}


def test_counters_are_each_registrys_own_delta_summed_by_prefix():
    import gc

    from copycat_tpu.utils.metrics import MetricsRegistry

    a, b, gone = MetricsRegistry(), MetricsRegistry(), MetricsRegistry()
    a.counter("rounds").inc(5)
    gone.counter("rounds").inc(100)
    t = Tracer()
    t.register(a, "engine.")
    t.register(gone, "engine.")
    t.enable()
    late = MetricsRegistry()              # built inside the window
    late.counter("rounds").inc(7)         # before the tracer hears of it
    t.register(late, "engine.")
    t.register(b, "group.")
    for reg, n in ((a, 2), (b, 3), (late, 4), (gone, 50)):
        reg.counter("rounds").inc(n)
    b.counter("query_ops", level="atomic").inc(9)
    assert t.report()["counters"]["engine.rounds"] == 2 + 4 + 50   # live
    del gone, reg
    gc.collect()
    t.disable()
    a.counter("rounds").inc(1000)         # after the window
    assert t.report()["counters"] == {
        "engine.rounds": 2 + 4,           # a collected registry takes its own
        "group.rounds": 3, "group.query_ops{level=atomic}": 9}


def test_the_timeline_is_cut_by_innermost_cover_and_capped(monkeypatch):
    shares = tracing._timeline_shares(
        [(0.0, 10.0, "client.submit"), (2.0, 6.0, "read.eval"),
         (3.0, 4.0, "engine.query"), (12.0, 14.0, "client.stage"),
         (-5.0, 1.0, "client.resolve"), (19.0, 30.0, "apply.park")],
        0.0, 20.0)
    assert shares == pytest.approx({
        # client.resolve began first, so the later client.submit owns 0..1
        "client.submit": 100 * (2.0 + 4.0) / 20, "read.eval": 15.0,
        "engine.query": 5.0, "client.stage": 10.0, "apply.park": 5.0,
        "unspanned": 100 * 7.0 / 20}, abs=1e-9)
    assert tracing._timeline_shares([], 1.0, 1.0) == {"unspanned": 100.0}
    monkeypatch.setattr(tracing, "MAX_INTERVALS", 4)
    t = Tracer()
    t.enable()
    for k in range(6):
        t.span(k, "engine.wait", float(k), k + 0.5)
    t.disable()
    report = t.report()
    assert report["cut"] is True and report["spans"]["engine.wait"]["n"] == 6
    assert sum(report["timeline"].values()) == pytest.approx(100.0)


def test_with_the_tracer_off_no_span_and_no_annotation_is_made(monkeypatch):
    """The off switch: every new site reads ``TRACER.enabled`` and
    branches away, and the frames on the wire are the golden ones."""
    def refuse(*args, **kwargs):
        raise AssertionError("recorded with the tracer off")

    sent = []

    async def drive():
        server, client, ctrs = await _deployment()
        try:
            await _burst(ctrs, 1)
            await _burst(ctrs)
            monkeypatch.setattr(Tracer, "span", refuse)
            monkeypatch.setattr(Tracer, "open_span", refuse)
            monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
            real = client.client._request

            async def seen(request, *args, **kwargs):
                sent.append(request)
                return await real(request, *args, **kwargs)

            monkeypatch.setattr(client.client, "_request", seen)
            assert await _burst(ctrs, 2) == [3] * COUNTERS
            assert await _burst(ctrs) == [3] * COUNTERS
            engine = server.server.state_machine.device_engine._groups
            engine.step_rounds(2)
            engine.step_round()
        finally:
            await client.close()
            await server.close()

    arun(drive(), timeout=240)
    batches = [r for r in sent if isinstance(r, msg.CommandBatchRequest)]
    assert batches and all(r.trace is None for r in batches)
    assert any(isinstance(r, msg.QueryBatchRequest) for r in sent)
    assert not hasattr(msg.QueryBatchRequest(), "trace")
    assert TRACER.traces() == {} and TRACER.report()["spans"] == {}
    golden = json.loads(GOLDEN.read_text())
    serializer = Serializer()
    for name, obj in _golden_samples().items():
        buf = BufferOutput()
        serializer.write_object(obj, buf)
        assert buf.to_bytes().hex() == golden[name], name

"""One session's failover is one event (``client/client.py``): a session with
64 commands in flight loses its leader, starts ONE failover, and resubmits
what is unanswered as one block in sequence order; every reply is the plain
model's and nothing is applied twice (``benchmarks/reference_crash.py``)."""

import asyncio
import importlib.util
import os

from helpers import async_test
from raft_fixtures import create_cluster

from copycat_tpu.io.serializer import serialize_with
from copycat_tpu.protocol import messages as msg
from copycat_tpu.protocol.messages import Message
from copycat_tpu.protocol.operations import Command
from copycat_tpu.server.state_machine import Commit, StateMachine
from copycat_tpu.testing.nemesis import crash_server
from copycat_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference():
    spec = importlib.util.spec_from_file_location(
        "reference_crash", os.path.join(REPO, "benchmarks",
                                        "reference_crash.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@serialize_with(952)
class Add(Message, Command):
    _fields = ("key", "delta")


class Counters(StateMachine):
    """``add`` answers the value after it; ``applied`` counts applications."""

    def __init__(self) -> None:
        super().__init__()
        self.values: dict = {}
        self.applied = 0

    def add(self, commit: Commit[Add]) -> int:
        self.applied += 1
        op = commit.operation
        self.values[op.key] = self.values.get(op.key, 0) + op.delta
        return self.values[op.key]


@async_test(timeout=60)
async def test_a_session_with_64_commands_in_flight_loses_its_leader():
    cluster = await create_cluster(3, Counters, election_timeout=0.2,
                                   heartbeat_interval=0.04,
                                   session_timeout=10.0)
    nemesis = cluster.registry.attach_nemesis()
    try:
        client = await cluster.client(session_timeout=10.0)
        model = reference().PlainCounters()
        for k in range(8):                # the session sits at the leader
            got = await client.submit(Add(key=f"k{k}", delta=1))
            assert model.add(f"k{k}", 1, got) == ""
        leader = await cluster.await_leader()
        survivors = [s for s in cluster.servers if s is not leader]

        # what the client puts on the wire from here on
        sent: list = []
        real = client._request

        async def request(request, *args, **kwargs):
            sent.append(request)
            return await real(request, *args, **kwargs)

        client._request = request
        tracing.TRACER.clear()
        tracing.enable()
        dials = client._dials

        # 64 commands as four batches, held on the wire by a delay a leg
        nemesis.set_delay(0.05)
        ops = [(f"k{i % 8}", 1 + i) for i in range(64)]
        futures = []
        for start in range(0, 64, 16):
            futures += [client.submit_command_nowait(Add(key=k, delta=d))
                        for k, d in ops[start:start + 16]]
            await asyncio.sleep(0)
        await asyncio.sleep(0.08)         # at the leader, none answered
        assert not any(f.done() for f in futures)
        before = len(sent)                # the four batches, as they went
        assert [len(r.entries) for r in sent] == [16] * 4
        await crash_server(leader)
        nemesis.set_delay(0.0)
        replies = await asyncio.wait_for(asyncio.gather(*futures), 30)
        tracing.disable()

        # every reply the model's: each delta applied exactly once, in the
        # session's order
        for (key, delta), got in zip(ops, replies):
            assert model.add(key, delta, got) == "", (key, delta, got)
        # one failover, and what it resubmitted went as one block in
        # sequence order: no command batch of the session left on its own
        spans = [s for spans in tracing.TRACER.traces().values()
                 for s in spans if s.name == "client.failover"]
        assert len(spans) == 1
        assert spans[0].meta["inflight"] == 64
        assert spans[0].meta["resubmitted"] == 64
        assert spans[0].meta["attempts"] == client._dials - dials >= 1
        blocks = [r for r in sent[before:] if isinstance(
            r, (msg.CommandBatchRequest, msg.CommandRequest))]
        assert len(blocks) == 1
        seqs = [seq for seq, _ in blocks[0].entries]
        assert seqs == sorted(seqs) and len(seqs) == 64
        assert sum(isinstance(r, msg.KeepAliveRequest)
                   for r in sent[before:]) >= 1
        assert client.metrics.counter("commands_resubmitted").value == 64
        assert tracing.TRACER.report()["counters"][
            "client.commands_resubmitted"] == 64

        # the session goes on over the connection the failover left it,
        # by its own requests again
        got = await client.submit(Add(key="k0", delta=5))
        assert model.add("k0", 5, got) == ""
        assert client._failover is None and not client._unanswered

        # nothing applied twice on either survivor
        new_leader = await cluster.await_leader()
        assert new_leader in survivors
        for _ in range(200):
            if all(s.last_applied >= new_leader.commit_index
                   for s in survivors):
                break
            await asyncio.sleep(0.01)
        for server in survivors:
            machine = server.state_machine
            assert machine.applied == 8 + 64 + 1
            assert machine.values == model.values
        cached = sum(s.metrics.counter("commands_cached").value
                     for s in survivors)
        appended = sum(s.metrics.counter("commands_fast_lane").value
                       + s.metrics.counter("commands_general_lane").value
                       for s in survivors)
        assert cached + appended >= 64
    finally:
        tracing.disable()
        await cluster.close()

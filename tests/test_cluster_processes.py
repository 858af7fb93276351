"""Three-process Raft cluster over real TCP — the reference's deployment
shape (one server per machine), which no in-process test can cover:
server-to-server RPC crosses real sockets between separate interpreters,
and a server PROCESS dying mid-load exercises client re-route + failover
against genuinely independent peers.
"""

import asyncio
import os
import subprocess
import sys
import tempfile

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import DistributedAtomicLong  # noqa: E402
from copycat_tpu.deploy.topology import allocate_ports  # noqa: E402
from copycat_tpu.io.tcp import TcpTransport  # noqa: E402
from copycat_tpu.io.transport import Address  # noqa: E402
from copycat_tpu.manager.atomix import AtomixClient  # noqa: E402

from helpers import async_test  # noqa: E402

# ephemeral ports via the bind-port-0 probe (deploy.topology): parallel
# CI runs and leftover listeners can no longer collide the way the old
# hardcoded 19361-19363 could
ADDRS = [f"127.0.0.1:{p}" for p in allocate_ports(3)]


def _spawn(idx: int, logf):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(__file__)))
    members = [ADDRS[idx]] + [a for i, a in enumerate(ADDRS) if i != idx]
    return subprocess.Popen(
        [sys.executable, "-c",
         f"from copycat_tpu.cli import server; server({members!r})"],
        env=env, stdout=logf, stderr=subprocess.STDOUT)


@async_test(timeout=300)
async def test_three_process_cluster_survives_server_kill():
    logs = [tempfile.NamedTemporaryFile("w+b", suffix=f".{i}.log",
                                        delete=False) for i in range(3)]
    procs = [_spawn(i, logs[i]) for i in range(3)]
    try:
        client = (AtomixClient.builder([Address.parse(a) for a in ADDRS])
                  .with_transport(TcpTransport()).build())
        for attempt in range(60):
            try:
                await asyncio.wait_for(client.open(), 15)
                break
            except Exception:
                dead = [i for i, p in enumerate(procs)
                        if p.poll() is not None]
                if len(dead) == 3:
                    logs[0].seek(0)
                    pytest.fail("all servers died: "
                                + logs[0].read().decode(
                                    errors="replace")[-600:])
                await asyncio.sleep(0.25)
        else:
            pytest.fail("client never connected to the cluster")

        counter = await client.get("hits", DistributedAtomicLong)
        for want in range(1, 6):
            got = await asyncio.wait_for(counter.increment_and_get(), 30)
            assert got == want

        # kill one server PROCESS mid-run: 2/3 keep quorum; if the victim
        # was the leader the client must re-route after failover
        procs[0].kill()
        procs[0].wait(timeout=10)
        deadline = asyncio.get_event_loop().time() + 90
        want = 6
        while want <= 10:
            try:
                got = await asyncio.wait_for(
                    counter.increment_and_get(), 20)
                assert got == want, (got, want)
                want += 1
            except AssertionError:
                raise
            except Exception:
                if asyncio.get_event_loop().time() > deadline:
                    raise
                await asyncio.sleep(1)  # failover window: retry
        await client.close()
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()

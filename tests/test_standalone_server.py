"""Cross-process server: the packaged `copycat-server` driven by a real
remote client over TCP.

Single-process tests import the whole package, so they can never catch a
server that fails to REGISTER the resource catalog with the serializer —
which is exactly what happened through round 4: a standalone server
could not decode ``GetResource("x", DistributedAtomicValue)`` from a
client ("unknown class id 56") because class references travel by
registry id (the documented Class.forName deviation) and the server
process had never imported ``atomic/``. This test runs the server in a
REAL subprocess (fresh interpreter, fresh registry) like a user would.
"""

import asyncio
import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import DistributedAtomicLong, DistributedAtomicValue  # noqa: E402
from copycat_tpu.io.tcp import TcpTransport  # noqa: E402
from copycat_tpu.io.transport import Address  # noqa: E402
from copycat_tpu.manager.atomix import AtomixClient  # noqa: E402

from helpers import async_test  # noqa: E402

PORT = 19341  # fixed high port; TIME_WAIT is fine (fresh listen each run)


@async_test(timeout=240)
async def test_packaged_server_serves_remote_client():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(__file__)))
    # log to a FILE, not a pipe: an undrained pipe fills at ~64KB and
    # blocks the server mid-run (review finding)
    import tempfile
    logf = tempfile.NamedTemporaryFile("w+b", suffix=".log", delete=False)
    proc = subprocess.Popen(
        [sys.executable, "-c",
         f"from copycat_tpu.cli import server; server(['127.0.0.1:{PORT}'])"],
        env=env, stdout=logf, stderr=subprocess.STDOUT)
    try:
        client = (AtomixClient.builder([Address("127.0.0.1", PORT)])
                  .with_transport(TcpTransport()).build())
        # server boot = jax import + election; retry until reachable
        for attempt in range(40):
            try:
                await asyncio.wait_for(client.open(), 15)
                break
            except Exception:
                if proc.poll() is not None:
                    logf.seek(0)
                    out = logf.read().decode(errors="replace")
                    pytest.fail(f"server died rc={proc.returncode}: "
                                f"{out[-800:]}")
                await asyncio.sleep(0.25)
        else:
            pytest.fail("client never connected")

        value = await client.get("value", DistributedAtomicValue)
        await value.set("hello")
        assert await value.get() == "hello"

        counter = await client.get("hits", DistributedAtomicLong)
        assert await counter.increment_and_get() == 1
        assert await counter.increment_and_get() == 2

        await client.close()
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()

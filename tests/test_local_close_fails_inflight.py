"""A connection that closes fails what is in flight on it, on both transports:
a send that has not returned raises ``ConnectionClosedError`` when either end
closes, and a handler cancelled on the peer's side reaches the sender as a
``TransportError`` and cancels nothing (``io/local.py``, ``io/tcp.py``)."""

import asyncio
import itertools

import pytest

from helpers import async_test

from copycat_tpu.io.local import LocalServerRegistry, LocalTransport
from copycat_tpu.io.tcp import TcpTransport
from copycat_tpu.io.transport import (
    Address, ConnectionClosedError, TransportError)

_ports = itertools.count(18740)


async def _pair(kind: str):
    """A connected pair on ``kind``: the client's end, the server's end, the
    gate the server's handler waits on (so a send stays in flight), the
    handler tasks' own view, and a closer for the rest."""
    transport = (LocalTransport(LocalServerRegistry()) if kind == "local"
                 else TcpTransport())
    address = Address("127.0.0.1", next(_ports))
    server, client = transport.server(), transport.client()
    gate: asyncio.Future = asyncio.get_running_loop().create_future()
    seen: dict = {"entered": asyncio.Event(), "ends": []}

    def accept(conn):
        async def handle(message):
            seen["entered"].set()
            return await gate
        conn.handler(str, handle)
        seen["ends"].append(conn)

    await server.listen(address, accept)
    conn = await client.connect(address)
    for _ in range(50):                  # TCP accepts a moment later
        if seen["ends"]:
            break
        await asyncio.sleep(0.01)

    async def done():
        if not gate.done():
            gate.set_result("late")
        await client.close()
        await server.close()

    return conn, seen, gate, done


@pytest.mark.parametrize("closer", ["sender", "peer"])
@pytest.mark.parametrize("kind", ["local", "tcp"])
@async_test
async def test_a_send_in_flight_on_a_closed_connection_raises(kind, closer):
    conn, seen, gate, done = await _pair(kind)
    try:
        sends = [asyncio.ensure_future(conn.send(f"m{i}")) for i in range(3)]
        await asyncio.wait_for(seen["entered"].wait(), 5)
        assert not any(s.done() for s in sends)
        end = conn if closer == "sender" else seen["ends"][0]
        await end.close()
        results = await asyncio.wait_for(
            asyncio.gather(*sends, return_exceptions=True), 5)
        assert all(isinstance(r, ConnectionClosedError) for r in results), \
            results
        with pytest.raises(ConnectionClosedError):
            await conn.send("after")
    finally:
        await done()


@pytest.mark.parametrize("kind", ["local", "tcp"])
@async_test
async def test_a_handler_cancelled_at_the_peer_is_a_transport_error(kind):
    """What ``testing/nemesis.crash_server`` does to a handler: the future
    it waits on is cancelled under it. The sender's task is not cancelled;
    it is told, as by any failed handler."""
    conn, seen, gate, done = await _pair(kind)
    try:
        async def caller():
            try:
                await conn.send("m")
            except TransportError as e:
                return e
            return None

        task = asyncio.ensure_future(caller())
        await asyncio.wait_for(seen["entered"].wait(), 5)
        gate.cancel()
        got = await asyncio.wait_for(task, 5)
        assert isinstance(got, TransportError) and "Cancelled" in str(got)
        assert not isinstance(got, ConnectionClosedError)
        assert not task.cancelled() and not conn.closed
    finally:
        await done()


@async_test
async def test_a_sender_that_gives_up_takes_its_local_handler_with_it():
    """A timeout around ``send`` on the in-memory wire still ends the
    handler it was waiting for, as when the handler ran on the sender's
    own task."""
    conn, seen, gate, done = await _pair("local")
    try:
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(conn.send("m"), 0.05)
        await asyncio.sleep(0)
        assert gate.cancelled() and not conn._inflight
    finally:
        await done()


@async_test
async def test_a_request_still_on_the_wire_when_the_connection_closes_is_lost():
    """The nemesis's delay is the wire: a request that has not arrived when
    either end closes reaches no handler, and its sender is told at once
    (a killed server's handler would take it and never answer)."""
    registry = LocalServerRegistry()
    registry.attach_nemesis().set_delay(0.05)
    transport = LocalTransport(registry)
    address = Address("127.0.0.1", next(_ports))
    server, client = transport.server(), transport.client()
    handled = []

    def accept(conn):
        async def handle(message):
            handled.append(message)
            return message
        conn.handler(str, handle)

    await server.listen(address, accept)
    conn = await client.connect(address)
    try:
        send = asyncio.ensure_future(conn.send("m"))
        await asyncio.sleep(0.01)          # on the wire, not yet arrived
        await server.close()
        with pytest.raises(ConnectionClosedError):
            await asyncio.wait_for(send, 5)
        assert handled == []
    finally:
        await client.close()

"""The client runtime: sessions, exactly-once submission, consistency routing.

Mirrors the consumed Copycat client surface (SURVEY.md §2.3 "Client runtime"):
``submit(Command/Query)`` with consistency-dependent routing (commands and
LINEARIZABLE/BOUNDED queries to the leader; SEQUENTIAL/CAUSAL queries to any
server), ``ConnectionStrategy`` (the reference's AtomixReplica pins its client
to the colocated server — ``CombinedConnectionStrategy``), client-assigned
command sequence numbers for exactly-once application, keep-alives, and the
session event channel (``Session.publish/onEvent`` by event name).
"""

from __future__ import annotations

import asyncio
import itertools
import random
import time
import uuid
from typing import Any, Callable

from ..io.transport import Address, Connection, Transport, TransportError
from ..protocol import messages as msg
from ..protocol.operations import Command, Operation, Query
from ..utils import knobs
from ..utils.listeners import Listener, Listeners
from ..utils.managed import Managed
from ..utils.metrics import MetricsRegistry
from ..utils.scheduled import Scheduled
from ..utils.tasks import spawn
from ..utils.tracing import TRACER

_client_counter = itertools.count()

# A keep-alive is also sent once the session holds this many replies the
# server has not been told of: the server caches a reply until a committed
# keep-alive says the client has it, so its cache (copied, serialised,
# installed and restored with every snapshot) holds this many and what is in
# flight, whatever the session's timeout. The timer is the session's
# liveness and stays.
_KEEPALIVE_REPLIES = 8192


class ApplicationError(Exception):
    """A state machine raised while applying the operation."""


class SessionExpiredError(Exception):
    """The server expired this client's session (missed keep-alives)."""


class ConnectionStrategy:
    """Orders servers for connection attempts."""

    def order(self, members: list[Address]) -> list[Address]:  # pragma: no cover
        raise NotImplementedError


class AnyConnectionStrategy(ConnectionStrategy):
    def order(self, members: list[Address]) -> list[Address]:
        shuffled = list(members)
        random.shuffle(shuffled)
        return shuffled


class PinnedConnectionStrategy(ConnectionStrategy):
    """Always try a specific server first (the reference replica's
    ``CombinedConnectionStrategy`` — client pinned to the in-process server)."""

    def __init__(self, address: Address) -> None:
        self.address = address

    def order(self, members: list[Address]) -> list[Address]:
        rest = [m for m in members if m != self.address]
        random.shuffle(rest)
        return [self.address] + rest


class ClientSession:
    """Client-side session state + event dispatch (Copycat ``Session``)."""

    def __init__(self, client: "RaftClient") -> None:
        self._client = client
        self.id: int | None = None
        self.timeout = 0.0
        self.state = "closed"  # closed -> open -> expired/closed
        # Per-group event channels (docs/SHARDING.md): a multi-group
        # server numbers each group's event stream independently; the
        # single-group plane lives entirely in key 0 (the legacy scalar,
        # via the ``event_index`` property).
        self._event_indices: dict[int, int] = {}
        self._event_listeners: dict[str, Listeners] = {}
        self._open_listeners = Listeners()
        self._close_listeners = Listeners()

    def on_event(self, event: str, callback: Callable[[Any], Any]) -> Listener:
        return self._event_listeners.setdefault(event, Listeners()).add(callback)

    def on_open(self, callback: Callable[[Any], Any]) -> Listener:
        return self._open_listeners.add(callback)

    def on_close(self, callback: Callable[[Any], Any]) -> Listener:
        return self._close_listeners.add(callback)

    @property
    def event_index(self) -> int:
        return self._event_indices.get(0, 0)

    @event_index.setter
    def event_index(self, value: int) -> None:
        self._event_indices[0] = value

    @property
    def is_open(self) -> bool:
        return self.state == "open"

    @property
    def is_expired(self) -> bool:
        return self.state == "expired"

    def publish(self, event: str, message: Any = None) -> None:
        """Local loopback publish (client-side listeners only)."""
        self._dispatch(event, message)

    def _dispatch(self, event: str, message: Any) -> None:
        listeners = self._event_listeners.get(event)
        if listeners is not None:
            listeners.accept(message)

    def _opened(self) -> None:
        self.state = "open"
        self._open_listeners.accept(self)

    def _expired(self) -> None:
        if self.state != "expired":
            self.state = "expired"
            self._close_listeners.accept(self)

    def _closed(self) -> None:
        if self.state == "open":
            self.state = "closed"
            self._close_listeners.accept(self)


class RaftClient(Managed):
    """Submits commands/queries to a Raft cluster over one live connection."""

    def __init__(
        self,
        members: list[Address],
        transport: Transport,
        session_timeout: float = 5.0,
        connection_strategy: ConnectionStrategy | None = None,
    ) -> None:
        super().__init__()
        self.members = list(members)
        self.transport = transport
        self.session_timeout = session_timeout
        self.strategy = connection_strategy or AnyConnectionStrategy()
        self.client_id = f"client-{uuid.uuid4().hex[:8]}-{next(_client_counter)}"
        # Observability: submit->response latency and submitted-operation
        # counters (docs/OBSERVABILITY.md). The hot path pays one counter
        # add and, per flushed batch, one histogram record.
        self.metrics = MetricsRegistry()
        TRACER.register(self.metrics, "client.")
        self._m_events_received = self.metrics.counter("events_received")
        # batch-scope tracing (utils/tracing.py): the open client.stage
        # span of the command micro-batch and of the read batch being
        # staged (first operation staged -> its flush began)
        self._stage_span: Any = None
        self._query_stage_span: Any = None

        self._client = transport.client()
        self._loop: asyncio.AbstractEventLoop | None = None  # pinned at open
        self._connection: Connection | None = None
        self._connected_to: Address | None = None
        self._leader_hint: Address | None = None
        self._failed_last: Address | None = None  # dialed last by _connect
        self._session = ClientSession(self)
        self._command_seq = 0
        # Exactly-once bookkeeping: the server may prune its response cache
        # only up to the CONTIGUOUS prefix of completed seqs — a higher seq
        # completing first must not ack a lower seq still being retried.
        self._completed_seqs: set[int] = set()
        self._acked_command_seq = 0
        # the early keep-alive (``_replies_resolved``): what the last
        # answered keep-alive acknowledged, and the early one on its way
        self._kept_alive_seq = 0
        self._early_keepalive: asyncio.Task | None = None
        self._m_keepalives_early = self.metrics.counter("keepalives_early")
        # High-water applied index seen, per Raft group (sequential
        # consistency). Single-group servers live entirely in key 0 —
        # the legacy scalar; a multi-group server (RegisterResponse
        # ``groups`` > 1) tags response indices with the owning group
        # (``index * G + g``) and reads the whole dict on queries.
        self._indices: dict[int, int] = {}
        self._num_groups = 1
        self._keepalive: Scheduled | None = None
        # Command micro-batching: same-turn submits coalesce into ONE
        # CommandBatchRequest (flushed via call_soon at the end of the
        # event-loop turn); a lone submit still rides CommandRequest.
        self._pending_batch: list = []
        self._batch_scheduled = False
        # One session's failover is one event: every command batch sent
        # and not yet answered stands here under its first seq, with the
        # future its flush waits on once its own send has failed. The
        # first flush to lose the connection (or to be told it no longer
        # talks to the leader) starts ``_run_failover``; the others join.
        self._unanswered: dict[int, tuple[list, asyncio.Future]] = {}
        self._failover: asyncio.Task | None = None
        self._dials = 0
        self._m_resubmitted = self.metrics.counter("commands_resubmitted")
        # Query micro-batching: same-turn reads bucket by consistency
        # level (the server's gate differs per level) and ride one
        # QueryBatchRequest — the linearizable gate's quorum round is
        # amortized over the whole batch.
        self._pending_queries: dict[str, list] = {}
        self._query_flush_scheduled = False
        # Follower read scale-out: SEQUENTIAL/CAUSAL reads round-robin
        # across ALL members instead of pinning the session connection
        # (usually the leader) — any server may serve them at or after
        # the client's index (the server-side client-index wait), so
        # read throughput scales with replicas. Leader fallback on lag
        # refusal / unreachable follower. COPYCAT_CLIENT_FOLLOWER_READS=0
        # restores leader-pinned reads (the scale-out A/B knob).
        self._follower_reads = knobs.get_bool("COPYCAT_CLIENT_FOLLOWER_READS")
        self._read_connections: dict[Address, Connection] = {}
        self._read_rr = 0
        # Edge read tier (docs/EDGE_READS.md): client-local CRDT
        # replicas serving CAUSAL/SEQUENTIAL reads without a server
        # hop, fed by per-resource deltas over the session event
        # channel. COPYCAT_EDGE_READS=0 removes the tier entirely — no
        # replica, no subscribe fields, the server-read plane
        # bit-identically (the A/B discipline).
        self._edge = None
        if knobs.get_bool("COPYCAT_EDGE_READS"):
            from .edge import EdgeReadTier
            self._edge = EdgeReadTier(self)

    # -- lifecycle ---------------------------------------------------------

    def session(self) -> ClientSession:
        return self._session

    @property
    def index(self) -> int:
        return max(self._indices.values(), default=0)

    def _read_index(self) -> Any:
        """The ``index`` field for outgoing reads: the legacy scalar on a
        single-group server, the per-group dict on a multi-group one
        (the server extracts the owning group's entry per routed op)."""
        if self._num_groups == 1:
            return self._indices.get(0, 0)
        return dict(self._indices)

    def _note_index(self, value: Any) -> None:
        """Fold a response index into the per-group high-water map:
        scalars are group-0 (single-group) or group-tagged
        (``idx * G + g``, multi-group); dicts are per-group maps
        (multi-group query batches)."""
        if not value:
            return
        if isinstance(value, dict):
            for g, idx in value.items():
                g = int(g)
                if idx and idx > self._indices.get(g, 0):
                    self._indices[g] = idx
            return
        if self._num_groups > 1:
            g = value % self._num_groups
            idx = value // self._num_groups
        else:
            g, idx = 0, value
        if idx > self._indices.get(g, 0):
            self._indices[g] = idx

    async def _do_open(self) -> None:
        self._loop = asyncio.get_running_loop()
        await self._register()
        interval = max(self._session.timeout / 4.0, 0.05)
        self._keepalive = Scheduled(interval, interval, self._send_keepalive)

    async def _do_close(self) -> None:
        if self._keepalive is not None:
            self._keepalive.cancel()
            self._keepalive = None
        if self._failover is not None:
            self._failover.cancel()
        if self._early_keepalive is not None:
            self._early_keepalive.cancel()
        if self._session.is_open and self._session.id is not None:
            try:
                response = await self._request(
                    msg.UnregisterRequest(session_id=self._session.id))
            except (TransportError, OSError, msg.ProtocolError, asyncio.TimeoutError):
                pass
        self._session._closed()
        await self._client.close()
        self._connection = None
        self._read_connections.clear()

    # -- connection management --------------------------------------------

    async def _connect(self) -> Connection:
        if self._connection is not None and not self._connection.closed:
            return self._connection
        candidates: list[Address] = []
        if self._leader_hint is not None:
            candidates.append(self._leader_hint)
        order = self.strategy.order(self.members)
        if isinstance(self.strategy, AnyConnectionStrategy):
            # a shuffle states no preference, so the member whose attempt
            # just failed goes to its back (a hint still goes first):
            # redialed at random, a partitioned leader that still takes
            # commands and never answers caught the retry one time in
            # three for another whole per-try timeout
            order.sort(key=lambda a: a == self._failed_last)
        candidates += [a for a in order if a not in candidates]
        last_error: Exception | None = None
        for address in candidates:
            self._dials += 1
            try:
                conn = await self._client.connect(address)
            except (TransportError, OSError) as e:
                last_error = e
                continue
            current = self._connection
            if current is not None and not current.closed:
                # another request's dial landed while this one was out:
                # the session keeps one connection
                spawn(conn.close(), name="drop-connection")
                return current
            conn.handler(msg.PublishRequest, self._on_publish)
            self._connection = conn
            self._connected_to = address
            return conn
        raise TransportError(f"no reachable server in {self.members}") from last_error

    def _drop_connection(self) -> None:
        conn = self._connection
        self._connection = None
        self._connected_to = None
        if conn is not None and not conn.closed:
            spawn(conn.close(), name="drop-connection")

    async def _request(self, request: Any, leader_required: bool = True,
                       attempts: int = 30,
                       per_try_timeout: float | None = None) -> Any:
        """Send with retry/re-route until a non-routing error or success.

        ``per_try_timeout`` bounds ONE attempt (default: the session
        timeout). Keep-alives pass a fraction of it: an attempt stuck at
        a stale leader (appended, never committable) otherwise burns the
        whole session budget before re-routing — the session then
        expires at the real leader even though the majority was
        reachable all along (found by the partition nemesis once
        new-leader expiry actually worked)."""
        backoff = 0.01
        last: Exception | None = None
        tmo = per_try_timeout if per_try_timeout is not None \
            else self.session_timeout
        for _ in range(attempts):
            conn = None
            try:
                conn = await self._connect()
                response = await asyncio.wait_for(conn.send(request), tmo)
            except (TransportError, OSError, asyncio.TimeoutError) as e:
                last = e
                if conn is not None and conn is not self._connection:
                    # another request's failure already replaced the
                    # connection this attempt rode (a keep-alive gives up
                    # sooner than a command): retry on the new one, do
                    # not drop it and dial again
                    continue
                self._failed_last = self._connected_to
                # A hinted leader that failed the attempt gets no second
                # pin: _connect prefers the hint, so keeping it after a
                # timeout re-dialed the SAME stuck server every retry —
                # under a partitioned-but-dialable old leader the client
                # never reached the majority side (found by the
                # partition nemesis, tests/test_nemesis_raft.py).
                if self._connected_to is not None \
                        and self._connected_to == self._leader_hint:
                    self._leader_hint = None
                self._drop_connection()
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, 0.25)
                continue
            error = getattr(response, "error", None)
            if error in (msg.NOT_LEADER, msg.NO_LEADER):
                self._leader_hint = getattr(response, "leader", None)
                members = getattr(response, "members", None)
                if members:
                    self.members = list(members)
                if leader_required or self._leader_hint is None:
                    self._drop_connection()
                    await asyncio.sleep(backoff)
                    backoff = min(backoff * 2, 0.25)
                    continue
            return response
        raise msg.ProtocolError(msg.NO_LEADER, f"no leader after retries: {last}")

    async def _request_read(self, request: Any) -> Any:
        """Send one SEQUENTIAL/CAUSAL read to the next server round-robin
        (followers included — they serve at or after the client's index
        via the server-side applied wait), falling back to the routed
        leader path when a follower is unreachable, lagging behind the
        client's index, or refuses to serve. Read connections are cached
        separately from the session connection so follower reads never
        steal the event/command channel."""
        members = list(self.members)
        count = len(members)
        for _ in range(count):
            address = members[self._read_rr % count]
            self._read_rr += 1
            conn = self._read_connections.get(address)
            if conn is None or conn.closed:
                try:
                    conn = await self._client.connect(address)
                except (TransportError, OSError):
                    continue
                self._read_connections[address] = conn
            try:
                response = await asyncio.wait_for(
                    conn.send(request), self.session_timeout)
            except (TransportError, OSError, asyncio.TimeoutError):
                self._read_connections.pop(address, None)
                if not conn.closed:
                    spawn(conn.close(), name="drop-read-connection")
                continue
            error = getattr(response, "error", None)
            if error in (msg.NOT_LEADER, msg.NO_LEADER, msg.INTERNAL):
                # lag refusal ("state lagging behind client index") or a
                # server that won't serve: take the leader-routed path
                break
            self.metrics.counter("client_reads_follower_lane").inc()
            return response
        return await self._request(request, leader_required=False)

    # -- session protocol --------------------------------------------------

    async def _register(self) -> None:
        response = await self._request(msg.RegisterRequest(
            client_id=self.client_id, timeout=self.session_timeout))
        response.raise_if_error()
        self._session.id = response.session_id
        self._session.timeout = response.timeout or self.session_timeout
        if response.members:
            self.members = list(response.members)
        # multi-group server (docs/SHARDING.md): switch on per-group
        # read indices + event channels for this session's lifetime
        self._num_groups = max(1, getattr(response, "groups", None) or 1)
        self._session._opened()

    async def _send_keepalive(self) -> None:
        if not self._session.is_open:
            return
        unsub = (self._edge.take_unsubscribes()
                 if self._edge is not None else None)
        command_seq = self._acked_command_seq
        try:
            session = self._session
            event_index: Any = (session.event_index
                                if self._num_groups == 1
                                else dict(session._event_indices))
            response = await self._request(
                msg.KeepAliveRequest(
                    session_id=session.id,
                    command_seq=command_seq,
                    event_index=event_index,
                    unsubscribe=unsub),
                # timeout/4 = the keep-alive interval: a stuck attempt
                # yields to the next tick's re-route, and the floor
                # keeps slow-but-healthy commits (hundreds of ms) from
                # spuriously dropping the shared connection
                per_try_timeout=max(1.0, self._session.timeout / 4.0))
        except (msg.ProtocolError, TransportError, OSError, asyncio.TimeoutError):
            if self._edge is not None:
                # retiring a subscription is idempotent: re-stage for
                # the next tick instead of leaking the registry entry
                self._edge.restage_unsubscribes(unsub)
            return
        if response.error == msg.UNKNOWN_SESSION:
            self._session._expired()
        elif response.ok:
            self._kept_alive_seq = max(self._kept_alive_seq, command_seq)
            if response.members:
                self.members = list(response.members)

    def _replies_resolved(self) -> None:
        """A reply frame's futures are resolved: acknowledge by count.
        Starts a keep-alive once ``_KEEPALIVE_REPLIES`` replies have come
        in since the last answered one acknowledged its prefix, unless
        such a one is on its way (the next frame after its answer asks
        again)."""
        if (self._acked_command_seq - self._kept_alive_seq
                >= _KEEPALIVE_REPLIES and self._session.is_open
                and (self._early_keepalive is None
                     or self._early_keepalive.done())):
            self._m_keepalives_early.inc()
            self._early_keepalive = spawn(self._send_keepalive(),
                                          name="keepalive-early")

    async def _on_publish(self, request: msg.PublishRequest) -> msg.PublishResponse:
        session = self._session
        trace = getattr(request, "trace", None)
        t0 = time.perf_counter() if trace is not None else 0.0
        # the event channel is per group on a multi-group server (the
        # response's event_index is the position on THAT group's channel)
        g = getattr(request, "group", None) or 0
        position = session._event_indices.get(g, 0)
        if request.session_id != session.id:
            return msg.PublishResponse(event_index=position)
        deltas = getattr(request, "deltas", None)
        if deltas and self._edge is not None:
            # edge state deltas (docs/EDGE_READS.md): merged BEFORE the
            # event-channel gap check — the CRDT merge needs no position
            self._edge.ingest(deltas, trace)
        if request.event_index is None:
            # delta-only push: the event channel's position is untouched
            return msg.PublishResponse(event_index=position)
        # the batch in the request's own fields, then the session's further
        # sealed batches in order (``more``), each under the same rule
        batches = [(request.event_index, request.prev_event_index,
                    request.events)]
        more = getattr(request, "more", None)
        if more:
            batches += more
        received = 0
        for event_index, prev_event_index, events in batches:
            if prev_event_index != position:
                # Gap or replay: report our position; the server resends
                # from there.
                break
            for event, message in events or ():
                try:
                    session._dispatch(event, message)
                except Exception:  # listener errors must not poison the channel
                    pass
            received += len(events or ())
            position = session._event_indices[g] = event_index
        self._m_events_received.inc(received)
        if trace is not None and received:
            # traced event delivery: receipt + listener dispatch on the
            # originating causal timeline (member tag "client")
            TRACER.span(trace, "client.event", t0, time.perf_counter(),
                        group=g, n=received)
        return msg.PublishResponse(event_index=position)

    # -- operation submission ---------------------------------------------

    async def submit(self, operation: Operation) -> Any:
        if isinstance(operation, Query):
            return await self._submit_query(operation)
        return await self._submit_command(operation)

    def submit_command_nowait(self, operation: Command) -> "asyncio.Future":
        """Stage one command into the current micro-batch and return its
        future directly (no coroutine frame). The awaitable-returning hot
        path: resource facades flatten their submit chain through this,
        cutting ~4 async frames per op off the public SPI plane."""
        if not self._session.is_open:
            raise SessionExpiredError("session is not open")
        self._command_seq += 1
        seq = self._command_seq
        loop = self._loop  # pinned at open: one lookup per op saved
        fut: asyncio.Future = loop.create_future()
        self._pending_batch.append((seq, operation, fut))
        if not self._batch_scheduled:
            self._batch_scheduled = True
            if TRACER.enabled:
                self._stage_span = TRACER.open_span("client.stage")
            loop.call_soon(self._launch_batch)
        return fut

    async def _submit_command(self, operation: Command) -> Any:
        return await self.submit_command_nowait(operation)

    def _launch_batch(self) -> None:
        self._batch_scheduled = False
        batch, self._pending_batch = self._pending_batch, []
        staged, self._stage_span = self._stage_span, None
        if batch:
            spawn(self._flush_batch(batch, staged), name="command-batch")
        elif staged is not None:
            staged.drop()

    def _submit_done(self, t0: float, n: int, trace: int | None) -> Any:
        """Per-request latency bookkeeping: one histogram sample per wire
        request (every command in a batch experienced that latency), one
        ``client.submit`` span when tracing. Returns the batch's open
        ``client.resolve`` span (responses correlated -> last future
        resolved) when traced, else ``None``."""
        end = time.perf_counter()
        self.metrics.histogram("submit_latency_ms").record((end - t0) * 1e3)
        if trace is None:
            return None
        TRACER.span(trace, "client.submit", t0, end, n=n)
        return TRACER.open_span("client.resolve", trace, start=end)

    async def _flush_batch(self, batch: list, staged: Any = None) -> None:
        self.metrics.counter("commands_submitted").inc(len(batch))
        if staged is not None:
            # the staging span joins the flush's trace (its id crosses
            # the wire); the flush begins where the staging ends
            trace = staged.trace_id
            t0 = staged.close(n=len(batch))
        else:
            trace = TRACER.new_trace() if TRACER.enabled else None
            t0 = time.perf_counter()
        if len(batch) == 1:
            seq, operation, fut = batch[0]
            try:
                response = await self._send_commands(batch, trace)
                result = self._finish(response, seq)
            except BaseException as e:  # noqa: BLE001 — delivered via fut
                if not fut.done():
                    fut.set_exception(e)
                return
            resolve = self._submit_done(t0, 1, trace)
            if not fut.done():
                fut.set_result(result)
            if resolve is not None:
                resolve.close(n=1)
            self._replies_resolved()
            return
        try:
            response = await self._send_commands(batch, trace)
            # batch-level fatal (UNKNOWN_SESSION etc.): _finish raises
            # the right exception type for every entry
            if getattr(response, "error", None):
                self._finish(response, None)
        except BaseException as e:  # noqa: BLE001
            for _, _, fut in batch:
                if not fut.done():
                    fut.set_exception(e)
            return
        resolve = self._submit_done(t0, len(batch), trace)
        resp_entries = response.entries or []
        # positional fast path: the server answers in request order, so
        # the common case correlates by zip — the by-seq dict is built
        # only when shapes/seqs disagree (partial or reordered response).
        # The seq comparison runs as two listcomps + one C-level list
        # compare (measurably cheaper than a per-pair generator walk).
        if len(resp_entries) == len(batch) and \
                [e[0] for e in resp_entries] == [b[0] for b in batch]:
            paired = zip(batch, resp_entries)
        else:
            by_seq = {entry[0]: entry for entry in resp_entries}
            paired = ((b, by_seq.get(b[0])) for b in batch)
        try:
            for (seq, _, fut), entry in paired:
                if entry is None:
                    if not fut.done():
                        fut.set_exception(msg.ProtocolError(
                            msg.INTERNAL,
                            f"seq {seq} missing from batch response"))
                    continue
                _, index, result, code, detail = entry
                # ack BEFORE consulting fut.done(): a caller-cancelled
                # command that succeeded server-side must still advance
                # the contiguous ack prefix, or server response-cache
                # pruning stalls behind it forever
                if code in (None, msg.APPLICATION):
                    self._ack_seq(seq, index)
                if fut.done():
                    continue
                if code == msg.APPLICATION:
                    fut.set_exception(
                        ApplicationError(detail or "application error"))
                elif code:
                    fut.set_exception(msg.ProtocolError(code, detail or ""))
                else:
                    fut.set_result(result)
        except BaseException as e:  # noqa: BLE001 — no caller may hang
            for _, _, fut in batch:
                if not fut.done():
                    fut.set_exception(e)
            raise
        finally:
            if resolve is not None:
                resolve.close(n=len(batch))
        self._replies_resolved()

    def _command_request(self, entries: list, trace: int | None) -> Any:
        """The wire request for ``entries`` (``(seq, operation, ...)``):
        a lone command rides ``CommandRequest``."""
        if len(entries) == 1:
            return msg.CommandRequest(
                session_id=self._session.id, seq=entries[0][0],
                operation=entries[0][1], trace=trace)
        return msg.CommandBatchRequest(
            session_id=self._session.id,
            entries=[(e[0], e[1]) for e in entries], trace=trace)

    async def _send_commands(self, batch: list, trace: int | None) -> Any:
        """One batch's response: its own request on the session's
        connection while that stands; once the connection is lost, or the
        member at its end says it does not lead, what the session's one
        failover brings back for it (:meth:`_run_failover`)."""
        first = batch[0][0]
        waiter: asyncio.Future = self._loop.create_future()
        self._unanswered[first] = (batch, waiter)
        try:
            if self._failover is None:
                try:
                    # one try on the session's connection; a lost
                    # connection or a member that does not lead has been
                    # dropped, and the leader's hint taken, by the time
                    # it gives up
                    return await self._request(
                        self._command_request(batch, trace), attempts=1)
                except (msg.ProtocolError, TransportError, OSError):
                    pass
                if waiter.done():    # a failover already resubmitted it
                    return waiter.result()
                if self._failover is None:
                    self._failover = spawn(self._run_failover(),
                                           name="client-failover")
            return await waiter
        finally:
            self._unanswered.pop(first, None)
            if not waiter.done():
                waiter.cancel()

    async def _run_failover(self) -> None:
        """The session's failover, once for everything in flight (the
        connection it was on is dropped already): reach the leader with
        ONE keep-alive through the routed loop (it dials, is told who
        leads, dials again), then resubmit every unanswered command as
        one block in sequence order.
        The new leader answers what it already applied from the session's
        replicated response cache and appends the rest (exactly once by
        seq). Batches flushed meanwhile wait and ride the next block, so
        nothing of the session overtakes what is unanswered."""
        t0 = time.perf_counter()
        dials = self._dials
        inflight = sum(len(b) for b, _ in self._unanswered.values())
        error: BaseException = msg.ProtocolError(
            msg.INTERNAL, "failover ended with commands unanswered")
        try:
            session = self._session
            response = await self._request(msg.KeepAliveRequest(
                session_id=session.id, command_seq=self._acked_command_seq,
                event_index=(session.event_index if self._num_groups == 1
                             else dict(session._event_indices))),
                per_try_timeout=max(1.0, session.timeout / 4.0))
            if response.error == msg.UNKNOWN_SESSION:
                session._expired()
                raise SessionExpiredError("session expired")
            response.raise_if_error()
            if TRACER.enabled:
                TRACER.span(TRACER.new_trace(), "client.failover", t0,
                            time.perf_counter(), inflight=inflight,
                            resubmitted=sum(
                                len(b) for b, w in self._unanswered.values()
                                if not w.done()),
                            attempts=self._dials - dials)
            while True:
                waiting = [(batch, waiter) for _, (batch, waiter)
                           in sorted(self._unanswered.items())
                           if not waiter.done()]
                if not waiting:
                    break
                entries = [e for batch, _ in waiting for e in batch]
                self._m_resubmitted.inc(len(entries))
                response = await self._request(
                    self._command_request(entries, None))
                self._hand_out(response, waiting)
        except BaseException as e:  # noqa: BLE001 - handed to every waiter
            error = e
            if not isinstance(e, Exception):
                raise
        finally:
            self._failover = None
            for _, waiter in list(self._unanswered.values()):
                if not waiter.done():
                    waiter.set_exception(error)

    @staticmethod
    def _hand_out(response: Any, waiting: list) -> None:
        """Give each waiting batch its part of a resubmitted block's
        response, in the shape its own request would have been answered
        in. A response-level error is every batch's."""
        if getattr(response, "error", None):
            for _, waiter in waiting:
                if not waiter.done():
                    waiter.set_result(response)
            return
        if isinstance(response, msg.CommandResponse):   # a block of one
            (batch, waiter), = waiting
            if not waiter.done():
                waiter.set_result(response)
            return
        by_seq = {entry[0]: entry for entry in response.entries or ()}
        for batch, waiter in waiting:
            if waiter.done():
                continue
            if len(batch) > 1:
                waiter.set_result(msg.CommandBatchResponse(
                    event_index=response.event_index,
                    entries=[by_seq[seq] for seq, _, _ in batch
                             if seq in by_seq]))
                continue
            seq = batch[0][0]
            _, index, result, code, detail = by_seq.get(seq) or (
                seq, 0, None, msg.INTERNAL,
                f"seq {seq} missing from batch response")
            waiter.set_result(msg.CommandResponse(
                index=index, result=result, error=code, error_detail=detail,
                event_index=response.event_index))

    def _ack_seq(self, seq: int, index: int | None) -> None:
        """Per-command success bookkeeping (the _finish tail): advance the
        sequential-read index and the contiguous completed-seq prefix the
        keep-alive acks for server response-cache pruning."""
        self._note_index(index)
        # in-order completion (every batch entry in a healthy run): just
        # bump the prefix — the out-of-order set stays untouched/empty
        if seq == self._acked_command_seq + 1 and not self._completed_seqs:
            self._acked_command_seq = seq
            return
        self._completed_seqs.add(seq)
        while self._acked_command_seq + 1 in self._completed_seqs:
            self._acked_command_seq += 1
            self._completed_seqs.discard(self._acked_command_seq)

    async def _submit_query(self, operation: Query) -> Any:
        if not self._session.is_open:
            raise SessionExpiredError("session is not open")
        self.metrics.counter("queries_submitted").inc()
        consistency = operation.consistency().value
        edge = self._edge
        if edge is not None and consistency not in (
                "linearizable", "bounded_linearizable"):
            # edge fast path (docs/EDGE_READS.md): a warm replica
            # serves SYNCHRONOUSLY — no future, no micro-batch flush,
            # no wire round-trip; misses fall through to the staged
            # server path (which subscribes + seeds)
            result = edge.try_serve(operation)
            if result is not edge.MISS:
                return result
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._pending_queries.setdefault(consistency, []).append(
            (operation, fut))
        if not self._query_flush_scheduled:
            self._query_flush_scheduled = True
            if TRACER.enabled:
                self._query_stage_span = TRACER.open_span("client.stage")
            loop.call_soon(self._launch_query_batches)
        return await fut

    def _launch_query_batches(self) -> None:
        self._query_flush_scheduled = False
        pending, self._pending_queries = self._pending_queries, {}
        staged, self._query_stage_span = self._query_stage_span, None
        t_staged = staged.start if staged is not None else None
        for consistency, items in pending.items():
            if items:
                if staged is None and t_staged is not None:
                    # a further level staged this turn: a batch of its own
                    staged = TRACER.open_span("client.stage", start=t_staged)
                spawn(self._flush_query_batch(consistency, items, staged),
                      name="query-batch")
                staged = None
        if staged is not None:
            staged.drop()

    async def _flush_query_batch(self, consistency: str,
                                 items: list, staged: Any = None) -> None:
        """One consistency level's read batch. ``staged`` is its open
        ``client.stage`` span when traced; the batch's ``client.query``
        (flush -> responses correlated) and ``client.resolve`` spans
        follow it under the same client-local id (a ``Query*Request``
        carries no trace id)."""
        n = len(items)
        query = (staged.then("client.query", n=n)
                 if staged is not None else None)
        leader_required = consistency in ("linearizable",
                                          "bounded_linearizable")
        # Edge read tier (docs/EDGE_READS.md): these reads already
        # missed the replica (the fast path in _submit_query serves
        # hits synchronously) — edge-shaped misses carry the
        # `subscribe` flag and route over the SESSION connection (the
        # member that pushes this session's deltas), so the response
        # seeds the replica and later reads stay local.
        edge = self._edge if not leader_required else None
        subscribe = (1 if edge is not None and edge.wants_subscribe(items)
                     else None)
        # every read is tagged with its consistency (the request field);
        # sub-linearizable levels route round-robin across replicas
        # (subscribing reads excepted — deltas flow over the session
        # connection, so the subscription must land on its holder)
        round_robin = (not leader_required and self._follower_reads
                       and subscribe is None and len(self.members) > 1)
        if len(items) == 1:
            operation, fut = items[0]
            request = msg.QueryRequest(
                session_id=self._session.id, index=self._read_index(),
                operation=operation, consistency=consistency,
                subscribe=subscribe)
            try:
                if round_robin:
                    response = await self._request_read(request)
                else:
                    response = await self._request(
                        request, leader_required=leader_required)
                result = self._finish(response, None)
            except BaseException as e:  # noqa: BLE001 — delivered via fut
                if query is not None:
                    query.close(n=1, error=type(e).__name__)
                if not fut.done():
                    fut.set_exception(e)
                return
            resolve = (query.then("client.resolve", n=1)
                       if query is not None else None)
            if subscribe is not None and edge is not None:
                edge.seed_response(items, getattr(response, "edge", None))
            if not fut.done():
                fut.set_result(result)
            if resolve is not None:
                resolve.close(n=1)
            return
        try:
            request = msg.QueryBatchRequest(
                session_id=self._session.id, index=self._read_index(),
                consistency=consistency,
                operations=[op for op, _ in items],
                subscribe=subscribe)
            if round_robin:
                response = await self._request_read(request)
            else:
                response = await self._request(
                    request, leader_required=leader_required)
            if getattr(response, "error", None):
                self._finish(response, None)  # raises the right exception
        except BaseException as e:  # noqa: BLE001
            if query is not None:
                query.close(n=n, error=type(e).__name__)
            for _, fut in items:
                if not fut.done():
                    fut.set_exception(e)
            return
        resolve = (query.then("client.resolve", n=n)
                   if query is not None else None)
        if subscribe is not None and edge is not None:
            edge.seed_response(items, getattr(response, "edge", None))
        try:
            self._note_index(response.index)
            entries = response.entries or []
            for k, (operation, fut) in enumerate(items):
                if fut.done():
                    continue
                if k >= len(entries):
                    fut.set_exception(msg.ProtocolError(
                        msg.INTERNAL, "missing batch query entry"))
                    continue
                result, code, detail = entries[k]
                if code == msg.APPLICATION:
                    fut.set_exception(
                        ApplicationError(detail or "application error"))
                elif code:
                    fut.set_exception(msg.ProtocolError(code, detail or ""))
                else:
                    fut.set_result(result)
        except BaseException as e:  # noqa: BLE001 — no caller may hang
            for _, fut in items:
                if not fut.done():
                    fut.set_exception(e)
            raise
        finally:
            if resolve is not None:
                resolve.close(n=n)

    def _finish(self, response: Any, seq: int | None) -> Any:
        error = getattr(response, "error", None)
        if error == msg.UNKNOWN_SESSION:
            self._session._expired()
            raise SessionExpiredError("session expired")
        if error == msg.APPLICATION:
            if seq is not None:
                # an application error IS a delivered response: ack the
                # seq or the contiguous ack prefix (and server response-
                # cache pruning) would stall behind it forever
                self._ack_seq(seq, getattr(response, "index", None))
            raise ApplicationError(response.error_detail or "application error")
        response.raise_if_error()
        if seq is not None:
            self._ack_seq(seq, response.index)
        else:
            self._note_index(getattr(response, "index", None))
        return response.result

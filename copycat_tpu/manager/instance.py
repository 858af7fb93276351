"""Client-side resource virtualization (reference ``InstanceClient.java:35``,
``InstanceSession.java:33``).

``InstanceClient`` implements the RaftClient submit surface but prefixes every
operation with the instance id; ``InstanceSession`` filters the parent
session's events down to this instance (by ``InstanceEvent.resource``).
"""

from __future__ import annotations

import weakref
from typing import Any, Callable

from ..client.client import ClientSession, RaftClient
from ..protocol.operations import Command, Operation, Query
from ..resource.operations import DeleteCommand
from ..utils.listeners import Listener, Listeners
from .operations import DeleteResource, InstanceCommand, InstanceEvent, InstanceQuery


class _EventRouter:
    """One listener an event name on a client session for ALL of its
    instances: an ``InstanceEvent`` goes to the instance it names, by a
    dictionary. (A listener an instance, each comparing the event's
    instance id with its own as ``InstanceSession.java`` handleEvent does,
    costs every event a call an instance: 10,000 locks on a session made
    each grant 10,000 calls.) An event that names no instance goes to all
    of them, as before."""

    def __init__(self) -> None:
        self.routes: dict[str, dict[int, "InstanceSession"]] = {}
        self.listeners: dict[str, Listener] = {}

    def add(self, event: str, session: "InstanceSession") -> None:
        by_id = self.routes.get(event)
        if by_id is None:
            by_id = self.routes[event] = {}
            self.listeners[event] = session.parent.on_event(
                event, lambda message, _e=event: self._handle(_e, message))
        by_id[session.id] = session

    def remove(self, session: "InstanceSession") -> None:
        for event in [e for e, by_id in self.routes.items()
                      if by_id.pop(session.id, None) is not None
                      and not by_id]:
            del self.routes[event]
            self.listeners.pop(event).close()

    def _handle(self, event: str, message: Any) -> None:
        by_id = self.routes.get(event)
        if not by_id:
            return
        if isinstance(message, InstanceEvent):
            session = by_id.get(message.resource)
            if session is not None:
                session._handle(event, message.message)
        else:
            for session in list(by_id.values()):
                session._handle(event, message)


#: the router of each client session that has instances (it holds no
#: reference to the session, so the entry goes with it)
_ROUTERS: "weakref.WeakKeyDictionary[ClientSession, _EventRouter]" = \
    weakref.WeakKeyDictionary()


class InstanceSession:
    """Per-resource view over the parent client session."""

    def __init__(self, instance_id: int, parent: ClientSession) -> None:
        self.id = instance_id
        self.parent = parent
        self._local_listeners: dict[str, Listeners] = {}

    @property
    def is_open(self) -> bool:
        return self.parent.is_open

    def on_event(self, event: str, callback: Callable[[Any], Any]) -> Listener:
        listeners = self._local_listeners.get(event)
        if listeners is None:
            listeners = self._local_listeners[event] = Listeners()
            # the parent's events reach this instance through the session's
            # one router, by instance id
            router = _ROUTERS.get(self.parent)
            if router is None:
                router = _ROUTERS[self.parent] = _EventRouter()
            router.add(event, self)
        local = listeners.add(callback)
        return local

    def _handle(self, event: str, payload: Any) -> None:
        listeners = self._local_listeners.get(event)
        if listeners is not None:
            listeners.accept(payload)

    def publish(self, event: str, message: Any = None) -> None:
        """Local loopback publish: only this node's listeners see it."""
        listeners = self._local_listeners.get(event)
        if listeners is not None:
            listeners.accept(message)

    def on_open(self, callback: Callable[[Any], Any]) -> Listener:
        return self.parent.on_open(callback)

    def on_close(self, callback: Callable[[Any], Any]) -> Listener:
        return self.parent.on_close(callback)

    def close(self) -> None:
        router = _ROUTERS.get(self.parent)
        if router is not None:
            router.remove(self)
        self._local_listeners.clear()


class InstanceClient:
    """RaftClient facade routing every op to one resource instance."""

    def __init__(self, instance_id: int, client: RaftClient,
                 on_delete=None) -> None:
        self.instance_id = instance_id
        self.client = client
        self._session = InstanceSession(instance_id, client.session())
        # notifies the owning Atomix facade so its get() singleton cache
        # drops the key — a later get() must create a FRESH resource, not
        # hand back a facade whose server-side instance is gone
        self._on_delete = on_delete

    def session(self) -> InstanceSession:
        return self._session

    def submit_command_nowait(self, operation: Operation) -> Any:
        """Future-returning command submit (the flattened hot path):
        wraps in the instance envelope and stages straight into the
        parent client's micro-batch. Plain commands only — delete
        chaining and queries keep the coroutine path."""
        return self.client.submit_command_nowait(
            InstanceCommand(self.instance_id, operation))

    async def submit(self, operation: Operation) -> Any:
        if isinstance(operation, DeleteCommand):
            # Reference InstanceClient.java:73-75: resource-level delete, then
            # catalog-level DeleteResource.
            result = await self.client.submit(
                InstanceCommand(self.instance_id, operation))
            await self.client.submit(DeleteResource(self.instance_id))
            self._session.close()
            if self._on_delete is not None:
                self._on_delete()
            return result
        if isinstance(operation, Query):
            return await self.client.submit(InstanceQuery(self.instance_id, operation))
        if isinstance(operation, Command):
            return await self.client.submit(InstanceCommand(self.instance_id, operation))
        raise TypeError(f"not an operation: {operation!r}")

    async def close(self) -> None:
        self._session.close()

"""Async test harness.

The reference's tests use ConcurrentUnit's ``resume()``/``await()`` pattern
(SURVEY.md §4); with asyncio we simply run each test body as a coroutine with a
hard timeout so a hung cluster fails rather than wedging the suite.
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
from typing import Any, Awaitable, Callable


def arun(coro: Awaitable[Any], timeout: float = 60.0) -> Any:
    async def wrapped() -> Any:
        return await asyncio.wait_for(coro, timeout)

    return asyncio.run(wrapped())


def async_test(fn: Callable[..., Awaitable[None]] | None = None, *, timeout: float = 60.0):
    """Decorator turning ``async def test_*`` into a sync pytest test."""

    def deco(f: Callable[..., Awaitable[None]]):
        @functools.wraps(f)
        def sync(*args: Any, **kwargs: Any) -> None:
            arun(f(*args, **kwargs), timeout=timeout)

        return sync

    if fn is not None:
        return deco(fn)
    return deco


@contextlib.contextmanager
def the_tick_after_the_window():
    """A loaded server's order of callbacks: the fused collector's tick
    (``RaftServer._fused_tick``) comes round after the read window that
    was staged in the same turn, so the window finds the run parked and
    its reads ride the run's round. (A server with one burst in flight
    runs the tick one callback ahead of the window, and nothing is parked
    by the time the window is evaluated.)"""
    from copycat_tpu.server.raft import RaftServer

    real = RaftServer._fused_tick

    def late(self, turns: int = 3) -> None:
        if turns:
            asyncio.get_running_loop().call_soon(late, self, turns - 1)
        else:
            real(self)

    RaftServer._fused_tick = late
    try:
        yield
    finally:
        RaftServer._fused_tick = real

"""The plain reference of the ``lock`` plane: a holder and a queue per lock.

``PlainLocks`` is a mutex with a FIFO of waiters, as the reference's
``LockState.java:41-58`` states it: an acquire of a free lock is granted at
once, of a held lock queued (or refused, for a try-lock); a release by the
holder hands the lock to the first waiter. Waiters are named by the id the
service gave their ``Lock`` command (its commit index), so ids of one lock
ascend in the order the commands committed. Nothing here imports
``copycat_tpu`` or takes anything the program computed beyond those ids.

:func:`grant_order` replays what the clients of one lock were told (the ids
their ``Lock`` calls were answered with, the unlocks that were acknowledged,
in the order each holder sent them) and answers who held the lock in which
order: whatever the interleaving of acquires and releases was, a FIFO mutex
grants in ascending id, each id once and none skipped.
"""

from __future__ import annotations

from collections import deque


class PlainLocks:
    """``locks`` plain mutexes: ``[holder or None, deque of waiter ids]``."""

    def __init__(self, locks: int) -> None:
        self.locks: list[list] = [[None, deque()] for _ in range(locks)]

    def acquire(self, lock: int, waiter: int, wait: bool = True) -> bool | None:
        """True: granted at once; None: queued; False: refused (a try-lock
        of a held lock)."""
        state = self.locks[lock]
        if state[0] is None:
            state[0] = waiter
            return True
        if not wait:
            return False
        state[1].append(waiter)
        return None

    def release(self, lock: int, waiter: int) -> int | None:
        """The holder ``waiter`` lets go: the id the lock passes to, or
        ``None`` where nobody waits. ``ValueError`` where ``waiter`` does not
        hold the lock."""
        state = self.locks[lock]
        if state[0] != waiter:
            raise ValueError(f"lock {lock}: {waiter} released, "
                             f"{state[0]} holds")
        state[0] = state[1].popleft() if state[1] else None
        return state[0]

    def holder(self, lock: int) -> int | None:
        return self.locks[lock][0]

    def waiting(self, lock: int) -> list[int]:
        return list(self.locks[lock][1])

    def free(self) -> int:
        """Locks with no holder and no waiter."""
        return sum(1 for holder, queue in self.locks
                   if holder is None and not queue)


def grant_order(model: PlainLocks, lock: int, acquired: list[int],
                released: list[int]) -> tuple[list[int], int]:
    """Replay one lock's history on ``model``: the ids its ``Lock`` calls
    were answered with (``acquired``, any order: they commit in ascending id)
    and the ids whose unlock was acknowledged, in the order the holders sent
    them (``released``). Returns the ids in the order the plain lock granted
    them, and how many releases came from an id that did not hold the lock
    (each is skipped)."""
    grants: list[int] = []
    for waiter in sorted(acquired):
        if model.acquire(lock, waiter):
            grants.append(waiter)
    refused = 0
    for waiter in released:
        try:
            passed_to = model.release(lock, waiter)
        except ValueError:
            refused += 1
            continue
        if passed_to is not None:
            grants.append(passed_to)
    return grants, refused

"""The plain reference of the ``election`` plane: a leader and a FIFO of
waiting candidates per election.

``PlainElections`` is ``LeaderElectionState.java:31-96`` as SURVEY.md states
it: the first ``listen`` of a vacant election wins it; later ones wait in the
order they committed; when the leader unlistens or its session ends
(``close:36-49``) the first waiting candidate whose session is still alive is
promoted and told; a waiting candidate that unlistens or dies is taken out of
the line; ``is_leader(epoch)`` is true for the current leader's epoch and for
no other. The epoch is the log index of the command (or session end) that made
the candidate leader: the winning listen's own index where the election was
vacant, the index of the hand-over otherwise, so an election's epochs rise.
Nothing here imports ``copycat_tpu`` or takes anything the program computed:
a caller gives every command its commit index and its candidate.

:func:`replay` runs one election's committed history (listens, unlistens and
session ends in commit order) and answers who led it in which order.
"""

from __future__ import annotations

from collections import deque

LISTEN, UNLISTEN, END = 0, 1, 2


class PlainElections:
    """``elections`` plain elections: ``[leader or None, epoch, deque of
    waiting candidates]``. A candidate is whatever the caller names it by
    (the plane: the number of a client's candidacy)."""

    def __init__(self, elections: int) -> None:
        self.elections: list[list] = [[None, 0, deque()]
                                      for _ in range(elections)]

    def listen(self, election: int, candidate, index: int) -> int | None:
        """``candidate`` asks to lead: the epoch where it wins now, ``None``
        where it waits (or already leads or waits: a listen twice is once)."""
        state = self.elections[election]
        if state[0] is None:
            state[0], state[1] = candidate, index
            return index
        if state[0] != candidate and candidate not in state[2]:
            state[2].append(candidate)
        return None

    def unlisten(self, election: int, candidate, index: int,
                 alive=lambda candidate: True) -> tuple | None:
        """``candidate`` resigns or leaves the line: ``(successor, epoch)``
        where the leader went and somebody alive waited, else ``None``."""
        state = self.elections[election]
        if state[0] == candidate:
            return self._promote(state, index, alive)
        try:
            state[2].remove(candidate)
        except ValueError:
            pass
        return None

    def session_end(self, election: int, candidates, index: int,
                    alive=lambda candidate: True) -> tuple | None:
        """The session that held ``candidates`` of this election ended, in
        the order given: each is unlisted; ``(successor, epoch)`` of the last
        hand-over that told somebody alive, else ``None``. ``alive`` has to
        be false for every one of ``candidates``: a successor that is itself
        dead is passed over."""
        told = None
        for candidate in candidates:
            told = self.unlisten(election, candidate, index, alive) or told
        return told

    @staticmethod
    def _promote(state: list, index: int, alive) -> tuple | None:
        state[0] = None
        while state[2]:
            successor = state[2].popleft()
            if alive(successor):
                state[0], state[1] = successor, index
                return successor, index
        return None

    def is_leader(self, election: int, epoch: int) -> bool:
        state = self.elections[election]
        return state[0] is not None and state[1] == epoch

    def leader(self, election: int):
        return self.elections[election][0]

    def epoch(self, election: int) -> int | None:
        state = self.elections[election]
        return None if state[0] is None else state[1]

    def waiting(self, election: int) -> list:
        return list(self.elections[election][2])


def replay(model: PlainElections, election: int, history: list,
           alive=lambda candidate, index: True) -> list[tuple]:
    """Run one election's committed history on ``model``: ``history`` is
    ``(index, kind, who)`` in commit order, ``who`` a candidate for
    ``LISTEN`` and ``UNLISTEN`` and the dead session's candidates of this
    election, in the order the session got them, for ``END``.
    ``alive(candidate, index)`` says whether the candidate's session had
    not ended by ``index``. Returns ``(candidate, epoch)`` of every leader
    in order."""
    leaders: list[tuple] = []
    for index, kind, who in history:
        if kind == LISTEN:
            epoch = model.listen(election, who, index)
            told = None if epoch is None else (who, epoch)
        else:
            living = lambda c, _i=index: alive(c, _i)  # noqa: E731
            told = model.unlisten(election, who, index, living) \
                if kind == UNLISTEN \
                else model.session_end(election, who, index, living)
        if told is not None:
            leaders.append(told)
    return leaders

"""The plain reference of the ``crash`` plane: counters as a dict of ints, with
a note of the adds nobody answered.

It applies each acknowledged delta once, in the order of the replies of one
counter, and knows nothing of the program: no session, no sequence number, no
log, no leader. One call is outstanding per counter, so the value after an
acknowledged add is exact and a double apply or a lost apply is one wrong
reply. An add that ended without a reply (it raised, it was cut off) may or
may not have been applied: until the counter's next reply says which, the
model allows the value with or without each such delta. A wrong reply is
counted once and then taken as the counter's value, so one fault does not
repeat itself in every later reply of that counter.
"""

from __future__ import annotations

#: the model enumerates the sums of a counter's first eight unanswered adds
#: (2**8 values); a client that loses more than that in a row has failed the
#: run long since
MAX_UNANSWERED = 8


def sums(deltas: list[int]) -> set[int]:
    """Every sum of a subset of ``deltas``: what the unanswered adds may
    have contributed."""
    out = {0}
    for d in deltas[:MAX_UNANSWERED]:
        out |= {s + d for s in out}
    return out


class PlainCounters:
    """``name -> int``; a counter that was never added to reads 0, as a
    ``DistributedAtomicLong`` does."""

    def __init__(self) -> None:
        self.values: dict[str, int] = {}
        self.unanswered: dict[str, list[int]] = {}

    def get(self, name: str) -> int:
        return self.values.get(name, 0)

    def lost(self, name: str, delta: int) -> None:
        """An ``add_and_get(delta)`` ended without a reply."""
        self.unanswered.setdefault(name, []).append(delta)

    def allowed(self, name: str) -> set[int]:
        """The values the counter may hold now."""
        base = self.get(name)
        return {base + s for s in sums(self.unanswered.get(name, []))}

    def add(self, name: str, delta: int, reply: int) -> str:
        """One acknowledged ``add_and_get(delta)`` that answered ``reply``;
        ``""`` if the reply is the value after exactly one application of
        ``delta``, else what was wrong. The reply settles the unanswered
        adds before it: a session's commands apply in its order or never."""
        want = {v + delta for v in self.allowed(name)}
        self.values[name] = reply
        self.unanswered.pop(name, None)
        if reply in want:
            return ""
        return (f"{name}: add {delta} answered {reply}, the model holds "
                + " or ".join(str(v) for v in sorted(want)))


def differences(model: PlainCounters, names: list[str],
                got: list) -> tuple[int, str]:
    """How many of ``got`` (one value per name) the model does not allow,
    and the first such as text."""
    wrong, first = 0, ""
    for name, value in zip(names, got):
        allowed = model.allowed(name)
        if value not in allowed:
            wrong += 1
            first = first or (f"{name}: read {value}, the model holds "
                              + " or ".join(str(v) for v in sorted(allowed)))
    return wrong, first

"""The plain reference of the ``cluster`` plane: counters as a dict of ints.

It applies each acknowledged delta in the order the acknowledgements came and
knows nothing of the program: no session, no log, no engine. One call is
outstanding per counter, so the value after an add is exact and every reply,
every read-back and every member's own copy can be held to it.
"""

from __future__ import annotations


class PlainCounters:
    """``name -> int``; a counter that was never added to reads 0, as a
    ``DistributedAtomicLong`` does."""

    def __init__(self) -> None:
        self.values: dict[str, int] = {}

    def add(self, name: str, delta: int) -> int:
        """Apply one acknowledged ``add_and_get(delta)``; the value the
        reply has to carry."""
        value = self.values.get(name, 0) + delta
        self.values[name] = value
        return value

    def get(self, name: str) -> int:
        return self.values.get(name, 0)


def differences(model: PlainCounters, names: list[str], got: list,
                pending: dict[str, int] | None = None) -> tuple[int, str]:
    """How many of ``got`` (one value per name) differ from the model, and
    the first such as text. ``pending`` holds, for a counter whose last add
    was cut off unanswered, that add's delta: the add may or may not have
    been committed, so the model's value with or without it is right."""
    wrong, first = 0, ""
    for name, value in zip(names, got):
        want = model.get(name)
        allowed = {want}
        if pending and name in pending:
            allowed.add(want + pending[name])
        if value not in allowed:
            wrong += 1
            first = first or (f"{name}: read {value}, the model holds "
                              + " or ".join(str(v) for v in sorted(allowed)))
    return wrong, first

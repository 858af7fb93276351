"""The plain reference: a dict and an int per group, and the replay of the
device's apply reports on it.

Copies of ``chip_smoke.py``'s ``PlainGroup`` and ``replay_reports`` (proven on
the chip in PR 21). Nothing here imports ``ops/apply.py``'s kernels or takes
anything the program has computed; only the opcode numbers, which are the wire
vocabulary, come from the program.
"""

from __future__ import annotations

import numpy as np

FAIL = -(2 ** 31)


class PlainGroup:
    """One group's resources as plain Python values."""

    def __init__(self, queue_slots: int = 16, wait_slots: int = 8) -> None:
        self.counter = 0
        self.map: dict[int, int] = {}
        self.set: set[int] = set()
        self.queue: list[int] = []
        self.holder: int | None = None
        self.waiters: list[int] = []
        self.leader: int | None = None
        self.epoch = 0
        self.listeners: list[int] = []
        self._q, self._w = queue_slots, wait_slots

    def apply(self, op: int, a: int, b: int, index: int | None) -> int | None:
        """Result of one committed op; ``None`` = not modelled (an election
        epoch when the entry's log index is unknown)."""
        from copycat_tpu.ops import apply as ap

        if op == ap.OP_LONG_ADD:
            self.counter += a
            return self.counter
        if op == ap.OP_MAP_PUT:
            old = self.map.get(a, 0)
            self.map[a] = b
            return old
        if op == ap.OP_MAP_GET:
            return self.map.get(a, 0)
        if op == ap.OP_SET_ADD:
            new = a not in self.set
            self.set.add(a)
            return int(new)
        if op == ap.OP_SET_REMOVE:
            had = a in self.set
            self.set.discard(a)
            return int(had)
        if op == ap.OP_Q_OFFER:
            if len(self.queue) >= self._q:
                return 0
            self.queue.append(a)
            return 1
        if op == ap.OP_Q_POLL:
            return self.queue.pop(0) if self.queue else FAIL
        if op == ap.OP_LOCK_ACQUIRE:
            if self.holder is None:
                self.holder = a
                return 1
            if self.holder == a:
                return 1
            if a in self.waiters:
                return 2
            if b != 0 and len(self.waiters) < self._w:
                self.waiters.append(a)
                return 2
            return 0
        if op == ap.OP_LOCK_RELEASE:
            if self.holder != a:
                return 0
            self.holder = self.waiters.pop(0) if self.waiters else None
            return 1
        if op == ap.OP_ELECT_LISTEN:
            if self.leader is None:
                self.leader = a
                self.epoch = index if index is not None else -1
                return index
            if self.leader == a:
                return self.epoch if self.epoch >= 0 else None
            if a not in self.listeners and len(self.listeners) < self._w:
                self.listeners.append(a)
            return 0
        if op == ap.OP_ELECT_RESIGN:
            if self.leader != a:
                if a in self.listeners:
                    self.listeners.remove(a)
                return 0
            if self.listeners:
                self.leader = self.listeners.pop(0)
                self.epoch = index if index is not None else -1
            else:
                self.leader = None
            return 1
        raise ValueError(f"op {op} is not in the mix")


def replay_reports(reports, pattern, S: int, groups) -> tuple[int, int, str]:
    """Replay each sampled group's apply reports in log-index order on a
    :class:`PlainGroup`. Returns (results compared, results that differ or
    break exactly-once, first difference as text)."""
    opc, a_, b_ = pattern
    valid, tag, result, index = (np.asarray(x) for x in reports)
    compared = wrong = 0
    first = ""
    for k, g in enumerate(groups):
        seen: dict[int, tuple[int, int]] = {}
        rr, aa = np.nonzero(valid[:, k] & (tag[:, k] > 0))
        for r, a in zip(rr.tolist(), aa.tolist()):
            entry = (int(tag[r, k, a]), int(result[r, k, a]))
            idx = int(index[r, k, a])
            # at-least-once: a lane catching up re-reports the same entry
            if seen.setdefault(idx, entry) != entry:
                wrong += 1
                first = first or (f"group {g}: index {idx} reported twice "
                                  f"with different contents: {seen[idx]} vs "
                                  f"{entry}")
        tags = [t for t, _ in seen.values()]
        if len(set(tags)) != len(tags):
            wrong += len(tags) - len(set(tags))
            first = first or f"group {g}: an op applied twice"
        model = PlainGroup()
        for idx in sorted(seen):
            t, got = seen[idx]
            j = (t - 1) % S
            want = model.apply(int(opc[j]), int(a_[j]), int(b_[j]), idx)
            compared += 1
            if want != got:
                wrong += 1
                first = first or (
                    f"group {g} index {idx} tag {t} (op {int(opc[j])}): "
                    f"device returned {got}, the plain model {want}")
    return compared, wrong, first

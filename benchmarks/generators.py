"""Traffic generators of the raw consensus plane, with every size an argument.

Copies of ``copycat_tpu/bench.py``'s ``mixed_submits``, ``isolation_masks``,
``victim_deliver`` and ``percentiles`` (sound there, but
``bench.py`` fixes its sizes from ``COPYCAT_BENCH_*`` as it is imported, so the
benchmark does not import it). They live here so that a later change to the
program's own generators cannot move the yardstick.
"""

from __future__ import annotations

import numpy as np

#: one round of BASELINE config #5 per group: (opcode name, a, b) per submit slot
MIXED_PATTERN = (
    ("OP_LONG_ADD", 1, 0), ("OP_MAP_PUT", 3, 5), ("OP_MAP_GET", 3, 0),
    ("OP_SET_ADD", 5, 0), ("OP_SET_REMOVE", 5, 0),
    ("OP_Q_OFFER", 6, 0), ("OP_Q_POLL", 0, 0),
    ("OP_LOCK_ACQUIRE", 9, -1), ("OP_LOCK_RELEASE", 9, 0),
    ("OP_ELECT_LISTEN", 4, 0), ("OP_ELECT_RESIGN", 4, 0),
    ("OP_LONG_ADD", 1, 0), ("OP_MAP_PUT", 7, 8),
    ("OP_Q_OFFER", 6, 0), ("OP_Q_POLL", 0, 0), ("OP_MAP_GET", 7, 0),
)


def mixed_pattern(S: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-slot (opcode, a, b) rows of the mixed round, tiled to ``S`` slots."""
    from copycat_tpu.ops import apply as ap

    rows = [MIXED_PATTERN[j % len(MIXED_PATTERN)] for j in range(S)]
    return (np.asarray([getattr(ap, name) for name, _, _ in rows], np.int32),
            np.asarray([a for _, a, _ in rows], np.int32),
            np.asarray([b for _, _, b in rows], np.int32))


def mixed_submits(G: int, S: int):
    """Every resource kernel in one round, offered to every group."""
    import jax.numpy as jnp

    from copycat_tpu.ops.consensus import Submits

    opc, a, b = (jnp.broadcast_to(jnp.asarray(x)[None, :], (G, S))
                 for x in mixed_pattern(S))
    ones = jnp.ones((G, S), jnp.int32)
    return Submits(opcode=opc, a=a, b=b, c=ones * 0, tag=ones,
                   valid=ones.astype(bool))


def isolation_masks(rounds: int, G: int, P: int, period: int,
                    seed: int) -> np.ndarray:
    """Per-round victim peer per group (-1 = no fault), ``[rounds, G]`` int32:
    the first half of every ``period`` rounds isolates one seeded peer."""
    rng = np.random.default_rng(seed)
    victims = np.full((rounds, G), -1, np.int32)
    for r in range(0, rounds, period):
        victims[r: r + period // 2] = rng.integers(0, P, G, dtype=np.int32)
    return victims


def victim_deliver(victim, G: int, P: int):
    """``deliver[G,P,P]`` isolating ``victim[G]`` (-1 = fully connected)."""
    import jax.numpy as jnp

    peers = jnp.arange(P)
    hit = peers[None, :] == victim[:, None]
    cut = hit[:, :, None] | hit[:, None, :]
    return ~cut | (victim[:, None, None] < 0)


def percentile_rounds(hist: np.ndarray, q: float) -> tuple[int, float]:
    """The ``q`` quantile of an exact count histogram (index = value): the
    bucket it falls in, and the same with the position inside the bucket
    (a latency of ``b`` rounds lies somewhere in ``[b, b + 1)`` of the round
    clock, so the share of the bucket's count below the quantile places it)."""
    total = int(hist.sum())
    if total == 0:
        return 0, 0.0
    cum = np.cumsum(hist)
    b = int(np.searchsorted(cum, q * total))
    below = int(cum[b - 1]) if b else 0
    return b, b + (q * total - below) / max(int(hist[b]), 1)

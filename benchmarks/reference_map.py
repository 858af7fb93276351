"""The plain reference of the ``map`` plane: a dict per map.

``PlainMaps`` applies acknowledged operations in each client's order (one call
outstanding a map makes every reply exact) and answers what a map that lost
nothing would: a ``put``'s previous value, a ``get``'s value, a map's size.
Nothing here imports ``copycat_tpu`` or takes anything the program computed;
the keys and values come from the seed, through :func:`keys_of`.
"""

from __future__ import annotations

import numpy as np

FNV_OFFSET, FNV_PRIME = 0x811C9DC5, 0x01000193


def fnv1a31(ordinals: np.ndarray) -> np.ndarray:
    """The low 31 bits of 32-bit FNV-1a over the eight little-endian bytes of
    each ordinal (YCSB hashes its key's ordinal the same way, at 64 bits)."""
    x = np.asarray(ordinals, np.uint64)
    h = np.full(x.shape, FNV_OFFSET, np.uint64)
    for byte in range(8):
        h = ((h ^ ((x >> np.uint64(8 * byte)) & np.uint64(0xFF)))
             * np.uint64(FNV_PRIME)) & np.uint64(0xFFFFFFFF)
    return (h & np.uint64(0x7FFFFFFF)).astype(np.int64)


def keys_of(map_index: int, n: int, space: int) -> np.ndarray:
    """The first ``n`` distinct keys of map ``map_index``: the hashes of its
    own ordinals ``map_index * space ...`` in order, an ordinal whose hash the
    map already holds skipped (10,000 draws from 2**31 repeat one in 43
    maps)."""
    keys = fnv1a31(map_index * space + np.arange(n + 64))
    _, first = np.unique(keys, return_index=True)
    keys = keys[np.sort(first)][:n]
    if keys.size != n:
        raise ValueError(f"map {map_index}: {keys.size} distinct keys of {n}")
    return keys


def zipf_ranks(rng: np.random.Generator, n: int, constant: float,
               draws: int) -> np.ndarray:
    """``draws`` ranks in ``0..n-1``, rank ``r`` with weight
    ``1 / (r + 1) ** constant`` (YCSB's zipfian request distribution)."""
    cdf = np.cumsum(1.0 / np.arange(1, n + 1) ** constant)
    return np.searchsorted(cdf, rng.random(draws) * cdf[-1]).clip(0, n - 1)


class PlainMaps:
    """``maps`` plain dicts."""

    def __init__(self, maps: int) -> None:
        self.maps: list[dict[int, int]] = [{} for _ in range(maps)]

    def put(self, m: int, key: int, value: int) -> int | None:
        """The value ``key`` had, or ``None``."""
        previous = self.maps[m].get(key)
        self.maps[m][key] = value
        return previous

    def get(self, m: int, key: int) -> int | None:
        return self.maps[m].get(key)

    def remove(self, m: int, key: int) -> int | None:
        return self.maps[m].pop(key, None)

    def put_if_absent(self, m: int, key: int, value: int) -> int | None:
        """The value ``key`` has, or ``None`` once it is put."""
        return self.maps[m].setdefault(key, value) \
            if key in self.maps[m] else self.put(m, key, value)

    def replace(self, m: int, key: int, value: int) -> int | None:
        """The value ``key`` had; a key that is not there stays out."""
        return self.put(m, key, value) if key in self.maps[m] else None

    def get_or_default(self, m: int, key: int, default: int) -> int:
        return self.maps[m].get(key, default)

    def contains_key(self, m: int, key: int) -> bool:
        return key in self.maps[m]

    def is_empty(self, m: int) -> bool:
        return not self.maps[m]

    def size(self, m: int) -> int:
        return len(self.maps[m])

    def total(self) -> int:
        return sum(len(d) for d in self.maps)

"""The deep drive plans from its submission's own order and counts
(``models/bulk.py``): "sorted" pays for a stable argsort and an unsort,
"grouped" (``groups`` non-decreasing) plans over the arrays as they lie,
"dense" (grouped, every group the same count) builds no index an operation.
Tier 1 is the definition: each case drives a submission as given on one
engine and, on a second engine of the same seed, the same submission under a
permutation that keeps every group's own order, and everything that comes
back, and the engines' states, are equal bit for bit.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.models import BulkDriver  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.utils import tracing  # noqa: E402
from copycat_tpu.utils.tracing import TRACER  # noqa: E402

from engines import G, MONOTONE, device_plane  # noqa: E402

#: the device plane's four submit slots; eight operations a group at most,
#: so every case runs the programs of two windows and [G, 8] accumulators
S, B = 4, 8
ROUNDS = B // S + 3


def _dense(groups, per):
    g = np.repeat(np.asarray(groups), per)
    k = np.arange(g.size)
    return g, np.where(k % 3 == 0, ap.OP_VALUE_GET, ap.OP_LONG_ADD), k % 5 + 1


def _ragged():
    g = np.concatenate([np.full(i % B + 1, i) for i in range(G)])
    k = np.arange(g.size)
    return g, np.where(k % 4 == 1, ap.OP_VALUE_GET, ap.OP_LONG_ADD), k % 7 + 1


#: name -> (the submission in group order, the tier it takes as given)
CASES = {
    "dense-over-all-groups": (_dense(np.arange(G), B), "dense"),
    "dense-over-a-subset": (_dense([1, 3, 4, 6], B), "dense"),
    "count-not-a-multiple-of-slots": (_dense(np.arange(G), B - 2), "dense"),
    "grouped-and-ragged": (_ragged(), "grouped"),
    "burst-uniform-payload": (
        (np.repeat(np.arange(G), B), ap.OP_LONG_ADD, 3), "dense"),
}


def shuffled(sub, seed):
    """``sub`` and a permutation of it in which groups interleave and each
    group's operations keep their order: ``permuted[i] = given[perm[i]]``."""
    g = sub[0]
    mixed = np.random.default_rng(seed).permutation(g)
    assert (np.diff(mixed) < 0).any()
    perm = np.empty(g.size, np.int64)
    perm[np.argsort(mixed, kind="stable")] = np.arange(g.size)
    assert (g[perm] == mixed).all()
    return perm, tuple(np.broadcast_to(x, g.shape)[perm] for x in sub)


def drive_traced(rg, driver, sub, **kw):
    """The result, the ``plan`` the drive's ``bulk.plan`` span names and
    what the two counters moved by."""
    counters = [rg.metrics.counter(f"bulk_{t}_drives")
                for t in ("grouped", "dense")]
    before = [c.value for c in counters]
    TRACER.clear()
    tracing.enable()
    try:
        res = driver.drive(*sub, **kw)
    finally:
        tracing.disable()
    (spans,) = TRACER.traces().values()
    report = TRACER.report()["counters"]
    TRACER.clear()
    (plan,) = [s.meta["plan"] for s in spans if s.name == "bulk.plan"]
    moved = tuple(c.value - b for c, b in zip(counters, before))
    assert moved == (report["engine.bulk_grouped_drives"],
                     report["engine.bulk_dense_drives"])
    return res, plan, moved


def same_state(one, other):
    for x, y in zip(jax.tree.leaves(jax.device_get(one.state)),
                    jax.tree.leaves(jax.device_get(other.state))):
        assert np.array_equal(x, y)
    assert np.array_equal(one._stream_count, other._stream_count)
    assert one.rounds == other.rounds


def compare(given, res, perm, res_sorted):
    assert res.rounds == res_sorted.rounds
    for name in ("results", "dispatch_round", "resolve_round"):
        x, y = getattr(res, name), getattr(res_sorted, name)
        assert x.dtype == y.dtype == np.int64 and x.shape == given[0].shape
        assert np.array_equal(x[perm], y), name


@pytest.mark.parametrize("case", list(CASES) + ["straggler-phase"])
@pytest.mark.parametrize("scan", [True, False], ids=["scan", "dispatch"])
def test_a_submission_in_group_order_returns_what_its_sorted_form_does(
        scan, case):
    sub, tier = CASES.get(case, CASES["dense-over-all-groups"])
    seed = 39 + sorted(CASES).index(case) if case in CASES else 5
    engines = []
    for _ in range(2):
        rg = device_plane(MONOTONE, seed=seed)
        rg.wait_for_leaders()
        engines.append((rg, BulkDriver(rg, deep_scan=scan)))
    (as_given, d_given), (as_sorted, d_sorted) = engines
    # a fresh engine's leaders hold no lease yet: its first drive resolves
    # in straggler passes, three rounds each; the other cases warm up first
    fresh = case == "straggler-phase"
    if not fresh:
        for _, driver in engines:
            driver.drive(*_dense(np.arange(G), B))
    for turn in range(2):
        perm, mixed = shuffled(sub, seed=seed + turn)
        res, plan, moved = drive_traced(as_given, d_given, sub)
        res_sorted, plan_sorted, moved_sorted = drive_traced(
            as_sorted, d_sorted, mixed)
        assert (plan, moved) == (tier, (1, int(tier == "dense")))
        assert (plan_sorted, moved_sorted) == ("sorted", (0, 0))
        compare(sub, res, perm, res_sorted)
        same_state(as_given, as_sorted)
        if fresh and turn == 0:
            assert res.rounds > ROUNDS and (res.rounds - ROUNDS) % 3 == 0
        elif not fresh:
            assert res.rounds == ROUNDS
        # every operation resolved, inside the drive
        assert (res.resolve_round < res.rounds).all()
        assert (res.dispatch_round <= res.resolve_round).all()


def test_the_results_are_the_operations_own():
    """Not only equal to the sorted form's: per-group FIFO sums."""
    rg = device_plane(MONOTONE, seed=3)
    rg.wait_for_leaders()
    driver = BulkDriver(rg, deep_scan=True)
    g = np.repeat(np.arange(G), B - 2)
    amounts = np.tile(np.arange(1, B - 1), G)
    for turn in range(2):
        res = driver.drive(g, ap.OP_LONG_ADD, amounts)
        want = np.cumsum(np.arange(1, B - 1)) + turn * amounts[:B - 2].sum()
        assert (res.results.reshape(G, B - 2) == want).all()
    assert (res.dispatch_round.reshape(G, B - 2)
            == np.arange(B - 2) // S).all()
    # the arrays handed back are the drive's own, not the caller's
    assert not any(np.shares_memory(x, y) for x in (g, amounts) for y in (
        res.results, res.dispatch_round, res.resolve_round))

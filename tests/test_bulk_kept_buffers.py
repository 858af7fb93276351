"""The deep drive's host arrays kept from drive to drive (``models/bulk.py``,
``_KeptArrays``): a driver that has driven a burst's shape before allocates
nothing of an operation's or a group's size for the next one, and nobody can
tell but by the clock and by ``bulk_kept_bytes``. Every case drives one engine
with one driver and its twin of the same seed with a fresh driver a drive, and
holds the two to each other bit for bit: what came back, what was handed to
the device (every accumulator seed and payload leaf, slots no operation fills
included) and the state left behind.

Since PR 47 the arrays cross while the host works: the scan's planes are put
as they are written and the accumulators harvested a chip's block at a time
from the copies asked for at dispatch. The cases at the end hold that walk to
a plain assembled fetch written here, the two counters of what crossed early
to the bytes by shape, and the kept planes to the rule that none is written
while its put may still read it.
"""

import inspect
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.models import BulkDriver, bulk  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.parallel.mesh import make_mesh  # noqa: E402
from copycat_tpu.testing.nemesis import Nemesis  # noqa: E402
from copycat_tpu.utils import profiler  # noqa: E402

from engines import G, MONOTONE, device_plane  # noqa: E402

#: the device plane's four submit slots; at most eight operations a group:
#: two windows, three settle rounds, [G, 8] accumulators in every case
S, B = 4, 8
ROUNDS = B // S + 3
ARRAYS = ("results", "dispatch_round", "resolve_round")
MODES = pytest.mark.parametrize("scan", [True, False],
                                ids=["scan", "dispatch"])


@pytest.fixture(autouse=True)
def _no_sampler():
    """Crash-nemesis tests elsewhere leak the process's sampling profiler
    on purpose (``tests/test_profile_surface.py``). Its thread holds the
    main thread's frames while it folds them, a just-returned ``drive``'s
    among them with the result in its locals: for those milliseconds the
    arrays count as held and the next drive rightly makes its own, which
    the byte counts below would read as a leak."""
    with profiler._ACQUIRE_LOCK:
        leaked, profiler.PROFILER = profiler.PROFILER, None
    if leaked is not None:
        leaked.stop()
    yield


def dense(per=B, groups=None, a=None):
    """``per`` adds for each of ``groups`` in group order; amounts that
    differ by operation unless ``a`` is one amount for all."""
    g = np.repeat(np.arange(G) if groups is None else np.asarray(groups), per)
    return g, ap.OP_LONG_ADD, np.arange(g.size) % 5 + 1 if a is None else a


def ragged():
    g = np.concatenate([np.full(i % B + 1, i) for i in range(G)])
    k = np.arange(g.size)
    return g, np.where(k % 4 == 1, ap.OP_VALUE_GET, ap.OP_LONG_ADD), k % 7 + 1


def shuffled():
    g, op, a = dense()
    perm = np.random.default_rng(46).permutation(g.size)
    return g[perm], op, a[perm]


TIERS = {"sorted": shuffled, "grouped": ragged, "dense": dense}


class Twins:
    """Two engines of one seed: ``drive`` drives the first with the one
    driver under test and the second with a driver made for the drive."""

    def __init__(self, scan, seed=46, mesh=False, assembled=False):
        self.scan, self.engines, self.staged = scan, [], ([], [])
        for log in self.staged:
            rg = device_plane(MONOTONE, seed=seed, mesh=make_mesh(
                devices=jax.devices()[:4]) if mesh else None)
            rg.wait_for_leaders()
            note = rg._note_stage
            rg._note_stage = lambda host, log=log, note=note: (
                log.append(jax.tree.map(np.array, host)), note(host))[1]
            self.engines.append(rg)
        if assembled:   # the twin's harvest walks one block: the whole
            self.engines[1]._ask_acc = lambda arrays: [
                [np.asarray(jax.device_get(x))] for x in arrays]
        self.rg = self.engines[0]
        self.driver = BulkDriver(self.rg, deep_scan=scan)
        self._bytes = [self.rg.metrics.counter(f"bulk_{name}_bytes")
                       for name in ("host", "kept")]

    def drive(self, sub, **kw):
        """The kept driver's result, held to the fresh driver's; also the
        bytes the drive took and those of them that were kept."""
        before = [c.value for c in self._bytes]
        got, want = (
            driver.drive(*sub, **{k: v[i] for k, v in kw.items()})
            for i, driver in enumerate((self.driver, BulkDriver(
                self.engines[1], deep_scan=self.scan))))
        assert got.rounds == want.rounds
        for name in ARRAYS:
            x, y = getattr(got, name), getattr(want, name)
            assert x.dtype == y.dtype == np.int64
            assert np.array_equal(x, y), name
        ours, theirs = (jax.tree.leaves(log) for log in self.staged)
        assert len(ours) == len(theirs) > 0
        for x, y in zip(ours, theirs):
            assert x.dtype == y.dtype and np.array_equal(x, y)
        for log in self.staged:
            log.clear()
        host, kept = (c.value - b for c, b in zip(self._bytes, before))
        return got, host, kept

    def same_state(self):
        one, other = self.engines
        for x, y in zip(jax.tree.leaves(jax.device_get(one.state)),
                        jax.tree.leaves(jax.device_get(other.state))):
            assert np.array_equal(x, y)
        assert np.array_equal(one._stream_count, other._stream_count)
        assert one.rounds == other.rounds

    def kept_bytes(self):
        """Bytes of the arrays the driver holds now, a result's apart."""
        return sum(arr.nbytes for arr, _ in self.driver._kept._arrays.values())


def handed(res, tier):
    """Bytes of the arrays of a result that are its own (a dense drive's
    ``dispatch_round`` is the shape's, kept and shared)."""
    return sum(getattr(res, name).nbytes for name in ARRAYS
               if (tier, name) != ("dense", "dispatch_round"))


@MODES
@pytest.mark.parametrize("tier", list(TIERS))
def test_equal_drives_on_one_driver_return_what_fresh_drivers_return(
        tier, scan):
    twins, sub = Twins(scan), TIERS[tier]()
    held = []
    for turn in range(4):
        res, host, kept = twins.drive(sub)
        # (a fresh engine's leaders hold no lease: straggler passes)
        assert (res.rounds > ROUNDS) == (turn == 0)
        assert host > 0
        # the caller holds every result: its arrays are made for it, and
        # from the second drive on nothing else is
        assert host - kept == (host if turn == 0 else handed(res, tier))
        assert twins.kept_bytes() == host - handed(res, tier)
        held.append(res)
    del held, res
    # let go of, the last two results' arrays are the next drives'
    for _ in range(3):
        _, host, kept = twins.drive(sub)
        assert host == kept > 0
    twins.same_state()


@MODES
def test_a_result_the_caller_holds_is_never_written_again(scan):
    twins, sub = Twins(scan), dense()
    twins.drive(sub)
    first, _, _ = twins.drive(sub)
    want = {name: getattr(first, name).copy() for name in ARRAYS}
    # one caller keeps the result, another only views of two of its arrays
    second, _, _ = twins.drive(sub)
    view, rounds = second.results.reshape(G, B), second.resolve_round[::2]
    want_view, want_rounds = view.copy(), rounds.copy()
    assert view.base is second.results
    del second
    for turn in range(3):
        res, host, kept = twins.drive(sub)
        del res
        # both remembered sets are spoken for: one more is made, and is
        # the next drives' once its own result is let go of
        assert host - kept == (0 if turn else handed(first, "dense"))
    for name in ARRAYS:
        assert np.array_equal(getattr(first, name), want[name]), name
    assert np.array_equal(view, want_view)
    assert np.array_equal(rounds, want_rounds)
    # the shape's dispatch rounds are one array in every result's hand
    assert not first.dispatch_round.flags.writeable
    assert (first.dispatch_round.reshape(G, B) == np.arange(B) // S).all()
    assert first.results.flags.writeable and first.resolve_round.flags.writeable


@MODES
def test_a_changing_burst_resizes_and_leaks_nothing(scan):
    """Every step changes what an array's shape or constant contents depend
    on; ``Twins.drive`` holds each to a fresh driver's results and staged
    arrays (a stale plane, tag or valid slot differs there)."""
    twins = Twins(scan)
    steps = [    # (what changes, the submission, its tier, all kept?)
        ("the first", dense(), "dense", False),
        ("nothing", dense(), "dense", True),
        ("fewer operations a group, the last window part full",
         dense(B - 2), "dense", False),
        ("a leaf turns uniform", dense(B - 2, a=3), "dense", False),
        ("uniform with another value", dense(B - 2, a=5), "dense", False),
        ("it varies again", dense(B - 2), "dense", False),
        ("fewer groups present", dense(B - 2, [1, 3, 4, 6]), "dense", False),
        ("other groups present", dense(B - 2, [0, 3, 5]), "dense", False),
        ("all of them again", dense(B - 2), "dense", False),
        ("ragged", ragged(), "grouped", False),
        ("nothing, ragged", ragged(), "grouped", True),
        ("shuffled", shuffled(), "sorted", False),
        ("nothing, shuffled", shuffled(), "sorted", True),
        ("dense after all that", dense(), "dense", False),
    ]
    for what, sub, tier, steady in steps:
        res, host, kept = twins.drive(sub)
        # one set: what the driver holds is what this drive took
        assert twins.kept_bytes() == host - handed(res, tier), what
        # (dispatch mode stacks no plane for a uniform leaf, so a leaf's
        # turn can leave it nothing to make)
        assert host == kept if steady else kept < host or not scan, what
        del res
    twins.same_state()


def test_stragglers_and_a_partition_that_heals_with_kept_arrays():
    """Dispatch mode under ``testing/verdict.run_deep_verdict``'s kind of
    schedule: the blind phase partitioned, healed after it, so the groups
    cut off from their quorum resolve in straggler passes, over arrays an
    earlier drive left and for the drive after."""
    import jax.numpy as jnp

    twins, sub = Twins(scan=False, seed=5), dense()
    twins.drive(sub)
    schedules = []
    for rg in twins.engines:
        nemesis = Nemesis(rg, seed=7)
        fault, heal = (jnp.asarray(nemesis._mask(kind))
                       for kind in ("partition", "heal"))
        schedules.append(lambda r, fault=fault, heal=heal: (
            fault if r < ROUNDS else heal))
    res, host, kept = twins.drive(sub, deliver_schedule=schedules)
    assert res.rounds > ROUNDS and (res.rounds - ROUNDS) % 3 == 0
    assert host == kept
    assert (res.resolve_round < res.rounds).all()
    del res
    res, host, kept = twins.drive(sub)
    # (the healed groups' new leaders hold no lease yet: passes again)
    assert res.rounds >= ROUNDS and host == kept
    assert (res.results.reshape(G, B)[:, -1]
            == 3 * dense()[2].reshape(G, B).sum(axis=1)).all()
    twins.same_state()


def test_a_drive_that_raises_releases_the_set():
    """An abandoned drive's transfers may still be reading the kept arrays:
    the driver lets them go, and the next drive after ``recover()`` sizes a
    set of its own."""
    import jax.numpy as jnp

    twins, sub = Twins(scan=False, seed=5), dense()
    twins.drive(sub)
    assert twins.kept_bytes() > 0
    drivers = [twins.driver, BulkDriver(twins.engines[1])]
    for rg, driver in zip(twins.engines, drivers):
        cut = jnp.asarray(Nemesis(rg, seed=7)._mask("partition"))
        with pytest.raises(TimeoutError):
            driver.drive(*sub, max_rounds=ROUNDS + 6,
                         deliver_schedule=lambda r: cut)
        driver.recover()
    assert twins.kept_bytes() == 0 and not twins.driver._kept._handed
    for log in twins.staged:
        log.clear()
    res, host, kept = twins.drive(sub)
    assert kept == 0 and res.rounds >= ROUNDS
    _, host, kept = twins.drive(sub)
    assert host - kept == handed(res, "dense")
    twins.same_state()


def test_kept_arrays_over_a_mesh_of_four():
    twins, sub = Twins(scan=True, mesh=True), dense(B - 2)
    twins.drive(sub)
    for _ in range(3):
        _, host, kept = twins.drive(sub)
    assert host == kept > 0
    res, _, _ = twins.drive(shuffled())
    assert (res.resolve_round < res.rounds).all()
    twins.same_state()


def test_the_mark_the_benchmarks_test_reads_is_the_one_staged():
    """``tests/benchmark/test_benchmark_bulk_plane.py`` finds the round
    accumulator's mark for "no result" in this module's text by this
    expression; the seed is a kept constant now, and the text a comment
    beside the value."""
    mark = re.search(r"rndbuf = rg\._stage_acc\(\s*np\.full\(\(G, Bpad\), "
                     r"([^,]+), np\.int32\)\)", inspect.getsource(bulk))
    assert mark and eval(mark.group(1), {}) == bulk._UNRESOLVED
    rg = device_plane(MONOTONE, seed=46)
    rg.wait_for_leaders()
    staged = []
    note = rg._note_stage
    rg._note_stage = lambda host: (staged.append(host), note(host))[1]
    BulkDriver(rg, deep_scan=True).drive(*dense())
    seeds = [x for x in staged if isinstance(x, np.ndarray)
             and x.shape == (G, B) and x.dtype == np.int32]
    assert [int(x.max()) for x in seeds[:2]] == [0, bulk._UNRESOLVED]
    assert (seeds[1] == bulk._UNRESOLVED).all()


def share_metric(name):
    """A share of two counters that is data alone: its file on the reducer
    ``program_report`` and its entry in ``BENCHMARK.json``, wherever in the
    list a later PR left it. Returns the file and the reducer's module."""
    import importlib.util
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = json.load(open(os.path.join(
        repo, "benchmarks", "layer_metrics", name + ".json")))
    (entry,) = [m for m in json.load(open(os.path.join(
        repo, "BENCHMARK.json")))["per_layer"] if m["name"] == name]
    assert entry == {**{k: spec[k] for k in (
        "name", "unit", "better", "source", "layer", "moves")},
        "workloads": ["mixed-400kx5-4chip.bulk"]}
    assert (spec["reducer"], spec["kind"]) == ("program_report", "report")
    path = os.path.join(repo, "benchmarks", "reducers", "program_report.py")
    loader = importlib.util.spec_from_file_location("program_report", path)
    reducer = importlib.util.module_from_spec(loader)
    loader.loader.exec_module(reducer)
    return spec, reducer


def test_the_benchmarks_share_reads_the_two_counters():
    """``bulk.kept_bytes_share`` is data alone: its file on the reducer
    ``program_report`` and its entry in ``BENCHMARK.json``. Read here from
    the window report of three traced drives: the first makes its arrays,
    the next two keep all of them."""
    from copycat_tpu.utils import tracing

    spec, reducer = share_metric("bulk.kept_bytes_share")
    assert reducer.reduce_report({"counters": {}}, {}, spec) is None

    rg = device_plane(MONOTONE, seed=46)
    rg.wait_for_leaders()
    BulkDriver(rg, deep_scan=True).drive(*dense())    # (the leases warm)
    driver = BulkDriver(rg, deep_scan=True)
    tracing.TRACER.clear()
    tracing.enable()
    try:
        for _ in range(3):
            driver.drive(*dense())
    finally:
        tracing.disable()
    report = tracing.TRACER.report()
    spans = [s for t in tracing.TRACER.traces().values() for s in t
             if s.name == "bulk.return"]
    tracing.TRACER.clear()
    host, kept = (report["counters"][f"engine.bulk_{k}_bytes"]
                  for k in ("host", "kept"))
    assert [s.meta["kept"] for s in spans] == [0, host // 3, host // 3]
    assert [s.meta["host"] for s in spans] == [host // 3] * 3
    assert reducer.reduce_report(report, {}, spec) == kept / host == 2 / 3


# -- PR 47: the transfers beside the host passes ---------------------------

#: bytes by shape (``tests/test_bulk_spans.py`` derives them): what a drive
#: puts for its accumulators, what it fetches back, and the payload, stacked
#: over every round for the scan, a window's rows of the one varying leaf,
#: the valid plane and the tags with that window's call in dispatch mode
ACCUMULATORS = G * (B * (4 + 1 + 4) + 1 + 4)
FETCHED = G * (B * (4 + 1 + 4) + 1)
PAYLOAD = {True: ROUNDS * G * (S * (4 * 4 + 1) + 4),
           False: B // S * G * (S * (4 + 1) + 4) + 3 * G * (S + 4)}


def some(groups):
    return lambda: dense(B - 2, groups)


@MODES
@pytest.mark.parametrize("tier,second", [
    ("sorted", ragged), ("grouped", some([0, 1, 6])),
    ("dense", some([1, 3, 4, 6]))], ids=list(TIERS))
def test_blocks_harvested_as_they_arrive_equal_an_assembled_fetch(
        tier, second, scan):
    """Over four virtual devices a drive's accumulators come back as four
    blocks of two groups, each written into the results from its own host
    copy; the twin's come back whole, fetched and assembled here. The first
    drive of a fresh engine runs straggler passes, so harvests again; the
    second leaves blocks with one segment of two rows, and with none."""
    twins = Twins(scan, mesh=True, assembled=True)
    res, _, _ = twins.drive(TIERS[tier]())
    assert res.rounds > ROUNDS
    del res
    twins.drive(second())
    twins.same_state()


def test_one_device_is_one_block():
    twins = Twins(scan=True, assembled=True)
    for sub in (ragged(), dense(B - 2, [0, 3, 5]), shuffled()):
        twins.drive(sub)
    twins.same_state()


@MODES
def test_what_crossed_early_by_shape_and_one_fetch_a_drive(scan):
    """``bulk_link_bytes`` is every byte a drive stages and fetches;
    ``bulk_early_bytes`` those put ahead of the program's call (the seeds
    always, the scan's planes too) or read from copies asked for at
    dispatch (the accumulators). One counted fetch a drive."""
    rg = device_plane(MONOTONE, seed=46, mesh=make_mesh(
        devices=jax.devices()[:4]))
    rg.wait_for_leaders()
    driver = BulkDriver(rg, deep_scan=scan)
    driver.drive(*dense())                      # (the leases warm)
    counters = [rg.metrics.counter(name) for name in (
        "bulk_link_bytes", "bulk_early_bytes", "staged_bytes",
        "fetch_bytes", "fetches")]
    before = [c.value for c in counters]
    notes = []
    note = rg._note_fetch
    rg._note_fetch = lambda host: (notes.append(host), note(host))[1]
    res = driver.drive(*dense())
    assert res.rounds == ROUNDS and len(notes) == 1
    link, early, staged, fetched, fetches = (
        c.value - b for c, b in zip(counters, before))
    assert (staged, fetched, fetches) \
        == (ACCUMULATORS + PAYLOAD[scan], FETCHED, 1)
    assert link == staged + fetched
    assert early == link - (0 if scan else PAYLOAD[False])


def test_no_kept_plane_is_written_while_its_put_may_read_it(monkeypatch):
    """The runtime reads a host array after its put has returned (on the
    chip a write made right after the return showed up on the device; on
    the CPU a put may alias the array outright). So every array a drive
    takes from its kept set to write is taken after the outputs of the
    last drive's program are ready, which has then read every array put
    for it, and at a drive's end the device's copies are what the host
    arrays held when they were put."""
    rg = device_plane(MONOTONE, seed=46)
    rg.wait_for_leaders()
    driver = BulkDriver(rg, deep_scan=True)
    driver.drive(*dense())                      # (the leases warm)
    put, stage_acc, take = [], rg._stage_acc, bulk._KeptArrays.take

    def staging(arr, axis=0):
        put.append((arr.copy(), stage_acc(arr, axis)))
        return put[-1][1]

    def taking(kept, name, *args, **kw):
        if "payload" in str(name) or name == "stream.base":
            assert all(x.is_ready() for x in jax.tree.leaves(rg.state)), name
        return take(kept, name, *args, **kw)

    rg._stage_acc = staging
    monkeypatch.setattr(bulk._KeptArrays, "take", taking)
    for k in range(3):
        driver.drive(*dense(a=np.arange(G * B) % 5 + k))
        planes = [(was, dev) for was, dev in put if was.ndim == 3]
        assert len(planes) == 6
        for was, dev in planes:     # (the scan donates no plane)
            assert np.array_equal(was, np.asarray(dev))
        put.clear()


def test_the_benchmarks_share_reads_what_crossed_early():
    """``bulk.early_bytes_share`` is data alone, as
    ``bulk.kept_bytes_share`` is: 1.0 over scanned drives, whose every
    byte starts across ahead of its stage, less in dispatch mode."""
    from copycat_tpu.utils import tracing

    spec, reducer = share_metric("bulk.early_bytes_share")
    assert (spec["key"], spec["over"]) == (
        ["counters", "engine.bulk_early_bytes"],
        ["counters", "engine.bulk_link_bytes"])
    # (the parent of the PR that added the counters: left out of the line)
    assert reducer.reduce_report(
        {"counters": {"engine.bulk_host_bytes": 1}}, {}, spec) is None

    rg = device_plane(MONOTONE, seed=46)
    rg.wait_for_leaders()
    shares = []
    for scan in (True, False):
        driver = BulkDriver(rg, deep_scan=scan)
        driver.drive(*dense())
        tracing.TRACER.clear()
        tracing.enable()
        try:
            driver.drive(*dense())
        finally:
            tracing.disable()
        shares.append(reducer.reduce_report(
            tracing.TRACER.report(), {}, spec))
        tracing.TRACER.clear()
    whole = ACCUMULATORS + FETCHED + PAYLOAD[False]
    assert shares == [1.0, (whole - PAYLOAD[False]) / whole]

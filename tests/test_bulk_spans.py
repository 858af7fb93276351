"""The bulk drive on the record (``models/bulk.py``): one span a drive and
stage under one trace id, the bytes a drive stages and fetches counted where
they cross, a straggler phase under the same names with ``phase=2``, and
nothing at all with the tracer off: no span, no annotation, no wait before
the fetch, no clock read beyond the drive's own two, and the same arrays
back. Deep scan and deep dispatch mode, on one device and over a mesh of
four virtual ones.
"""

import time
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.models import BulkDriver, bulk  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.parallel.mesh import make_mesh  # noqa: E402
from copycat_tpu.testing.nemesis import Nemesis  # noqa: E402
from copycat_tpu.utils import tracing  # noqa: E402
from copycat_tpu.utils.tracing import TRACER, Tracer  # noqa: E402

from engines import G, MONOTONE, device_plane  # noqa: E402

STAGES = ["bulk.admit", "bulk.plan", "bulk.stage", "bulk.dispatch",
          "bulk.wait", "bulk.fetch", "bulk.harvest", "bulk.return"]
#: a straggler pass records these again, in this order
PASS = STAGES[2:7]
#: the device plane's shape (tests/engines.py) and this file's drive: eight
#: operations for each of eight groups, two windows of four submit slots
S, B, WINDOWS, SETTLE = 4, 8, 2, 3
ROUNDS = WINDOWS + SETTLE
#: what a drive puts for its accumulators (result int32, valid bool, round
#: int32 an operation; an event flag and a stream base a group) and what it
#: fetches back (the first four)
ACCUMULATORS = G * (B * (4 + 1 + 4) + 1 + 4)
FETCHED = G * (B * (4 + 1 + 4) + 1)
#: the payload that travels as the program's arguments. Scan mode: four
#: int32 planes and a valid plane a slot, a tag a group, every round stacked.
#: Dispatch mode with one payload leaf that varies (``a``): that plane, the
#: valid plane and the tags a window; the valid plane and the tags a settle
#: round; the uniform leaves are scalars and count 0
PAYLOAD = {True: ROUNDS * G * (S * (4 * 4 + 1) + 4),
           False: WINDOWS * G * (S * (4 + 1) + 4) + SETTLE * G * (S + 4)}

MODES = [(True, False), (False, False), (True, True), (False, True)]
IDS = ["scan", "dispatch", "scan-mesh-of-4", "dispatch-mesh-of-4"]


def engine(mesh: bool, seed: int = 38):
    rg = device_plane(MONOTONE, seed=seed, mesh=make_mesh(
        devices=jax.devices()[:4]) if mesh else None)
    rg.wait_for_leaders()
    return rg


def burst():
    """Eight adds for every group, amounts that differ by operation."""
    return (np.repeat(np.arange(G), B), ap.OP_LONG_ADD,
            np.tile(np.arange(1, B + 1), G))


@pytest.fixture
def tracer():
    TRACER.clear()
    yield TRACER
    tracing.disable()
    TRACER.clear()


@pytest.fixture(scope="module", params=MODES, ids=IDS)
def recorded(request):
    """One warm-up drive, then two traced ones, of one engine: the ring,
    the report and the counters' deltas over the traced pair."""
    scan, mesh = request.param
    rg = engine(mesh)
    driver = BulkDriver(rg, deep_scan=scan)
    driver.drive(*burst())
    counters = {name: rg.metrics.counter(name) for name in (
        "staged_bytes", "fetch_bytes", "fetches")}
    before = {name: c.value for name, c in counters.items()}
    TRACER.clear()
    tracing.enable()
    try:
        results = [driver.drive(*burst()) for _ in range(2)]
    finally:
        tracing.disable()
    traces, report = TRACER.traces(), TRACER.report()
    TRACER.clear()
    delta = {name: c.value - before[name] for name, c in counters.items()}
    return scan, results, traces, report, delta


def test_a_drive_records_the_nine_spans_under_one_id(recorded):
    scan, results, traces, report, _ = recorded
    assert len(traces) == 2                     # a drive, an id
    for res, (trace_id, spans) in zip(results, sorted(traces.items())):
        assert [s.name for s in spans] == STAGES + ["bulk.drive"]
        assert {s.trace_id for s in spans} == {trace_id}
        *stages, root = spans
        assert root.parent is None
        assert all(s.parent == "bulk.drive" for s in stages)
        assert root.meta == {"n": G * B, "rounds": ROUNDS,
                             "windows": WINDOWS, "scan": scan}
        assert res.rounds == ROUNDS
        # each stage begins at the instant the one before it ended, the
        # first with the root, and the last ends inside it
        assert stages[0].start == root.start
        assert all(b.start == a.end for a, b in zip(stages, stages[1:]))
        assert root.start <= stages[-1].end <= root.end
        # the result's own wall ends before the drive's arrays are freed,
        # the last stage and the root after
        assert res.wall_s <= stages[-1].end - root.start
        assert "phase" not in {k for s in spans for k in (s.meta or {})}
    assert set(report["spans"]) == set(STAGES) | {"bulk.drive"}
    root = report["spans"]["bulk.drive"]
    assert root["n"] == 2
    assert sum(report["spans"][name]["total_ms"] for name in STAGES) \
        == pytest.approx(root["total_ms"] - root["self_ms"])
    assert 0 <= root["self_ms"] <= 0.03 * root["total_ms"]


def test_the_stages_bytes_are_the_counters_and_the_shapes(recorded):
    scan, _, traces, report, delta = recorded
    for spans in traces.values():
        meta = {s.name: s.meta or {} for s in spans}
        assert meta["bulk.plan"] == {"segments": G, "plan": "dense"}
        # (the scan's stacked planes are put as they are written, inside
        # the stage; dispatch mode's windows travel with their calls)
        assert meta["bulk.stage"] == {
            "bytes": ACCUMULATORS + (PAYLOAD[True] if scan else 0)}
        assert meta["bulk.dispatch"] == {"rounds": ROUNDS}
        assert meta["bulk.fetch"] == {"bytes": FETCHED}
        assert meta["bulk.harvest"] == {"resolved": G * B}
    assert delta == {"staged_bytes": 2 * (ACCUMULATORS + PAYLOAD[scan]),
                     "fetch_bytes": 2 * FETCHED, "fetches": 2}
    assert report["counters"]["engine.staged_bytes"] == delta["staged_bytes"]
    assert report["counters"]["engine.fetch_bytes"] == delta["fetch_bytes"]


def test_a_straggler_phase_records_its_stages_again_with_phase_2(tracer):
    """Dispatch mode under a schedule that partitions the blind phase and
    heals after it (``testing/verdict.run_deep_verdict``'s): the groups cut
    off from their quorum resolve in straggler passes."""
    import jax.numpy as jnp

    rg = engine(mesh=False, seed=5)
    driver = BulkDriver(rg)
    driver.drive(*burst())
    nemesis = Nemesis(rg, seed=7)
    fault, heal = (jnp.asarray(nemesis._mask(kind))
                   for kind in ("partition", "heal"))
    tracing.enable()
    res = driver.drive(*burst(), deliver_schedule=lambda r: (
        fault if r < ROUNDS else heal))
    tracing.disable()
    assert (res.results.reshape(G, B)[:, -1] == 2 * B * (B + 1) // 2).all()
    passes, rest = divmod(res.rounds - ROUNDS, 3)
    assert passes >= 1 and rest == 0
    (spans,) = tracer.traces().values()
    assert [s.name for s in spans] \
        == STAGES[:7] + PASS * passes + ["bulk.return", "bulk.drive"]
    assert all(s.parent == "bulk.drive" for s in spans[:-1])
    first, again = spans[:7], spans[7:-2]
    assert all("phase" not in (s.meta or {}) for s in first)
    assert all(s.meta["phase"] == 2 for s in again)
    assert first[-1].meta["resolved"] < G * B
    assert again[-1].meta["resolved"] == G * B
    assert [s.meta["rounds"] for s in again if s.name == "bulk.dispatch"] \
        == [3] * passes
    # a pass puts no accumulator; its payload travels with its three calls
    assert all("bytes" not in s.meta for s in again if s.name == "bulk.stage")
    assert all(b.start == a.end for a, b in zip(spans[:-1], spans[1:-1]))
    root = tracer.report()["spans"]["bulk.drive"]
    assert spans[-1].meta["rounds"] == res.rounds
    assert 0 <= root["self_ms"] <= 0.03 * root["total_ms"]


def ragged():
    """One to eight adds a group, in group order."""
    g = np.concatenate([np.full(i + 1, i) for i in range(G)])
    return g, ap.OP_LONG_ADD, np.arange(1, g.size + 1)


def shuffled():
    g, op, a = burst()
    perm = np.random.default_rng(39).permutation(g.size)
    return g[perm], op, a[perm]


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "dispatch"])
@pytest.mark.parametrize("submission,plan,segments", [
    (shuffled, "sorted", G), (ragged, "grouped", G), (burst, "dense", G)],
    ids=["sorted", "grouped", "dense"])
def test_the_stages_share_their_boundaries_whatever_the_plan(
        tracer, scan, submission, plan, segments):
    """The plan a submission's order and counts select changes what the
    stages do, not where they begin and end: nine spans, each stage from
    the instant the one before it ended, and ``bulk.plan`` names the plan."""
    rg = engine(mesh=False)
    driver = BulkDriver(rg, deep_scan=scan)
    driver.drive(*burst())
    counters = [rg.metrics.counter(f"bulk_{t}_drives")
                for t in ("grouped", "dense")]
    before = [c.value for c in counters]
    tracing.enable()
    res = driver.drive(*submission())
    tracing.disable()
    (spans,) = tracer.traces().values()
    assert [s.name for s in spans] == STAGES + ["bulk.drive"]
    *stages, root = spans
    assert stages[0].start == root.start
    assert all(b.start == a.end for a, b in zip(stages, stages[1:]))
    assert root.start <= stages[-1].end <= root.end
    assert all(s.parent == "bulk.drive" for s in stages)
    assert stages[1].meta == {"segments": segments, "plan": plan}
    assert root.meta == {"n": res.results.size, "rounds": ROUNDS,
                         "windows": WINDOWS, "scan": scan}
    assert [c.value - b for c, b in zip(counters, before)] \
        == [int(plan != "sorted"), int(plan == "dense")]
    report = tracer.report()
    assert report["counters"]["engine.bulk_grouped_drives"] \
        == int(plan != "sorted")
    assert report["counters"]["engine.bulk_dense_drives"] \
        == int(plan == "dense")
    root = report["spans"]["bulk.drive"]
    assert 0 <= root["self_ms"] <= 0.03 * root["total_ms"]


def test_a_classic_drive_records_the_root_and_the_admit(tracer):
    rg = device_plane(seed=38)
    rg.wait_for_leaders()
    tracing.enable()
    res = BulkDriver(rg).drive(*burst())
    tracing.disable()
    (spans,) = tracer.traces().values()
    assert [(s.name, s.parent) for s in spans] == [
        ("bulk.admit", "bulk.drive"), ("bulk.drive", None)]
    assert spans[1].meta == {"n": G * B, "rounds": res.rounds, "scan": False}
    assert spans[0].start == spans[1].start and spans[0].end <= spans[1].end


def test_an_idle_drive_closes_what_it_opened(tracer):
    rg = engine(mesh=False)
    tracing.enable()
    res = BulkDriver(rg, deep_scan=True).drive(
        np.zeros(0, np.int64), ap.OP_LONG_ADD, 1)
    tracing.disable()
    assert res.rounds == 0 and res.results.size == 0
    (spans,) = tracer.traces().values()
    assert [s.name for s in spans] == ["bulk.admit", "bulk.plan",
                                       "bulk.return", "bulk.drive"]
    assert spans[1].meta == {"segments": 0, "plan": "grouped"}
    assert spans[3].meta == {"n": 0, "rounds": 0, "windows": 0, "scan": True}


@pytest.mark.parametrize("scan,mesh", MODES, ids=IDS)
def test_with_the_tracer_off_a_drive_records_nothing_and_returns_the_same(
        scan, mesh, monkeypatch):
    """Two engines of one seed, one driven traced and one with every
    recording entry point refusing to be called: no span, no annotation,
    no wait before the fetch, the drive's own two clock reads and no
    third, and bit-identical arrays."""
    def refuse(*args, **kwargs):
        raise AssertionError("recorded with the tracer off")

    TRACER.clear()
    traced, plain = engine(mesh), engine(mesh)
    tracing.enable()
    try:
        want = [BulkDriver(traced, deep_scan=scan).drive(*burst())
                for _ in range(2)]
    finally:
        tracing.disable()
    TRACER.clear()
    driver = BulkDriver(plain, deep_scan=scan)
    got = [driver.drive(*burst())]              # compiles; then the watch
    reads = []
    clock = types.SimpleNamespace(
        perf_counter=lambda: reads.append(1) or time.perf_counter())
    monkeypatch.setattr(bulk, "time", clock)
    monkeypatch.setattr(tracing, "time", clock)
    monkeypatch.setattr(Tracer, "span", refuse)
    monkeypatch.setattr(Tracer, "open_span", refuse)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(jax, "block_until_ready", refuse)
    got.append(driver.drive(*burst()))
    assert len(reads) == 2
    monkeypatch.undo()
    assert TRACER.traces() == {} and TRACER.report()["spans"] == {}
    # (the first drive of a fresh engine meets cold leases and runs
    # straggler passes, traced or not; the second is the blind phase alone)
    assert [r.rounds for r in want] == [r.rounds for r in got]
    assert got[0].rounds > got[1].rounds == ROUNDS
    for a, b in zip(want, got):
        for name in ("results", "dispatch_round", "resolve_round"):
            x, y = getattr(a, name), getattr(b, name)
            assert x.dtype == y.dtype and np.array_equal(x, y), name
    assert (got[1].results.reshape(G, B)[:, -1] == 2 * B * (B + 1) // 2).all()


def test_the_two_deep_programs_bear_their_functions_names():
    """``jax.jit`` names a bare partial ``jit__unknown``; a trace tells the
    scan and the step apart by the module's name."""
    scan = bulk._deep_scan_program(MONOTONE)
    step = bulk._deep_program(MONOTONE)
    assert (scan.__name__, step.__name__) == ("deep_scan", "deep_step")
    bound = bulk._named(lambda x, k: x * k, k=2)
    bound.__name__ = "doubled"
    text = jax.jit(bound).lower(np.ones(3, np.float32)).as_text()
    assert "module @jit_doubled" in text and "jit__unknown" not in text

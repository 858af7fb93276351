"""The bulk client plane: ``models.bulk.BulkDriver``'s deep drives, back to back,
one outstanding, over an engine that lies on one chip or is split by groups
over the chips of a host.

A drive commits ``ops_per_group`` operations for every group of the deployment
and returns every result to its caller at its end. The deployment and the drive
are ``chip_smoke._drive_mesh``'s (proven on four chips in PR 21, which took no
rate); the pattern is the mixed round of ``generators.mixed_pattern`` tiled over
a drive. Sizes come from the cell's configuration and traffic files alone.
"""

from __future__ import annotations

import re
import statistics
import sys
import time

import numpy as np

from benchmarks import generators as gen
from benchmarks import reference
from benchmarks.planes.raw import build_config

#: largest over smallest peak bytes a chip: sound runs read 1.0001; one
#: drive's stacked payload whole on one chip would read 1.26, the state 3.4
PEAK_SKEW_LIMIT = 1.1
#: a traced run profiles its second and third drive; every window holds
#: three drives at least
TRACE_FROM, TRACE_TO = 1, 3
#: settle rounds after a drive's windows (``models/bulk.py``: ``W_total``)
SETTLE_ROUNDS = 3


def replay_drives(sampled: list[np.ndarray], groups: np.ndarray,
                  ops: tuple) -> tuple[int, int, str, list[int]]:
    """Each sampled group's results, drive after drive in submission order,
    against a :class:`reference.PlainGroup` of its own. ``sampled[d]`` is
    ``[len(groups), B]``; ``ops`` the drive's ``[G, B]`` opcode, a and b.
    Returns (results compared, results that differ, the first difference,
    each model's final counter). An election epoch is a log index, which a
    drive does not hand back: it is not modelled and not compared."""
    opc, a_, b_ = ops
    compared = wrong = 0
    first, counters = "", []
    for k, g in enumerate(groups.tolist()):
        model = reference.PlainGroup()
        row = [x[g].tolist() for x in (opc, a_, b_)]
        for d, results in enumerate(sampled):
            got = results[k].tolist()
            for j, (op, a, b) in enumerate(zip(*row)):
                want = model.apply(op, a, b, None)
                if want is None:
                    continue
                compared += 1
                if want != got[j]:
                    wrong += 1
                    first = first or (
                        f"group {g} drive {d} op {j} (opcode {op}): the "
                        f"drive returned {got[j]}, the plain model {want}")
        counters.append(model.counter)
    return compared, wrong, first, counters


def driven_scan(program, config, donate: bool) -> tuple:
    """The scan program this process's drives called and the form of its
    accumulators (``onehot``), found without stating the form. ``program``
    is the program's own cached builder: asked for each form in turn it
    hands back the very jitted function the drives called, and only a
    function that was called holds compiled entries (one that this asking
    built holds none, nor does the text lowered from it below). Raises
    where not exactly one form was called.

    The builder's cache is the process's and its key is the configuration,
    so this sees every drive of the process under ``config``, not one
    engine's: a process that drives both forms under one configuration (a
    test that does, not a run of a cell, which builds one engine) gets the
    error, which is why the tiny mesh cell has a configuration of its own.
    The count of compiled entries is JAX's ``_cache_size``, which is not
    public: where a later JAX drops it the plane says so and does not
    guess."""
    forms = [(program(config, onehot=onehot, donate=donate), onehot)
             for onehot in (False, True)]
    sizes = [getattr(scan, "_cache_size", None) for scan, _ in forms]
    if not all(callable(size) for size in sizes):
        raise RuntimeError(
            "bulk plane: this JAX's jitted functions have no _cache_size(), "
            "by which the plane tells the scan the drives called from the "
            "one it asked for; planes/bulk.py: driven_scan needs another way")
    called = [form for form, size in zip(forms, sizes) if size()]
    if len(called) != 1:
        raise RuntimeError(
            "bulk plane: the drives of this process called "
            f"{len(called)} of the scan program's two forms with this "
            "configuration; it reads the text of exactly one")
    return called[0]


def run(ctx) -> dict:
    import jax

    from copycat_tpu.models import BulkDriver, RaftGroups
    from copycat_tpu.models.bulk import _deep_scan_program
    from copycat_tpu.utils import tracing

    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    G, P, L, S = (cfg["groups"], cfg["peers"], cfg["log_slots"],
                  cfg["submit_slots"])
    B, chips = mix["ops_per_group"], ctx.chips
    devices = jax.devices()[:chips]
    mesh = None
    if chips > 1:
        from copycat_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(devices=devices)

    t_setup = time.perf_counter()
    seed = int(np.random.SeedSequence(ctx.seed).generate_state(1)[0] >> 1)
    rg = RaftGroups(G, P, log_slots=L, submit_slots=S, seed=seed, mesh=mesh,
                    config=build_config(cfg)._replace(
                        monotone_tag_accept=True))
    rg.wait_for_leaders()
    t_elected = time.perf_counter()
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(rg.state))
    sample = np.sort(np.random.default_rng(ctx.seed).choice(
        G, min(mix["sample_groups"], G), replace=False))
    # one drive's arrays, built once: the mixed round tiled over the
    # ops_per_group operations of every group
    pattern = [np.tile(x, -(-B // S))[:B] for x in gen.mixed_pattern(S)]
    groups = np.repeat(np.arange(G), B)
    opcode, a, b = (np.tile(x, G) for x in pattern)
    ops = tuple(x.reshape(G, B) for x in (opcode, a, b))
    driver = BulkDriver(rg, deep_scan=mix["deep_scan"])
    scan_rounds = -(-B // S) + SETTLE_ROUNDS
    say(f"bulk plane: mixed G={G} P={P} L={L} S={S} pallas="
        f"{'on' if rg.config.use_pallas else 'off'} chips={chips}: state "
        f"{state_bytes:,} bytes in non-empty leaves ({state_bytes // chips:,}"
        f" a chip), a drive = {B} ops for every group = {groups.size:,} ops "
        f"in {scan_rounds} scanned rounds; elected after {rg.rounds} rounds, "
        f"{t_elected - t_setup:.1f}s; {ctx.compiles.note()}")

    # warm-up: whole drives, until one has compiled nothing (the first
    # compiles the scan; the next takes the buffers that one donated)
    sampled, all_rounds, warm_walls = [], [], []

    def came_back(res) -> int:
        """Results of a drive that came back resolved: those that name the
        round of this drive in which they did. Whatever else the program
        leaves there, its own mark for "none" or another, counts as none."""
        return int((res.resolve_round < res.rounds).sum())

    def keep(res) -> int:
        """What a drive leaves for the checks; returns how many of its
        results came back resolved."""
        sampled.append(res.results.reshape(G, B)[sample])
        all_rounds.append(res.rounds)
        return came_back(res)

    unresolved = 0
    while True:
        before = ctx.compiles.count
        res = driver.drive(groups, opcode, a, b)
        warm_walls.append(round(res.wall_s, 3))
        unresolved += groups.size - keep(res)
        if ctx.compiles.count == before:
            break
        if len(sampled) == 4:
            raise RuntimeError("bulk plane: the fourth warm-up drive still "
                               "compiled")
    warm_drives = len(sampled)
    say(f"bulk plane: set-up {time.perf_counter() - t_setup:.1f}s; "
        f"{warm_drives} warm-up drives of {all_rounds} rounds in "
        f"{warm_walls} s; {ctx.compiles.note()}")

    # -- the window ----------------------------------------------------------
    ctx.gc_tune()
    fetches, fetch_bytes = (rg.metrics.counter(name)
                            for name in ("fetches", "fetch_bytes"))
    if ctx.trace:
        tracing.TRACER.clear()
        tracing.enable()
    compiled_before = ctx.compiles.count
    fetches0, bytes0 = fetches.value, fetch_bytes.value
    walls: list[float] = []
    resolved = last = 0
    t_start = time.perf_counter()
    while True:
        if ctx.trace and len(walls) == TRACE_FROM:
            ctx.profile_start()
        t0 = time.perf_counter()
        with ctx.annotate("drive"):
            res = driver.drive(groups, opcode, a, b)
        now = time.perf_counter()
        walls.append(now - t0)
        last = keep(res)
        resolved += last
        if ctx.trace and len(walls) == TRACE_TO:
            ctx.profile_stop()
        if now - t_start >= ctx.seconds and len(walls) >= TRACE_TO:
            break
    t_end = time.perf_counter()
    window = t_end - t_start
    compiled_inside = ctx.compiles.count - compiled_before
    counters = {"fetches": fetches.value - fetches0,
                "fetch_bytes": fetch_bytes.value - bytes0}
    spans: dict[str, list[float]] = {}
    if ctx.trace:
        tracing.disable()
        for trace in tracing.TRACER.traces().values():
            for s in trace:
                spans.setdefault(s.name, []).append(s.duration_ms)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    drives, rounds = len(walls), all_rounds[warm_drives:]
    submitted = drives * groups.size
    say(f"bulk plane: window {window:.3f}s, {drives} drives of "
        f"{groups.size:,} ops in {rounds} rounds, {resolved:,} results "
        f"returned resolved; drive walls "
        + ", ".join(f"{w:.3f}" for w in walls) + f" s; {counters['fetches']} "
        f"fetches of {counters['fetch_bytes']:,} bytes; spans recorded: "
        f"{sorted(spans) or 'none'}; peak bytes a chip {peaks}; "
        f"compilations inside the window: {compiled_inside} (limit 0)")

    # -- the checks, outside the window -------------------------------------
    t_check = time.perf_counter()
    with ctx.annotate("check"):
        value, applied = (np.asarray(x) for x in jax.device_get(
            (rg.state.resources.value, rg.state.applied_index)))
    if ctx.fault == "flip-result":
        sampled[-1][0, 0] ^= 1
    elif ctx.fault == "drop-ack":
        # the last drive's first result loses its round: the count itself
        # has to see it
        res.resolve_round[0] = res.rounds
        resolved += came_back(res) - last
    unresolved += submitted - resolved
    compared, wrong, first, counters_want = replay_drives(sampled, sample, ops)
    best = value[np.arange(G), applied.argmax(axis=1)]
    lost = int((best[sample] != np.asarray(counters_want)).sum())
    split = int(((applied[:, :, None] == applied[:, None, :])
                 & (value[:, :, None] != value[:, None, :])
                 ).any(axis=(1, 2)).sum())
    # the leaves as the last drive left them: a drive donates its state
    leaves = [x for x in jax.tree.leaves(rg.state) if x.size]
    uneven = sum(
        sorted(s.data.shape[0] for s in x.addressable_shards)
        != [G // chips] * chips
        or len({s.device.id for s in x.addressable_shards}) != chips
        for x in leaves)
    checks = [
        ("sampled results that differ from the plain model "
         f"({compared:,} compared: {sample.size} groups, {len(sampled)} "
         "drives, warm-up included)" + (f": {first}" if first else ""),
         wrong, 0),
        (f"operations of {submitted + warm_drives * groups.size:,} whose "
         "result did not come back resolved", unresolved, 0),
        (f"sampled groups of {sample.size} whose counter differs from the "
         "plain model's after the last drive", lost, 0),
        (f"groups of {G:,} whose replicas disagree on an applied prefix",
         split, 0),
        (f"state leaves of {len(leaves)} not split {G // chips:,} groups a "
         f"chip over {chips}", uneven, 0),
        ("compilations inside the window", compiled_inside, 0),
    ]
    clock = {"drive_ms": statistics.median(walls) * 1e3,
             "drive_max_ms": max(walls) * 1e3, "drives": drives,
             "window_s": window, "acked_ops": resolved,
             "rounds": sum(rounds), "rounds_per_drive": sum(rounds) / drives,
             "state_bytes": state_bytes // chips,
             "rounds_per_dispatch": scan_rounds}
    if mix["deep_scan"]:
        # the text of the program the window drove, compiled as it was
        # driven: its module's name finds it in the trace, and over a mesh
        # it must hold no collective
        from copycat_tpu.ops.consensus import Submits
        from copycat_tpu.parallel.scaling import census_text

        scan, form = driven_scan(_deep_scan_program, rg.config, rg.donate)
        say(f"bulk plane: the drives built the scan with onehot={form}")
        staged = lambda dtype, *tail: rg._stage_acc(
            np.zeros((G, *tail), dtype))
        stacked = lambda dtype, width: jax.ShapeDtypeStruct(
            (scan_rounds, G, width), dtype)
        text = scan.lower(
            rg.state, staged(np.int32, B), staged(bool, B),
            staged(np.int32, B), staged(bool), staged(np.int32),
            Submits(*(stacked(np.int32, S),) * 4, tag=stacked(np.int32, 1),
                    valid=stacked(bool, S)),
            rg.deliver, rg._key).compile().as_text()
        module = re.match(r"HloModule ([^\s,]+)", text)
        if module is None or "ENTRY" not in text:
            raise RuntimeError("bulk plane: the compiled scan has no text "
                               "to count collectives in")
        # the deep step's module bears the same name: the traced drives'
        # modules are the scan's only where no straggler phase ran in them
        if set(rounds[TRACE_FROM:TRACE_TO]) == {scan_rounds}:
            clock["program"] = module.group(1)
        if mesh is not None:
            census = census_text(text)
            clock["collectives"] = float(sum(census.values()))
            checks.append((
                f"collectives in the compiled scan ({module.group(1)}, "
                f"{len(text):,} characters of text) over {chips} chips"
                + (f": {census}" if census else ""),
                clock["collectives"], 0))
    if mesh is not None and all(peaks):
        # no chip was handed more than its share
        clock["peak_skew"] = max(peaks) / min(peaks)
        checks.append(("largest over smallest peak bytes a chip",
                       round(clock["peak_skew"], 4), PEAK_SKEW_LIMIT))
    correct = (compared > 0 and resolved > 0
               and all(v <= lim for _, v, lim in checks))
    say(f"bulk plane: checks took {time.perf_counter() - t_check:.1f}s")
    # each number compared beside its limit, the last lines of standard error
    for what, value, limit in checks:
        print(f"bulk plane: check: {what}: {value} (limit {limit})",
              file=sys.stderr, flush=True)
    return {
        "window_start": t_start,
        "correct": correct, "attempted": submitted,
        "failed": wrong + unresolved + lost + split, "checks": checks,
        "end_to_end": {"bulk_ops_per_s": resolved / window},
        "clock": clock, "spans": spans, "counters": counters,
    }

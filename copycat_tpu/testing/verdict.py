"""Linearizability verdict at scale (BASELINE.md's "Jepsen pass").

The reference's claim to fame is external Jepsen verification
(``/root/reference/README.md:8``); the in-tree Wing & Gong checker
(:mod:`linearize`) covers it on small histories in tests. This runner
produces the VERDICT ARTIFACT at scale: a ``RaftGroups`` batch of
≥10k groups runs under a randomized nemesis (partitions, isolation,
message loss) with client load, histories are recorded on a sample of
groups across three resource models (register/counter, map, try-lock),
and every sampled history is checked. Output: one JSON line on stdout +
``LINEARIZABILITY.md`` rewritten with the verdict.

Run: ``python -m copycat_tpu.testing.verdict`` (env overrides:
``COPYCAT_VERDICT_GROUPS/SAMPLE/ROUNDS/SEED``, plus
``COPYCAT_VERDICT_CHURN=0`` to disable the default membership churn —
with churn on, groups run 5 peer lanes with 3 initial voters and server
join/leave cycles through the voter sets mid-faults).
"""

from __future__ import annotations

import json
import math
import sys
import time

import numpy as np

from ..utils import knobs

from ..models.raft_groups import RaftGroups
from ..ops import apply as ap
from .history import HistoryRecorder
from .linearize import (
    HOp,
    LockModel,
    RegisterModel,
    check_linearizable_windowed,
    check_map_linearizable,
)
from .nemesis import Nemesis

GROUPS = knobs.get_int("COPYCAT_VERDICT_GROUPS")
SAMPLE = knobs.get_int("COPYCAT_VERDICT_SAMPLE")
ROUNDS = knobs.get_int("COPYCAT_VERDICT_ROUNDS")
SEED = knobs.get_int("COPYCAT_VERDICT_SEED")
# ops per sampled group per round (round-3 depth was one op every 4
# rounds ≈ 100 ops/group; VERDICT r3 #7 wants ≥1k — the windowed checker
# keeps the deeper histories tractable)
OP_EVERY_ROUNDS = max(1, knobs.get_int("COPYCAT_VERDICT_OP_EVERY"))
# Bounded client concurrency per group (a real client's pipelining
# window): without it a long fault piles up in-flight recorded ops
# (observed: 2,105 pending at round 300), leaving incomplete ops that
# both distort the workload and make the checker's incomplete-op subsets
# explode.
MAX_INFLIGHT = max(1, knobs.get_int("COPYCAT_VERDICT_INFLIGHT"))
BACKGROUND_PER_ROUND = 500  # untracked load spread over the other groups
# Membership churn (default ON): groups run 5 peer lanes with 3 initial
# voters and the nemesis is joined by server join/leave — every sampled
# group cycles lanes 3/4 in and out of its voter set while its history
# is recorded. Jepsen's hardest configuration for the reference is
# exactly faults + membership changes together; linearizability of
# client ops must hold across config changes.
CHURN = knobs.get_bool("COPYCAT_VERDICT_CHURN")
CHURN_PERIOD = 20
CHURN_CYCLE = (("add", 3), ("add", 4), ("remove", 3), ("remove", 4))
# Deep-plane block (VERDICT r4 #4): drive the monotone-tag pipelined
# plane — the path the north-star number rides — under per-epoch static
# faults, and Wing-&-Gong-check the recorded histories. Off with
# COPYCAT_VERDICT_DEEP=0.
DEEP = knobs.get_bool("COPYCAT_VERDICT_DEEP")
DEEP_GROUPS = knobs.get_int("COPYCAT_VERDICT_DEEP_GROUPS")
DEEP_SAMPLE = knobs.get_int("COPYCAT_VERDICT_DEEP_SAMPLE")
DEEP_EPOCHS = knobs.get_int("COPYCAT_VERDICT_DEEP_EPOCHS")
DEEP_OPS_PER_EPOCH = 4          # recorded ops / sampled group / epoch


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _invoke_register(rec: HistoryRecorder, g: int, rng) -> None:
    kind = int(rng.integers(4))
    if kind == 0:
        v = int(rng.integers(1, 50))
        rec.invoke(g, ap.OP_VALUE_SET, ("set", v), a=v)
    elif kind == 1:
        # half the reads ride the lease-gated ATOMIC query lane (no log
        # append) — the checker validates them against real time, which
        # is exactly the leader-lease soundness claim under test
        query = "atomic" if rng.random() < 0.5 else None
        rec.invoke(g, ap.OP_VALUE_GET, ("get",), query=query)
    elif kind == 2:
        e, u = int(rng.integers(0, 50)), int(rng.integers(1, 50))
        rec.invoke(g, ap.OP_VALUE_CAS, ("cas", e, u), a=e, b=u)
    else:
        d = int(rng.integers(1, 5))
        rec.invoke(g, ap.OP_LONG_ADD, ("add", d), a=d)


def _invoke_map(rec: HistoryRecorder, g: int, rng) -> None:
    kind = int(rng.integers(4))
    k = int(rng.integers(0, 8))
    if kind == 0:
        v = int(rng.integers(1, 99))
        rec.invoke(g, ap.OP_MAP_PUT, ("put", k, v), a=k, b=v)
    elif kind == 1:
        # half the map reads ride the lease-gated ATOMIC query lane too
        # (VERDICT r3 #6: lease reads checked under churn at scale in
        # every model that reads)
        query = "atomic" if rng.random() < 0.5 else None
        rec.invoke(g, ap.OP_MAP_GET, ("get", k), a=k, query=query)
    elif kind == 2:
        rec.invoke(g, ap.OP_MAP_REMOVE, ("remove", k), a=k)
    else:
        rec.invoke(g, ap.OP_MAP_CONTAINS_KEY, ("contains", k), a=k)


def _invoke_lock(rec: HistoryRecorder, g: int, rng) -> None:
    who = int(rng.integers(1, 4))
    if rng.random() < 0.5:
        rec.invoke(g, ap.OP_LOCK_ACQUIRE, ("acquire", who), a=who, b=0)
    else:
        rec.invoke(g, ap.OP_LOCK_RELEASE, ("release", who), a=who)


def _telemetry_summary(rg) -> dict:
    """Final device.* telemetry + invariant-monitor verdict for the JSON
    artifact (fields documented in LINEARIZABILITY.md). The monitor ran
    on EVERY fetched round of the run, so violations==0 here is an
    online safety witness alongside the offline Wing & Gong check."""
    hub = getattr(rg, "telemetry", None)
    if hub is None:
        return {}
    out = {k: v for k, v in hub.snapshot().items()
           if k.startswith("device.") and not isinstance(v, dict)}
    out["invariants"] = hub.monitor.summary()
    return out


def run_verdict() -> dict:
    from ..ops.consensus import Config

    t0 = time.time()
    if CHURN:
        rg = RaftGroups(GROUPS, 5, log_slots=64, submit_slots=4, seed=SEED,
                        config=Config(dynamic_membership=True,
                                      telemetry=True), voters=3)
    else:
        rg = RaftGroups(GROUPS, 3, log_slots=64, submit_slots=4, seed=SEED,
                        config=Config(telemetry=True))
    rg.wait_for_leaders()
    rec = HistoryRecorder(rg)
    nemesis = Nemesis(rg, seed=SEED + 1, period=12)
    rng = np.random.default_rng(SEED + 2)

    # sample split across the three checked models
    sampled = rng.choice(GROUPS, size=SAMPLE, replace=False)
    third = SAMPLE // 3
    reg_groups = [int(g) for g in sampled[:third]]
    map_groups = [int(g) for g in sampled[third:2 * third]]
    lock_groups = [int(g) for g in sampled[2 * third:]]
    others = np.setdiff1d(np.arange(GROUPS), sampled)

    _log(f"verdict: G={GROUPS} sample={SAMPLE} rounds={ROUNDS} "
         f"nemesis period=12 device load={BACKGROUND_PER_ROUND}/round")
    bg_tags: set[int] = set()
    cfg_tags: set[int] = set()
    cfg_submitted = cfg_applied = 0
    churn_step = 0
    for round_no in range(ROUNDS):
        nemesis.tick()
        if CHURN and round_no % CHURN_PERIOD == CHURN_PERIOD // 2:
            # server join/leave on every sampled group (and a slice of
            # the background) while their histories are recorded; the
            # kernel serializes per group, the host requeues early ones
            kind, lane = CHURN_CYCLE[churn_step % len(CHURN_CYCLE)]
            churn_step += 1
            targets = [int(g) for g in sampled]
            targets += [int(g) for g in
                        rng.choice(others, size=min(200, len(others)),
                                   replace=False)]
            for g in targets:
                cfg_tags.add(rg.add_peer(g, lane) if kind == "add"
                             else rg.remove_peer(g, lane))
                cfg_submitted += 1
        # recorded client ops: one per sampled group per OP_EVERY_ROUNDS,
        # gated by the client concurrency window
        if round_no % OP_EVERY_ROUNDS == 0:
            for g in reg_groups:
                if rec.pending_count(g) < MAX_INFLIGHT:
                    _invoke_register(rec, g, rng)
            for g in map_groups:
                if rec.pending_count(g) < MAX_INFLIGHT:
                    _invoke_map(rec, g, rng)
            for g in lock_groups:
                if rec.pending_count(g) < MAX_INFLIGHT:
                    _invoke_lock(rec, g, rng)
        # background load on the rest of the batch (untracked counters —
        # their resolved results are reaped so rg.results stays bounded)
        n_bg = min(BACKGROUND_PER_ROUND, len(others))
        for g in rng.choice(others, size=n_bg, replace=False):
            bg_tags.add(rg.submit(int(g), ap.OP_LONG_ADD, 1))
        rec.tick()
        bg_tags = {t for t in bg_tags if rg.results.pop(t, None) is None}
        done_cfg = {t for t in cfg_tags if t in rg.results}
        cfg_applied += len(done_cfg)
        for t in done_cfg:
            rg.results.pop(t)
        cfg_tags -= done_cfg
        if round_no % 50 == 49:
            _log(f"verdict: round {round_no + 1}/{ROUNDS} "
                 f"fault={nemesis.current} pending={len(rec._pending)}")
    nemesis.heal()
    for _ in range(300):
        if not rec._pending:
            break
        rec.tick()

    checked = failures = undecided = total_ops = total_nodes = 0
    for groups, checker, name in (
            (reg_groups,
             lambda h: check_linearizable_windowed(h, RegisterModel),
             "RegisterModel"),
            (map_groups, check_map_linearizable, "MapModel(per-key)"),
            (lock_groups,
             lambda h: check_linearizable_windowed(h, LockModel),
             "LockModel")):
        for g in groups:
            hist = rec.history(g)
            total_ops += len(hist)
            checked += 1
            try:
                res = checker(hist)
            except RuntimeError as e:
                # search budget exceeded (too-concurrent history): record
                # the group as undecided rather than aborting the run —
                # NEVER counted as a pass (undecided>0 fails the gate)
                undecided += 1
                _log(f"verdict: UNDECIDED group {g} ({name}): {e}")
                continue
            total_nodes += res.nodes
            if not res.ok:
                failures += 1
                _log(f"verdict: VIOLATION group {g} ({name}): {hist}")

    result = {
        "linearizable": failures == 0 and undecided == 0,
        "groups": GROUPS,
        "undecided_groups": undecided,
        "sampled_groups": checked,
        "checked_ops": total_ops,
        "rounds": ROUNDS,
        "nemesis": "partition/isolate/loss, period 12"
                   + (", membership churn" if CHURN else ""),
        "violations": failures,
        "search_nodes": total_nodes,
        "incomplete_ops": len(rec._pending),
        "wall_s": round(time.time() - t0, 1),
        "seed": SEED,
        "device_telemetry": _telemetry_summary(rg),
    }
    if CHURN:
        result["membership_changes_applied"] = cfg_applied
        result["membership_changes_submitted"] = cfg_submitted
    return result


def run_deep_verdict() -> dict:
    """Wing & Gong verdict for the DEEP (monotone-tag) client plane.

    The round-4 headline number comes from ``models/bulk.py``'s deep
    pipelined drive, whose exactly-once story was argued in docstrings
    but never driven by this harness (VERDICT r4 weak #5). This block
    drives it: per epoch a static fault mask (heal / 30% loss /
    2-side partition / single-peer isolation — the envelope whose
    liveness the plane supports via its phase-2 suffix retries) is
    installed, every sampled group commits a burst of recorded register
    ops through ``BulkDriver.drive`` (device-gated FIFO + dedup), half
    the epochs also serve lease-gated ATOMIC reads through
    ``drive_queries``, and real-time windows come from the drive's
    per-op dispatch/resolve rounds. A drive that exceeds its round
    budget (liveness lost under a static mask) marks its burst
    maybe-applied — the Jepsen crashed-client treatment — and recovers
    via ``BulkDriver.recover`` (heal → settle → cursor resync), which is
    exactly the protocol a production client must follow.
    """
    from ..models.bulk import BulkDriver
    from ..ops.consensus import Config

    t0 = time.time()
    rg = RaftGroups(DEEP_GROUPS, 3, log_slots=64, submit_slots=4,
                    seed=SEED + 10,
                    config=Config(monotone_tag_accept=True,
                                  telemetry=True))
    rg.wait_for_leaders()
    driver = BulkDriver(rg)
    rng = np.random.default_rng(SEED + 11)
    nemesis = Nemesis(rg, seed=SEED + 12)

    sampled = [int(g) for g in
               rng.choice(DEEP_GROUPS, size=DEEP_SAMPLE, replace=False)]
    others = np.setdiff1d(np.arange(DEEP_GROUPS), sampled)
    # Histories are kept as SEGMENTS of (init_state, ops): an aborted
    # drive leaves maybe-applied (forever-incomplete) ops, and every
    # incomplete op blocks all later quiescent cuts — a few aborts would
    # collapse the rest of the run into one exponential checker segment.
    # recover() is a FENCE (an abandoned op can never apply after it),
    # so after each abort the current segment is closed with an ANCHOR —
    # a lease-gated linearizable read whose value both constrains the
    # closing segment's linearization and seeds the next segment's
    # init_state.
    segments: dict[int, list] = {g: [] for g in sampled}
    cur_ops: dict[int, list] = {g: [] for g in sampled}
    cur_init: dict[int, int] = {g: 0 for g in sampled}
    op_id = [0]
    drive_aborts = anchor_timeouts = 0

    def _epoch_ops():
        """One recorded burst: DEEP_OPS_PER_EPOCH register ops per
        sampled group + untracked background adds on other groups."""
        gs, ops, av, bv, labels = [], [], [], [], []
        for g in sampled:
            for _ in range(DEEP_OPS_PER_EPOCH):
                kind = int(rng.integers(4))
                if kind == 0:
                    v = int(rng.integers(1, 50))
                    gs.append(g); ops.append(ap.OP_VALUE_SET)
                    av.append(v); bv.append(0); labels.append(("set", v))
                elif kind == 1:
                    gs.append(g); ops.append(ap.OP_VALUE_GET)
                    av.append(0); bv.append(0); labels.append(("get",))
                elif kind == 2:
                    e, u = int(rng.integers(0, 50)), int(rng.integers(1, 50))
                    gs.append(g); ops.append(ap.OP_VALUE_CAS)
                    av.append(e); bv.append(u); labels.append(("cas", e, u))
                else:
                    d = int(rng.integers(1, 5))
                    gs.append(g); ops.append(ap.OP_LONG_ADD)
                    av.append(d); bv.append(0); labels.append(("add", d))
        n_rec = len(gs)
        bg = rng.choice(others, size=min(400, len(others)), replace=False)
        gs += [int(g) for g in bg]
        ops += [ap.OP_LONG_ADD] * len(bg)
        av += [1] * len(bg)
        bv += [0] * len(bg)
        return (np.asarray(gs), np.asarray(ops), np.asarray(av),
                np.asarray(bv), labels, n_rec)

    _log(f"deep verdict: G={DEEP_GROUPS} sample={DEEP_SAMPLE} "
         f"epochs={DEEP_EPOCHS} x {DEEP_OPS_PER_EPOCH} ops/group")
    import jax.numpy as jnp
    heal_mask = jnp.asarray(nemesis._mask("heal"))
    for epoch in range(DEEP_EPOCHS):
        fault = ("heal", "loss", "partition", "isolate")[
            int(rng.integers(4))]
        # the fault lasts FAULT_ROUNDS of the drive, then heals — the
        # deep plane's liveness envelope is faults-with-recovery (its
        # phase-2 suffix retries then resolve everything); a fault held
        # static forever is a liveness loss by design, exercised
        # separately by the abort path below
        fault_mask = jnp.asarray(nemesis._mask(fault))
        fault_rounds = int(rng.integers(6, 16))
        schedule = (lambda r, fm=fault_mask, fr=fault_rounds:
                    fm if r % 60 < fr else heal_mask)
        budget = 400
        if epoch % 7 == 6 and fault != "heal":
            # every 7th epoch the fault is held STATIC with a small round
            # budget: the drive must lose liveness (by design), abort,
            # mark its burst maybe-applied, and walk the recover()
            # protocol — the crashed-client path checked at scale
            schedule = lambda r, fm=fault_mask: fm  # noqa: E731
            budget = 120
        gs, ops, av, bv, labels, n_rec = _epoch_ops()
        base_round = rg.rounds
        try:
            res = driver.drive(gs, ops, av, bv, max_rounds=budget,
                               deliver_schedule=schedule)
        except TimeoutError:
            drive_aborts += 1
            for k in range(n_rec):
                op_id[0] += 1
                cur_ops[int(gs[k])].append(HOp(
                    op_id=op_id[0], op=labels[k], result=None,
                    invoke=base_round, complete=math.inf))
            nemesis.heal()
            driver.recover(settle_rounds=30)
            # fence + anchor: close every group's segment on a
            # linearizable read of the post-recovery state
            fence = rg.rounds
            try:
                vals = driver.drive_queries(
                    np.asarray(sampled), ap.OP_VALUE_GET,
                    consistency="atomic", max_rounds=200)
            except TimeoutError:
                anchor_timeouts += 1  # rare: keep segments open
            else:
                for g, v in zip(sampled, vals):
                    op_id[0] += 1
                    cur_ops[g].append(HOp(
                        op_id=op_id[0], op=("get",), result=int(v),
                        invoke=fence, complete=rg.rounds))
                    segments[g].append((cur_init[g], cur_ops[g]))
                    cur_ops[g] = []
                    cur_init[g] = int(v)
            continue
        for k in range(n_rec):
            op_id[0] += 1
            cur_ops[int(gs[k])].append(HOp(
                op_id=op_id[0], op=labels[k],
                result=int(res.results[k]),
                invoke=base_round + int(res.dispatch_round[k]),
                complete=base_round + int(res.resolve_round[k])))
        if epoch % 2 == 1:
            # lease-gated linearizable reads through the query lane
            # (no log append) — windows span the whole call, which is
            # sound (wider window = more permissive)
            nemesis.heal()  # static faults would starve the lease gate
            q0 = rg.rounds
            try:
                vals = driver.drive_queries(
                    np.asarray(sampled), ap.OP_VALUE_GET,
                    consistency="atomic", max_rounds=200)
            except TimeoutError:
                anchor_timeouts += 1
            else:
                for g, v in zip(sampled, vals):
                    op_id[0] += 1
                    cur_ops[g].append(HOp(
                        op_id=op_id[0], op=("get",), result=int(v),
                        invoke=q0, complete=rg.rounds))
        if epoch % 10 == 9:
            _log(f"deep verdict: epoch {epoch + 1}/{DEEP_EPOCHS} "
                 f"rounds={rg.rounds} aborted={drive_aborts}")
    nemesis.heal()
    for g in sampled:
        segments[g].append((cur_init[g], cur_ops[g]))

    checked = failures = undecided = total_ops = nodes = 0
    incomplete = 0
    for g in sampled:
        checked += 1
        bad = und = False
        for init, seg in segments[g]:
            hist = sorted(seg, key=lambda h: (h.invoke, h.op_id))
            total_ops += len(hist)
            incomplete += sum(1 for h in hist if h.result is None)
            try:
                res = check_linearizable_windowed(hist, RegisterModel,
                                                  init_state=init)
            except RuntimeError as e:
                und = True
                _log(f"deep verdict: UNDECIDED group {g}: {e}")
                continue
            nodes += res.nodes
            if not res.ok:
                bad = True
                _log(f"deep verdict: VIOLATION group {g} "
                     f"(segment init={init}): {hist}")
        failures += bad
        undecided += und

    return {
        "linearizable": failures == 0 and undecided == 0,
        "groups": DEEP_GROUPS,
        "sampled_groups": checked,
        "checked_ops": total_ops,
        "incomplete_ops": incomplete,
        "epochs": DEEP_EPOCHS,
        "aborted_drives": drive_aborts,
        "anchor_timeouts": anchor_timeouts,
        "undecided_groups": undecided,
        "violations": failures,
        "search_nodes": nodes,
        "wall_s": round(time.time() - t0, 1),
        "seed": SEED,
        "device_telemetry": _telemetry_summary(rg),
    }


def _write_artifact(result: dict) -> None:
    churn_clause = ""
    if "membership_changes_applied" in result:
        churn_clause = (
            " WITH live membership churn (server join/leave cycling"
            " lanes 3/4 of every sampled group's voter set — Jepsen's"
            " hardest configuration:"
            f" {result['membership_changes_applied']:,} config changes"
            " applied mid-faults)")
    lines = [
        "# LINEARIZABILITY — verdict artifact at bench scale",
        "",
        "BASELINE.md's metric line ends \"Jepsen pass\" (the reference's"
        " claim rests on",
        "external Jepsen runs, `README.md:8`). This artifact is the"
        " in-tree equivalent,",
        "produced by `python -m copycat_tpu.testing.verdict`: a"
        f" {result['groups']:,}-group device",
        "batch ran under a randomized nemesis (partitions, single-peer"
        " isolation,",
        "30% message loss; period 12 rounds)" + churn_clause
        + " with client load;"
        f" {result['sampled_groups']}",
        "sampled groups recorded real-time histories across three"
        " resource models",
        "(linearizable register/counter, map, try-lock), each checked"
        " with the",
        "Wing & Gong checker (`copycat_tpu/testing/linearize.py`).",
        "",
        "```json",
        json.dumps(result, indent=2),
        "```",
        "",
        "Semantics of the verdict: every completed operation's result is",
        "explainable by a total order consistent with real-time"
        " (invoke/complete",
        "windows in driver rounds); operations that never completed"
        " (e.g. submitted",
        "into a partitioned leader) may linearize at any point or"
        " never — exactly a",
        "Jepsen client's crashed-request semantics.",
        "",
        "## Device telemetry fields (round 8)",
        "",
        "`device_telemetry` (and `deep_plane.device_telemetry`) embed the"
        " run's final",
        "device-plane flight-recorder counters"
        " (docs/OBSERVABILITY.md § device plane):",
        "`device.elections_started`, `device.leader_changes`,"
        " `device.term_bumps`,",
        "`device.leaderless_rounds` (group-rounds without a leader),",
        "`device.commit_advance`, `device.submit_rejections`"
        " (backpressure/lease-gate",
        "requeues), `device.vote_splits`, `device.events_drained` /"
        " `_dropped`, and",
        "`device.applies{pool=...}` — all accumulated from the jitted"
        " step's on-device",
        "reductions across every round of the run. `invariants` is the"
        " ONLINE monitor's",
        "verdict: `{mode, violations, watched_groups, leaderless_max}` —"
        " per-fetch checks",
        "of commit-total/per-group commit monotonicity, per-group leader-"
        "term",
        "monotonicity, the leaderless-fraction bound, and a sampled"
        " ≤1-leader-per-term",
        "watch-list. `violations: 0` means no fetched round ever"
        " contradicted Raft's",
        "safety claims while the nemesis ran; under"
        " `COPYCAT_INVARIANTS=strict` the run",
        "would have aborted at the first violation instead.",
        "",
    ]
    if "deep_plane" in result:
        d = result["deep_plane"]
        lines += [
            "## Deep (monotone-tag) client plane",
            "",
            "The flagship throughput number rides `models/bulk.py`'s deep"
            " pipelined",
            "drive (device-enforced FIFO + dedup, zero blocking fetches)."
            " This block is",
            "the same Wing & Gong harness pointed at THAT plane"
            f" (round-5, VERDICT r4 #4): {d['groups']:,}",
            f"groups, {d['sampled_groups']} sampled, {d['epochs']} epochs"
            " of per-epoch static faults (heal/30% loss/",
            "2-side partition/peer isolation) with recorded register"
            " bursts through",
            "`BulkDriver.drive` and lease-gated ATOMIC reads through the"
            " query lane.",
            f"Command drives that lost liveness under a static mask"
            f" ({d['aborted_drives']} of {d['epochs']}) marked their"
            " bursts",
            "maybe-applied, recovered via `BulkDriver.recover`"
            " (heal → settle → cursor",
            "resync — the fence that makes post-abandon tag reuse"
            " impossible), and the",
            "history was re-anchored on a lease-gated linearizable read"
            " that both",
            "constrains the closing segment and seeds the next one.",
            "",
        ]
    with open("LINEARIZABILITY.md", "w") as f:
        f.write("\n".join(lines))


def main() -> None:
    from ..utils.platform import enable_compilation_cache, require_platform
    device = require_platform()
    enable_compilation_cache()
    result = {**run_verdict(), **device}
    if DEEP:
        deep = run_deep_verdict()
        result["deep_plane"] = deep
        result["linearizable"] = result["linearizable"] and \
            deep["linearizable"]
    # COPYCAT_VERDICT_ARTIFACT=0 skips rewriting LINEARIZABILITY.md — the
    # committed artifact records the BENCH-scale verdict; smoke runs (CI,
    # local debugging at small GROUPS) must not clobber it.
    if knobs.get_bool("COPYCAT_VERDICT_ARTIFACT"):
        _write_artifact(result)
    print(json.dumps(result))
    if not result["linearizable"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

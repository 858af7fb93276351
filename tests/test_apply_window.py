"""Conflict-partitioned apply (ops/apply.py apply_window) equivalence.

The partitioned path (Config.pool_budgets set) must be observably
identical to the sequential apply_entry scan: same per-tag results, same
final resource state, same event streams — budgets only defer entries
across rounds, never reorder them within a pool.
"""

import numpy as np
import pytest

from copycat_tpu.models.raft_groups import RaftGroups
from copycat_tpu.ops import apply as ap
from copycat_tpu.ops.consensus import Config

from engines import wide_window


def _drive(config: Config, seed: int) -> RaftGroups:
    """FIXED step schedule (not run_until): both executions see identical
    round counts, hence identical logical clocks — so even TTL deadlines
    (now + c) must come out bit-equal between the two paths."""
    rg = wide_window(config, seed=3)
    rg.wait_for_leaders(max_rounds=60)
    extra = 60 - rg.rounds
    for _ in range(extra):  # normalize the election warm-up length
        rg.step_round()
    rng = np.random.default_rng(seed)
    ops_pool = [
        (ap.OP_LONG_ADD, lambda r: (int(r.integers(1, 5)), 0, 0)),
        (ap.OP_VALUE_SET, lambda r: (int(r.integers(1, 9)), 0,
                                     int(r.integers(0, 6)))),  # TTL'd
        (ap.OP_VALUE_CAS, lambda r: (int(r.integers(0, 3)),
                                     int(r.integers(0, 9)), 0)),
        (ap.OP_MAP_PUT, lambda r: (int(r.integers(0, 6)),
                                   int(r.integers(1, 9)),
                                   int(r.integers(0, 8)))),    # TTL'd
        (ap.OP_MAP_GET, lambda r: (int(r.integers(0, 6)), 0, 0)),
        (ap.OP_MAP_REMOVE, lambda r: (int(r.integers(0, 6)), 0, 0)),
        (ap.OP_SET_ADD, lambda r: (int(r.integers(0, 6)), 0,
                                   int(r.integers(0, 8)))),    # TTL'd
        (ap.OP_SET_REMOVE, lambda r: (int(r.integers(0, 6)), 0, 0)),
        (ap.OP_Q_OFFER, lambda r: (int(r.integers(1, 9)), 0, 0)),
        (ap.OP_Q_POLL, lambda r: (0, 0, 0)),
        (ap.OP_LOCK_ACQUIRE, lambda r: (int(r.integers(1, 4)), -1, 0)),
        (ap.OP_LOCK_RELEASE, lambda r: (int(r.integers(1, 4)), 0, 0)),
        (ap.OP_ELECT_LISTEN, lambda r: (int(r.integers(10, 14)), 0, 0)),
        (ap.OP_ELECT_RESIGN, lambda r: (int(r.integers(10, 14)), 0, 0)),
    ]
    tags = []
    for _ in range(25):  # 25 batches of one op per group, 4 rounds each
        for g in range(8):
            opcode, gen = ops_pool[rng.integers(0, len(ops_pool))]
            a, b, c = gen(rng)
            tags.append(rg.submit(g, opcode, a, b, c))
        for _ in range(4):
            rg.step_round()
    for _ in range(60):  # settle tail: tight budgets drain their backlog
        rg.step_round()
    missing = [t for t in tags if t not in rg.results]
    assert not missing, f"unresolved tags: {missing[:5]}"
    return rg


def test_partitioned_apply_matches_sequential():
    sequential = Config(applies_per_round=8)                # legacy scan
    partitioned = sequential._replace(
        pool_budgets=(2,) * 8)                    # tight budgets
    rg_seq = _drive(sequential, seed=99)
    rg_par = _drive(partitioned, seed=99)

    # identical per-tag results for the identical op stream
    assert rg_seq.results == rg_par.results

    # identical final resource state — EVERY field, TTL deadlines and
    # wait/listener rings included (clocks are aligned by construction)
    seq_res = rg_seq.state.resources
    par_res = rg_par.state.resources
    for name in seq_res._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(seq_res, name)),
            np.asarray(getattr(par_res, name)), err_msg=name)

    # identical event streams (order included)
    assert rg_seq.events == rg_par.events


def test_tight_budgets_still_apply_everything():
    """Budgets of 1 defer heavily but must never drop or reorder."""
    config = Config(applies_per_round=8,
                    pool_budgets=(1,) * 8)
    rg = wide_window(config)
    rg.wait_for_leaders()
    tags = [rg.submit(0, ap.OP_LONG_ADD, 1) for _ in range(24)]
    tags += [rg.submit(0, ap.OP_MAP_PUT, k, k * 2) for k in range(6)]
    rg.run_until(tags, max_rounds=400)
    assert rg.results[tags[23]] == 24          # all increments, in order
    get = rg.submit(0, ap.OP_MAP_GET, 3)
    rg.run_until([get])
    assert rg.results[get] == 6

"""Bulk data plane example: batch-scale client workloads with zero
per-op Python (``copycat_tpu.models.bulk`` — no analogue in the
reference, whose client runtime is one RPC per command).

Drives N committed increments per group across G Raft groups through the
pipelined vectorized driver and prints client-visible throughput +
latency percentiles:

    python examples/bulk_counters.py [groups] [ops_per_group]

Works on CPU or TPU (same jitted program; JAX picks the backend).
"""

import sys

import numpy as np

from copycat_tpu.models import BulkDriver, RaftGroups
from copycat_tpu.ops.apply import OP_LONG_ADD
from copycat_tpu.ops.consensus import Config


def main() -> None:
    groups_n = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
    per_group = int(sys.argv[2]) if len(sys.argv) > 2 else 64

    # monotone_tag_accept = the DEEP pipeline: FIFO + dedup enforced on
    # device by the tag gate, so the driver dispatches with zero blocking
    # fetches and harvests one buffer per drive
    rg = RaftGroups(groups_n, 3, log_slots=64, submit_slots=16,
                    config=Config(monotone_tag_accept=True,
                                  append_window=16, applies_per_round=16))
    print(f"electing leaders across {groups_n} groups x 3 peers ...")
    rg.wait_for_leaders()

    driver = BulkDriver(rg)
    groups = np.repeat(np.arange(groups_n), per_group)
    print(f"driving {groups.size:,} committed increments ...")
    driver.drive(groups, OP_LONG_ADD, 1)  # warm (compile + transfers)
    res = driver.drive(groups, OP_LONG_ADD, 1)

    pct = res.latency_percentiles_ms()
    print(f"{groups.size:,} ops in {res.wall_s:.3f}s over {res.rounds} "
          f"rounds -> {groups.size / res.wall_s:,.0f} client-visible "
          f"committed ops/sec")
    print(f"latency p50={pct['p50']:.1f} ms p99={pct['p99']:.1f} ms")
    # per-group FIFO: the last op of group 0 saw every earlier increment
    final = res.results.reshape(groups_n, per_group)[:, -1]
    assert (final == 2 * per_group).all(), "FIFO prefix sums violated?"
    print("per-group FIFO verified")

    # and the read lane: ATOMIC (leader-lease gated) reads of every
    # counter — linearizable, zero log entries
    import time
    from copycat_tpu.ops.apply import OP_VALUE_GET
    driver.drive_queries(groups[:groups_n], OP_VALUE_GET,
                         consistency="atomic")  # warm (query jit compile)
    t0 = time.perf_counter()
    got = driver.drive_queries(groups, OP_VALUE_GET, consistency="atomic")
    dt = time.perf_counter() - t0
    assert (got == 2 * per_group).all()
    print(f"{groups.size:,} ATOMIC lease reads in {dt:.3f}s -> "
          f"{groups.size / dt:,.0f} linearizable reads/sec")


if __name__ == "__main__":
    main()

"""Compile ``chip_smoke.py``'s programs at their real sizes for a v5e that
is described and not attached — the rehearsal to make before a chip call.

    JAX_PLATFORMS=cpu python tests/tpu_compile_rehearsal.py [--chips 4]

Run by hand: the 100k x 5 and 400k x 5 compiles take too long for tier-1
(``tests/test_tpu_compile.py`` keeps the small ones there). Prints, per
program, compile seconds, argument / temporary / aliased bytes on each
device, whether the Pallas kernel is inside and which collectives are. A
compile that passes is not a chip run and says nothing of results or time.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, SingleDeviceSharding  # noqa: E402

import chip_smoke  # noqa: E402
import test_tpu_compile as t  # noqa: E402
from copycat_tpu.models.raft_groups import (  # noqa: E402
    _fused_rounds_program,
    _jitted_programs,
)
from copycat_tpu.ops.apply import ResourceConfig  # noqa: E402
from copycat_tpu.ops.consensus import Config  # noqa: E402


def report(name: str, build) -> None:
    t0 = time.perf_counter()
    compiled = build()
    mem = compiled.memory_analysis()
    mib = lambda b: f"{b / 2**20:,.0f}"
    print(f"{name}: {time.perf_counter() - t0:.1f}s  args {mib(mem.argument_size_in_bytes)} "
          f"MiB, temp {mib(mem.temp_size_in_bytes)} MiB, aliased "
          f"{mib(mem.alias_size_in_bytes)} MiB per device; kernel "
          f"{'inside' if t.has_kernel(compiled) else 'absent'}; collectives "
          f"{t.collectives_in(compiled) or 'none'}", flush=True)


def one_chip(chip) -> None:
    S = 16
    # raw plane: mixed 100k x 5 — bench's step for the elections, then the
    # smoke's scan (kernel, nemesis masks, apply_window budgets inside)
    G, P, L, rounds, sample = 100_000, 5, 32, 48, 2048
    mixed = chip_smoke.mixed_config(S, pallas_interpret=False)
    report("step mixed 100kx5", lambda: t.compile_step(G, P, L, S, mixed, chip))
    state, pattern, _, key = t.step_args(G, P, L, S, mixed, chip)
    report("raw-plane scan mixed 100kx5", lambda: chip_smoke.raw_plane_program(
        mixed, G, P, S).lower(
            state, key, pattern, t._struct((rounds, G), jnp.int32, chip),
            t._struct((sample,), jnp.int32, chip)).compile())
    # bulk plane: 10k x 3, B=64, donation on
    deep = Config(use_pallas=True, append_window=S, applies_per_round=S,
                  resource=ResourceConfig.counters_only(),
                  monotone_tag_accept=True)
    shape = (10_000, 3, 64, S, 64, deep, chip)
    report("step counter 10kx3", lambda: t.compile_step(*shape[:4], deep, chip))
    report("deep_step 10kx3 B=64", lambda: t.compile_deep(*shape))
    report("deep_scan 10kx3 B=64 W=7",
           lambda: t.compile_deep(*shape, windows=64 // S + 3))
    # served path: the engine's programs at DeviceEngineConfig defaults
    engine = Config()
    step_program, query_program, _, joint_program = _jitted_programs(engine)
    args = t.round_args(1024, 3, 64, 4, engine, chip)
    report("engine step 1024x3", lambda: step_program.lower(*args).compile())
    for n in (2, 3, 4):
        report(f"engine fused rounds n={n}",
               lambda: _fused_rounds_program(engine, n).lower(*args).compile())
    for width in (1, 4, 16):
        report(f"engine query_step S={width}",
               lambda: query_program.lower(*t.round_args(
                   1024, 3, 64, width, engine, chip, planes=7)).compile())
    report("engine step and query_step S=1",
           lambda: joint_program.lower(*t.joint_args(
               1024, 3, 64, 4, engine, chip)).compile())


def four_chips(mesh) -> None:
    S, G, P, L, B = 16, 400_000, 5, 32, 32
    config = chip_smoke.mixed_config(S, pallas_interpret=False,
                                     monotone_tag_accept=True,
                                     kernel_mesh=mesh)
    report("4 chips: step mixed 400kx5", lambda: t.compile_step(G, P, L, S, config, mesh))
    report("4 chips: deep_step B=32", lambda: t.compile_deep(G, P, L, S, B, config, mesh))
    report("4 chips: deep_scan B=32 W=5",
           lambda: t.compile_deep(G, P, L, S, B, config, mesh, windows=B // S + 3))
    # what it is compared with: the same engine on one chip
    chip = SingleDeviceSharding(mesh.devices.flat[0])
    single = config._replace(kernel_mesh=None)
    report("1 chip: step mixed 400kx5", lambda: t.compile_step(G, P, L, S, single, chip))
    report("1 chip: deep_step B=32", lambda: t.compile_deep(G, P, L, S, B, single, chip))
    report("1 chip: deep_scan B=32 W=5",
           lambda: t.compile_deep(G, P, L, S, B, single, chip, windows=B // S + 3))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = parser.parse_args()
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    if args.chips == 4:
        four_chips(Mesh(np.asarray(topo.devices), ("groups",)))
    else:
        one_chip(SingleDeviceSharding(topo.devices[0]))


if __name__ == "__main__":
    main()

"""A census of the compiled step's own arithmetic, by source line.

Compiled code has no hit rate: what says whether the committed window comes
out of the log ring by rotation (``ops.consensus._window_gather``) is how
many element-operations its equations are. The census walks
``jax.make_jaxpr`` of ``step`` + ``install_snapshots`` as
``benchmarks/planes/raw.py`` composes them, charges every equation the
larger of its input and output element counts (a scan's body times its
length) to the innermost frame of its traceback under ``copycat_tpu/ops/``,
and divides by the groups. It runs on the CPU and counts; it measures no
time.

    python tests/test_step_census.py        # the table, at the raw shapes
"""

from __future__ import annotations

import inspect
import math
import os
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from copycat_tpu.ops import consensus, pallas_kernels
from copycat_tpu.ops.apply import ResourceConfig
from copycat_tpu.ops.consensus import (
    Config, full_delivery, init_state, install_snapshots, make_submits, step)

OPS_DIR = os.path.join("copycat_tpu", "ops") + os.sep
GROUPS = 256
#: what the window's reader was charged before PR 52, when it was six
#: ``[G,P,A,L]`` one-hot select-reduces and their shared compare: elements a
#: group and round at the raw shapes (P = 5, L = 32, A = 16; 72,800 of it
#: the six gathers), and elements of all 256 groups at the served engines'
#: (P = 3, L = 64, A = 4: 23,628.5 a group)
ONEHOT_RAW = 75_440
ONEHOT_SERVED = 6_048_903


def raw_config(A: int, kernels: bool = False) -> Config:
    """``benchmarks/configs/mixed-100kx5.json`` with ``A`` applies a round,
    its Pallas kernels on (a kernel's body is charged once a grid step) or
    off (their jnp forms)."""
    return Config(use_pallas=kernels, pallas_interpret=kernels,
                  append_window=16, applies_per_round=A,
                  pool_budgets=(4, 6, 4, 6, 4, 4, 4, 4) if A >= 6 else None,
                  timer_min=2, timer_max=4,
                  resource=ResourceConfig(multimap_slots=0, topic_slots=0))


def _size(v) -> int:
    """Elements of a value; a kernel's block ref counts nothing itself:
    reading or writing it is charged what is read or written."""
    if hasattr(v.aval, "inner_aval"):
        return 0
    return math.prod(getattr(v.aval, "shape", ()))


def _frame(eqn) -> tuple[str, str, int]:
    """(file under ops/, function, line) of the equation's innermost frame
    in this repository's ``ops`` package."""
    tb = eqn.source_info.traceback
    for f in (tb.frames if tb is not None else ()):
        if OPS_DIR in f.file_name:
            return (f.file_name.split(OPS_DIR)[-1], f.function_name,
                    f.line_num)
    return ("?", "?", 0)


def _walk(jaxpr, times: int, into: Counter, eqns: Counter) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("optimization_barrier",
                                  "layout_constraint"):
            continue                      # fences for the compiler: no work
        subs = [(v.jaxpr if hasattr(v, "jaxpr") else v)
                for v in eqn.params.values()
                if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
        subs += [b.jaxpr for v in eqn.params.values()
                 if isinstance(v, (tuple, list))
                 for b in v if hasattr(b, "jaxpr")]
        if subs:
            inner = times * eqn.params.get("length", 1)
            if eqn.primitive.name == "pallas_call":
                for n in eqn.params["grid_mapping"].grid:
                    inner *= n
            for sub in subs:
                _walk(sub, inner, into, eqns)
            continue
        n = max([_size(v) for v in (*eqn.invars, *eqn.outvars)] or [1])
        into[_frame(eqn)] += n * times
        eqns[_frame(eqn)] += 1


def census(G: int, P: int, L: int, S: int, config: Config
           ) -> tuple[Counter, Counter]:
    """Element-operations of one round by (file, function, line), and the
    equations behind them, for ``G`` groups."""
    key = jax.random.PRNGKey(0)

    def one_round(state, submits, deliver, key):
        state, out = step(state, submits, deliver, key, config=config)
        return install_snapshots(state, out.stale, out.leader,
                                 config=config), out

    state = jax.eval_shape(
        lambda k: init_state(G, P, L, k, config=config), key)
    closed = jax.make_jaxpr(one_round)(
        state, make_submits(G, S), full_delivery(G, P), key)
    ops, eqns = Counter(), Counter()
    _walk(closed.jaxpr, 1, ops, eqns)
    return ops, eqns


def _lines(fn) -> range:
    src, first = inspect.getsourcelines(fn)
    return range(first, first + len(src))


def gather_ops(ops: Counter) -> int:
    """What the window's reader is charged: ``_window_gather`` and the
    barrel shifter it calls, jnp or kernel."""
    where = [("consensus.py", _lines(consensus._window_gather)),
             *(("pallas_kernels.py", _lines(fn)) for fn in (
                 pallas_kernels._ring_kernel, pallas_kernels._ring_blocks))]
    return sum(n for (file, _, line), n in ops.items()
               if any(file == f and line in lines for f, lines in where))


def table(ops: Counter, eqns: Counter, G: int, top: int = 10) -> str:
    """The ``top`` functions by element-operations a group, lines merged."""
    by_fn: Counter = Counter()
    n_eqns: Counter = Counter()
    for (file, fn, _), n in ops.items():
        by_fn[file, fn] += n
    for (file, fn, _), n in eqns.items():
        n_eqns[file, fn] += n
    total = sum(by_fn.values())
    rows = [f"{total / G:12,.0f} element-ops a group and round, "
            f"{sum(n_eqns.values())} equations"]
    for (file, fn), n in by_fn.most_common(top):
        rows.append(f"{100 * n / total:5.1f}%  {n / G:10,.0f}  "
                    f"{file}:{fn}  ({n_eqns[file, fn]} equations)")
    return "\n".join(rows)


@pytest.mark.parametrize("L,A,kernels", [
    # the raw and bulk shapes as their cells run them, the kernels on
    (32, 16, True),
    # the same with the kernels off, and the served engines' shape: the
    # one-hot, as it was
    (32, 16, False),
    (64, 4, False),
])
def test_window_gather_share_of_the_step(L, A, kernels):
    """The rotation is charged under three tenths of the one-hot (a stage
    is a rolled copy, a broadcast mask and a select, each charged the
    block's size: 21,785 a group of 75,440); the one-hot where it is kept
    is charged what it was, to the element."""
    P, S = (5, 16) if A == 16 else (3, 4)
    config = raw_config(A, kernels)
    ops, eqns = census(GROUPS, P, L, S, config)
    got = gather_ops(ops)
    if consensus._window_form(A, L, config) == "rotate":
        assert kernels and 0 < got / GROUPS < ONEHOT_RAW * 3 // 10, \
            f"{got / GROUPS}\n{table(ops, eqns, GROUPS)}"
    else:
        want = ONEHOT_RAW * GROUPS if A == 16 else ONEHOT_SERVED
        assert abs(got - want) < GROUPS, \
            f"{got} != {want}\n{table(ops, eqns, GROUPS)}"


if __name__ == "__main__":
    for kernels in (False, True):
        ops, eqns = census(GROUPS, 5, 32, 16, raw_config(16, kernels))
        print(f"kernels {'on' if kernels else 'off'}:")
        print(table(ops, eqns, GROUPS, top=16))
        print(f"window gather: {gather_ops(ops) / GROUPS:,.0f} a group")

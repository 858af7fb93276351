"""Pallas quorum-tally kernel tests (ops/pallas_kernels.py).

Differential against the jnp closed-form selection and against numpy
sort; plus a full consensus run with Config(use_pallas=True), in
interpret mode (the tests run on the CPU; tests/test_tpu_compile.py
compiles the kernel for the chip).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from copycat_tpu.ops.pallas_kernels import (  # noqa: E402
    kth_largest,
    kth_largest_pallas,
)

from engines import device_plane  # noqa: E402


@pytest.mark.parametrize("P,k", [(3, 2), (5, 3), (7, 4), (4, 1), (3, 3)])
def test_kth_largest_matches_numpy(P, k):
    rng = np.random.default_rng(P * 10 + k)
    x = rng.integers(-100, 100, (257, P)).astype(np.int32)
    expect = np.sort(x, axis=1)[:, ::-1][:, k - 1]
    got = np.asarray(kth_largest(jnp.asarray(x), k))
    assert (got == expect).all()


@pytest.mark.parametrize("G", [64, 512, 1000])
def test_pallas_kernel_matches_reference(G):
    rng = np.random.default_rng(G)
    x = rng.integers(0, 1 << 20, (G, 3)).astype(np.int32)
    expect = np.asarray(kth_largest(jnp.asarray(x), 2))
    got = np.asarray(kth_largest_pallas(jnp.asarray(x), 2, block=256,
                                       interpret=True))
    assert (got == expect).all()


def test_pallas_with_duplicates():
    x = jnp.asarray([[5, 5, 5], [1, 1, 2], [0, 7, 7]], jnp.int32)
    got = np.asarray(kth_largest_pallas(x, 2, block=256, interpret=True))
    assert got.tolist() == [5, 1, 7]


def test_consensus_with_pallas_quorum():
    from copycat_tpu.ops import apply as ap
    from copycat_tpu.ops.consensus import Config

    rg = device_plane(Config(use_pallas=True, pallas_interpret=True))
    rg.wait_for_leaders()
    tags = [rg.submit(g, ap.OP_LONG_ADD, g + 1) for g in range(4)
            for _ in range(3)]
    rg.run_until(tags)
    rg.run(5)
    val = np.asarray(rg.state.resources.value)
    for g in range(4):
        assert (val[g] == 3 * (g + 1)).all()

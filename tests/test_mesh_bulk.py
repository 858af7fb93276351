"""Deep bulk plane over a sharded mesh (round 4).

The scaling artifact's claim — the client data path runs over
group-sharded engines with ZERO cross-device collectives — needs an
automated guard, not just the hand-run `parallel/scaling` script: a
wrong PartitionSpec or an accumulator formulation that reshards (the
round-4 census caught the `.at[]` scatter compiling to all-gathers of
the [G,B] buffers) would otherwise ship silently.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.models import BulkDriver  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.ops.consensus import Config  # noqa: E402
from copycat_tpu.parallel.mesh import make_mesh  # noqa: E402
from copycat_tpu.parallel.scaling import census_text, _deep_census  # noqa: E402

from engines import G, MONOTONE, device_plane  # noqa: E402


def _mesh_engine(seed=51):
    mesh = make_mesh()  # all 8 virtual devices, 1D groups axis
    rg = device_plane(MONOTONE, seed=seed, mesh=mesh)
    rg.wait_for_leaders()
    return rg


def test_deep_drive_on_sharded_mesh_fifo_and_reads():
    rg = _mesh_engine()
    driver = BulkDriver(rg)
    # uneven per-group counts exercise the padded [G,B] accumulators
    g = np.concatenate([np.full((i % 7) + 1, i) for i in range(G)])
    res = driver.drive(g, ap.OP_LONG_ADD, 1)
    off = 0
    for i in range(G):
        cnt = (i % 7) + 1
        assert (res.results[off:off + cnt] == np.arange(1, cnt + 1)).all()
        off += cnt
    # second drive continues streams across the mesh
    res2 = driver.drive(np.arange(G), ap.OP_LONG_ADD, 1)
    assert (res2.results == (np.arange(G) % 7) + 2).all()
    # and the query lane serves ATOMIC lease reads over the mesh
    got = driver.drive_queries(np.arange(G), ap.OP_VALUE_GET,
                               consistency="atomic")
    assert (got == (np.arange(G) % 7) + 2).all()


def test_a_dense_drive_on_a_mesh_returns_what_its_sorted_form_does():
    """The plan is read from the submission alone (``models/bulk.py``): over
    a sharded engine the reshaped payload and the sliced harvest give what
    the stable sort and the index arrays give, and leave the same state."""
    engines = [_mesh_engine(seed=39), _mesh_engine(seed=39)]
    dense, sorted_ = (BulkDriver(rg, deep_scan=True) for rg in engines)
    g = np.repeat(np.arange(G), 6)     # two windows, the second half full
    amounts = np.arange(g.size) % 5 + 1
    mixed = np.random.default_rng(39).permutation(g)
    perm = np.empty(g.size, np.int64)
    perm[np.argsort(mixed, kind="stable")] = np.arange(g.size)
    for _ in range(2):
        res = dense.drive(g, ap.OP_LONG_ADD, amounts)
        res_sorted = sorted_.drive(mixed, ap.OP_LONG_ADD, amounts[perm])
        assert res.rounds == res_sorted.rounds
        for name in ("results", "dispatch_round", "resolve_round"):
            assert np.array_equal(getattr(res, name)[perm],
                                  getattr(res_sorted, name)), name
    assert (res.results.reshape(G, 6)[:, -1] == 2 * amounts.reshape(G, 6)
            .sum(axis=1)).all()
    for x, y in zip(*(jax.tree.leaves(jax.device_get(rg.state))
                      for rg in engines)):
        assert np.array_equal(x, y)
    counts = [[rg.metrics.counter(f"bulk_{t}_drives").value
               for t in ("grouped", "dense")] for rg in engines]
    assert counts == [[2, 2], [0, 0]]


def test_deep_step_census_zero_collectives_on_mesh():
    devices = jax.devices("cpu")
    config = Config(append_window=8, applies_per_round=8,
                    monotone_tag_accept=True)
    assert _deep_census(2, devices, config) == {}
    assert _deep_census(8, devices, config) == {}


def test_deep_scan_census_zero_collectives_on_mesh():
    """The round-5 fused scan program is a DISTINCT compiled module; its
    zero-collective property must be verified, not inherited."""
    from copycat_tpu.parallel.scaling import _deep_scan_census

    devices = jax.devices("cpu")
    config = Config(append_window=8, applies_per_round=8,
                    monotone_tag_accept=True)
    assert _deep_scan_census(2, devices, config) == {}
    assert _deep_scan_census(8, devices, config) == {}


def test_query_step_census_zero_collectives_on_mesh():
    """The round-9 read plane: the ``query_step`` program (the batched
    read pump's device leg) is leader-lane selects + one fused apply
    pass per group — group-local by construction — and must compile to
    zero cross-device collectives like the step."""
    from copycat_tpu.parallel.scaling import _query_census

    devices = jax.devices("cpu")
    assert _query_census(2, devices) == {}
    assert _query_census(8, devices) == {}


def test_census_positive_control():
    """The census must be able to SEE collectives — a broken tally that
    always returns {} would turn the scaling artifact into a false
    pass (this exact bug appeared and was caught in round-4 review:
    an over-escaped regex matched nothing)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh()
    x = jax.device_put(np.ones(64, np.float32),
                       NamedSharding(mesh, P("groups")))
    txt = jax.jit(lambda v: v.sum()).lower(x).compile().as_text()
    census = census_text(txt)
    assert census, f"cross-shard sum must census >=1 collective: {txt[:200]}"

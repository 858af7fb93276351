"""The committed window out of the log ring: ``ops.consensus._window_gather``
against the ``[A, L]`` one-hot select-reduce it replaced at the raw and bulk
cells' shapes, bit for bit, alone and through forty rounds of the whole step.

The one-hot form is kept here as the reference. It reads ring slot
``(applied_index + i) % L`` for every window position ``i < A``, past the
commit index too, where the slot holds whatever the ring holds: the new
form has to return that as well.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import generators as gen
from copycat_tpu.ops import consensus
from copycat_tpu.ops.apply import ResourceConfig
from copycat_tpu.ops.consensus import (
    Config, init_state, install_snapshots, step)

#: (L, A) and the form the static sizes select where the kernels are on
#: (off, every shape keeps the one-hot): the rotation where A > ceil(log2 L)
#: and the kernel fits (A <= L, both whole sublane tiles of 8)
SHAPES = {(32, 16): "rotate", (64, 4): "onehot", (64, 16): "rotate",
          (32, 32): "rotate", (8, 4): "onehot",
          # a window longer than the ring, and a ring that is no power of two
          (8, 12): "onehot", (24, 16): "rotate", (24, 4): "onehot"}
G, P = 257, 5
I32 = np.iinfo(np.int32)
JNP = Config()
#: the kernel of ``ops/pallas_kernels.py`` in Pallas's interpreter
KERNEL = Config(use_pallas=True, pallas_interpret=True)


def onehot_gather(slot_all, L, config=None):
    """The form of ``consensus.py`` before PR 52."""
    win_oh = slot_all[..., None] == jnp.arange(L, dtype=jnp.int32)
    return lambda log: jnp.where(win_oh, log[:, :, None, :], 0).sum(axis=-1)


def _slots(applied, A, L):
    idx_all = applied[..., None] + 1 \
        + jnp.arange(A, dtype=jnp.int32)[None, None, :]
    return (idx_all - 1) % L


@pytest.mark.parametrize("config", [JNP, KERNEL], ids=["jnp", "kernel"])
@pytest.mark.parametrize("L,A", list(SHAPES))
def test_gather_is_the_onehot_bit_for_bit(L, A, config):
    rng = np.random.default_rng(L * 100 + A)
    # several wraps of the ring, every start slot, and index 0
    applied = rng.integers(0, 7 * L, (G, P)).astype(np.int32)
    applied[0] = 0
    applied[1, :] = np.arange(P) * L
    applied[2:2 + L, 0] = np.arange(L)
    planes = rng.integers(I32.min, I32.max, (6, G, P, L), dtype=np.int64,
                          endpoint=True).astype(np.int32)
    planes[0, :, :, ::3] = I32.min
    planes[0, :, :, 1::3] = I32.max
    slot_all = _slots(jnp.asarray(applied), A, L)

    def read_all(slot_all, planes):
        ga = consensus._window_gather(slot_all, L, config)
        return jnp.stack([ga(plane) for plane in planes])

    got = jax.jit(read_all)(slot_all, jnp.asarray(planes))
    want = jnp.stack([onehot_gather(slot_all, L)(p)
                      for p in jnp.asarray(planes)])
    assert got.dtype == want.dtype == jnp.int32
    assert got.shape == (6, G, P, A)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and it is the ring's own content at those slots
    np.testing.assert_array_equal(
        np.asarray(got[3]),
        np.take_along_axis(planes[3], np.asarray(slot_all), axis=-1))


@pytest.mark.parametrize("L,A", list(SHAPES))
def test_static_sizes_select_the_form(L, A):
    assert consensus._window_form(A, L, KERNEL) == SHAPES[L, A]
    assert consensus._window_form(A, L, JNP) == "onehot"
    # the form in use, read off the traced program: the one-hot's
    # [G,P,A,L] compare is there, or one kernel call is
    slot_all = _slots(jnp.zeros((2, 3), jnp.int32), A, L)
    for config, form in ((KERNEL, SHAPES[L, A]), (JNP, "onehot")):
        jaxpr = jax.make_jaxpr(
            lambda s, x: consensus._window_gather(s, L, config)(x))(
                slot_all, jnp.zeros((2, 3, L), jnp.int32))
        text = str(jaxpr)
        widest = max(int(np.prod(v.aval.shape)) for e in jaxpr.eqns
                     for v in e.outvars)
        assert ("pallas_call" in text) == (form == "rotate")
        assert (widest == 2 * 3 * A * L) == (form == "onehot")


def _raw_rounds(gather, rounds=40, groups=64, peers=5, period=20, seed=11):
    """The raw plane's scan body (``benchmarks/planes/raw.py``: step, then
    snapshot install, under the victim schedule), every round's outputs
    kept, with ``gather`` as the window's reader."""
    L, S = 32, 16
    config = Config(
        use_pallas=True, pallas_interpret=True, append_window=16,
        applies_per_round=16, pool_budgets=(4, 6, 4, 6, 4, 4, 4, 4),
        timer_min=2, timer_max=4,
        resource=ResourceConfig(multimap_slots=0, topic_slots=0))
    pattern = gen.mixed_submits(groups, S)
    slot = jnp.arange(S, dtype=jnp.int32)[None, :]
    victims = jnp.asarray(gen.isolation_masks(
        rounds, groups, peers, period=period, seed=seed))

    def body(carry, xs):
        state, key = carry
        victim, r = xs
        key, k = jax.random.split(key)
        sub = pattern._replace(
            tag=jnp.broadcast_to(r * S + slot + 1, (groups, S)))
        state, out = step(state, sub,
                          gen.victim_deliver(victim, groups, peers), k,
                          config=config)
        state = install_snapshots(state, out.stale, out.leader,
                                  config=config)
        return (state, key), out

    def run(state, key):
        return jax.lax.scan(
            body, (state, key),
            (victims, jnp.arange(rounds, dtype=jnp.int32)))

    key, init_key = jax.random.split(jax.random.PRNGKey(seed))
    state = init_state(groups, peers, L, init_key, config=config)
    was = consensus._window_gather
    consensus._window_gather = gather
    try:
        (state, _), outs = jax.jit(run)(state, key)
    finally:
        consensus._window_gather = was
    return state, outs


def test_forty_nemesis_rounds_equal_on_every_leaf_and_output():
    new_state, new_outs = _raw_rounds(consensus._window_gather)
    ref_state, ref_outs = _raw_rounds(onehot_gather)
    applied = np.asarray(new_state.applied_index)
    # the schedule did something: entries applied well past a ring's wrap,
    # lanes apart, and results reported
    assert applied.max() > 3 * 32 and (applied.max(1) > applied.min(1)).any()
    assert np.asarray(new_outs.out_valid).sum() > 40 * 64
    for what, new, ref in (("state", new_state, ref_state),
                           ("outputs", new_outs, ref_outs)):
        flat_new, tree_new = jax.tree.flatten_with_path(new)
        flat_ref, tree_ref = jax.tree.flatten_with_path(ref)
        assert tree_new == tree_ref
        for (path, a), (_, b) in zip(flat_new, flat_ref):
            assert a.dtype == b.dtype and a.shape == b.shape, (what, path)
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{what}{jax.tree_util.keystr(path)}")

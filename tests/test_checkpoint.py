"""Checkpoint/resume tests (models/checkpoint.py).

The reference recovers by log replay only (no snapshots, SURVEY.md §5.4);
here a full snapshot must resume bit-exactly: committed state, logs,
resource pools, event dedup cursors and the logical clock all survive.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.models import checkpoint  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402

from engines import G, device_plane  # noqa: E402


def test_save_load_roundtrip(tmp_path):
    rg = device_plane()
    rg.wait_for_leaders()
    tags = [rg.submit(0, ap.OP_LONG_ADD, 2) for _ in range(5)]
    tags += [rg.submit(1, ap.OP_MAP_PUT, 7, 70)]
    tags += [rg.submit(1, ap.OP_LOCK_ACQUIRE, 4, -1)]
    rg.run_until(tags)
    rg.run(5)

    path = tmp_path / "snap.npz"
    checkpoint.save(rg, path)
    restored = checkpoint.load(path)

    assert restored.rounds == rg.rounds
    assert restored.clock == rg.clock
    for a, b in zip(jax.tree_util.tree_leaves(rg.state),
                    jax.tree_util.tree_leaves(restored.state)):
        assert (np.asarray(a) == np.asarray(b)).all()

    # the restored cluster continues committing from where it stopped
    t = restored.submit(0, ap.OP_LONG_ADD, 2)
    restored.run_until([t])
    assert restored.results[t] == 12  # 5 * 2 before + 2 after
    t2 = restored.submit(1, ap.OP_MAP_GET, 7)
    restored.run_until([t2])
    assert restored.results[t2] == 70
    # lock holder survived the snapshot
    t3 = restored.submit(1, ap.OP_LOCK_HOLDER)
    restored.run_until([t3])
    assert restored.results[t3] == 4


def test_restore_preserves_event_dedup(tmp_path):
    rg = device_plane()
    rg.wait_for_leaders()
    tags = [rg.submit(0, ap.OP_LOCK_ACQUIRE, 1, -1),
            rg.submit(0, ap.OP_LOCK_ACQUIRE, 2, -1),
            rg.submit(0, ap.OP_LOCK_RELEASE, 1)]
    rg.run_until(tags)
    rg.run(5)
    grants = [e for e in rg.events.get(0, []) if e[1] == ap.EV_LOCK_GRANT]
    assert len(grants) == 1  # grant to 2

    path = tmp_path / "snap.npz"
    checkpoint.save(rg, path)
    restored = checkpoint.load(path)
    restored.run(10)
    # the buffered grant survives the snapshot EXACTLY once: persisted in
    # rg.events and not re-harvested from the device ring (seq dedup)
    grants2 = [e for e in restored.events.get(0, [])
               if e[1] == ap.EV_LOCK_GRANT]
    assert grants2 == grants

    # a facade created AFTER restore must NOT consume the pre-snapshot
    # grant (session events die with the session); it recovers through the
    # authoritative holder register instead
    from copycat_tpu.models.device_resources import DeviceLock
    lock = DeviceLock(restored, 0, holder_id=2)
    assert not lock._next_grant()
    t = restored.submit(0, ap.OP_LOCK_HOLDER)
    restored.run_until([t])
    assert restored.results[t] == 2  # ground truth: 2 holds the lock


def test_load_snapshot_missing_newer_pool_leaves(tmp_path):
    """Snapshots saved before newer ResourceState pools/fields existed must
    restore with fresh template values — both the legacy positional
    format (trailing-leaf padding) and the path-keyed format (missing
    fields keep template values)."""
    import json

    import jax

    rg = device_plane()
    rg.wait_for_leaders()
    tag = rg.submit(0, ap.OP_LONG_ADD, 7)
    rg.run_until([tag])
    rg.run(5)  # let every lane (incl. peer 0) apply before snapshotting
    path = tmp_path / "now.npz"
    checkpoint.save(rg, path)

    with np.load(str(path), allow_pickle=False) as data:
        meta = json.loads(str(data["meta"]))
        arrays = {k: data[k] for k in data.files if k != "meta"}

    # (a) path-keyed format with newer fields missing entirely
    partial = {k: v for k, v in arrays.items()
               if not any(f in k for f in ("mm_", "tp_", "lease", "member"))}
    old_pk = tmp_path / "path-keyed-old.npz"
    np.savez_compressed(str(old_pk), meta=json.dumps(meta), **partial)
    restored = checkpoint.load(old_pk)
    assert restored.value(0) == 7
    t = restored.submit(0, ap.OP_MM_PUT, 1, 2)
    restored.run_until([t])
    assert restored.results[t] == 1

    # (b) legacy positional format (leaf_i), truncated before mm/tp/lease
    flat = jax.tree_util.tree_flatten_with_path(rg.state)[0]
    legacy = {k: v for k, v in arrays.items() if not k.startswith("state.")}
    n = 0
    for path_keys, leaf in flat:
        name = "state." + ".".join(
            getattr(pk, "name", str(pk)) for pk in path_keys)
        if any(f in name for f in ("mm_", "tp_", "lease", "member")):
            continue
        legacy[f"leaf_{n}"] = arrays[name]
        n += 1
    meta["num_leaves"] = n
    old_pos = tmp_path / "positional-old.npz"
    np.savez_compressed(str(old_pos), meta=json.dumps(meta), **legacy)
    restored2 = checkpoint.load(old_pos)
    assert restored2.value(0) == 7
    t2 = restored2.submit(0, ap.OP_MM_PUT, 3, 4)
    restored2.run_until([t2])
    assert restored2.results[t2] == 1


def test_restore_onto_different_device_layout(tmp_path):
    """Hardware elasticity: a snapshot from an UNSHARDED engine restores
    onto an 8-device mesh (and back), resumes identically, and the
    mesh restore really is distributed. The save format is placement-
    free (plain npz arrays), so layout is purely a load-time choice —
    the operational story for moving a cluster between hosts with
    different chip counts."""
    from copycat_tpu.parallel import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh (conftest)")

    rg = device_plane()
    rg.wait_for_leaders()
    tags = [rg.submit(g, ap.OP_LONG_ADD, g + 1) for g in range(G)]
    rg.run_until(tags)
    rg.run(3)
    path = tmp_path / "snap.npz"
    checkpoint.save(rg, path)

    def assert_states_equal(sa, sb):
        fa = jax.tree_util.tree_flatten_with_path(sa)[0]
        fb = jax.tree_util.tree_flatten_with_path(sb)[0]
        for (pa, a), (_, b) in zip(fa, fb, strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b)), pa

    mesh = make_mesh(groups=8)
    onto_mesh = checkpoint.load(path, mesh=mesh)
    assert len(onto_mesh.state.term.devices()) == 8  # really sharded
    assert_states_equal(rg.state, onto_mesh.state)

    # both resume and agree on new work
    for drv in (rg, onto_mesh):
        t2 = [drv.submit(g, ap.OP_LONG_ADD, 10) for g in range(G)]
        drv.run_until(t2)
    assert_states_equal(rg.state, onto_mesh.state)

    # and the mesh snapshot restores back onto a single device
    path2 = tmp_path / "snap2.npz"
    checkpoint.save(onto_mesh, path2)
    back = checkpoint.load(path2)
    assert len(back.state.term.devices()) == 1
    assert_states_equal(onto_mesh.state, back.state)


def test_cut_is_the_state_at_its_instant_whatever_runs_after():
    """``cut`` returns before anything is fetched, and the rounds after it
    donate the very buffers it was taken from: the image written later,
    from another thread, is still the driver at the cut, leaf for leaf,
    with the host fields and the key as they were."""
    import threading

    rg = device_plane()
    rg.wait_for_leaders()
    rg.run_until([rg.submit(g, ap.OP_LONG_ADD, g + 1) for g in range(4)])
    names = [checkpoint._leaf_name(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(rg.state)[0]]
    before = [np.asarray(x).copy()
              for x in jax.tree_util.tree_leaves(rg.state)]
    fields = (rg.rounds, rg.clock, rg._next_tag, np.asarray(rg._key).tolist())
    taken = checkpoint.cut(rg)
    old_leaves = jax.tree_util.tree_leaves(rg.state)

    rg.run_until([rg.submit(g, ap.OP_LONG_ADD, 100) for g in range(4)])
    rg.run(3)
    assert all(x.is_deleted() for x in old_leaves)     # donated since

    blob = []
    worker = threading.Thread(target=lambda: blob.append(taken.to_bytes()))
    worker.start()
    worker.join(60)
    assert not worker.is_alive() and blob
    restored = checkpoint.load_bytes(blob[0])
    for name, a, b in zip(names, before,
                          jax.tree_util.tree_leaves(restored.state),
                          strict=True):
        assert np.array_equal(a, np.asarray(b)), name
    assert (restored.rounds, restored.clock, restored._next_tag,
            np.asarray(restored._key).tolist()) == fields
    assert rg.rounds > restored.rounds
    t = [restored.submit(g, ap.OP_LONG_ADD, 0) for g in range(4)]
    restored.run_until(t)
    assert [restored.results[x] for x in t] == [1, 2, 3, 4]

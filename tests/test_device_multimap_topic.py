"""Device multimap + topic kernels (round-2 VERDICT directive #7).

Raw-path coverage for the two kernels added in round 3: the (key,value)
pair-probe multimap (reference ``MultiMapState.java:30``) and the topic
subscriber table with broadcast-event publish (``TopicState.java:31``),
plus replica-convergence and facade behavior.
"""

import numpy as np

from copycat_tpu.models.device_resources import DeviceMultiMap, DeviceTopic
from copycat_tpu.models.raft_groups import RaftGroups
from copycat_tpu.ops import apply as ap

from engines import device_plane, wide_window


def _groups() -> RaftGroups:
    rg = device_plane(seed=5)
    rg.wait_for_leaders()
    return rg


def test_multimap_kernel_semantics():
    rg = _groups()
    mm = DeviceMultiMap(rg, 0)
    assert mm.is_empty()
    assert mm.put(1, 10)
    assert mm.put(1, 11)
    assert not mm.put(1, 10)        # duplicate (key, value) pair
    assert mm.put(2, 10)
    assert mm.size() == 3
    assert mm.count(1) == 2
    assert mm.contains_key(1)
    assert mm.contains_entry(1, 11)
    assert not mm.contains_entry(2, 11)
    assert mm.contains_value(10)
    assert mm.remove_entry(1, 11)
    assert not mm.remove_entry(1, 11)
    assert mm.count(1) == 1
    assert mm.remove(1) == 1        # removes every pair under the key
    assert not mm.contains_key(1)
    assert mm.size() == 1
    mm.clear()
    assert mm.is_empty()


def test_multimap_ttl_expiry_is_lazy_and_deterministic():
    rg = _groups()
    mm = DeviceMultiMap(rg, 0)
    assert mm.put(7, 70, ttl=3)     # expires at clock+3
    assert mm.contains_entry(7, 70)
    rg.run(6)                       # advance the replicated clock past ttl
    assert not mm.contains_entry(7, 70)
    assert mm.size() == 0
    # replicas converge bit-exactly (same applied prefix)
    for field in ("mm_key", "mm_val", "mm_live", "mm_dl"):
        arr = np.asarray(getattr(rg.state.resources, field))
        for p in range(1, arr.shape[1]):
            np.testing.assert_array_equal(arr[:, 0], arr[:, p], err_msg=field)


def test_topic_publish_fans_out_to_subscribers():
    rg = _groups()
    alice = DeviceTopic(rg, 0, subscriber_id=1)
    bob = DeviceTopic(rg, 0, subscriber_id=2)
    alice.subscribe()
    assert alice.subscriber_count() == 1
    assert rg.events is not None

    # published before bob subscribes: only alice sees it
    assert DeviceTopic(rg, 0, subscriber_id=9).publish(41) == 1
    rg.run(4)
    assert alice.poll_messages() == [41]
    assert bob.poll_messages() == []  # not subscribed

    bob.subscribe()
    assert bob.subscriber_count() == 2
    pub = DeviceTopic(rg, 0, subscriber_id=9)
    assert pub.publish(42) == 2
    assert pub.publish(43) == 2
    rg.run(4)
    assert alice.poll_messages() == [42, 43]
    assert bob.poll_messages() == [42, 43]

    alice.unsubscribe()
    assert pub.publish(44) == 1
    rg.run(4)
    assert alice.poll_messages() == []
    assert bob.poll_messages() == [44]


def test_topic_subscribe_is_idempotent_and_bounded():
    rg = _groups()
    t = DeviceTopic(rg, 1, subscriber_id=5)
    t.subscribe()
    t.subscribe()                    # idempotent: no duplicate entry
    assert t.subscriber_count() == 1
    # fill the table (topic_slots=8)
    for i in range(7):
        DeviceTopic(rg, 1, subscriber_id=10 + i).subscribe()
    full = DeviceTopic(rg, 1, subscriber_id=99)
    result = full._call(ap.OP_TOPIC_LISTEN, 99)
    assert result == ap.FAIL         # table full -> explicit overflow


def test_multimap_topic_independent_of_other_pools():
    """Multimap/topic ops interleaved with every other pool in one batch
    stream — the conflict-partitioned window must keep them all straight."""
    from copycat_tpu.ops.consensus import Config
    config = Config(applies_per_round=8,
                    pool_budgets=(2, 2, 2, 2, 2, 2, 2, 2))
    rg = wide_window(config)
    rg.wait_for_leaders()
    tags = {}
    tags["add"] = rg.submit(0, ap.OP_LONG_ADD, 5)
    tags["mapput"] = rg.submit(0, ap.OP_MAP_PUT, 1, 100)
    tags["mmput"] = rg.submit(0, ap.OP_MM_PUT, 1, 200)
    tags["sub"] = rg.submit(0, ap.OP_TOPIC_LISTEN, 3)
    tags["pub"] = rg.submit(0, ap.OP_TOPIC_PUB, 77)
    tags["mmcount"] = rg.submit(0, ap.OP_MM_COUNT, 1)
    rg.run_until(list(tags.values()))
    assert rg.results[tags["add"]] == 5
    assert rg.results[tags["mapput"]] == 0
    assert rg.results[tags["mmput"]] == 1
    assert rg.results[tags["sub"]] == 1
    assert rg.results[tags["pub"]] == 1      # one subscriber at publish
    assert rg.results[tags["mmcount"]] == 1
    evs = rg.events.get(0, [])
    assert any(c == ap.EV_TOPIC_MSG and a == 77 for _, c, _t, a in evs)

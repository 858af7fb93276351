"""Native wire codec (native/copycat_codec.c) vs the pure-Python reference.

The C extension must be BYTE-IDENTICAL to serializer.py on encode and
produce equal object graphs on decode, for every corner of the format:
primitives, containers (incl. the sorted-set determinism rule),
generic field-list messages, custom-serialized classes (fallback
hooks), class references, and >64-bit ints (graceful Fallback). And
for the third class shape, the log entries' fixed head (raw i64 index,
i64 term, f64 timestamp, then generic fields): every entry class on
both walks, the head's edges, segments written by one walk and
recovered by the other, a stale binary, and the counter that says the
walk stayed in C.
"""

import os
import shutil
import struct

import pytest

pytest.importorskip("jax")  # repo-wide platform pin in conftest

from copycat_tpu.atomic import commands as ac  # noqa: E402
from copycat_tpu.io.buffer import BufferInput, BufferOutput  # noqa: E402
from copycat_tpu.io import codec as codec_mod  # noqa: E402
from copycat_tpu.io.codec import codec  # noqa: E402
from copycat_tpu.io.serializer import _ID_BY_TYPE, Serializer  # noqa: E402
from copycat_tpu.io.transport import Address  # noqa: E402
from copycat_tpu.manager import operations as mo  # noqa: E402
from copycat_tpu.protocol import messages as pm  # noqa: E402
from copycat_tpu.server import log as sl  # noqa: E402

C = codec()
pytestmark = pytest.mark.skipif(C is None, reason="no native toolchain")

_ser = Serializer()


def _py_write(obj) -> bytes:
    buf = BufferOutput()
    _ser.write_object(obj, buf)
    return buf.to_bytes()


def _py_read(data: bytes):
    return _ser.read_object(BufferInput(data))


def _entry(cls, index=0, term=0, timestamp=0.0, **fields):
    entry = cls(term=term, timestamp=timestamp, **fields)
    entry.index = index
    return entry


def _command(i: int, index: int = 0, term: int = 3,
             timestamp: float = 1727500000.125):
    """A CommandEntry of the shape cluster-3x1k.write logs."""
    return _entry(sl.CommandEntry, index or 100000 + i, term, timestamp + i,
                  session_id=1000 + i, seq=57,
                  operation=mo.InstanceCommand(
                      20 + i, ac.CompareAndSet(expect=57, update=64,
                                               ttl=None)))


I64_MAX, I64_MIN = 2**63 - 1, -(2**63)

#: one populated instance of each of the six entry classes
ENTRIES = [
    _entry(sl.NoOpEntry, 1, 1, 1.5),
    _entry(sl.RegisterEntry, 2, 1, 2.5, client_id="client-1", timeout=5.0,
           session_id=77),
    _entry(sl.KeepAliveEntry, 3, 1, 3.5, session_id=2, command_seq=9,
           event_index=3),
    _entry(sl.UnregisterEntry, 4, 2, 4.5, session_id=2, expired=True),
    _command(0),                             # a nested InstanceCommand
    _entry(sl.ConfigurationEntry, 6, 2, 6.5,   # a custom body inside the head
           members=[Address("h", 1), Address("h", 2)]),
]

ENTRY_CORPUS = ENTRIES + [
    # default construction: index 0, term 0, timestamp 0.0, every field None
    sl.NoOpEntry(), sl.RegisterEntry(), sl.KeepAliveEntry(),
    sl.UnregisterEntry(), sl.CommandEntry(), sl.ConfigurationEntry(),
    # the head's edges: what a raw i64 and a raw f64 can hold
    _command(1, index=I64_MAX, term=I64_MAX),
    _command(2, index=I64_MIN, term=I64_MIN),
    _command(3, index=-1, term=-1),
    _entry(sl.NoOpEntry, I64_MAX, 0, float("inf")),
    _entry(sl.NoOpEntry, 7, I64_MIN, float("-inf")),
    _entry(sl.KeepAliveEntry, 8, 1, float("nan"), session_id=1),
    _entry(sl.UnregisterEntry, 9, 1, -0.0, session_id=None, expired=False),
    _entry(sl.CommandEntry, 10, 1, 5e-324, session_id=2**62, seq=-1,
           operation=None),
    # entries where the cluster ships them: a 64-entry window
    pm.AppendRequest(term=3, leader=0, prev_index=99999, prev_term=3,
                     entries=[_command(i) for i in range(64)],
                     commit_index=99990, global_index=99000, fill_to=100063,
                     group=None, trace=None),
    [sl.NoOpEntry(), {"k": _command(4)}, (_command(5),)],
]

CORPUS = [
    None, True, False,
    0, 1, -1, 63, 64, -64, -65, 127, 128, -300, 2**31, -(2**31),
    2**62 - 1, -(2**62),
    0.0, -0.0, 3.141592653589793, float("inf"), float("-inf"),
    "", "ascii", "héllo ✓ ☃", "a" * 300,
    b"", b"bytes", bytearray(b"mutable"),
    [], [1, "two", None, [3.0]], (), (1,), ((2, 3), [4]),
    {}, {"k": 1, 2: "v", None: [True]},
    set(), {1, 2, 3}, {"a", b"b", 3}, frozenset({9, "z"}),
    mo.InstanceCommand(7, ac.Set(value=42, ttl=None)),
    mo.InstanceQuery(3, ac.Get()),
    mo.InstanceEvent(1, "changed"),
    mo.GetResource("res", ac.Set),           # class reference field
    mo.DeleteResource(11),
    pm.CommandBatchRequest(
        session_id=9,
        entries=[(1, mo.InstanceCommand(1, ac.Get())), (2, None)]),
    pm.RegisterResponse(error=None, error_detail=None, leader=None,
                        session_id=5, timeout=10.0, members=["a:1"]),
    Address("host", 8080),                   # custom write/read (fallback)
    [Address("h", 1), mo.InstanceCommand(2, ac.CompareAndSet(
        expect=1, update=2, ttl=None))],     # fallback nested in fast path
] + ENTRY_CORPUS


@pytest.mark.parametrize("obj", CORPUS, ids=lambda o: repr(o)[:40])
def test_encode_byte_identical(obj):
    assert C.encode(obj) == _py_write(obj)


@pytest.mark.parametrize("obj", CORPUS, ids=lambda o: repr(o)[:40])
def test_decode_cross_paths_equal(obj):
    wire = _py_write(obj)
    via_c = C.decode(wire)
    via_py = _py_read(wire)
    # object graphs may lack __eq__ (Message classes) — compare by
    # re-encoding, which is a faithful structural fingerprint
    assert _py_write(via_c) == _py_write(via_py) == wire


def test_set_encoding_is_deterministic():
    # same set, different construction order -> same bytes (the sorted
    # per-item-encoding rule)
    a = C.encode({3, 1, 2, "x"})
    b = C.encode({"x", 2, 1, 3})
    assert a == b == _py_write({1, 2, 3, "x"})


def test_bigint_falls_back_not_corrupts():
    big = 2**70
    with pytest.raises(C.Fallback):
        C.encode(big)
    # the public API falls back silently and round-trips
    assert _ser.read(_ser.write(big)) == big
    assert _ser.read(_ser.write(-big)) == -big
    # and decode of a Python-encoded bigint falls back too
    with pytest.raises(C.Fallback):
        C.decode(_py_write(big))


def test_unregistered_type_raises_fallback():
    class Unregistered:
        pass

    with pytest.raises(C.Fallback):
        C.encode(Unregistered())


def test_truncated_input_raises_eof():
    wire = C.encode([1, 2, 3])
    with pytest.raises(EOFError):
        C.decode(wire[:-1])


def test_trailing_bytes_rejected():
    with pytest.raises(C.Fallback):
        C.decode(C.encode(1) + b"\x00")


def test_serializer_write_read_use_native_and_match():
    msg = pm.CommandBatchRequest(
        session_id=1,
        entries=[(i, mo.InstanceCommand(i, ac.Set(value=i, ttl=None)))
                 for i in range(50)])
    wire = _ser.write(msg)
    assert wire == _py_write(msg)          # native path, same bytes
    back = _ser.read(wire)
    assert _py_write(back) == wire


def test_full_registry_roundtrip_default_instances():
    """Every registered type must survive encode->decode on BOTH paths
    (constructible ones with default args)."""
    from copycat_tpu.io.serializer import _TYPE_REGISTRY
    # import the catalogs so the registry is fully populated
    import copycat_tpu.collections.commands  # noqa: F401
    import copycat_tpu.coordination.commands  # noqa: F401
    import copycat_tpu.resource.operations  # noqa: F401
    import copycat_tpu.server.log  # noqa: F401

    checked = 0
    for type_id, cls in sorted(_TYPE_REGISTRY.items()):
        if not hasattr(cls, "write_object"):
            continue  # registered only for class-reference serialization
        try:
            obj = cls()
        except Exception:
            continue  # needs constructor args; covered by CORPUS cases
        wire_py = _py_write(obj)
        assert C.encode(obj) == wire_py, (type_id, cls)
        assert _py_write(C.decode(wire_py)) == wire_py, (type_id, cls)
        checked += 1
    assert checked >= 40  # the catalogs are actually populated


def test_fuzz_decode_garbage_never_crashes():
    """The C decoder parses UNTRUSTED wire bytes: any garbage must raise
    a Python exception (EOFError / Fallback / UnicodeDecodeError /
    MemoryError...), never crash the process."""
    import random

    rng = random.Random(0xC0DEC)
    for trial in range(3000):
        size = rng.randrange(0, 64)
        data = bytes(rng.randrange(256) for _ in range(size))
        try:
            C.decode(data)
        except Exception:
            pass  # any Python-level failure is fine


def test_fuzz_truncations_of_valid_wire():
    """Every prefix of a real message must fail cleanly, not crash."""
    msg = pm.CommandBatchRequest(
        session_id=3,
        entries=[(i, mo.InstanceCommand(i, ac.Set(value=i, ttl=None)))
                 for i in range(8)])
    wire = C.encode(msg)
    for cut in range(len(wire)):
        try:
            C.decode(wire[:cut])
        except Exception:
            pass


def _random_graph(rng, depth=0):
    kinds = ["int", "str", "bytes", "float", "none", "bool"]
    if depth < 3:
        kinds += ["list", "tuple", "dict", "set", "msg"]
    k = rng.choice(kinds)
    if k == "int":
        return rng.randrange(-2**62, 2**62)
    if k == "str":
        return "".join(chr(rng.randrange(32, 0x2FF))
                       for _ in range(rng.randrange(8)))
    if k == "bytes":
        return bytes(rng.randrange(256) for _ in range(rng.randrange(8)))
    if k == "float":
        return rng.uniform(-1e9, 1e9)
    if k == "none":
        return None
    if k == "bool":
        return rng.random() < 0.5
    if k == "list":
        return [_random_graph(rng, depth + 1)
                for _ in range(rng.randrange(4))]
    if k == "tuple":
        return tuple(_random_graph(rng, depth + 1)
                     for _ in range(rng.randrange(4)))
    if k == "dict":
        return {rng.randrange(1000): _random_graph(rng, depth + 1)
                for _ in range(rng.randrange(4))}
    if k == "set":
        return {rng.randrange(1000) for _ in range(rng.randrange(4))}
    return mo.InstanceCommand(rng.randrange(100),
                              ac.Set(value=rng.randrange(1000), ttl=None))


def test_fuzz_random_graphs_roundtrip_both_paths():
    import random

    rng = random.Random(7)
    for trial in range(300):
        obj = _random_graph(rng)
        wire = _py_write(obj)
        assert C.encode(obj) == wire, repr(obj)[:80]
        assert _py_write(C.decode(wire)) == wire, repr(obj)[:80]


# ---------------------------------------------------------------------------
# frame-burst walk (decode_frames / encode_frames): the TCP wire framing
# [u32 len][u8 kind][u64 corr][payload] walked in one C call per read
# burst — must match io/tcp.py's Python struct walk byte-for-byte.

import struct  # noqa: E402

_FRAME = struct.Struct(">IBQ")


def _py_frame(kind: int, corr: int, obj) -> bytes:
    payload = _py_write(obj)
    return _FRAME.pack(len(payload), kind, corr) + payload


def test_encode_frames_byte_identical_to_python_framing():
    burst = [(0, 1, mo.InstanceCommand(1, ac.Get())),
             (1, 2, [1, "two", None]),
             (2, 2**40, "TypeError: boom")]
    assert C.encode_frames(burst) == b"".join(
        _py_frame(k, co, o) for k, co, o in burst)


def test_decode_frames_walks_whole_burst():
    burst = [(0, i, mo.InstanceCommand(i, ac.Set(value=i, ttl=None)))
             for i in range(20)]
    wire = C.encode_frames(burst)
    frames, consumed = C.decode_frames(wire)
    assert consumed == len(wire)
    assert [(k, co) for k, co, _ in frames] == [(0, i) for i in range(20)]
    for (_, _, got), (_, _, sent) in zip(frames, burst):
        assert _py_write(got) == _py_write(sent)


def test_decode_frames_stops_at_torn_frame():
    whole = _py_frame(1, 7, "complete")
    torn = _py_frame(0, 8, ["partial", "frame"])
    for cut in range(1, len(torn)):
        frames, consumed = C.decode_frames(whole + torn[:cut])
        assert consumed == len(whole)
        assert len(frames) == 1 and frames[0][:2] == (1, 7)


def test_decode_frames_inexpressible_payload_raises_fallback():
    # a >64-bit int inside one frame aborts the WHOLE burst with
    # Fallback — io/tcp.py then re-walks it frame-by-frame in Python
    wire = _py_frame(1, 1, 1) + _py_frame(1, 2, 2**70)
    with pytest.raises(C.Fallback):
        C.decode_frames(wire)


def test_fuzz_decode_frames_garbage_never_crashes():
    import random

    rng = random.Random(0xF4A3E)
    real = C.encode_frames([(0, 5, mo.InstanceCommand(5, ac.Get()))])
    for trial in range(2000):
        if rng.random() < 0.5:
            data = bytes(rng.randrange(256)
                         for _ in range(rng.randrange(0, 48)))
        else:  # bit-flipped real frames: valid headers, corrupt payloads
            data = bytearray(real)
            for _ in range(rng.randrange(1, 4)):
                data[rng.randrange(len(data))] ^= 1 << rng.randrange(8)
            data = bytes(data)
        try:
            C.decode_frames(data)
        except Exception:
            pass  # any Python-level failure is fine; crashing is not


def test_frame_walk_fuzz_roundtrip_random_bursts():
    import random

    rng = random.Random(31)
    for trial in range(100):
        burst = [(rng.randrange(3), rng.randrange(2**63),
                  _random_graph(rng)) for _ in range(rng.randrange(1, 8))]
        wire = C.encode_frames(burst)
        assert wire == b"".join(_py_frame(*f) for f in burst)
        frames, consumed = C.decode_frames(wire)
        assert consumed == len(wire) and len(frames) == len(burst)
        for (k, co, got), (k0, co0, sent) in zip(frames, burst):
            assert (k, co) == (k0, co0)
            assert _py_write(got) == _py_write(sent)


def test_deep_nesting_falls_back_never_segfaults():
    """Unbounded recursion in the C walkers was a crash vector (found by
    fuzzing: 200k-deep nesting segfaulted; crafted deep WIRE bytes could
    crash decode from untrusted input). Past MAX_DEPTH both sides raise
    Fallback; the public Serializer then surfaces Python's clean
    RecursionError."""
    obj = 0
    for _ in range(5000):
        obj = [obj]
    with pytest.raises(C.Fallback):
        C.encode(obj)
    # zigzag(T_LIST)=14, zigzag(len=1)=2: a 5000-deep crafted wire graph
    wire = bytes([14, 2]) * 5000 + bytes([0])
    with pytest.raises(C.Fallback):
        C.decode(wire)
    with pytest.raises(RecursionError):
        _ser.write(obj)
    # shallow graphs still take the C fast path untouched
    assert C.decode(C.encode([[[1]]])) == [[[1]]]


# ---------------------------------------------------------------------------
# the fixed head: log entries (ids 230-235) on the C walk


def _f64_bits(value: float) -> bytes:
    return struct.pack(">d", value)          # NaN compares by its bits


def _same_entry(a, b) -> bool:
    return (type(a) is type(b) and a.index == b.index and a.term == b.term
            and _f64_bits(a.timestamp) == _f64_bits(b.timestamp)
            and _py_write(a) == _py_write(b))


def test_entry_classes_are_registered_with_the_head():
    from copycat_tpu.io import serializer as s

    head = (("index", "i64"), ("term", "i64"), ("timestamp", "f64"))
    for type_id in range(230, 236):
        cls = s._TYPE_REGISTRY[type_id]
        assert s._CODEC_HEAD[type_id] == head
        assert s._CODEC_FIELDS[type_id] == tuple(cls._fields)
        assert s._CODEC_OPTIONAL[type_id] == 0
    # nothing else has one, and a subclass that writes itself is custom
    assert set(s._CODEC_HEAD) == set(range(230, 236))

    class Own(sl.Entry):
        _fields = ("x",)

        def write_object(self, buf, serializer):
            buf.write_varint(7)

    assert s._fixed_head(Own) is None
    assert s._fixed_head(sl.CommandEntry) == head


@pytest.mark.parametrize(
    "entry", [e for e in ENTRY_CORPUS if isinstance(e, sl.Entry)],
    ids=lambda e: repr(e)[:40])
def test_entry_head_and_fields_survive_both_walks(entry):
    wire = C.encode(entry)
    assert wire == _py_write(entry)
    # tag, then the head exactly as BufferOutput writes it
    tag = BufferOutput().write_varint(
        16 + _ID_BY_TYPE[type(entry)]).to_bytes()
    assert wire.startswith(tag + struct.pack(
        ">qqd", entry.index, entry.term, entry.timestamp))
    for back in (C.decode(wire), _py_read(wire), _ser.read(_ser.write(entry))):
        assert _same_entry(back, entry)
        assert type(back.index) is int and type(back.timestamp) is float


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: type(e).__name__)
def test_fuzz_entry_truncations_and_garbage_never_crash(entry):
    import random

    wire = C.encode(entry)
    for cut in range(len(wire)):
        with pytest.raises(Exception):       # EOFError as a rule; no crash
            C.decode(wire[:cut])
    rng = random.Random(len(wire))
    for trial in range(400):
        data = bytearray(wire)
        for _ in range(rng.randrange(1, 4)):
            data[rng.randrange(len(data))] ^= 1 << rng.randrange(8)
        try:
            back = C.decode(bytes(data))
        except Exception:
            continue                         # any Python-level failure is fine
        # what still decodes is what the Python walk reads from those bytes
        assert _py_write(back) == _py_write(_py_read(bytes(data)))


def _np(name):
    return getattr(pytest.importorskip("numpy"), name)


@pytest.mark.parametrize("field,make", [
    ("timestamp", lambda: 5),                      # an int where a float goes
    ("timestamp", lambda: _np("float32")(1.5)),
    ("index", lambda: _np("int64")(12)),           # __index__, not an int
    ("term", lambda: _np("int32")(-3)),
], ids=["int-timestamp", "float32-timestamp", "int64-index", "int32-term"])
def test_head_the_c_walk_cannot_express_falls_back_and_roundtrips(field,
                                                                  make):
    entry = _command(0)
    setattr(entry, field, make())
    with pytest.raises(C.Fallback):
        C.encode(entry)
    with pytest.raises(C.Fallback):               # wherever the entry sits
        C.encode(pm.AppendRequest(term=1, leader=0, prev_index=0,
                                  prev_term=0, entries=[_command(1), entry],
                                  commit_index=0, global_index=0, fill_to=2,
                                  group=None, trace=None))
    wire = _ser.write(entry)                      # the public API: Python walk
    assert wire == _py_write(entry)
    back = _ser.read(wire)
    assert getattr(back, field) == make() and _py_write(back) == wire


@pytest.mark.parametrize("field,value", [
    ("index", 2**63), ("term", -(2**63) - 1), ("index", 2**70)])
def test_head_beyond_64_bits_raises_the_python_walks_own_error(field, value):
    entry = _command(0)
    setattr(entry, field, value)
    with pytest.raises(C.Fallback):
        C.encode(entry)
    with pytest.raises(struct.error):             # as before the C head
        _ser.write(entry)


def _fill(log):
    log.append(_entry(sl.RegisterEntry, term=1, timestamp=0.5,
                      client_id="c", timeout=5.0))
    log.append_block([_command(i, term=1) for i in range(40)])
    for entry in ENTRIES:
        log.append(_entry(type(entry), term=2, timestamp=entry.timestamp,
                          **{f: getattr(entry, f) for f in entry._fields}))
    log.sync()
    log.close()


def _segment_bytes(directory):
    return {name: open(os.path.join(directory, name), "rb").read()
            for name in sorted(os.listdir(directory))}


@pytest.mark.parametrize("level", ["DISK", "MAPPED"])
@pytest.mark.parametrize("writer", ["python", "c"])
def test_segments_written_by_one_walk_recover_under_the_other(
        tmp_path, monkeypatch, writer, level):
    def storage(name):
        directory = tmp_path / name
        directory.mkdir(exist_ok=True)
        return sl.Storage(sl.StorageLevel[level], str(directory),
                          max_entries_per_segment=16)

    def on_walk(walk, job):
        """Run ``job`` with the native codec on ("c") or forced off."""
        with monkeypatch.context() as patch:
            if walk == "python":
                patch.setattr(codec_mod, "_codec", None)
                patch.setattr(codec_mod, "_codec_err", "forced off by a test")
            assert (codec() is None) == (walk == "python")
            return job()

    reader = "c" if writer == "python" else "python"
    on_walk(writer, lambda: _fill(sl.Log(storage("one"), "log")))
    recovered = on_walk(reader, lambda: sl.Log(storage("one"), "log"))
    expected = sl.Log(sl.Storage(), "log")
    _fill(expected)
    assert recovered.last_index == expected.last_index == 47
    for index in range(1, 48):
        assert _same_entry(recovered.get(index), expected.get(index)), index
    recovered.close()
    # and the other walk writes the same files, byte for byte
    on_walk(reader, lambda: _fill(sl.Log(storage("two"), "log")))
    assert _segment_bytes(tmp_path / "one") == _segment_bytes(tmp_path / "two")


def _native_copy(tmp_path):
    for name in ("Makefile", "copycat_codec.c"):
        shutil.copy(codec_mod._NATIVE_DIR / name, tmp_path / name)
    return tmp_path / "copycat_codec.so"


def test_a_stale_binary_is_rebuilt_before_it_is_loaded(tmp_path):
    """A binary that does not state the ABI this Python needs (here the
    real one, restamped as its predecessor and NEWER than the source)
    would write entries as plain fields: it is rebuilt, never loaded."""
    so_path = _native_copy(tmp_path)
    current = (codec_mod._NATIVE_DIR / "copycat_codec.so").read_bytes()
    marker = b"copycat_codec_abi=%d\0" % codec_mod._ABI
    assert current.count(marker) == 1
    so_path.write_bytes(current.replace(marker, b"copycat_codec_abi=1\0"))
    future = (tmp_path / "copycat_codec.c").stat().st_mtime + 3600
    os.utime(so_path, (future, future))
    assert not codec_mod._states_abi(so_path)
    mod = codec_mod._build_and_load(tmp_path)
    assert mod.ABI == codec_mod._ABI and codec_mod._states_abi(so_path)
    assert mod.__file__ == str(so_path) and mod is not C


@pytest.mark.parametrize("missing", ["Makefile", "copycat_codec.c"])
def test_a_stale_binary_that_cannot_be_rebuilt_is_refused_aloud(
        tmp_path, monkeypatch, missing):
    so_path = _native_copy(tmp_path)
    (tmp_path / missing).unlink()                 # no way to build here
    so_path.write_bytes(b"\x7fELF a binary from before the fixed head")
    with pytest.raises(codec_mod.StaleCodecError, match="codec ABI 2"):
        codec_mod._build_and_load(tmp_path)
    assert not codec_mod._states_abi(so_path)
    # codec() then runs without the extension and says so
    monkeypatch.setattr(codec_mod, "_codec", None)
    monkeypatch.setattr(codec_mod, "_codec_err", None)
    build = codec_mod._build_and_load
    monkeypatch.setattr(codec_mod, "_build_and_load", lambda: build(tmp_path))
    with pytest.warns(RuntimeWarning, match="native codec refused"):
        assert codec() is None
    assert "codec ABI 2" in codec_mod.codec_error()
    entry = _command(0)
    assert _ser.write(entry) == _py_write(entry)   # the Python walk answers


def test_python_bodies_counts_only_what_leaves_the_c_walk():
    from copycat_tpu.utils import tracing

    counter = codec_mod.METRICS.counter("python_bodies")
    entries = [_command(i) for i in range(1000)]
    request = pm.AppendRequest(term=3, leader=0, prev_index=0, prev_term=0,
                               entries=entries[:64], commit_index=0,
                               global_index=0, fill_to=64, group=None,
                               trace=None)
    tracing.TRACER.clear()
    tracing.enable()
    try:
        before = counter.value
        for entry in entries:
            assert _same_entry(_ser.read(_ser.write(entry)), entry)
        assert len(_ser.read(_ser.write(request)).entries) == 64
        assert counter.value == before             # 1,064 entries, all in C
        assert _ser.read(_ser.write(Address("host", 1))) == Address("host", 1)
        assert counter.value == before + 2         # one body each way
        assert tracing.TRACER.report()["counters"]["codec.python_bodies"] == 2
    finally:
        tracing.disable()
        tracing.TRACER.clear()

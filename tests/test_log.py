"""Log storage-level tests (server/log.py).

The reference Storage contract exposes three levels (SURVEY.md §2.3 storage
row); MAPPED is a distinct path — mmap-backed segments whose recovery trusts
a persisted watermark — not an alias of DISK's buffered+flushed files.
"""

import os
import shutil
import zlib

import pytest

from copycat_tpu.io.buffer import BufferOutput
from copycat_tpu.io.serializer import Serializer
from copycat_tpu.server.log import (
    CommandEntry,
    Log,
    NoOpEntry,
    Storage,
    StorageLevel,
)


def _fill(log: Log, n: int, term: int = 1) -> None:
    for i in range(n):
        log.append(CommandEntry(term=term, timestamp=float(i),
                                session_id=7, seq=i, operation=f"op-{i}"))


def _segments(directory: str, ext: str) -> list[str]:
    return sorted(f for f in os.listdir(directory) if f.endswith("." + ext))


def test_disk_recover_roundtrip(tmp_path):
    storage = Storage(StorageLevel.DISK, str(tmp_path), max_entries_per_segment=4)
    log = storage.build_log()
    _fill(log, 10)
    log.close()
    assert len(_segments(str(tmp_path), "seg")) >= 3
    assert not _segments(str(tmp_path), "mseg")

    recovered = storage.build_log()
    assert recovered.last_index == 10
    assert recovered.get(3).operation == "op-2"


def test_mapped_recover_roundtrip(tmp_path):
    storage = Storage(StorageLevel.MAPPED, str(tmp_path), max_entries_per_segment=4)
    log = storage.build_log()
    _fill(log, 10)
    log.append(NoOpEntry(term=2, timestamp=10.0))
    log.close()
    # distinct on-disk format, rolled by entry count
    assert len(_segments(str(tmp_path), "mseg")) >= 3
    assert not _segments(str(tmp_path), "seg")

    recovered = storage.build_log()
    assert recovered.last_index == 11
    assert recovered.get(5).operation == "op-4"
    assert recovered.term_at(11) == 2
    assert recovered.term_at(4) == 1


def test_mapped_truncate_then_reopen(tmp_path):
    storage = Storage(StorageLevel.MAPPED, str(tmp_path), max_entries_per_segment=4)
    log = storage.build_log()
    _fill(log, 9)
    log.truncate(5)  # follower conflict resolution: drop [5..9]
    log.append(CommandEntry(term=3, timestamp=9.0, session_id=7, seq=99,
                            operation="new-5"))
    log.close()

    recovered = storage.build_log()
    assert recovered.last_index == 5
    assert recovered.get(5).operation == "new-5"
    assert recovered.get(5).term == 3
    assert recovered.get(4).operation == "op-3"


def test_mapped_watermark_bounds_torn_tail(tmp_path):
    """Garbage past the watermark (a torn post-crash frame) is not observed."""
    storage = Storage(StorageLevel.MAPPED, str(tmp_path), max_entries_per_segment=64)
    log = storage.build_log()
    _fill(log, 5)
    log.close()
    (path,) = (os.path.join(str(tmp_path), f)
               for f in _segments(str(tmp_path), "mseg"))
    with open(path, "r+b") as f:
        used = int.from_bytes(f.read(8), "little")
        f.seek(8 + used)
        f.write(b"\xde\xad\xbe\xef" * 8)  # torn bytes inside the capacity

    recovered = storage.build_log()
    assert recovered.last_index == 5
    assert recovered.get(5).operation == "op-4"


def test_mapped_oversize_frame_gets_own_segment(tmp_path):
    storage = Storage(StorageLevel.MAPPED, str(tmp_path), max_entries_per_segment=64)
    log = storage.build_log()
    big = "x" * (Log.MAPPED_SEGMENT_BYTES + 1024)
    log.append(CommandEntry(term=1, timestamp=0.0, session_id=1, seq=0,
                            operation="small"))
    log.append(CommandEntry(term=1, timestamp=1.0, session_id=1, seq=1,
                            operation=big))
    log.close()
    assert len(_segments(str(tmp_path), "mseg")) == 2

    recovered = storage.build_log()
    assert recovered.get(2).operation == big


def test_mapped_crc_bounds_reordered_writeback(tmp_path):
    """Kernel writeback may flush the watermark page before the tail frame's
    pages; recovery must CRC-reject the unwritten (zeroed) tail frame and
    keep everything before it."""
    storage = Storage(StorageLevel.MAPPED, str(tmp_path), max_entries_per_segment=64)
    log = storage.build_log()
    _fill(log, 6)
    log.close()
    (path,) = (os.path.join(str(tmp_path), f)
               for f in _segments(str(tmp_path), "mseg"))
    # Simulate the torn state: watermark says 6 frames are valid, but the
    # last frame — HEADER PAGE INCLUDED — never hit the disk. The all-zero
    # header must not validate (crc32(b"")==0 would, without the seed).
    with open(path, "r+b") as f:
        used = int.from_bytes(f.read(8), "little")
        f.seek(8 + used - (used // 6))       # start of the last frame
        f.write(b"\x00" * (used // 6))

    recovered = storage.build_log()
    assert recovered.last_index == 5          # torn frame 6 dropped
    assert recovered.get(5).operation == "op-4"

    # Payload-only tear (header survived, payload pages did not).
    storage2 = Storage(StorageLevel.MAPPED, str(tmp_path) + "2",
                       max_entries_per_segment=64)
    log2 = storage2.build_log()
    _fill(log2, 6)
    log2.close()
    (path2,) = (os.path.join(str(tmp_path) + "2", f)
                for f in _segments(str(tmp_path) + "2", "mseg"))
    with open(path2, "r+b") as f:
        used = int.from_bytes(f.read(8), "little")
        f.seek(8 + used - (used // 6) + 8)   # past the last frame's header
        f.write(b"\x00" * (used // 6 - 8))
    recovered2 = storage2.build_log()
    assert recovered2.last_index == 5
    assert recovered2.get(5).operation == "op-4"


def test_append_replicated_block_matches_per_entry():
    """The follower's block ingest must land the exact structure the
    per-entry append_replicated walk produced: same entries, same gap
    slots, same term boundaries (term_at over compacted slots)."""

    def entries():
        out = []
        for i, (index, term) in enumerate(
                [(1, 1), (2, 1), (4, 2), (5, 2), (8, 3)]):  # gaps at 3, 6-7
            e = CommandEntry(term=term, timestamp=float(i), session_id=1,
                             seq=i + 1, operation=f"op-{index}")
            e.index = index
            out.append(e)
        return out

    per_entry = Storage(StorageLevel.MEMORY).build_log()
    for e in entries():
        per_entry.append_replicated(e)
    block = Storage(StorageLevel.MEMORY).build_log()
    block.append_replicated_block(entries())

    assert block.last_index == per_entry.last_index == 8
    for i in range(1, 9):
        a, b = per_entry.get(i), block.get(i)
        assert (a is None) == (b is None), i
        if a is not None:
            assert (a.index, a.term, a.operation) == \
                (b.index, b.term, b.operation), i
        assert per_entry.term_at(i) == block.term_at(i), i


def test_append_replicated_block_continues_existing_log():
    log = Storage(StorageLevel.MEMORY).build_log()
    _fill(log, 3)
    tail = []
    for index in (5, 6):  # gap at 4 (compacted on the leader)
        e = NoOpEntry(term=2, timestamp=float(index))
        e.index = index
        tail.append(e)
    log.append_replicated_block(tail)
    assert log.last_index == 6
    assert log.get(4) is None
    assert log.term_at(6) == 2
    assert log.term_at(2) == 1
    log.append_replicated_block([])  # no-op, not an error


def test_append_replicated_block_persists(tmp_path):
    storage = Storage(StorageLevel.MAPPED, str(tmp_path),
                      max_entries_per_segment=4)
    log = storage.build_log()
    block = []
    for index in range(1, 11):
        e = CommandEntry(term=1, timestamp=float(index), session_id=1,
                         seq=index, operation=f"op-{index}")
        e.index = index
        block.append(e)
    log.append_replicated_block(block)
    log.close()
    recovered = storage.build_log()
    assert recovered.last_index == 10
    assert recovered.get(7).operation == "op-7"


def test_recover_reopens_last_segment_no_small_segment_buildup(tmp_path):
    """Repeated restarts must not roll one near-empty segment per run: the
    newest segment is reopened for continued appends (DISK via append mode,
    MAPPED via watermark-resumed mmap) when it still has entry budget."""
    for level, ext in ((StorageLevel.DISK, "seg"), (StorageLevel.MAPPED, "mseg")):
        directory = str(tmp_path / ext)
        os.makedirs(directory)
        storage = Storage(level, directory, max_entries_per_segment=8)
        log = storage.build_log()
        _fill(log, 3)
        log.close()
        before = len(_segments(directory, ext))
        for _ in range(3):  # restart + append, 3 times
            log = storage.build_log()
            _fill(log, 1, term=2)
            log.close()
        assert len(_segments(directory, ext)) == before, ext
        recovered = storage.build_log()
        assert recovered.last_index == 6, ext
        assert recovered.get(6).term == 2, ext
        assert recovered.get(2).operation == "op-1", ext


# -- the block writer (Log._persist_block) ---------------------------------
#
# One write path: append_block and append_replicated_block hand the segment
# file a whole stretch at once, append/append_replicated/set_slot a stretch
# of one. The files must not be able to tell.


def _commands(indices, term=1):
    """Fresh CommandEntry objects stamped with ``indices`` (payloads of
    different lengths, so frame boundaries do not fall on a grid)."""
    out = []
    for i in indices:
        e = CommandEntry(term=term, timestamp=float(i), session_id=7,
                         seq=i, operation="op-" + "x" * (i % 5) + str(i))
        e.index = i
        out.append(e)
    return out


def _frame(entry) -> bytes:
    """The DISK frame written out plainly, independent of Log:
    [varint len][payload][varint crc32(payload, 0xA5C6)]."""
    data = Serializer().write(entry)
    return (BufferOutput().write_bytes(data)
            .write_varint(zlib.crc32(data, 0xA5C6)).to_bytes())


def _plain_segments(entries, limit) -> dict[str, bytes]:
    """What a directory holds after ``entries`` by the format's own rules:
    a segment takes ``limit`` frames and is named by the entry that
    opened it."""
    files: dict[str, bytes] = {}
    name, count = None, 0
    for e in entries:
        if name is None or count >= limit:
            name, count = f"log-{e.index}.seg", 0
            files[name] = b""
        files[name] += _frame(e)
        count += 1
    return files


def _read_dir(directory) -> dict[str, bytes]:
    out = {}
    for fname in sorted(os.listdir(directory)):
        with open(os.path.join(directory, fname), "rb") as f:
            out[fname] = f.read()
    return out


def _shape(log: Log) -> list:
    return [(e.index, e.term, e.seq, e.operation) if e is not None else None
            for e in (log.get(i) for i in range(log.first_index,
                                                log.last_index + 1))]


@pytest.mark.parametrize("fsync", ["never", "commit", "always"])
@pytest.mark.parametrize("limit,held,block,rolls", [
    (4, 2, 2, 0),        # fills the open segment to its brim: no roll
    (4, 2, 5, 1),
    (4, 0, 14, 4),       # opens the first segment too: 4 + 4 + 4 + 2
    (1024, 2, 64, 0),    # the cell's segment size, a follower's window
    (1024, 1000, 64, 1),
], ids=["4-none", "4-one", "4-several", "1024-none", "1024-one"])
@pytest.mark.parametrize("method", ["append_block", "append_replicated_block"])
def test_block_writer_leaves_the_files_of_entry_by_entry_appends(
        tmp_path, monkeypatch, method, limit, held, block, rolls, fsync):
    """Names, bytes, every fsync's reach, ``synced_tail`` after a
    ``sync()`` and the recovered log are those of one-entry appends of the
    same entries, and of the format written out plainly; ``log.writes``
    says the block went down in one write a segment stretch (one an entry
    where ``fsync="always"`` promises an fsync an entry)."""
    replicated = method == "append_replicated_block"
    # a follower's block skips a slot the leader compacted (never persisted)
    gap = held + block // 2 + 1 if replicated else None
    indices = [i for i in range(1, held + block + 2) if i != gap][:held + block]

    reached: list[int] = []   # bytes the kernel holds of the file, per fsync
    monkeypatch.setattr(os, "fsync",
                        lambda fd: reached.append(os.fstat(fd).st_size))

    def run(directory, in_block):
        del reached[:]
        storage = Storage(StorageLevel.DISK, str(directory),
                          max_entries_per_segment=limit, fsync=fsync)
        log = storage.build_log()
        entries = _commands(indices)
        one = log.append_replicated if replicated else log.append
        for e in entries[:held]:
            one(e)
        if in_block:
            getattr(log, method)(entries[held:])
        else:
            for e in entries[held:]:
                one(e)
        log.sync()
        tail = log.synced_tail
        log.close()
        return (log, list(reached),
                tail and (os.path.basename(tail[0]), tail[1]), storage)

    by_entry, by_entry_fsyncs, by_entry_tail, _ = run(tmp_path / "entry", False)
    by_block, by_block_fsyncs, by_block_tail, storage = run(tmp_path / "block", True)

    files = _read_dir(tmp_path / "block")
    assert files == _read_dir(tmp_path / "entry")
    assert files == _plain_segments(_commands(indices), limit)
    assert len(files) == (held + block + limit - 1) // limit
    assert by_block_fsyncs == by_entry_fsyncs   # no sync moved, merged, dropped
    assert by_block_tail == by_entry_tail
    last = sorted(files, key=lambda n: int(n[4:-4]))[-1]
    assert by_block_tail == (last, len(files[last]))
    assert by_block.bytes_appended.value == by_entry.bytes_appended.value \
        == sum(len(b) for b in files.values())
    assert _shape(by_block) == _shape(by_entry)

    assert by_entry.writes.value == held + block
    # the segments the block lands in: one it rolls to, and the open one
    # where the held entries left it room
    stretches = rolls + (1 if held % limit else 0)
    assert by_block.writes.value == held + (
        block if fsync == "always" else stretches)

    recovered = storage.build_log()
    assert _shape(recovered) == _shape(by_block)
    assert recovered.last_index == indices[-1]
    if replicated:
        assert recovered.get(gap) is None
    recovered.close()


def test_truncate_rewrites_the_survivors_as_one_block(tmp_path):
    """A follower's conflict truncation rewrites its segments through the
    same writer: one write a segment, the same files as fresh appends."""
    storage = Storage(StorageLevel.DISK, str(tmp_path),
                      max_entries_per_segment=4)
    log = storage.build_log()
    log.append_block(_commands(range(1, 12)))
    before = log.writes.value
    log.truncate(10)
    assert log.writes.value - before == 3   # 4 + 4 + 1 entries
    log.close()
    assert _read_dir(tmp_path) == _plain_segments(_commands(range(1, 10)), 4)


def test_mapped_block_counts_a_write_a_frame(tmp_path):
    """MAPPED has no system call an entry to win: a block is copied into
    the mapping frame by frame, and ``log.writes`` counts the copies."""
    log = Storage(StorageLevel.MAPPED, str(tmp_path),
                  max_entries_per_segment=4).build_log()
    log.append_block(_commands(range(1, 11)))
    assert log.writes.value == 10
    assert len(_segments(str(tmp_path), "mseg")) == 3
    log.close()


@pytest.mark.parametrize("method", ["append_block", "append_replicated_block"])
def test_torn_block_recovers_the_whole_frames_before_the_cut(tmp_path, method):
    """A block goes down in one write, so a crash can tear it anywhere:
    cut at every byte inside its last two frames, the segment recovers
    exactly the frames that are whole (the seeded CRC bounds a torn block
    as it bounds a torn entry) and is trimmed back to them."""
    storage = Storage(StorageLevel.DISK, str(tmp_path / "whole"))
    log = storage.build_log()
    entries = _commands(range(1, 8))
    getattr(log, method)(entries)
    assert log.writes.value == 1
    log.close()
    ends = [0]
    for e in entries:
        ends.append(ends[-1] + len(_frame(e)))
    with open(tmp_path / "whole" / "log-1.seg", "rb") as f:
        assert len(f.read()) == ends[-1]
    for cut in range(ends[-3] + 1, ends[-1]):
        torn = tmp_path / f"cut-{cut}"
        shutil.copytree(tmp_path / "whole", torn)
        os.truncate(torn / "log-1.seg", cut)
        recovered = Storage(StorageLevel.DISK, str(torn)).build_log()
        whole = 5 if cut < ends[-2] else 6
        assert recovered.last_index == whole, cut
        assert _shape(recovered) == [
            (e.index, e.term, e.seq, e.operation) for e in entries[:whole]]
        assert os.path.getsize(torn / "log-1.seg") == ends[whole], cut
        recovered.close()


@pytest.mark.parametrize("new_segment", ["missing", "empty", "torn"])
def test_crash_between_a_mid_block_roll_and_the_rest(tmp_path, new_segment):
    """A block that crosses a roll closes (and fsyncs) the full segment
    before the rest goes into the next one. A crash in between, with the
    new segment not yet created, created and empty, or holding half a
    frame, recovers the closed segment whole, and appends go on."""
    storage = Storage(StorageLevel.DISK, str(tmp_path),
                      max_entries_per_segment=4)
    log = storage.build_log()
    log.append_block(_commands(range(1, 8)))
    log.close()
    assert sorted(os.listdir(tmp_path)) == ["log-1.seg", "log-5.seg"]
    closed = _read_dir(tmp_path)["log-1.seg"]
    if new_segment == "missing":
        os.remove(tmp_path / "log-5.seg")
    else:
        os.truncate(tmp_path / "log-5.seg", 0 if new_segment == "empty" else 7)
    recovered = storage.build_log()
    assert recovered.last_index == 4
    assert _shape(recovered) == [
        (e.index, e.term, e.seq, e.operation) for e in _commands(range(1, 5))]
    recovered.append_block(_commands(range(5, 7)))
    recovered.close()
    assert _read_dir(tmp_path) == {
        "log-1.seg": closed,
        "log-5.seg": b"".join(_frame(e) for e in _commands(range(5, 7)))}

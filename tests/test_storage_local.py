"""Local storage engine: version journal, atomic commits, walk, bitrot framing."""

import os

import numpy as np
import pytest

from minio_tpu.storage import bitrot
from minio_tpu.storage.local import (LocalStorage, StorageError, VolumeExists,
                                     VolumeNotFound)
from minio_tpu.storage.meta import (ErasureInfo, FileInfo, FileNotFoundErr,
                                    ObjectPartInfo, VersionNotFoundErr,
                                    XLMeta, new_uuid, now_ns)


@pytest.fixture
def disk(tmp_path):
    return LocalStorage(str(tmp_path / "drive0"))


def _fi(name="obj", vid="", data_dir="", size=0, deleted=False, mod_time=None):
    return FileInfo(volume="bkt", name=name, version_id=vid,
                    data_dir=data_dir, size=size, deleted=deleted,
                    mod_time=mod_time if mod_time is not None else now_ns(),
                    erasure=ErasureInfo(data_blocks=2, parity_blocks=2,
                                        block_size=1 << 20, index=1,
                                        distribution=(1, 2, 3, 4)))


class TestVolumes:
    def test_make_list_stat_delete(self, disk):
        disk.make_vol("bkt")
        with pytest.raises(VolumeExists):
            disk.make_vol("bkt")
        assert [v.name for v in disk.list_vols()] == ["bkt"]
        assert disk.stat_vol("bkt").name == "bkt"
        disk.delete_vol("bkt")
        with pytest.raises(VolumeNotFound):
            disk.stat_vol("bkt")

    def test_sys_volume_hidden(self, disk):
        assert disk.list_vols() == []

    def test_invalid_names(self, disk):
        for bad in ("", ".", "..", "a/b"):
            with pytest.raises(StorageError):
                disk.make_vol(bad)


class TestMetaJournal:
    def test_roundtrip(self):
        xl = XLMeta()
        fi = _fi(vid=new_uuid(), data_dir=new_uuid(), size=123)
        fi.parts = [ObjectPartInfo(number=1, size=123, actual_size=123)]
        xl.add_version(fi)
        xl2 = XLMeta.load(xl.dump())
        got = xl2.to_fileinfo("bkt", "obj", fi.version_id)
        assert got.size == 123
        assert got.erasure.data_blocks == 2
        assert got.parts[0].number == 1
        assert got.is_latest

    def test_latest_ordering_and_delete_marker(self):
        xl = XLMeta()
        v1, v2 = new_uuid(), new_uuid()
        xl.add_version(_fi(vid=v1, mod_time=100))
        xl.add_version(_fi(vid=v2, mod_time=200))
        xl.add_version(_fi(vid="", deleted=True, mod_time=300))
        latest = xl.to_fileinfo("bkt", "obj")
        assert latest.deleted and latest.is_latest
        old = xl.to_fileinfo("bkt", "obj", v1)
        assert not old.deleted and not old.is_latest

    def test_inline_data(self):
        xl = XLMeta()
        fi = _fi(vid=new_uuid())
        fi.inline_data = b"shardbytes"
        xl.add_version(fi)
        xl2 = XLMeta.load(xl.dump())
        assert xl2.to_fileinfo("b", "o", fi.version_id, read_data=True).inline_data == b"shardbytes"
        # Without read_data the marker is an empty-bytes sentinel.
        assert xl2.to_fileinfo("b", "o", fi.version_id).inline_data == b""


class TestVersionedStorage:
    def test_write_read_delete_version(self, disk):
        disk.make_vol("bkt")
        vid = new_uuid()
        disk.write_metadata("bkt", "a/b/obj", _fi(vid=vid, size=7))
        got = disk.read_version("bkt", "a/b/obj")
        assert got.version_id == vid and got.size == 7
        disk.delete_version("bkt", "a/b/obj", vid)
        with pytest.raises(FileNotFoundErr):
            disk.read_version("bkt", "a/b/obj")
        # empty parents cleaned up
        assert not os.path.exists(os.path.join(disk.root, "bkt", "a"))

    def test_rename_data_commit(self, disk):
        disk.make_vol("bkt")
        ddir = new_uuid()
        staging = f"staging-{new_uuid()}"
        disk.create_file(".mtpu.sys", f"{staging}/{ddir}/part.1", b"SHARD")
        fi = _fi(vid=new_uuid(), data_dir=ddir, size=5)
        disk.rename_data(".mtpu.sys", staging, fi, "bkt", "obj")
        got = disk.read_version("bkt", "obj")
        assert got.data_dir == ddir
        assert disk.read_file("bkt", f"obj/{ddir}/part.1") == b"SHARD"
        # staging dir gone
        assert not os.path.exists(os.path.join(disk.root, ".mtpu.sys", staging))

    def test_nested_objects_coexist(self, disk):
        disk.make_vol("bkt")
        disk.write_metadata("bkt", "a", _fi(name="a", vid=new_uuid()))
        disk.write_metadata("bkt", "a/b", _fi(name="a/b", vid=new_uuid()))
        assert disk.read_version("bkt", "a").name == "a"
        assert disk.read_version("bkt", "a/b").name == "a/b"

    def test_walk_dir(self, disk):
        disk.make_vol("bkt")
        names = ["z", "a/1", "a/2", "m/x/deep"]
        for n in names:
            disk.write_metadata("bkt", n, _fi(name=n, vid=new_uuid()))
        # staged uuid data dir inside an object must not appear
        ddir = new_uuid()
        disk.create_file("bkt", f"a/1/{ddir}/part.1", b"x")
        walked = [p for p, _ in disk.walk_dir("bkt")]
        assert walked == sorted(names)

    def test_update_metadata_missing_version(self, disk):
        disk.make_vol("bkt")
        disk.write_metadata("bkt", "o", _fi(vid=new_uuid()))
        with pytest.raises(VersionNotFoundErr):
            disk.update_metadata("bkt", "o", _fi(vid=new_uuid()))


class TestBitrotFraming:
    def test_frame_and_read_roundtrip(self):
        rng = np.random.default_rng(3)
        shard = rng.integers(0, 256, size=10_000, dtype=np.uint8)
        blob = bitrot.frame_shard(shard, shard_size=4096)
        assert len(blob) == bitrot.shard_file_size(10_000, 4096)
        r = bitrot.FramedShardReader(blob, 4096, 10_000)
        got = np.concatenate([r.block(i) for i in range(3)])
        assert np.array_equal(got, shard)

    def test_batch_framing_matches_single(self):
        rng = np.random.default_rng(4)
        shards = rng.integers(0, 256, size=(6, 5000), dtype=np.uint8)
        batch = bitrot.frame_shards_batch(shards, shard_size=2048)
        for i in range(6):
            assert batch[i] == bitrot.frame_shard(shards[i], 2048)

    def test_corruption_detected(self):
        shard = np.arange(5000, dtype=np.int32).astype(np.uint8)
        blob = bytearray(bitrot.frame_shard(shard, shard_size=2048))
        blob[40] ^= 0xFF  # flip a data byte in block 0
        r = bitrot.FramedShardReader(bytes(blob), 2048, 5000)
        with pytest.raises(bitrot.BitrotError):
            r.block(0)
        r.block(1)  # other blocks still verify

    def test_whole_file_algorithms_unframed(self):
        assert bitrot.shard_file_size(100, 10, bitrot.SHA256) == 100


def test_create_file_odirect_roundtrip(tmp_path):
    """Streaming shard writes ride O_DIRECT with aligned bulk + ragged
    tail (reference: cmd/xl-storage.go:2147 writeAllDirect); bytes read
    back identical for aligned, unaligned and multi-chunk shapes."""
    from minio_tpu.storage import local as local_mod
    d = local_mod.LocalStorage(str(tmp_path / "od"))
    d.make_vol("v")
    cases = [
        [b"x" * 4096],                       # exactly one block
        [b"y" * (1 << 20), b"z" * 133],      # big + ragged tail
        [b"a" * 100],                        # tail-only
        [b"b" * 5000, b"c" * 7000, b"d" * 3],
        [],                                  # empty
    ]
    for i, chunks in enumerate(cases):
        d.create_file("v", f"f{i}", iter(chunks))
        want = b"".join(chunks)
        assert d.read_file("v", f"f{i}") == want, f"case {i}"


def test_create_file_falls_back_without_odirect(tmp_path, monkeypatch):
    from minio_tpu.storage import local as local_mod
    monkeypatch.setattr(local_mod, "O_DIRECT_ENABLED", False)
    d = local_mod.LocalStorage(str(tmp_path / "nod"))
    d.make_vol("v")
    d.create_file("v", "f", iter([b"q" * 9999]))
    assert d.read_file("v", "f") == b"q" * 9999


def test_read_file_odirect_matches_buffered(tmp_path, monkeypatch):
    """Bulk reads mirror the O_DIRECT write path: byte-identical to the
    buffered path across aligned/unaligned offsets and lengths, at EOF,
    and for whole-file reads (length=-1)."""
    import os as _os

    from minio_tpu.storage import local as local_mod
    d = local_mod.LocalStorage(str(tmp_path / "odr"))
    d.make_vol("v")
    blob = bytes(range(256)) * ((3 << 20) // 256) + b"tail" * 33
    d.create_file("v", "f", blob)
    # Force the direct path by dropping the size floor; every case
    # must match the buffered result exactly (including EOF clamps).
    monkeypatch.setattr(local_mod.LocalStorage, "_DIRECT_READ_MIN", 1)
    cases = [(0, len(blob)), (0, -1), (4096, 1 << 20),
             (4097, (1 << 20) + 13), (123, 456789),
             (len(blob) - 100, 100), (len(blob) - 7, 999),
             (0, len(blob) + 5000)]
    for off, ln in cases:
        got = d.read_file("v", "f", offset=off, length=ln)
        want = blob[off:] if ln < 0 else blob[off:off + ln]
        assert got == want, (off, ln, len(got), len(want))
    # The direct opener actually engaged (or cleanly fell back) —
    # either way behavior is identical; exercise fallback explicitly.
    monkeypatch.setattr(local_mod, "O_DIRECT_ENABLED", False)
    assert d.read_file("v", "f", offset=11, length=1 << 20) == \
        blob[11:11 + (1 << 20)]


def test_read_file_odirect_missing_file_raises(tmp_path):
    from minio_tpu.storage import local as local_mod
    from minio_tpu.storage.meta import FileNotFoundErr
    d = local_mod.LocalStorage(str(tmp_path / "odm"))
    d.make_vol("v")
    with pytest.raises(FileNotFoundErr):
        d.read_file("v", "nope", offset=0, length=4 << 20)


# -- the shard stream's stages and counters (PR 32) ------------------------------

import errno  # noqa: E402
import threading  # noqa: E402

from minio_tpu.storage import local as local_mod  # noqa: E402
from minio_tpu.utils import tracing  # noqa: E402

MiB = 1 << 20
STREAM = "disk.stream"
PARTS = ("disk.stream.open", "disk.stream.row_wait", "disk.stream.write",
         "disk.stream.sync")
# 2 MiB + 300,133 bytes: two full bounce buffers, an aligned rest, a tail
CHUNKS = [b"a" * MiB, b"b" * MiB, b"c" * 300_000, b"d" * 133]


def stages_since(before: dict) -> dict:
    """{stage: (wall s, entries)} gained since `before`."""
    out = {}
    for name, (wall, _, n) in tracing.stage_totals().items():
        w0, _, n0 = before.get(name, (0.0, 0.0, 0))
        if n > n0:
            out[name] = (wall - w0, n - n0)
    return out


def streams_since(before: dict) -> dict:
    now = local_mod.STREAM_STATS.snapshot()["streams"]
    return {m: now[m] - before["streams"][m] for m in now
            if now[m] != before["streams"][m]}


def in_flight() -> tuple:
    snap = local_mod.STREAM_STATS.snapshot()
    return snap["streams_open"], snap["syncs_in_flight"]


IDLE = (0, {"shard": 0, "meta": 0})


class DirectFds:
    """Stands under `os.open` and `os.write`, passing everything
    through, so that a test can say what the mount does with O_DIRECT
    whatever filesystem the suite runs on: `open` never refuses the
    flag (it is retried without), and the nth `os.write` to a file that
    was opened with it fails with `fail_with`."""

    def __init__(self, monkeypatch, fail_write: int = 0,
                 fail_with: int = errno.EIO):
        self.fds: dict = {}             # fd -> writes so far
        self.fail_write, self.fail_with = fail_write, fail_with
        real_open, real_write = os.open, os.write

        def open_(path, flags, *a, **kw):
            if not flags & os.O_DIRECT:
                return real_open(path, flags, *a, **kw)
            try:
                fd = real_open(path, flags, *a, **kw)
            except OSError:
                fd = real_open(path, flags & ~os.O_DIRECT, *a, **kw)
            self.fds[fd] = 0
            return fd

        def write(fd, data):
            if fd in self.fds:
                self.fds[fd] += 1
                if self.fds[fd] == self.fail_write:
                    raise OSError(self.fail_with, "injected")
            return real_write(fd, data)

        monkeypatch.setattr(os, "open", open_)
        monkeypatch.setattr(os, "write", write)


def expected_writes(mode: str, chunks) -> int:
    """direct: one write a full bounce buffer, one for the aligned rest,
    one for the ragged tail; buffered: one a chunk."""
    if mode == "buffered":
        return len(chunks)
    total = sum(map(len, chunks))
    aligned = total // 4096 * 4096
    return -(-aligned // MiB) + (1 if total > aligned else 0)


@pytest.mark.parametrize("form", ["iterator", "bytes"])
@pytest.mark.parametrize("o_direct", [True, False], ids=["direct", "buffered"])
def test_one_stream_enters_each_stage_the_counted_number_of_times(
        tmp_path, monkeypatch, o_direct, form):
    monkeypatch.setattr(local_mod, "O_DIRECT_ENABLED", o_direct)
    d = LocalStorage(str(tmp_path / "d"))
    d.make_vol("v")
    chunks = CHUNKS if form == "iterator" else [b"".join(CHUNKS)]
    before, stats = tracing.stage_totals(), local_mod.STREAM_STATS.snapshot()
    d.create_file("v", "f", iter(chunks) if form == "iterator" else chunks[0])
    got = stages_since(before)
    modes = streams_since(stats)
    assert sum(modes.values()) == 1
    (mode,) = modes
    if not o_direct or form == "bytes":
        assert mode == "buffered"
    assert got[STREAM][1] == got["disk.stream.open"][1] == 1
    assert got["disk.stream.sync"][1] == 1
    assert got["disk.stream.write"][1] == expected_writes(mode, chunks)
    assert "disk.stream.row_wait" not in got      # no queue to wait on
    assert "disk.meta.sync" not in got
    # one after the other inside the whole: together no longer than it
    assert sum(got[p][0] for p in PARTS if p in got) <= got[STREAM][0]
    assert d.read_file("v", "f") == b"".join(chunks)
    after = local_mod.STREAM_STATS.snapshot()
    assert after["sync_hist"]["shard"]["count"] \
        - stats["sync_hist"]["shard"]["count"] == 1
    assert in_flight() == IDLE


@pytest.mark.parametrize("forced", ["direct", "direct_dropped",
                                    "open_refused", "switched_off"])
def test_streams_total_says_how_the_file_was_written(tmp_path, monkeypatch,
                                                     forced):
    d = LocalStorage(str(tmp_path / "d"))
    d.make_vol("v")
    if forced == "direct":
        DirectFds(monkeypatch)
    elif forced == "direct_dropped":
        # the mount takes open(O_DIRECT) and refuses the first write
        DirectFds(monkeypatch, fail_write=1, fail_with=errno.EINVAL)
    elif forced == "open_refused":
        monkeypatch.setattr(LocalStorage, "_open_direct",
                            staticmethod(lambda dest: None))
    else:
        monkeypatch.setattr(local_mod, "O_DIRECT_ENABLED", False)
    before, stats = tracing.stage_totals(), local_mod.STREAM_STATS.snapshot()
    d.create_file("v", "f", iter(CHUNKS))
    want = forced if forced.startswith("direct") else "buffered"
    assert streams_since(stats) == {want: 1}
    got = stages_since(before)
    # a refused write is entered again: one entry more
    assert got["disk.stream.write"][1] == expected_writes(want, CHUNKS) \
        + (forced == "direct_dropped")
    assert got["disk.stream.open"][1] == got["disk.stream.sync"][1] == 1
    assert d.read_file("v", "f") == b"".join(CHUNKS)
    assert in_flight() == IDLE


def raising_chunks():
    yield CHUNKS[0]
    yield CHUNKS[1]
    raise RuntimeError("the producer died")


@pytest.mark.parametrize("fault", ["iterator_raises", "write_fails",
                                   "sync_fails"])
@pytest.mark.parametrize("o_direct", [True, False], ids=["direct", "buffered"])
def test_no_count_leaks_from_a_stream_that_fails(tmp_path, monkeypatch,
                                                 o_direct, fault):
    monkeypatch.setattr(local_mod, "O_DIRECT_ENABLED", o_direct)
    d = LocalStorage(str(tmp_path / "d"))
    d.make_vol("v")
    before, stats = tracing.stage_totals(), local_mod.STREAM_STATS.snapshot()
    chunks, raised = iter(CHUNKS), OSError
    if fault == "iterator_raises":
        chunks, raised = raising_chunks(), RuntimeError
    elif fault == "sync_fails":
        def fdatasync(fd):
            assert in_flight()[1]["shard"] == 1
            raise OSError(errno.EIO, "injected")
        monkeypatch.setattr(os, "fdatasync", fdatasync)
    elif o_direct:
        # the second write: the first has landed, so nothing falls back
        DirectFds(monkeypatch, fail_write=2)
    else:
        # the file object refuses what is no buffer, inside the stage
        chunks, raised = iter([CHUNKS[0], "not bytes"]), TypeError
    with pytest.raises(raised):
        d.create_file("v", "f", chunks)
    assert in_flight() == IDLE
    assert streams_since(stats) == {}              # completed streams only
    got = stages_since(before)
    assert got[STREAM][1] == got["disk.stream.open"][1] == 1
    assert ("disk.stream.sync" in got) == (fault == "sync_fails")
    # and the next stream on the same drive is counted as ever
    monkeypatch.undo()
    d.create_file("v", "g", iter(CHUNKS))
    assert sum(streams_since(stats).values()) == 1
    assert in_flight() == IDLE


@pytest.mark.parametrize("kind", ["shard", "meta"])
def test_a_sync_over_the_threshold_is_counted_slow(tmp_path, monkeypatch,
                                                   kind):
    """The clock is the test's: no second is slept."""
    d = LocalStorage(str(tmp_path / "d"))
    d.make_vol("v")
    clock = [100.0]
    took = [0.2]
    monkeypatch.setattr(local_mod, "_now", lambda: clock[0])
    real = os.fdatasync

    def fdatasync(fd):
        real(fd)
        clock[0] += took[0]
    monkeypatch.setattr(os, "fdatasync", fdatasync)

    def one():
        if kind == "shard":
            d.create_file("v", "f", iter(CHUNKS))
        else:
            d.write_all("v", "cfg.json", b"{}")
        snap = local_mod.STREAM_STATS.snapshot()
        return snap["slow_syncs"], snap["sync_hist"][kind]
    other = "meta" if kind == "shard" else "shard"
    slow0, hist0 = one()
    took[0] = local_mod.SLOW_SYNC_S + 0.5
    slow1, hist1 = one()
    assert slow1[kind] == slow0[kind] + 1 and slow1[other] == slow0[other]
    took[0] = 45.0                  # past the API histograms' 10 s
    slow2, hist2 = one()
    assert slow2[kind] == slow1[kind] + 1
    assert hist2["count"] == hist0["count"] + 2
    assert hist2["sum"] == pytest.approx(hist0["sum"] + 46.5)
    le = dict(local_mod.Histogram.cumulative(hist2, local_mod.SYNC_BUCKETS))
    le0 = dict(local_mod.Histogram.cumulative(hist0, local_mod.SYNC_BUCKETS))
    assert le["30"] - le0["30"] == 1 and le["60"] - le0["60"] == 2
    assert le["1"] - le0["1"] == 0


def test_a_sync_says_its_company_when_armed(tmp_path, monkeypatch):
    """The span of a sync carries the bytes it covers and the syncs
    already in flight, in the process and on its drive."""
    drives = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(2)]
    for d in drives:
        d.make_vol("v")
    inside, release = threading.Event(), threading.Event()
    real = os.fdatasync
    first = [True]

    def fdatasync(fd):
        if first[0]:
            first[0] = False
            inside.set()
            assert release.wait(30)
        real(fd)
    monkeypatch.setattr(os, "fdatasync", fdatasync)
    ctx = tracing.TraceContext()
    tracing.arm("test")
    try:
        def held():
            with tracing.bind(ctx):
                drives[0].create_file("v", "held", iter(CHUNKS))
        t = threading.Thread(target=held)
        t.start()
        assert inside.wait(30)
        assert in_flight() == (1, {"shard": 1, "meta": 0})
        with tracing.bind(ctx):
            drives[0].write_all("v", "a.json", b"{}")       # same drive
            drives[1].create_file("v", "f", b"x" * 5000)    # another
        release.set()
        t.join(30)
    finally:
        release.set()
        tracing.disarm("test")
    syncs = {s["name"] + str(s["tags"]["bytes"]): s["tags"]
             for s in ctx.spans if s["name"].endswith(".sync")}
    total = sum(map(len, CHUNKS))
    assert syncs[f"disk.stream.sync{total}"]["peers"] == 0
    assert syncs["disk.meta.sync2"] == {
        "bytes": 2, "peers": 1, "peers_drive": 1, "direct": False}
    assert syncs["disk.stream.sync5000"] == {
        "bytes": 5000, "peers": 1, "peers_drive": 0, "direct": False}
    assert in_flight() == IDLE

"""Group-commit write plane (storage/group_commit + commit_group).

Covers the ISSUE-chartered suite: batched-vs-solo journal byte-identity
across member mixes, same-object merge ordering, member-failure
isolation, deadline-cull without poisoning, WAL replay semantics, the
no-op short-circuit, the coalesced-bump funnel, and — in a subprocess
fleet — 2-pre-forked-worker coherence of the coalesced invalidation.
"""

from __future__ import annotations

import os
import shutil
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from minio_tpu.object.erasure_object import ErasureSet
from minio_tpu.storage import group_commit as gc_mod
from minio_tpu.storage.group_commit import GroupCommit, GroupOp, replay_wals
from minio_tpu.storage.local import SYS_VOL, LocalStorage
from minio_tpu.storage.meta import (ErasureInfo, FileInfo, ObjectPartInfo,
                                    XLMeta, now_ns)

BKT = "b"


def mkdisk(tmp_path, name="d0"):
    d = LocalStorage(str(tmp_path / name))
    os.makedirs(os.path.join(d.root, BKT), exist_ok=True)
    return d


def mkfi(key, mod_time=None, vid="", data=b"x" * 64, deleted=False,
         ddir=""):
    return FileInfo(
        volume=BKT, name=key, version_id=vid, deleted=deleted,
        data_dir=ddir, mod_time=mod_time or now_ns(), size=len(data),
        metadata={"etag": "e"},
        parts=[ObjectPartInfo(number=1, size=len(data),
                              actual_size=len(data))],
        erasure=ErasureInfo(data_blocks=2, parity_blocks=1,
                            block_size=1 << 20, index=1,
                            distribution=(1, 2, 3)),
        inline_data=None if deleted else data)


def read_xl(d, key):
    with open(os.path.join(d.root, BKT, key, "xl.meta"), "rb") as f:
        return f.read()


# ---------------------------------------------------------------------------
# commit_group protocol
# ---------------------------------------------------------------------------

def test_batched_vs_solo_byte_identity(tmp_path):
    """One batch over a mix of fresh keys, overwrites, delete markers
    and same-object sequences produces journals byte-identical to the
    same ops applied solo in the same order."""
    da, db = mkdisk(tmp_path, "da"), mkdisk(tmp_path, "db")
    t0 = now_ns()
    fis = [
        ("k1", mkfi("k1", t0)),
        ("k2", mkfi("k2", t0 + 1)),
        ("k1", mkfi("k1", t0 + 2)),                    # null overwrite
        ("k3", mkfi("k3", t0 + 3, vid="11111111-0000-0000-0000-"
                                      "000000000001")),
        ("k3", mkfi("k3", t0 + 4, vid="11111111-0000-0000-0000-"
                                      "000000000002")),
        ("k2", mkfi("k2", t0 + 5, deleted=True)),      # delete marker
    ]
    # Pre-existing journal for k4 so the overwrite path is covered too.
    for d in (da, db):
        d.write_metadata(BKT, "k4", mkfi("k4", t0 - 5))
    fis.append(("k4", mkfi("k4", t0 + 6)))

    info = {}
    res = da.commit_group([GroupOp.write_meta(BKT, k, fi)
                           for k, fi in fis], _info=info)
    assert res == [None] * len(fis)
    # Seven members on four objects: three merge into a batch-mate's
    # journal, and ONE WAL fdatasync stands for the four journals'
    # own, so three are saved (a count, not a time).
    assert (info["objects"], info["merged"]) == (4, 3)
    assert info["fsyncs_saved"] == 3
    for k, fi in fis:
        db.write_metadata(BKT, k, fi)
    for k in ("k1", "k2", "k3", "k4"):
        assert read_xl(da, k) == read_xl(db, k), f"journal differs: {k}"


def test_same_object_merge_ordering(tmp_path):
    """Same-object members merge in arrival order into ONE journal
    rewrite: the last null-version member wins the null slot, and the
    commit writes the object's journal exactly once."""
    d = mkdisk(tmp_path)
    t0 = now_ns()
    ops = [GroupOp.write_meta(BKT, "hot", mkfi("hot", t0 + i,
                                               data=bytes([i]) * 32))
           for i in range(5)]
    info = {}
    assert d.commit_group(ops, _info=info) == [None] * 5
    assert info["objects"] == 1
    assert info["merged"] == 4
    xl = XLMeta.load(read_xl(d, "hot"))
    assert len(xl.versions) == 1
    fi = xl.to_fileinfo(BKT, "hot", read_data=True)
    assert fi.inline_data == bytes([4]) * 32   # arrival order: last wins


def test_member_failure_isolation(tmp_path):
    """A rename_data member whose staging is missing fails ALONE;
    batch-mates commit normally."""
    d = mkdisk(tmp_path)
    good = GroupOp.write_meta(BKT, "ok1", mkfi("ok1"))
    bad = GroupOp.rename("nosuchvol", "missing",
                         mkfi("broken", ddir="0" * 8), BKT, "broken")
    good2 = GroupOp.write_meta(BKT, "ok2", mkfi("ok2"))
    res = d.commit_group([good, bad, good2])
    assert res[0] is None and res[2] is None
    assert isinstance(res[1], Exception)
    assert XLMeta.load(read_xl(d, "ok1")).versions
    assert XLMeta.load(read_xl(d, "ok2")).versions
    assert not os.path.exists(os.path.join(d.root, BKT, "broken",
                                           "xl.meta"))


def test_rename_data_members_batch(tmp_path):
    """rename_data members move their staged data dirs in and the
    journal claims them — equivalent to solo rename_data."""
    da, db = mkdisk(tmp_path, "da"), mkdisk(tmp_path, "db")
    t0 = now_ns()
    ops = []
    for d in (da, db):
        os.makedirs(os.path.join(d.root, SYS_VOL, "stage", "dd1"))
        with open(os.path.join(d.root, SYS_VOL, "stage", "dd1",
                               "part.1"), "wb") as f:
            f.write(b"shard")
    fi_a = mkfi("rk", t0, ddir="dd1", data=b"")
    fi_a.inline_data = None
    fi_b = mkfi("rk", t0, ddir="dd1", data=b"")
    fi_b.inline_data = None
    res = da.commit_group([GroupOp.rename(SYS_VOL, "stage", fi_a,
                                          BKT, "rk")])
    assert res == [None]
    db.rename_data(SYS_VOL, "stage", fi_b, BKT, "rk")
    assert read_xl(da, "rk") == read_xl(db, "rk")
    assert os.path.isfile(os.path.join(da.root, BKT, "rk", "dd1",
                                       "part.1"))
    # Staging cleaned on both paths.
    assert not os.path.exists(os.path.join(da.root, SYS_VOL, "stage"))


def test_noop_short_circuit_solo_and_batched(tmp_path):
    """A byte-identical version re-add skips the journal rewrite on
    both the solo and the batched path (the hot-key
    overwrite-with-same-content fix)."""
    d = mkdisk(tmp_path)
    fi = mkfi("nk", now_ns())
    d.write_metadata(BKT, "nk", fi)
    p = os.path.join(d.root, BKT, "nk", "xl.meta")
    st0 = os.stat(p)
    d.write_metadata(BKT, "nk", fi)          # solo no-op
    assert os.stat(p).st_mtime_ns == st0.st_mtime_ns
    info = {}
    res = d.commit_group([GroupOp.write_meta(BKT, "nk", fi)],
                         _info=info)
    assert res == [None]
    assert info["noops"] == 1
    assert os.stat(p).st_mtime_ns == st0.st_mtime_ns


# ---------------------------------------------------------------------------
# WAL replay
# ---------------------------------------------------------------------------

def _wal_with(d, recs, t_ns=None):
    path = gc_mod.wal_file_path(d.root)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "ab") as f:
        f.write(gc_mod.encode_frame(recs, t_ns=t_ns))
    return path


def test_replay_repairs_torn_destination(tmp_path):
    d = mkdisk(tmp_path)
    fi = mkfi("rw", now_ns())
    assert d.commit_group([GroupOp.write_meta(BKT, "rw", fi)]) == [None]
    blob = read_xl(d, "rw")
    dest = os.path.join(d.root, BKT, "rw", "xl.meta")
    # Fabricate the power-cut state: WAL frame present, dest torn.
    _wal_with(d, [(BKT, "rw", blob)], t_ns=time.time_ns())
    with open(dest, "wb") as f:
        f.write(blob[: len(blob) // 2])
    rep = replay_wals(d)
    assert rep["repaired"] == 1
    assert read_xl(d, "rw") == blob
    assert os.listdir(os.path.join(d.root, SYS_VOL,
                                   gc_mod.GC_DIR)) == []


def test_replay_installs_when_rename_lost(tmp_path):
    """Destination older than the frame (or absent, dir present): the
    rename never landed — the acked journal installs from the WAL."""
    d = mkdisk(tmp_path)
    old_fi = mkfi("rl", now_ns())
    d.write_metadata(BKT, "rl", old_fi)
    old_blob = read_xl(d, "rl")
    xl = XLMeta.load(old_blob)
    xl.add_version(mkfi("rl", now_ns() + 10, data=b"new" * 8))
    new_blob = xl.dump()
    _wal_with(d, [(BKT, "rl", new_blob)], t_ns=time.time_ns() + 10_000)
    assert replay_wals(d)["repaired"] == 1
    assert read_xl(d, "rl") == new_blob


def test_replay_leaves_newer_destination_alone(tmp_path):
    """A destination newer than the frame is a later committed write
    — replay must not roll it back."""
    d = mkdisk(tmp_path)
    stale = XLMeta()
    stale.add_version(mkfi("nw", now_ns() - 50))
    _wal_with(d, [(BKT, "nw", stale.dump())],
              t_ns=time.time_ns() - 10 ** 9)
    d.write_metadata(BKT, "nw", mkfi("nw", now_ns()))
    newer = read_xl(d, "nw")
    assert replay_wals(d)["repaired"] == 0
    assert read_xl(d, "nw") == newer


def test_replay_never_resurrects_deleted_object(tmp_path):
    """Object dir pruned by a post-batch delete: the WAL frame must
    not bring the object back."""
    d = mkdisk(tmp_path)
    fi = mkfi("dz", now_ns())
    d.write_metadata(BKT, "dz", fi)
    blob = read_xl(d, "dz")
    _wal_with(d, [(BKT, "dz", blob)], t_ns=time.time_ns())
    d.delete_version(BKT, "dz", "")
    assert not os.path.exists(os.path.join(d.root, BKT, "dz"))
    assert replay_wals(d)["repaired"] == 0
    assert not os.path.exists(os.path.join(d.root, BKT, "dz"))


def test_replay_discards_torn_tail_frame(tmp_path):
    """A torn tail frame (power cut mid-append) is discarded; intact
    frames before it still replay."""
    d = mkdisk(tmp_path)
    good = XLMeta()
    good.add_version(mkfi("tg", now_ns()))
    blob = good.dump()
    os.makedirs(os.path.join(d.root, BKT, "tg"))
    path = _wal_with(d, [(BKT, "tg", blob)], t_ns=time.time_ns())
    torn = gc_mod.encode_frame([(BKT, "zz", b"XTP1garbage")])
    with open(path, "ab") as f:
        f.write(torn[: len(torn) // 2])
    rep = replay_wals(d)
    assert rep["replayed"] == 1 and rep["discarded"] == 1
    assert read_xl(d, "tg") == blob


def test_checkpoint_truncates_wal(tmp_path):
    d = mkdisk(tmp_path)
    d._gc_auto = False
    for i in range(3):
        assert d.commit_group([GroupOp.write_meta(
            BKT, f"ck-{i}", mkfi(f"ck-{i}"))]) == [None] * 1
    assert d.gc_pending() == 3
    wal = gc_mod.wal_file_path(d.root)
    assert os.path.getsize(wal) > 0
    assert d.gc_checkpoint() == 3
    assert os.path.getsize(wal) == 0
    assert d.gc_pending() == 0
    d.gc_close()


def test_recovery_sweep_replays_first(tmp_path):
    """recovery_sweep replays WAL frames BEFORE the dangling-data-dir
    scan, so data dirs claimed only by WAL-recorded journals are not
    reaped as orphans."""
    from minio_tpu.storage.local import recovery_sweep
    d = mkdisk(tmp_path)
    ddir = "11111111-2222-3333-4444-555555555555"
    obj = os.path.join(d.root, BKT, "rs")
    os.makedirs(os.path.join(obj, ddir))
    with open(os.path.join(obj, ddir, "part.1"), "wb") as f:
        f.write(b"shard")
    xl = XLMeta()
    fi = mkfi("rs", now_ns(), ddir=ddir, data=b"")
    fi.inline_data = None
    xl.add_version(fi)
    _wal_with(d, [(BKT, "rs", xl.dump())], t_ns=time.time_ns())
    rep = recovery_sweep(d, min_age=0)
    assert rep["wal_repaired"] == 1
    assert os.path.isfile(os.path.join(obj, ddir, "part.1")), \
        "replayed journal's data dir was reaped as dangling"
    assert rep["dangling"] == 0


# ---------------------------------------------------------------------------
# the coalescer (GroupCommit lanes)
# ---------------------------------------------------------------------------

def _mkset(tmp_path, n=4, name="es"):
    disks = [LocalStorage(str(tmp_path / f"{name}{i}")) for i in range(n)]
    es = ErasureSet(disks)
    es.make_bucket(BKT)
    return es


def test_concurrent_inline_puts_coalesce_and_roundtrip(tmp_path):
    es = _mkset(tmp_path)
    assert es.group_commit is not None
    body = os.urandom(2048)
    ex = ThreadPoolExecutor(max_workers=12)

    def put(t):
        for i in range(15):
            es.put_object(BKT, f"k-{t}-{i}", body)

    list(ex.map(put, range(12)))
    st = es.group_commit.stats()
    assert st["members"] > 0, "no commit ever rode the lanes"
    assert st["batches"] < st["members"], "no coalescing happened"
    # Every key is its own object, so a batch of two or more members
    # is two or more journals under one WAL sync.
    assert st["fsyncs_saved"] > 0
    for t in (0, 5, 11):
        for i in (0, 14):
            _, data = es.get_object(BKT, f"k-{t}-{i}")
            assert data == body
    # Listing sees every key (the coalesced bump invalidated walks).
    res = es.list_objects(BKT, prefix="k-")
    assert len(res.objects) == 12 * 15
    es.close()
    ex.shutdown(wait=False)
    # Graceful close checkpoints: no WAL frames survive for replay.
    for d in es.disks:
        gdir = os.path.join(d.root, SYS_VOL, gc_mod.GC_DIR)
        for name in (os.listdir(gdir) if os.path.isdir(gdir) else []):
            assert os.path.getsize(os.path.join(gdir, name)) == 0


def test_solo_request_bypasses_lanes(tmp_path):
    """A lone PUT (no concurrency) takes the solo fan-out — identical
    behavior and no window wait."""
    es = _mkset(tmp_path)
    es.put_object(BKT, "solo", b"x" * 512)
    st = es.group_commit.stats()
    assert st["members"] == 0
    assert st["solo_bypass"] >= 1
    _, data = es.get_object(BKT, "solo")
    assert data == b"x" * 512
    es.close()


def test_deadline_cull_without_poisoning(tmp_path):
    """A member whose budget is spent at dispatch is culled alone with
    DeadlineExceeded; batch-mates commit."""
    from minio_tpu.utils.deadline import DeadlineExceeded
    d = mkdisk(tmp_path)
    gc = GroupCommit([d], _FakeEngine())

    class _DL:
        expires_at = time.monotonic() - 1.0

    live = gc_mod._Latch(1)
    dead = gc_mod._Latch(1)
    m_ok = gc_mod._Member(GroupOp.write_meta(BKT, "dc-ok", mkfi("dc-ok")),
                          None, live)
    m_dead = gc_mod._Member(GroupOp.write_meta(BKT, "dc-no",
                                               mkfi("dc-no")),
                            _DL(), dead)
    gc._run_batch(gc._lanes[0], [m_ok, m_dead])
    assert m_ok.exc is None
    assert isinstance(m_dead.exc, DeadlineExceeded)
    assert XLMeta.load(read_xl(d, "dc-ok")).versions
    assert not os.path.exists(os.path.join(d.root, BKT, "dc-no"))
    assert gc.stats()["deadline_culls"] == 1


class _FakeEngine:
    def submit_nowait(self, idx, fn):
        fn()


def test_solo_demotion_on_batch_fault(tmp_path):
    """A wholesale commit_group fault demotes every member to the solo
    path — the batch fault is invisible to callers when solo
    succeeds."""
    d = mkdisk(tmp_path)

    class Flaky:
        root = d.root
        endpoint = "flaky"

        def commit_group(self, ops, _info=None):
            raise OSError("batch machinery exploded")

        def write_metadata(self, vol, path, fi):
            return d.write_metadata(vol, path, fi)

    gc = GroupCommit([Flaky()], _FakeEngine())
    latch = gc_mod._Latch(2)
    ms = [gc_mod._Member(GroupOp.write_meta(BKT, f"sd-{i}",
                                            mkfi(f"sd-{i}")), None, latch)
          for i in range(2)]
    gc._run_batch(gc._lanes[0], ms)
    assert all(m.exc is None for m in ms)
    assert gc.stats()["solo_demotions"] == 2
    for i in range(2):
        assert XLMeta.load(read_xl(d, f"sd-{i}")).versions


def test_coalesced_bump_fires_before_ack(tmp_path):
    """The batch's metacache bump happens BEFORE members are acked:
    a reader observing the PUT's return can never hit a stale cached
    listing/fileinfo."""
    d = mkdisk(tmp_path)
    order = []

    class Latch(gc_mod._Latch):
        def dec(self):
            order.append("ack")
            super().dec()

    gc = GroupCommit([d], _FakeEngine())
    gc.bump = lambda bucket: order.append(f"bump:{bucket}")
    latch = Latch(1)
    m = gc_mod._Member(GroupOp.write_meta(BKT, "bf", mkfi("bf")),
                       None, latch)
    gc._run_batch(gc._lanes[0], [m])
    assert order == [f"bump:{BKT}", "ack"]


def test_delete_marker_storm_coalesces(tmp_path):
    """Versioned delete markers ride the same lanes as inline PUTs."""
    es = _mkset(tmp_path)
    body = b"v" * 256
    keys = [f"dm-{i}" for i in range(24)]
    for k in keys:
        es.put_object(BKT, k, body)
    from minio_tpu.object.types import DeleteOptions
    before = es.group_commit.stats()["members"]
    ex = ThreadPoolExecutor(max_workers=8)

    def rm(k):
        es.delete_object(BKT, k, DeleteOptions(versioned=True))

    list(ex.map(rm, keys))
    after = es.group_commit.stats()["members"]
    assert after > before, "delete markers never rode the lanes"
    for k in keys[:3]:
        from minio_tpu.object.types import ObjectNotFound
        with pytest.raises(ObjectNotFound):
            es.get_object(BKT, k)
    es.close()
    ex.shutdown(wait=False)


def test_group_commit_disabled_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv("MTPU_GROUP_COMMIT", "off")
    es = _mkset(tmp_path)
    assert es.group_commit is None
    es.put_object(BKT, "off", b"y" * 128)
    _, data = es.get_object(BKT, "off")
    assert data == b"y" * 128
    es.close()


# ---------------------------------------------------------------------------
# cross-process coherence of the coalesced bump (2 pre-forked workers)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gc_worker_server(tmp_path_factory):
    """A 2-worker pre-forked fleet on shared drives (subprocess — the
    pytest process has JAX loaded and fork-after-JAX is unsafe)."""
    import signal
    import socket
    import subprocess
    import sys
    root = tmp_path_factory.mktemp("gcworkers")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", MTPU_HTTP_WORKERS="2",
               MTPU_GROUP_COMMIT="on")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "minio_tpu.server",
         "--address", f"127.0.0.1:{port}", "--scanner-interval", "0",
         f"{root}/d{{1...4}}"],
        env=env, cwd=repo,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    from tests.s3client import S3Client
    address = f"127.0.0.1:{port}"
    deadline = time.time() + 90
    ready = False
    while time.time() < deadline:
        if proc.poll() is not None:
            break
        try:
            st, _, _ = S3Client(address).request(
                "GET", "/minio/health/live", sign=False)
            if st == 200:
                ready = True
                break
        except OSError:
            time.sleep(0.4)
    if not ready:
        out = proc.stdout.read().decode(errors="replace") \
            if proc.stdout else ""
        proc.kill()
        pytest.skip(f"worker fleet failed to boot: {out[-800:]}")
    yield address
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=25)
    except subprocess.TimeoutExpired:
        proc.kill()


def test_workers_coalesced_bump_coherence(gc_worker_server):
    """Concurrent small-object PUT storms through BOTH pre-forked
    workers (group-commit lanes engaged), then overwrites: no
    connection anywhere may serve stale bytes — the coalesced bump
    must invalidate sibling workers' caches exactly like per-request
    bumps did."""
    from tests.s3client import S3Client
    addr = gc_worker_server
    assert S3Client(addr).request("PUT", "/gcb")[0] == 200
    body1 = b"one" * 1000
    body2 = b"two" * 1100

    def storm(body, tag):
        def put(t):
            cli = S3Client(addr)
            for i in range(6):
                st, _, _ = cli.request("PUT", f"/gcb/k{t}-{i}",
                                       body=body)
                assert st == 200
            st, _, _ = cli.request("PUT", "/gcb/hot", body=body)
            assert st == 200
        ex = ThreadPoolExecutor(max_workers=8)
        list(ex.map(put, range(8)))
        ex.shutdown(wait=False)

    storm(body1, "a")
    for _ in range(8):       # fresh connections: both workers cache it
        st, _, got = S3Client(addr).request("GET", "/gcb/hot")
        assert st == 200 and got == body1
    storm(body2, "b")
    for _ in range(8):
        st, _, got = S3Client(addr).request("GET", "/gcb/hot")
        assert st == 200 and got == body2, \
            "stale bytes served across workers after group-commit " \
            "overwrite storm"
    # And listings converge on the full keyspace.
    st, _, page = S3Client(addr).request(
        "GET", "/gcb", query={"prefix": "k", "max-keys": "1000"})
    assert st == 200
    assert page.count(b"<Key>") == 48


# ---------------------------------------------------------------------------
# review-hardening regressions
# ---------------------------------------------------------------------------

def test_replay_survives_16_byte_torn_tail(tmp_path):
    """A torn tail of 16-19 bytes (magic+crc+partial body head) must
    be treated as torn, not raise out of replay (and through it, out
    of recovery_sweep)."""
    d = mkdisk(tmp_path)
    good = XLMeta()
    good.add_version(mkfi("tt", now_ns()))
    os.makedirs(os.path.join(d.root, BKT, "tt"))
    path = _wal_with(d, [(BKT, "tt", good.dump())], t_ns=time.time_ns())
    frame = gc_mod.encode_frame([(BKT, "zz", b"x")])
    with open(path, "ab") as f:
        f.write(frame[:17])
    rep = replay_wals(d)
    assert rep["replayed"] == 1 and rep["discarded"] == 1
    assert read_xl(d, "tt") == good.dump()


def test_commit_fanout_all_none_returns(tmp_path):
    """Every drive slot None (staging failed everywhere) must return
    immediately, not park on an un-signalled latch inside the ns
    lock."""
    d = mkdisk(tmp_path)
    gc = GroupCommit([d], _FakeEngine())
    t0 = time.monotonic()
    errors = gc.commit_fanout([None])
    assert time.monotonic() - t0 < 1.0
    assert errors == [None]
    gc.close()


def test_truncate_guard_skips_on_concurrent_append(tmp_path):
    """Frames appended between a checkpoint's sync and its truncate
    were not covered by that sync: the guarded truncate must skip
    (retire next round), never erase a live durability point."""
    d = mkdisk(tmp_path)
    d._gc_auto = False
    d.commit_group([GroupOp.write_meta(BKT, "tr-0", mkfi("tr-0"))])
    pre = d.gc_pending()
    assert pre == 1
    # A batch lands AFTER the (simulated) sync, BEFORE the truncate:
    d.commit_group([GroupOp.write_meta(BKT, "tr-1", mkfi("tr-1"))])
    assert d.gc_truncate_wal(expect=pre) == 0, \
        "truncate erased frames the sync never covered"
    assert d.gc_pending() == 2
    # Next round sees a stable count and retires both.
    assert d.gc_truncate_wal(expect=2) == 2
    d.gc_close()


def test_replay_mtime_lie_does_not_roll_back_overwrite(tmp_path):
    """Even when the destination's mtime reads OLDER than the frame
    (coarse-granularity fs, clock step), a destination whose journal
    already supersedes every frame version must not be rolled back."""
    d = mkdisk(tmp_path)
    t0 = now_ns()
    old = XLMeta()
    old.add_version(mkfi("cl", t0))
    # Destination holds a NEWER overwrite of the same null version.
    d.write_metadata(BKT, "cl", mkfi("cl", t0 + 1000,
                                     data=b"newer" * 8))
    newer = read_xl(d, "cl")
    # Frame stamped in the FUTURE: the mtime comparison alone would
    # say "destination is pre-batch, install".
    _wal_with(d, [(BKT, "cl", old.dump())],
              t_ns=time.time_ns() + 10 ** 12)
    assert replay_wals(d)["repaired"] == 0
    assert read_xl(d, "cl") == newer

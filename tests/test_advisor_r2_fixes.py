"""Regression tests for the round-2 advisor findings.

Covers: dsync read/write quorum overlap for odd locker counts
(reference internal/dsync/drwmutex.go:218-234), grid client pending-map
isolation across reconnects, walk_dir blob-cache boundedness, and the
TTL sweep of abandoned chunked-upload transfers.
"""

import os
import threading
import time

from minio_tpu.grid.dsync import DRWMutex, LockServer, LocalLocker
from minio_tpu.storage.local import LocalStorage
from minio_tpu.storage.remote import StorageRPCService


# ---------------------------------------------------------------------------
# dsync quorum math
# ---------------------------------------------------------------------------

def test_read_write_quorums_always_overlap():
    for n in range(1, 17):
        m = DRWMutex([object()] * n, "r")
        rq = m._quorum(write=False)
        wq = m._quorum(write=True)
        assert wq == n // 2 + 1
        assert rq + wq > n, f"n={n}: disjoint read+write quorums possible"
        assert 1 <= rq <= n


def test_reader_writer_exclusion_with_one_amnesiac_locker():
    # n=3: one locker restarts (loses its table). A writer holding a
    # quorum on the two live lockers must still block a new reader —
    # with the old read quorum of 1, the reader could win on the fresh
    # locker alone.
    servers = [LockServer() for _ in range(3)]
    lockers = [LocalLocker(s) for s in servers]
    w = DRWMutex(lockers, "res")
    assert w.lock(write=True, timeout=1.0)
    # Locker 0 "restarts": its lock table is wiped.
    servers[0]._res.clear()
    r = DRWMutex(lockers, "res")
    assert not r.lock(write=False, timeout=0.3)
    w.unlock()
    assert r.lock(write=False, timeout=1.0)
    r.unlock()


# ---------------------------------------------------------------------------
# grid client: old socket death must not kill new socket's calls
# ---------------------------------------------------------------------------

def test_drop_conn_only_fails_own_sockets_calls():
    import queue as queue_mod

    from minio_tpu.grid import loop
    from minio_tpu.grid.client import GridClient, _SENTINEL_ERR

    c = GridClient("127.0.0.1", 1)  # never actually connected
    # _drop_conn also tells the process-wide grid poller to forget the
    # socket. Whether one exists used to depend on which test files the
    # xdist worker had run before this one (none when run alone): have
    # it exist, so the same path runs in every order.
    if loop.available():
        loop.poller()

    class FakeSock:
        def fileno(self):
            return -1           # what a closed socket says

        def close(self):
            pass

    old_s, new_s = FakeSock(), FakeSock()
    q_old: "queue_mod.Queue[dict]" = queue_mod.Queue()
    q_new: "queue_mod.Queue[dict]" = queue_mod.Queue()
    with c._mu:
        c._sock = new_s
        c._pending[1] = (old_s, q_old)
        c._pending[2] = (new_s, q_new)
    c._drop_conn(old_s)
    # Old socket's call failed with the sentinel...
    msg = q_old.get_nowait()
    assert msg["e"] == _SENTINEL_ERR
    # ...but the new socket's call is untouched and still registered.
    assert q_new.empty()
    assert 2 in c._pending and 1 not in c._pending
    assert c._sock is new_s


# ---------------------------------------------------------------------------
# walk_dir blob cache stays bounded
# ---------------------------------------------------------------------------

def test_walk_dir_emit_keeps_single_cache_entry(tmp_path):
    from minio_tpu.object.erasure_object import ErasureSet
    from minio_tpu.object.types import PutOptions

    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    es = ErasureSet(disks)
    es.make_bucket("b")
    for i in range(8):
        es.put_object("b", f"k{i}", b"y" * 128, PutOptions())

    d = disks[0]
    gen = d.walk_dir("b")
    drained = 0
    for _ in gen:
        drained += 1
        # Inspect the running generator's frame: the journal cache is a
        # single slot, never an unbounded map.
        cache = gen.gi_frame.f_locals.get("last_blob")
        if cache is not None:
            assert len(cache) == 2
    assert drained == 8


# ---------------------------------------------------------------------------
# chunked-upload transfer TTL sweep
# ---------------------------------------------------------------------------

def test_stale_transfer_swept(tmp_path):
    d = LocalStorage(str(tmp_path / "d0"))
    svc = StorageRPCService({d.root: d}, xfer_idle_ttl=0.05)
    d.make_vol("v")
    xfer = svc._create_begin({"d": d.root, "a": ["v", "obj/part.1"]})
    st = svc._xfers[xfer]
    tmp_file = st["tmp"]
    assert os.path.exists(tmp_file)
    time.sleep(0.1)
    # A new begin triggers the sweep of the stale one.
    xfer2 = svc._create_begin({"d": d.root, "a": ["v", "obj/part.2"]})
    assert xfer not in svc._xfers
    assert not os.path.exists(tmp_file)
    assert xfer2 in svc._xfers
    # Active transfers are never swept while being written.
    svc._create_chunk({"a": [xfer2, b"data"]})
    svc._sweep_stale_xfers()
    assert xfer2 in svc._xfers
    svc._create_commit({"a": [xfer2]})
    assert d.read_file("v", "obj/part.2") == b"data"

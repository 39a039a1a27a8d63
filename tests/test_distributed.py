"""Distributed runtime: grid RPC mesh, remote StorageAPI, dsync quorum
locks, and a verify-healing-style multi-process cluster test
(reference: internal/grid, cmd/storage-rest-*, internal/dsync,
buildscripts/verify-healing.sh)."""

import os
import signal
import subprocess
import sys
import time

import pytest

from minio_tpu.grid import GridClient, GridError, GridServer, RemoteCallError
from minio_tpu.grid.dsync import (DRWMutex, DistNSLock, LocalLocker,
                                  LockServer, RemoteLocker)
from minio_tpu.storage.local import LocalStorage
from minio_tpu.storage.meta import (ErasureInfo, FileInfo, FileNotFoundErr,
                                    ObjectPartInfo)
from minio_tpu.storage.remote import RemoteStorage, StorageRPCService


# ---------------------------------------------------------------------------
# grid core
# ---------------------------------------------------------------------------

@pytest.fixture
def grid_pair():
    srv = GridServer(0, host="127.0.0.1")
    srv.start()
    client = GridClient("127.0.0.1", srv.port)
    yield srv, client
    client.close()
    srv.stop()


def test_grid_unary_and_concurrent(grid_pair):
    srv, client = grid_pair
    srv.register("echo", lambda p: p)
    srv.register("double", lambda p: p * 2)
    assert client.call("echo", {"a": [1, 2], "b": b"raw"}) == \
        {"a": [1, 2], "b": b"raw"}
    import threading
    results = []

    def worker(i):
        results.append(client.call("double", i))
    ts = [threading.Thread(target=worker, args=(i,)) for i in range(20)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert sorted(results) == [i * 2 for i in range(20)]


def test_grid_stream(grid_pair):
    srv, client = grid_pair
    srv.register_stream("gen", lambda p: (i for i in range(p)))
    assert list(client.stream("gen", 5)) == [0, 1, 2, 3, 4]


def test_grid_error_mapping(grid_pair):
    srv, client = grid_pair

    def boom(p):
        raise FileNotFoundErr("nope")
    srv.register("boom", boom)
    with pytest.raises(RemoteCallError) as ei:
        client.call("boom")
    assert ei.value.code == "FileNotFound"
    with pytest.raises(RemoteCallError) as ei:
        client.call("no-such-handler")
    assert ei.value.code == "NoSuchHandler"


def test_grid_reconnect_after_server_restart():
    srv = GridServer(0, host="127.0.0.1")
    srv.start()
    port = srv.port
    srv.register("echo", lambda p: p)
    client = GridClient("127.0.0.1", port)
    assert client.call("echo", 1) == 1
    srv.stop()
    time.sleep(0.1)
    with pytest.raises(GridError):
        client.call("echo", 2, timeout=2.0)
    srv2 = GridServer(port, host="127.0.0.1")
    srv2.register("echo", lambda p: p)
    srv2.start()
    try:
        # Next call reconnects transparently.
        deadline = time.time() + 5
        while True:
            try:
                assert client.call("echo", 3) == 3
                break
            except GridError:
                if time.time() > deadline:
                    raise
                time.sleep(0.1)
    finally:
        client.close()
        srv2.stop()


# ---------------------------------------------------------------------------
# remote StorageAPI
# ---------------------------------------------------------------------------

@pytest.fixture
def remote_drive(tmp_path):
    local = LocalStorage(str(tmp_path / "drv"))
    srv = GridServer(0, host="127.0.0.1")
    StorageRPCService({local.root: local}).register_into(srv)
    srv.start()
    rem = RemoteStorage("127.0.0.1", srv.port, local.root)
    yield local, rem
    srv.stop()


def test_remote_storage_round_trip(remote_drive):
    local, rem = remote_drive
    rem.make_vol("vol")
    assert rem.stat_vol("vol").name == "vol"
    rem.write_all("vol", "cfg/x.json", b"{}")
    assert rem.read_all("vol", "cfg/x.json") == b"{}"
    big = os.urandom(9 << 20)        # > one chunk: chunked create path
    rem.create_file("vol", "obj/data/part.1", big)
    assert rem.read_file("vol", "obj/data/part.1") == big
    assert rem.read_file("vol", "obj/data/part.1", offset=100,
                         length=50) == big[100:150]
    assert rem.stat_info_file("vol", "obj/data/part.1").st_size == len(big)
    with pytest.raises(FileNotFoundErr):
        rem.read_all("vol", "missing")


def test_remote_storage_versions_and_walk(remote_drive):
    local, rem = remote_drive
    rem.make_vol("b")
    fi = FileInfo(volume="b", name="k", version_id="", mod_time=123,
                  size=3, metadata={"etag": "abc"},
                  parts=[ObjectPartInfo(number=1, size=3, actual_size=3)],
                  erasure=ErasureInfo(data_blocks=2, parity_blocks=1,
                                      block_size=1 << 20, index=1,
                                      distribution=(1, 2, 3)),
                  inline_data=b"xyz")
    rem.write_metadata("b", "k", fi)
    got = rem.read_version("b", "k", read_data=True)
    assert got.size == 3 and got.inline_data == b"xyz"
    assert got.erasure.distribution == (1, 2, 3)
    assert [v.name for v in rem.list_versions("b", "k")] == ["k"]
    walked = list(rem.walk_dir("b"))
    assert walked and walked[0][0] == "k"
    # Same journal bytes the local drive sees.
    assert walked[0][1] == local.read_all("b", os.path.join("k", "xl.meta"))
    rem.delete_version("b", "k")
    with pytest.raises(FileNotFoundErr):
        rem.read_version("b", "k")


def test_remote_rename_data_commit(remote_drive):
    local, rem = remote_drive
    rem.make_vol("b")
    rem.make_vol_if_missing(".mtpu.sys")
    fi = FileInfo(volume="b", name="obj", data_dir="dd-1", mod_time=5,
                  size=4, erasure=ErasureInfo(data_blocks=1, parity_blocks=0,
                                              block_size=1 << 20, index=1,
                                              distribution=(1,)))
    rem.create_file(".mtpu.sys", "staging/u1/dd-1/part.1", b"data")
    rem.rename_data(".mtpu.sys", "staging/u1", fi, "b", "obj")
    got = rem.read_version("b", "obj")
    assert got.data_dir == "dd-1" and got.size == 4
    assert rem.read_file("b", "obj/dd-1/part.1") == b"data"


def test_erasure_set_over_remote_drives(tmp_path):
    """A full ErasureSet where half the drives are remote."""
    locals_ = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    srv = GridServer(0, host="127.0.0.1")
    StorageRPCService({d.root: d for d in locals_}).register_into(srv)
    srv.start()
    try:
        from minio_tpu.object.erasure_object import ErasureSet
        disks = [locals_[0], locals_[1],
                 RemoteStorage("127.0.0.1", srv.port, locals_[2].root),
                 RemoteStorage("127.0.0.1", srv.port, locals_[3].root)]
        es = ErasureSet(disks)
        es.make_bucket("bkt")
        data = os.urandom(3 << 20)
        es.put_object("bkt", "obj", data)
        _, got = es.get_object("bkt", "obj")
        assert got == data
        # All 4 drives hold shards (2 written over RPC).
        for d in locals_:
            assert d.read_version("bkt", "obj").size == len(data)
        info = es.list_objects("bkt")
        assert [o.name for o in info.objects] == ["obj"]
        es.delete_object("bkt", "obj")
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# dsync
# ---------------------------------------------------------------------------

def _lockers(n=3, remote=False):
    servers = [LockServer() for _ in range(n)]
    if not remote:
        return servers, [LocalLocker(s) for s in servers]
    grids, lks = [], []
    for s in servers:
        g = GridServer(0, host="127.0.0.1")
        s.register_into(g)
        g.start()
        grids.append(g)
        lks.append(RemoteLocker(GridClient("127.0.0.1", g.port)))
    return servers, lks, grids


def test_dsync_mutual_exclusion():
    _, lks = _lockers(3)
    m1 = DRWMutex(lks, "b/o")
    m2 = DRWMutex(lks, "b/o")
    assert m1.lock(write=True, timeout=1)
    assert not m2.lock(write=True, timeout=0.3)
    m1.unlock()
    assert m2.lock(write=True, timeout=1)
    m2.unlock()


def test_dsync_readers_share():
    _, lks = _lockers(3)
    r1 = DRWMutex(lks, "b/o")
    r2 = DRWMutex(lks, "b/o")
    w = DRWMutex(lks, "b/o")
    assert r1.lock(write=False, timeout=1)
    assert r2.lock(write=False, timeout=1)
    assert not w.lock(write=True, timeout=0.3)
    r1.unlock()
    r2.unlock()
    assert w.lock(write=True, timeout=1)
    w.unlock()


def test_dsync_quorum_with_one_locker_down():
    servers, lks, grids = _lockers(3, remote=True)
    grids[2].stop()          # one lock server dies
    time.sleep(0.1)
    m = DRWMutex(lks, "b/o")
    assert m.lock(write=True, timeout=3)   # 2/3 still a quorum
    m2 = DRWMutex(lks, "b/o")
    assert not m2.lock(write=True, timeout=0.3)
    m.unlock()
    for g in grids[:2]:
        g.stop()


def test_dsync_expiry_frees_crashed_holder():
    servers = [LockServer(ttl=0.2) for _ in range(3)]
    lks = [LocalLocker(s) for s in servers]
    m1 = DRWMutex(lks, "b/o")
    assert m1.lock(write=True, timeout=1)
    # Simulate holder crash: no unlock, no refresh; TTL frees it.
    m1._stop_refresh.set()
    time.sleep(0.35)
    m2 = DRWMutex(lks, "b/o")
    assert m2.lock(write=True, timeout=1)
    m2.unlock()


def test_dist_nslock_interface():
    _, lks = _lockers(3)
    ns = DistNSLock(lks)
    with ns.write("b", "o"):
        from minio_tpu.object.nslock import LockTimeout
        with pytest.raises(LockTimeout):
            with ns.write("b", "o", timeout=0.3):
                pass
    with ns.read("b", "o"):
        with ns.read("b", "o"):
            pass


# ---------------------------------------------------------------------------
# peer control plane
# ---------------------------------------------------------------------------

def test_peer_notifier_reload_handler():
    """PeerNotifier fan-out reaches a registered reload handler and
    drops the right caches (reference: cmd/notification.go)."""
    from minio_tpu.grid.peers import (PeerNotifier, RELOAD_HANDLER,
                                      make_reload_handler)

    class FakeIAM:
        invalidated = 0

        def invalidate(self):
            self.invalidated += 1

    class FakeLayer:
        dropped = None

        def invalidate_bucket_meta(self, bucket=""):
            self.dropped = bucket

    applied = []
    iam, layer = FakeIAM(), FakeLayer()
    srv = GridServer(0, host="127.0.0.1")
    srv.register(RELOAD_HANDLER, make_reload_handler(
        iam=iam, object_layer=layer,
        apply_config=lambda: applied.append(1)))
    srv.start()
    try:
        n = PeerNotifier([GridClient("127.0.0.1", srv.port)])
        n.broadcast("iam")
        n.broadcast("bucket-meta", bucket="bkt")
        n.broadcast("config")
        assert iam.invalidated == 1
        assert layer.dropped == "bkt"
        assert applied == [1]
        # Unknown kinds and dead peers are silently tolerated.
        n.broadcast("future-kind")
        dead = PeerNotifier([GridClient("127.0.0.1", 1)], timeout=0.5)
        dead.broadcast("iam")
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# multi-process cluster (verify-healing style)
# ---------------------------------------------------------------------------

BASE = 9480


def _node_cmd(idx: int, endpoints: list[str], base: int = BASE,
              extra: tuple = ()) -> list[str]:
    return [sys.executable, "-m", "minio_tpu.server",
            "--address", f"127.0.0.1:{base + idx}",
            "--ec-backend", "host", "--boot-timeout", "60",
            *extra, *endpoints]


def _spawn(idx, endpoints, tmp_path, base: int = BASE, extra: tuple = ()):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    log = open(tmp_path / f"node{idx}.log", "wb")
    return subprocess.Popen(_node_cmd(idx, endpoints, base, extra),
                            stdout=log, stderr=subprocess.STDOUT, env=env)


def _wait_ready(tmp_path, idx, timeout=90):
    deadline = time.time() + timeout
    path = tmp_path / f"node{idx}.log"
    while time.time() < deadline:
        if path.exists() and b"serving S3" in path.read_bytes():
            return
        time.sleep(0.5)
    raise TimeoutError(
        f"node {idx} not ready:\n{path.read_bytes().decode()[-2000:]}")


def test_two_node_change_propagation(tmp_path):
    """Bucket-metadata and IAM changes made on one node take effect on
    the other IMMEDIATELY via the peer control plane — no TTL sleeps
    anywhere in this test (reference: cmd/peer-rest-client.go:304
    fan-out on every shared-state write)."""
    import json as _json
    base = 9484
    endpoints = []
    for n in range(2):
        for d in range(2):
            os.makedirs(tmp_path / f"n{n}" / f"d{d}")
            endpoints.append(
                f"http://127.0.0.1:{base + n}{tmp_path}/n{n}/d{d}")

    procs = []
    try:
        for n in range(2):
            procs.append(_spawn(n, endpoints, tmp_path, base=base,
                                extra=("--scanner-interval", "0")))
        for n in range(2):
            _wait_ready(tmp_path, n)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from s3client import S3Client
        c0 = S3Client(f"127.0.0.1:{base}")
        c1 = S3Client(f"127.0.0.1:{base + 1}")

        # --- bucket metadata: versioning toggle ------------------------
        assert c0.request("PUT", "/propbkt")[0] == 200
        # Warm node1's bucket-meta cache with versioning OFF.
        assert c1.request("PUT", "/propbkt/obj", body=b"v1")[0] == 200
        # Toggle versioning via node0; node1 must see it on the very
        # next write (stale cache would overwrite without a version).
        vxml = (b'<VersioningConfiguration><Status>Enabled</Status>'
                b'</VersioningConfiguration>')
        st, _, b = c0.request("PUT", "/propbkt", query={"versioning": ""},
                              body=vxml)
        assert st == 200, b
        assert c1.request("PUT", "/propbkt/obj", body=b"v2")[0] == 200
        st, _, listing = c1.request("GET", "/propbkt",
                                    query={"versions": ""})
        assert st == 200
        assert listing.count(b"<Version>") == 2, listing

        # --- IAM: credential revocation --------------------------------
        st, _, b = c0.request("PUT", "/minio/admin/v3/add-user",
                              query={"accessKey": "tempu"},
                              body=_json.dumps(
                                  {"secretKey": "tempsecret1"}).encode())
        assert st == 200, b
        st, _, b = c0.request(
            "PUT", "/minio/admin/v3/set-user-or-group-policy",
            query={"userOrGroup": "tempu", "policyName": "readwrite"})
        assert st == 200, b
        u1 = S3Client(f"127.0.0.1:{base + 1}", access_key="tempu",
                      secret_key="tempsecret1")
        # Warm node1's IAM cache: the user works there.
        st, _, got = u1.request("GET", "/propbkt/obj")
        assert st == 200 and got == b"v2"
        # Revoke via node0; node1 must refuse the NEXT request.
        st, _, b = c0.request("DELETE", "/minio/admin/v3/remove-user",
                              query={"accessKey": "tempu"})
        assert st == 200, b
        assert u1.request("GET", "/propbkt/obj")[0] == 403
    finally:
        for p in procs:
            try:
                p.send_signal(signal.SIGKILL)
            except OSError:
                pass


@pytest.mark.slow
def test_three_node_cluster_kill_and_heal(tmp_path):
    """3 nodes x 2 drives (EC 3+3): write via node0, read via node1, kill
    node2 mid-workload, keep serving, restart, verify heal repairs its
    drives — the shape of buildscripts/verify-healing.sh."""
    sys_path = tmp_path
    endpoints = []
    for n in range(3):
        for d in range(2):
            os.makedirs(tmp_path / f"n{n}" / f"d{d}")
            endpoints.append(
                f"http://127.0.0.1:{BASE + n}{tmp_path}/n{n}/d{d}")
    procs = {}
    try:
        for n in range(3):
            procs[n] = _spawn(n, endpoints, tmp_path)
        for n in range(3):
            _wait_ready(tmp_path, n)

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from s3client import S3Client
        c0 = S3Client(f"127.0.0.1:{BASE}")
        c1 = S3Client(f"127.0.0.1:{BASE + 1}")

        st, _, b = c0.request("PUT", "/dbkt")
        assert st == 200, b
        payload = os.urandom(2 << 20)
        st, _, b = c0.request("PUT", "/dbkt/obj1", body=payload)
        assert st == 200, b
        # Cross-node read: node1 reads shards from node0/node2 drives.
        st, _, got = c1.request("GET", "/dbkt/obj1")
        assert st == 200 and got == payload

        # Kill node2; cluster keeps serving (EC 3+3, write quorum 4 of
        # the 4 remaining drives).
        procs[2].send_signal(signal.SIGKILL)
        procs[2].wait(timeout=10)
        payload2 = os.urandom(1 << 20)
        deadline = time.time() + 30
        while True:
            st, _, b = c0.request("PUT", "/dbkt/obj2", body=payload2)
            if st == 200:
                break
            assert time.time() < deadline, b
            time.sleep(1)
        st, _, got = c1.request("GET", "/dbkt/obj2")
        assert st == 200 and got == payload2
        st, _, got = c1.request("GET", "/dbkt/obj1")
        assert st == 200 and got == payload

        # Restart node2: its drives missed obj2; a read through node0
        # sees the gap and MRF-heals it in the background.
        procs[2] = _spawn(2, endpoints, tmp_path)
        _wait_ready(tmp_path, 2)
        st, _, got = c0.request("GET", "/dbkt/obj2")
        assert st == 200 and got == payload2
        deadline = time.time() + 30
        healed = False
        while time.time() < deadline and not healed:
            healed = all(
                os.path.exists(tmp_path / "n2" / f"d{d}" / "dbkt" / "obj2" /
                               "xl.meta") for d in range(2))
            if not healed:
                c0.request("GET", "/dbkt/obj2")
                time.sleep(1)
        assert healed, "node2 drives were not healed after restart"
        # And node2 itself serves the object.
        c2 = S3Client(f"127.0.0.1:{BASE + 2}")
        st, _, got = c2.request("GET", "/dbkt/obj2")
        assert st == 200 and got == payload2
    finally:
        for p in procs.values():
            try:
                p.send_signal(signal.SIGKILL)
            except OSError:
                pass


def test_remote_bulk_windowed_chunks(remote_drive):
    """Windowed (credit-limited) chunk uploads reassemble byte-identical
    regardless of arrival order, including odd sizes straddling chunk
    boundaries."""
    local, rem = remote_drive
    rem.make_vol("wv")
    for size in (4 * (1 << 20) + 17, 12 * (1 << 20) + 3):
        blob = os.urandom(size)
        rem.create_file("wv", f"big-{size}", blob)
        assert rem.read_file("wv", f"big-{size}") == blob
        assert local.read_file("wv", f"big-{size}") == blob

"""I/O engine subsystem: bufpool invariants, per-drive queues, fused
native framing byte-identity, and the pre-forked SO_REUSEPORT worker
front-end (conformance subset + divided admission + aggregation).

The pool invariants the ISSUE pins down:
  * no buffer aliasing across concurrent requests (two live leases
    never share memory; recycled buffers only after the last release);
  * a dropped lease is returned and counted, never lost;
  * hot PUT paths allocate zero fresh window buffers at steady state
    (pool hit rate ~100 % after warmup).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from minio_tpu.io.bufpool import BufferPool
from minio_tpu.io.engine import DriveQueue, EngineSaturated, IOEngine
from minio_tpu.object.erasure_object import ErasureSet
from minio_tpu.storage.local import LocalStorage
from tests.s3client import S3Client


# ---------------------------------------------------------------------------
# bufpool
# ---------------------------------------------------------------------------

def test_lease_recycles_after_release():
    pool = BufferPool(max_per_class=4)
    a = pool.lease(100_000)
    buf_id = id(a.raw)
    a.release()
    b = pool.lease(100_000)
    assert id(b.raw) == buf_id, "released buffer should be recycled"
    st = pool.stats()
    assert st["hits"] == 1 and st["misses"] == 1
    b.release()
    assert pool.stats()["outstanding"] == 0


def test_no_aliasing_between_live_leases():
    """Two live leases never share memory, under concurrency: every
    worker writes its own pattern and re-reads it intact."""
    pool = BufferPool(max_per_class=4)
    errors: list = []

    def worker(tag: int):
        rng = np.random.default_rng(tag)
        for i in range(40):
            lease = pool.lease(65_536)
            view = lease.view(65_536)
            pattern = bytes([tag]) * 65_536
            view[:] = pattern
            time.sleep(rng.uniform(0, 0.002))
            if bytes(view) != pattern:
                errors.append(f"worker {tag} iter {i}: torn buffer")
            lease.release()

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:3]
    assert pool.stats()["outstanding"] == 0
    assert pool.stats()["leaks"] == 0


def test_retained_lease_survives_first_release():
    """The recycled-under-a-live-reader hazard: a retained holder keeps
    the buffer out of the pool until ITS release."""
    pool = BufferPool(max_per_class=4)
    a = pool.lease(70_000)
    marker = b"held-by-writer"
    a.view(len(marker))[:] = marker
    a.retain()
    a.release()                       # original holder done
    b = pool.lease(70_000)            # must NOT alias a's buffer
    assert b.raw is not a.raw
    assert bytes(a.view(len(marker))) == marker
    a.release()                       # retained holder done -> recycled
    c = pool.lease(70_000)
    assert c.raw is a.raw
    b.release()
    c.release()


def test_dropped_lease_returned_and_counted():
    pool = BufferPool(max_per_class=4)
    lease = pool.lease(80_000)
    raw = lease.raw
    del lease                         # dropped without release()
    import gc
    gc.collect()
    st = pool.stats()
    assert st["leaks"] == 1, st
    assert st["outstanding"] == 0
    back = pool.lease(80_000)
    assert back.raw is raw, "leaked buffer should be back in the pool"
    back.release()


def test_double_release_counted_not_corrupting():
    pool = BufferPool(max_per_class=4)
    a = pool.lease(90_000)
    a.release()
    a.release()
    assert pool.stats()["double_releases"] == 1
    b = pool.lease(90_000)
    c = pool.lease(90_000)
    assert b.raw is not c.raw, "double release must not alias leases"
    b.release()
    c.release()


# The stripe batcher's largest staging buffer: a 256-block dispatch of
# route get at EC 8+4, 256 x 8 x (128 KiB + 32) bytes (ops/batcher._stage).
STAGED_GET = 256 * 8 * (131072 + 32)


@pytest.mark.parametrize("size,pooled", [
    ((1 << 26) + 1, True),        # over 64 MiB: the 128 MiB class
    (1 << 28, True),              # a full dispatch of route put
    (STAGED_GET, True),           # ... of route get: the 512 MiB class
    (1 << 29, True),              # the largest class, to the byte
    ((1 << 29) + 1, False),       # over it: unpooled, counted, served
])
def test_oversized_lease_served_unpooled(size, pooled):
    """A lease over the largest class is a fresh mapping that dies with
    its release; up to it the buffer is kept and handed out again.
    Only the pages written are ever resident."""
    pool = BufferPool(max_per_class=2)
    big = pool.lease(size)
    assert big.size == size
    big.view(64)[:] = b"x" * 64
    big.view(size)[size - 1:] = b"y"
    raw = big.raw
    big.release()
    st = pool.stats()
    assert st["oversized"] == (0 if pooled else 1)
    assert st["outstanding"] == 0
    assert (st["idle_bytes"] >= size) == pooled
    again = pool.lease(size)
    assert (again.raw is raw) == pooled
    assert pool.stats()["hits"] == (1 if pooled else 0)
    if pooled:
        # recycled as it was left: the holder zeroes what it must
        assert bytes(again.view(64)) == b"x" * 64
    again.release()
    pool.drain()


def test_staging_sized_lease_is_a_hit_on_the_same_mapping():
    """What the batcher's dispatcher does at depth two, round and
    round: two buffers of the staged size alive, each released and
    leased again — the same two mappings for good, nothing oversized."""
    pool = BufferPool(max_per_class=16)
    a, b = pool.lease(STAGED_GET), pool.lease(STAGED_GET)
    mappings = {id(a.raw), id(b.raw)}
    assert len(mappings) == 2
    for _ in range(3):
        a.release()
        a = pool.lease(STAGED_GET)
        b.release()
        b = pool.lease(STAGED_GET)
        assert {id(a.raw), id(b.raw)} == mappings
    st = pool.stats()
    assert st["misses"] == 2 and st["hits"] == 6 and st["oversized"] == 0
    a.release()
    b.release()
    pool.drain()


def test_large_classes_keep_two_idle_buffers_and_drain_closes_them():
    """The classes above 64 MiB keep two idle buffers whatever
    max_per_class allows the small ones; the third release unmaps.
    drain() drops the large ones with the rest."""
    pool = BufferPool(max_per_class=16)
    leases = [pool.lease(STAGED_GET) for _ in range(3)]
    raws = [ls.raw for ls in leases]
    small = [pool.lease(100_000) for _ in range(3)]
    for ls in leases + small:
        ls.release()
    st = pool.stats()
    assert st["outstanding"] == 0 and st["oversized"] == 0
    assert st["idle_bytes"] == 2 * (1 << 29) + 3 * (1 << 17)
    assert [r.closed for r in raws] == [False, False, True]
    # a smaller cap still rules everywhere
    tight = BufferPool(max_per_class=1)
    pair = [tight.lease(1 << 27), tight.lease(1 << 27)]
    for ls in pair:
        ls.release()
    assert tight.stats()["idle_bytes"] == 1 << 27
    tight.drain()
    pool.drain()
    assert pool.stats()["idle_bytes"] == 0
    assert all(r.closed for r in raws)
    fresh = pool.lease(STAGED_GET)
    assert pool.stats()["misses"] == 3 + 3 + 1
    fresh.release()
    pool.drain()


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def test_drive_queue_runs_and_bounds_depth():
    q = DriveQueue("t0", workers=1, depth=2)
    gate = threading.Event()
    started = threading.Event()

    def blocker():
        started.set()
        gate.wait(10)
        return "done"

    f1 = q.submit(blocker)
    assert started.wait(5)
    # Worker busy; fill the queue past depth.
    f2 = q.submit(lambda: 2)
    f3 = q.submit(lambda: 3)
    from minio_tpu.utils import deadline as deadline_mod
    with deadline_mod.bind(deadline_mod.Deadline(0.2)):
        with pytest.raises(EngineSaturated):
            q.submit(lambda: 4)
    assert q.stats()["rejected_total"] == 1
    gate.set()
    assert f1.result(10) == "done"
    assert f2.result(10) == 2 and f3.result(10) == 3
    q.close()


def test_engine_per_drive_isolation():
    """A backlog on one drive must not delay another drive's ops."""
    eng = IOEngine(["a", "b"], workers=1, depth=16)
    gate = threading.Event()
    eng.submit(0, lambda: gate.wait(10))       # drive 0 wedged
    t0 = time.monotonic()
    assert eng.submit(1, lambda: "fast").result(5) == "fast"
    assert time.monotonic() - t0 < 2.0
    gate.set()
    eng.close()


def test_fanout_via_engine_preserves_quorum_semantics(tmp_path):
    """End-to-end through ErasureSet: per-disk faults stay per-disk."""
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    es = ErasureSet(disks)
    es.make_bucket("engb")
    es.put_object("engb", "k", b"v" * 50_000)
    _, got = es.get_object("engb", "k")
    assert got == b"v" * 50_000
    results, errors = es._fanout(
        [lambda d=d: d.stat_vol("engb") for d in es.disks])
    assert all(e is None for e in errors)
    # Subset fan-outs (cleanup shapes) run too, via the shared pool.
    results, errors = es._fanout(
        [lambda d=d: d.stat_vol("engb") for d in es.disks[:2]])
    assert all(e is None for e in errors)
    es.close()


# ---------------------------------------------------------------------------
# fused framing + steady-state allocation
# ---------------------------------------------------------------------------

def test_frame_windows_byte_identical_to_reference_path(tmp_path):
    """The pooled fused native framing must produce exactly the bytes
    of the numpy encode+frame path, tails included."""
    from minio_tpu import native
    from minio_tpu.storage import bitrot
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(6)]
    es = ErasureSet(disks, parity=2)
    k, m = 4, 2
    rng = np.random.default_rng(7)
    for size in ((1 << 20), (1 << 20) + 12345, 3 * (1 << 20),
                 (1 << 20) - 1, 777):
        data = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
        chunks, lease = es._frame_windows(data, k, m)
        got = [b"".join(bytes(c) for c in row) for row in chunks]
        if lease is not None:
            lease.release()
        shards = es._encode_object(data, k, m)
        want = bitrot.frame_shards_batch(
            shards, es._erasure(k, m).shard_size())
        assert got == [bytes(w) for w in want], f"mismatch at size {size}"
    es.close()

    # k = 5 does not divide the 1 MiB block: the pooled native path is
    # ineligible and the fallback (split full blocks + separate tail
    # framing) must still be byte-identical to whole-object framing.
    disks7 = [LocalStorage(str(tmp_path / f"e{i}")) for i in range(7)]
    es7 = ErasureSet(disks7, parity=2)
    data = rng.integers(0, 256, size=(1 << 20) + 999,
                        dtype=np.uint8).tobytes()
    chunks, lease = es7._frame_windows(data, 5, 2)
    got = [b"".join(bytes(c) for c in row) for row in chunks]
    if lease is not None:
        lease.release()
    want = bitrot.frame_shards_batch(
        es7._encode_object(data, 5, 2), es7._erasure(5, 2).shard_size())
    assert got == [bytes(w) for w in want]
    es7.close()


def test_put_path_pool_hit_rate_steady_state(tmp_path):
    """Acceptance: hot PUT paths allocate zero fresh window buffers at
    steady state — pool hit rate ~100 % after warmup."""
    from minio_tpu import native
    if native.load() is None:
        pytest.skip("native library unavailable; pooled framing off")
    from minio_tpu.io.bufpool import global_pool
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(6)]
    es = ErasureSet(disks, parity=2)
    es.make_bucket("steady")
    body = os.urandom(1 << 20)
    for i in range(4):                      # warmup
        es.put_object("steady", f"warm-{i}", body)
    pool = global_pool()
    before = pool.stats()
    for i in range(12):                     # steady state
        es.put_object("steady", f"hot-{i}", body)
    after = pool.stats()
    assert after["misses"] == before["misses"], \
        "steady-state PUTs allocated fresh window buffers"
    assert after["hits"] >= before["hits"] + 12
    assert after["leaks"] == before["leaks"]
    es.close()


# ---------------------------------------------------------------------------
# pre-forked worker front-end
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def worker_server(tmp_path_factory):
    """A 2-worker pre-forked server on shared drives (subprocess: the
    pytest process has JAX loaded, and fork-after-JAX is unsafe)."""
    root = tmp_path_factory.mktemp("workers")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", MTPU_HTTP_WORKERS="2",
               MTPU_API_REQUESTS_MAX="4",
               MTPU_API_REQUESTS_DEADLINE="100ms")
    proc = subprocess.Popen(
        [sys.executable, "-m", "minio_tpu.server",
         "--address", f"127.0.0.1:{port}", "--scanner-interval", "0",
         f"{root}/d{{1...4}}"],
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
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


def _cli(address):
    return S3Client(address)


def test_workers_conformance_subset(worker_server):
    """The S3 surface behaves across worker processes: bucket + object
    CRUD, listings (fresh after cross-worker writes), ranged GET,
    multipart, delete — each request on a FRESH connection so the
    kernel spreads them over both workers."""
    addr = worker_server
    assert _cli(addr).request("PUT", "/confb")[0] == 200
    body = os.urandom(300_000)
    assert _cli(addr).request("PUT", "/confb/obj1", body=body)[0] == 200
    st, _, got = _cli(addr).request("GET", "/confb/obj1")
    assert st == 200 and got == body
    st, _, part = _cli(addr).request(
        "GET", "/confb/obj1", headers={"Range": "bytes=100-299"})
    assert st == 206 and part == body[100:300]
    for i in range(6):
        st, _, lst = _cli(addr).request("GET", "/confb")
        assert st == 200 and b"obj1" in lst
    # Multipart through whichever workers the kernel picks.
    st, _, resp = _cli(addr).request("POST", "/confb/mp",
                                     query={"uploads": ""})
    assert st == 200
    upload_id = resp.decode().split("<UploadId>")[1].split("<")[0]
    part1 = os.urandom(5 << 20)
    part2 = os.urandom(1 << 20)
    etags = []
    for num, data in ((1, part1), (2, part2)):
        st, hdr, _ = _cli(addr).request(
            "PUT", "/confb/mp",
            query={"partNumber": str(num), "uploadId": upload_id},
            body=data)
        assert st == 200
        etags.append(hdr.get("ETag", hdr.get("etag", '""')))
    complete = "<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{n}</PartNumber><ETag>{e}</ETag></Part>"
        for n, e in zip((1, 2), etags)) + "</CompleteMultipartUpload>"
    st, _, _ = _cli(addr).request("POST", "/confb/mp",
                                  query={"uploadId": upload_id},
                                  body=complete.encode())
    assert st == 200
    st, _, got = _cli(addr).request("GET", "/confb/mp")
    assert st == 200 and got == part1 + part2
    assert _cli(addr).request("DELETE", "/confb/mp")[0] == 204
    assert _cli(addr).request("DELETE", "/confb/obj1")[0] == 204
    for i in range(4):
        st, _, lst = _cli(addr).request("GET", "/confb")
        assert b"obj1" not in lst, "cross-worker stale listing"


def test_workers_admission_divided_and_shedding(worker_server):
    """MTPU_API_REQUESTS_MAX=4 over 2 workers -> 2 slots per worker;
    a burst of slow-ish requests must shed with 503 + Retry-After
    while in-quorum traffic still succeeds."""
    addr = worker_server
    st, _, info = _cli(addr).request("GET", "/minio/admin/v3/info")
    assert st == 200
    j = json.loads(info)
    assert j["admission"]["s3"]["limit"] == 2, \
        "admission budget not divided across workers"
    assert len(j.get("workers", [])) == 2
    body = os.urandom(1 << 20)
    _cli(addr).request("PUT", "/admb")
    results: list = []
    mu = threading.Lock()

    def put_one(i):
        try:
            st, hdr, _ = _cli(addr).request("PUT", f"/admb/o{i}",
                                            body=body)
            with mu:
                results.append((st, hdr))
        except Exception as e:  # noqa: BLE001 - recorded
            with mu:
                results.append((0, {"error": str(e)}))

    threads = [threading.Thread(target=put_one, args=(i,))
               for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    statuses = [s for s, _ in results]
    assert statuses.count(200) >= 4, statuses
    shed = [(s, h) for s, h in results if s == 503]
    for s, h in shed:
        retry = {k.lower(): v for k, v in h.items()}.get("retry-after")
        assert retry is not None, "503 without Retry-After"


def test_workers_metrics_aggregate(worker_server):
    """A /metrics scrape served by EITHER worker reports the whole
    fleet: per-worker in-flight gauges and fleet-total counters."""
    addr = worker_server
    _cli(addr).request("PUT", "/aggb")
    for i in range(4):
        _cli(addr).request("PUT", f"/aggb/m{i}", body=b"x" * 1000)
    st, _, met = _cli(addr).request("GET", "/minio/v2/metrics/cluster")
    assert st == 200
    text = met.decode()
    assert 'minio_tpu_worker_in_flight{worker="0"}' in text
    assert 'minio_tpu_worker_in_flight{worker="1"}' in text
    assert "minio_tpu_workers_total 2" in text
    assert "minio_tpu_bufpool_hits_total" in text
    assert "minio_tpu_drive_queue_depth" in text
    # Fleet-total request counters: the PUTs above must be visible in
    # a scrape no matter which worker serves it.
    total = 0
    for line in text.splitlines():
        if line.startswith("minio_tpu_http_requests_total{") \
                and 'api="PUT:object"' in line:
            total += int(float(line.rsplit(" ", 1)[1]))
    assert total >= 4, text[:1000]


def test_shared_gen_poll_interval(tmp_path):
    """Rate-limited SharedGen (the bucket-meta generation): calls
    inside the window reuse the last verdict, a sibling's bump is
    observed once the window expires, and our OWN bump resets the
    window so bump+check in one process never misses itself."""
    from minio_tpu.io.workers import SharedGen

    path = str(tmp_path / "meta.gen")
    writer = SharedGen(path)
    observer = SharedGen(path, poll_interval=3600.0)
    assert observer.changed() is True        # first look always syncs
    writer.bump()
    assert observer.changed() is False, \
        "inside the poll window the cached verdict must be reused"
    observer._polled_at = 0.0                # window expiry
    assert observer.changed() is True
    assert observer.changed() is False       # re-armed, no new bump
    observer.bump()                          # own bump resets window
    assert observer.changed() is True
    # The un-rate-limited writer still observes every change.
    observer.bump()
    assert writer.changed() is True

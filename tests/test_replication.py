"""Bucket replication: rules, async replication to a second live
cluster, status lifecycle, delete-marker replication, scanner resync,
durable WAL + replay, per-target breaker lanes, ordering, two-cluster
chaos convergence (reference: cmd/bucket-replication.go,
internal/bucket/replication)."""

import json
import os
import re
import time

import pytest

from minio_tpu.object.erasure_object import ErasureSet
from minio_tpu.object.scanner import Scanner
from minio_tpu.replication import (ReplicationEngine, ReplicationError,
                                   parse_replication_xml)
from minio_tpu.replication.engine import (BreakerOpen, LaneBreaker,
                                          ReplWAL)
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.local import LocalStorage
from tests.batcher_rig import until
from tests.s3client import S3Client

REPL_XML = b"""<ReplicationConfiguration>
  <Role>arn:minio:replication::r1:role</Role>
  <Rule><ID>r1</ID><Status>Enabled</Status><Priority>1</Priority>
    <DeleteMarkerReplication><Status>Enabled</Status>
    </DeleteMarkerReplication>
    <Destination><Bucket>arn:aws:s3:::dstb</Bucket></Destination>
  </Rule>
</ReplicationConfiguration>"""


def test_parse_replication_rules():
    rules = parse_replication_xml(REPL_XML)
    assert len(rules) == 1
    assert rules[0].enabled and rules[0].delete_markers
    assert rules[0].matches("any/key")
    with pytest.raises(ReplicationError):
        parse_replication_xml(b"<ReplicationConfiguration/>")
    with pytest.raises(ReplicationError):
        parse_replication_xml(
            b"<ReplicationConfiguration><Rule><ID>x</ID></Rule>"
            b"</ReplicationConfiguration>")


@pytest.fixture
def clusters(tmp_path):
    """Source (with replication engine) and target clusters."""
    src_disks = [LocalStorage(str(tmp_path / f"s{i}")) for i in range(4)]
    dst_disks = [LocalStorage(str(tmp_path / f"t{i}")) for i in range(4)]
    src_es, dst_es = ErasureSet(src_disks), ErasureSet(dst_disks)
    src = S3Server(src_es, address="127.0.0.1:0")
    dst = S3Server(dst_es, address="127.0.0.1:0")
    src.replicator = ReplicationEngine(src_es)
    src.start()
    dst.start()
    sc = S3Client(src.address)
    dc = S3Client(dst.address)
    assert sc.request("PUT", "/srcb")[0] == 200
    assert dc.request("PUT", "/dstb")[0] == 200
    # Register the remote target + rules on the source bucket.
    st, _, b = sc.request("PUT", "/minio/admin/v3/set-remote-target",
                          query={"bucket": "srcb"},
                          body=json.dumps({
                              "endpoint": dst.address,
                              "accessKey": "minioadmin",
                              "secretKey": "minioadmin",
                              "bucket": "dstb"}).encode())
    assert st == 200, b
    st, _, b = sc.request("PUT", "/srcb", query={"replication": ""},
                          body=REPL_XML)
    assert st == 200, b
    yield src, dst, sc, dc, src_es
    src.replicator.stop()
    src.stop()
    dst.stop()


def test_put_replicates_and_status_completes(clusters):
    src, dst, sc, dc, src_es = clusters
    body = b"replicate me" * 1000
    st, _, _ = sc.request("PUT", "/srcb/doc.txt", body=body,
                          headers={"x-amz-meta-team": "infra",
                                   "x-amz-tagging": "env=prod"})
    assert st == 200
    assert src.replicator.drain(15)
    # Replica landed with metadata and tags.
    st, hh, got = dc.request("GET", "/dstb/doc.txt")
    assert st == 200 and got == body
    assert hh.get("x-amz-meta-team") == "infra"
    assert hh.get("x-amz-meta-mtpu-replica") == "true"
    # Source status header reaches COMPLETED.
    for _ in range(50):
        st, hh, _ = sc.request("HEAD", "/srcb/doc.txt")
        if hh.get("x-amz-replication-status") == "COMPLETED":
            break
        time.sleep(0.1)
    assert hh.get("x-amz-replication-status") == "COMPLETED"


def test_delete_replicates(clusters):
    src, dst, sc, dc, src_es = clusters
    sc.request("PUT", "/srcb/gone.txt", body=b"x")
    assert src.replicator.drain(15)
    assert dc.request("GET", "/dstb/gone.txt")[0] == 200
    sc.request("DELETE", "/srcb/gone.txt")
    assert src.replicator.drain(15)
    assert dc.request("GET", "/dstb/gone.txt")[0] == 404


def test_get_remote_target_hides_secret(clusters):
    src, dst, sc, dc, src_es = clusters
    st, _, b = sc.request("GET", "/minio/admin/v3/get-remote-target",
                          query={"bucket": "srcb"})
    assert st == 200
    rec = json.loads(b)
    assert rec["endpoint"] == dst.address
    assert "secretKey" not in rec


def test_scanner_resyncs_failed_replication(tmp_path):
    """Target down at PUT time: status FAILED; once the target is back,
    the scanner hook re-queues and completes."""
    src_disks = [LocalStorage(str(tmp_path / f"s{i}")) for i in range(4)]
    dst_disks = [LocalStorage(str(tmp_path / f"t{i}")) for i in range(4)]
    src_es, dst_es = ErasureSet(src_disks), ErasureSet(dst_disks)
    src = S3Server(src_es, address="127.0.0.1:0")
    engine = ReplicationEngine(src_es)
    engine._RETRIES = 1          # fail fast for the test
    src.replicator = engine
    src.start()
    sc = S3Client(src.address)
    assert sc.request("PUT", "/srcb")[0] == 200
    # Point at a dead endpoint for now.
    sc.request("PUT", "/minio/admin/v3/set-remote-target",
               query={"bucket": "srcb"},
               body=json.dumps({"endpoint": "127.0.0.1:1",
                                "accessKey": "minioadmin",
                                "secretKey": "minioadmin",
                                "bucket": "dstb"}).encode())
    sc.request("PUT", "/srcb", query={"replication": ""}, body=REPL_XML)
    sc.request("PUT", "/srcb/lost.txt", body=b"data")
    assert engine.drain(15)
    st, hh, _ = sc.request("HEAD", "/srcb/lost.txt")
    assert hh.get("x-amz-replication-status") == "FAILED"

    # Target comes up; fix the remote-target record.
    dst = S3Server(dst_es, address="127.0.0.1:0")
    dst.start()
    dc = S3Client(dst.address)
    assert dc.request("PUT", "/dstb")[0] == 200
    sc.request("PUT", "/minio/admin/v3/set-remote-target",
               query={"bucket": "srcb"},
               body=json.dumps({"endpoint": dst.address,
                                "accessKey": "minioadmin",
                                "secretKey": "minioadmin",
                                "bucket": "dstb"}).encode())
    scanner = Scanner([src_es], throttle=0)
    scanner.on_object.append(engine.scanner_hook)
    scanner.scan_cycle()
    assert engine.drain(15)
    assert dc.request("GET", "/dstb/lost.txt")[2] == b"data"
    engine.stop()
    src.stop()
    dst.stop()

# ---------------------------------------------------------------------------
# v2 durable plane: breaker, WAL, spill, ordering
# ---------------------------------------------------------------------------


def test_breaker_trip_probe_recover():
    """Trip after N consecutive transport faults, admit exactly one
    half-open probe per cooldown window, double the cooldown on a
    failed probe, reset fully on success."""
    br = LaneBreaker(trip_after=3, cooldown=0.05, cooldown_max=0.4)
    for _ in range(3):
        br.admit()
        br.fault()
    assert br.state() == "open"
    with pytest.raises(BreakerOpen):
        br.admit()
    time.sleep(0.08)           # > cooldown * 1.25 (max jitter)
    assert br.state() == "half-open"
    br.admit()                 # takes the single probe slot
    with pytest.raises(BreakerOpen):
        br.admit()             # concurrent probe denied
    br.fault()                 # probe failed: cooldown doubles
    assert br.state() == "open"
    with pytest.raises(BreakerOpen):
        br.admit()
    time.sleep(0.15)           # > 2 * cooldown * 1.25
    br.admit()                 # next probe
    br.ok()                    # probe succeeded: fully closed
    assert br.state() == "closed"
    br.admit()


def test_wal_replay_and_torn_tail(tmp_path):
    """Incomplete intents replay from a dead instance's WAL; done
    intents and torn tail bytes do not; retired files are not replayed
    twice (idempotence)."""
    w1 = ReplWAL(str(tmp_path), fsync=False)
    w1.append_intent({"seq": 1, "b": "b", "k": "k1", "v": "",
                      "op": "put", "mt": 1})
    w1.append_intent({"seq": 2, "b": "b", "k": "k2", "v": "",
                      "op": "put", "mt": 2})
    w1.append_intent({"seq": 3, "b": "b", "k": "k2", "v": "",
                      "op": "put", "mt": 2})     # dup of k2 intent
    w1.mark_done(1)
    with open(w1.path, "ab") as fh:
        fh.write(b"RPW1torn-frame-garbage")      # simulated torn append
    w2 = ReplWAL(str(tmp_path), fsync=False)
    recs = w2.replay_others()
    # k1 completed, k2 deduped to one intent, garbage discarded.
    assert [r["k"] for r in recs] == ["k2"]
    assert w2.discarded >= 1
    w2.retire_replayed()
    assert not os.path.exists(w1.path)
    w3 = ReplWAL(str(tmp_path), fsync=False)
    assert w3.replay_others() == []
    for w in (w2, w3):
        w.close()


def test_probe_that_fails_off_the_wire_gives_the_slot_back(tmp_path):
    """A half-open probe whose delivery fails WITHOUT a transport fault
    (here: the source object is gone before the wire is touched) says
    nothing about the target: the slot goes back at once and the next
    chain may probe. It used to stay taken for PROBE_TTL (30 s) — the
    tail of test_chaos_target_kill_restart_converges, whose k0 PUT may
    still be queued when k0 is deleted."""
    from minio_tpu.utils import tracing
    es, eng = _solo_engine(tmp_path)
    try:
        eng.enqueue("srcb", "gone", "", "put", mod_time=1)
        lane = eng._lanes["127.0.0.1:1"]
        for _ in range(3):
            lane.breaker.admit()
            lane.breaker.fault()
        lane.breaker._open_since -= 100      # the cooldown is over
        assert lane.breaker.state() == "half-open"
        eng._service_inner("127.0.0.1:1", ("srcb", "gone"), tracing.NOOP)
        assert lane.chains[("srcb", "gone")][0].attempt == 1
        assert lane.breaker.faults_total == 3     # no fault counted
        assert lane.breaker.state() == "half-open"
        lane.breaker.admit()                  # the next probe, now
        with pytest.raises(BreakerOpen):
            lane.breaker.admit()
    finally:
        eng.stop()


def _solo_engine(tmp_path, endpoint="127.0.0.1:1", workers=0, **kw):
    """Engine over a real ErasureSet with replication config planted
    directly in bucket meta — no HTTP server, workers=0 leaves intents
    queued for introspection."""
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    es = ErasureSet(disks)
    es.make_bucket("srcb")
    meta = es.get_bucket_meta("srcb")
    meta["config:replication"] = REPL_XML.decode()
    meta["config:remote-target"] = json.dumps(
        {"endpoint": endpoint, "accessKey": "a", "secretKey": "s",
         "bucket": "dstb"})
    es.set_bucket_meta("srcb", meta)
    return es, ReplicationEngine(es, workers=workers, **kw)


def test_chain_orders_by_source_version(tmp_path):
    """Intents for one key queue in source-version order regardless of
    arrival order — the target's latest is the source's latest."""
    es, eng = _solo_engine(tmp_path)
    try:
        eng.enqueue("srcb", "k", "v-new", "put", mod_time=300)
        eng.enqueue("srcb", "k", "v-old", "put", mod_time=100)
        eng.enqueue("srcb", "k", "v-mid", "put", mod_time=200)
        lane = eng._lanes["127.0.0.1:1"]
        chain = lane.chains[("srcb", "k")]
        assert [i.version_id for i in chain] == ["v-old", "v-mid",
                                                "v-new"]
        # Duplicate intents dedup instead of stacking.
        eng.enqueue("srcb", "k", "v-mid", "put", mod_time=200)
        assert len(lane.chains[("srcb", "k")]) == 3
    finally:
        eng.stop()


def test_overflow_spills_never_drops(tmp_path):
    """queue.Full used to count as `failed` and LOSE the intent; now it
    spills to the persisted pending set and replays on the next boot."""
    es, eng = _solo_engine(tmp_path)
    try:
        eng._q_max = 2
        for i in range(5):
            eng.enqueue("srcb", f"k{i}", f"v{i}", "put", mod_time=i)
        assert eng.spilled == 3
        assert eng.dropped == 0
        assert eng.stats()["spill_backlog"] == 3
        assert eng.stats()["pending"] == 5
    finally:
        eng.stop()          # persists the spill set
    pending = tmp_path / "d0" / ".mtpu.sys" / "repl" / "pending.json"
    assert pending.exists()
    items = json.loads(pending.read_text())["items"]
    assert {r["k"] for r in items} == {"k2", "k3", "k4"}


def test_spill_drain_clears_pending_file(tmp_path):
    """Draining the spill set must not leave a stale pending.json
    behind: a stale file would re-enqueue already-delivered intents at
    the next boot (an old PUT replayed after a completed DELETE
    regresses the target's latest)."""
    es, eng = _solo_engine(tmp_path)
    pending = tmp_path / "d0" / ".mtpu.sys" / "repl" / "pending.json"
    try:
        eng._q_max = 1
        for i in range(3):
            eng.enqueue("srcb", f"k{i}", f"v{i}", "put", mod_time=i)
        with eng._mu:
            eng._maybe_save_spill_locked(force=True)
        assert pending.exists()
        # Room frees up (deliveries would drive this via _finish).
        eng._q_max = 100
        eng._refill_one()
        eng._refill_one()
        assert eng.stats()["spill_backlog"] == 0
        # The drain-to-empty refill removed the file immediately.
        assert not pending.exists()
    finally:
        eng.stop()
    assert not pending.exists()
    eng2 = ReplicationEngine(es, workers=0)
    try:
        assert eng2.stats()["spill_backlog"] == 0
    finally:
        eng2.stop()


def test_stop_unlinks_stale_pending_file(tmp_path):
    """stop() persists the spill state UNCONDITIONALLY: an engine whose
    spill drained between throttled saves removes the on-disk file at
    shutdown instead of leaving delivered intents listed."""
    es, eng = _solo_engine(tmp_path)
    pending = tmp_path / "d0" / ".mtpu.sys" / "repl" / "pending.json"
    eng._q_max = 1
    for i in range(2):
        eng.enqueue("srcb", f"k{i}", f"v{i}", "put", mod_time=i)
    with eng._mu:
        eng._maybe_save_spill_locked(force=True)
        # Simulate deliveries draining the spill with every throttled
        # save window missed.
        eng._spill.clear()
    assert pending.exists()
    eng.stop()
    assert not pending.exists()


def test_engine_restart_replays_wal_and_spill(tmp_path):
    """SIGKILL simulation: engine 1 dies (no stop()) with queued +
    spilled intents; engine 2 on the same node root replays every
    incomplete intent exactly once."""
    es, eng1 = _solo_engine(tmp_path)
    eng1._q_max = 2
    for i in range(4):
        eng1.enqueue("srcb", f"k{i}", f"v{i}", "put", mod_time=i)
    # Persist the spill set the way the throttled saver eventually
    # would, then abandon eng1 WITHOUT stop() — a crash, not a drain.
    with eng1._mu:
        eng1._maybe_save_spill_locked(force=True)
    eng2 = ReplicationEngine(es, workers=0)
    try:
        st = eng2.stats()
        # 2 chained intents replay from eng1's WAL; 2 more load from
        # pending.json; the idk dedup keeps each exactly once.
        assert st["pending"] == 4
        assert eng2.replayed >= 2
        lane = eng2._lanes["127.0.0.1:1"]
        keys = set(lane.chains) | {(r["b"], r["k"])
                                   for r in eng2._spill.values()}
        assert keys == {("srcb", f"k{i}") for i in range(4)}
    finally:
        eng2.stop()


def test_sse_versions_skip_with_accounting(tmp_path):
    """SSE objects never replicate: delivery is terminal on the first
    attempt, counted in sse_skipped (not retried, not a lane fault)."""
    from minio_tpu.object.types import PutOptions
    es, eng = _solo_engine(tmp_path, workers=2)
    try:
        info = es.put_object(
            "srcb", "enc", b"cipherbytes",
            PutOptions(internal_metadata={"x-internal-sse-alg":
                                          "AES256"}))
        eng.enqueue("srcb", "enc", info.version_id, "put",
                    mod_time=info.mod_time)
        assert eng.drain(10)
        assert eng.sse_skipped == 1
        assert eng.completed == 0
    finally:
        eng.stop()


def test_replica_delete_does_not_ping_pong(clusters):
    """A DELETE carrying the replica marker header (i.e. arriving FROM
    a peer) must not re-enqueue — active-active pairs would bounce
    delete markers forever."""
    src, dst, sc, dc, src_es = clusters
    sc.request("PUT", "/srcb/pp.txt", body=b"x")
    assert src.replicator.drain(15)
    before = src.replicator.queued
    st, _, _ = sc.request(
        "DELETE", "/srcb/pp.txt",
        headers={"x-amz-meta-mtpu-replica": "true"})
    assert st == 204
    assert src.replicator.queued == before


def test_versioned_delete_marker_replicates_with_status(tmp_path):
    """Versioned buckets: the marker replicates as a versioned marker
    (object 404s on the target), and the SOURCE marker itself carries
    PENDING -> COMPLETED status so the scanner can resync it."""
    from minio_tpu.replication import REPL_STATUS_KEY
    src_disks = [LocalStorage(str(tmp_path / f"s{i}")) for i in range(4)]
    dst_disks = [LocalStorage(str(tmp_path / f"t{i}")) for i in range(4)]
    src_es, dst_es = ErasureSet(src_disks), ErasureSet(dst_disks)
    src = S3Server(src_es, address="127.0.0.1:0")
    dst = S3Server(dst_es, address="127.0.0.1:0")
    src.replicator = ReplicationEngine(src_es)
    src.start()
    dst.start()
    sc, dc = S3Client(src.address), S3Client(dst.address)
    try:
        assert sc.request("PUT", "/srcb")[0] == 200
        assert dc.request("PUT", "/dstb")[0] == 200
        ver_xml = (b"<VersioningConfiguration><Status>Enabled</Status>"
                   b"</VersioningConfiguration>")
        assert sc.request("PUT", "/srcb", query={"versioning": ""},
                          body=ver_xml)[0] == 200
        assert dc.request("PUT", "/dstb", query={"versioning": ""},
                          body=ver_xml)[0] == 200
        sc.request("PUT", "/minio/admin/v3/set-remote-target",
                   query={"bucket": "srcb"},
                   body=json.dumps({"endpoint": dst.address,
                                    "accessKey": "minioadmin",
                                    "secretKey": "minioadmin",
                                    "bucket": "dstb"}).encode())
        sc.request("PUT", "/srcb", query={"replication": ""},
                   body=REPL_XML)
        sc.request("PUT", "/srcb/vk", body=b"v1")
        sc.request("PUT", "/srcb/vk", body=b"v2")
        assert src.replicator.drain(15)
        assert dc.request("GET", "/dstb/vk")[2] == b"v2"
        st, _, _ = sc.request("DELETE", "/srcb/vk")
        assert st == 204
        assert src.replicator.drain(15)
        # Marker replicated: latest on the target is a delete marker.
        assert dc.request("GET", "/dstb/vk")[0] == 404
        # The source marker carries COMPLETED status metadata.
        versions = src_es.list_versions_all("srcb", "vk")
        marker = next(v for v in versions if v.deleted)
        assert marker.metadata.get(REPL_STATUS_KEY) == "COMPLETED"
        # The target minted its marker WITH the source marker's version
        # id (the x-mtpu-replica-dm-version header, consumed by the
        # delete handler) — active-active peers hold the SAME marker.
        dst_versions = dst_es.list_versions_all("dstb", "vk")
        dst_marker = next(v for v in dst_versions if v.deleted)
        assert dst_marker.version_id == marker.version_id
    finally:
        src.replicator.stop()
        src.stop()
        dst.stop()


def test_retry_backoff_rides_timer_not_worker(tmp_path):
    """During an outage the delivery workers stay free (backoff parks
    on the timer heap): a healthy lane enqueued later still completes
    while the dead lane's retries wait."""
    es, eng = _solo_engine(tmp_path, workers=1)
    dst_disks = [LocalStorage(str(tmp_path / f"h{i}")) for i in range(4)]
    dst_es = ErasureSet(dst_disks)
    dst = S3Server(dst_es, address="127.0.0.1:0")
    dst.start()
    dc = S3Client(dst.address)
    try:
        assert dc.request("PUT", "/dstb")[0] == 200
        es.make_bucket("okb")
        meta = es.get_bucket_meta("okb")
        meta["config:replication"] = REPL_XML.decode()
        meta["config:remote-target"] = json.dumps(
            {"endpoint": dst.address, "accessKey": "minioadmin",
             "secretKey": "minioadmin", "bucket": "dstb"})
        es.set_bucket_meta("okb", meta)
        info = es.put_object("okb", "alive", b"healthy lane")
        # Dead-lane intent FIRST: under v1 its worker-thread backoff
        # (0.2 + 0.4 + ... ≈ 3s+) head-of-line blocked this worker.
        eng.enqueue("srcb", "stuck", "v1", "put", mod_time=1)
        eng.enqueue("okb", "alive", info.version_id, "put",
                    mod_time=info.mod_time)
        t0 = time.monotonic()
        deadline = t0 + 10
        while time.monotonic() < deadline:
            if eng.completed >= 1:
                break
            time.sleep(0.02)
        assert eng.completed == 1, "healthy lane blocked by dead lane"
        assert dc.request("GET", "/dstb/alive")[2] == b"healthy lane"
        # The healthy delivery finished while the dead lane was still
        # inside its retry schedule.
        assert eng.failed == 0 or eng.stats()["pending"] >= 1
    finally:
        eng.stop()
        dst.stop()


def test_kill_switch_reverts_to_memory_plane(tmp_path, monkeypatch):
    """MTPU_REPLICATION_DURABLE=off: no WAL on disk, no breaker lanes —
    but replication itself still converges (v1 semantics + the
    satellite fixes)."""
    monkeypatch.setenv("MTPU_REPLICATION_DURABLE", "off")
    src_disks = [LocalStorage(str(tmp_path / f"s{i}")) for i in range(4)]
    dst_disks = [LocalStorage(str(tmp_path / f"t{i}")) for i in range(4)]
    src_es, dst_es = ErasureSet(src_disks), ErasureSet(dst_disks)
    src = S3Server(src_es, address="127.0.0.1:0")
    dst = S3Server(dst_es, address="127.0.0.1:0")
    src.replicator = ReplicationEngine(src_es)
    src.start()
    dst.start()
    sc, dc = S3Client(src.address), S3Client(dst.address)
    try:
        assert src.replicator.durable is False
        assert src.replicator.wal is None
        assert sc.request("PUT", "/srcb")[0] == 200
        assert dc.request("PUT", "/dstb")[0] == 200
        sc.request("PUT", "/minio/admin/v3/set-remote-target",
                   query={"bucket": "srcb"},
                   body=json.dumps({"endpoint": dst.address,
                                    "accessKey": "minioadmin",
                                    "secretKey": "minioadmin",
                                    "bucket": "dstb"}).encode())
        sc.request("PUT", "/srcb", query={"replication": ""},
                   body=REPL_XML)
        sc.request("PUT", "/srcb/mem.txt", body=b"volatile plane")
        assert src.replicator.drain(15)
        assert dc.request("GET", "/dstb/mem.txt")[2] == b"volatile plane"
        wal_dir = tmp_path / "s0" / ".mtpu.sys" / "repl"
        assert not any(p.name.startswith("wal-")
                       for p in wal_dir.iterdir()) \
            if wal_dir.exists() else True
    finally:
        src.replicator.stop()
        src.stop()
        dst.stop()


def test_admin_replication_status_and_resync(clusters):
    """replication-status exposes the full v2 stats doc (v1 keys kept);
    replication-resync kicks a checkpointed sweep that re-queues
    unreplicated versions."""
    src, dst, sc, dc, src_es = clusters
    sc.request("PUT", "/srcb/adm.txt", body=b"x")
    assert src.replicator.drain(15)
    st, _, b = sc.request("GET", "/minio/admin/v3/replication-status")
    assert st == 200
    doc = json.loads(b)
    for k in ("queued", "completed", "failed", "spilled", "dropped",
              "pending", "lanes", "durable"):
        assert k in doc
    assert doc["completed"] >= 1
    # Plant an object that predates the replication config by wiping
    # its status, then prove resync picks it up.
    from minio_tpu.replication import REPL_STATUS_KEY
    src_es.update_version_metadata(
        "srcb", "adm.txt", "",
        lambda m: m.pop(REPL_STATUS_KEY, None))
    st, _, b = sc.request("POST", "/minio/admin/v3/replication-resync",
                          query={"bucket": "srcb"})
    assert st == 200
    doc = json.loads(b)
    assert doc["bucket"] == "srcb" and doc["state"] == "running"
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        st, _, b = sc.request("GET",
                              "/minio/admin/v3/replication-resync",
                              query={"bucket": "srcb"})
        doc = json.loads(b)
        if doc and doc.get("state") == "done":
            break
        time.sleep(0.1)
    assert doc["state"] == "done"
    assert doc["queued"] >= 1
    assert src.replicator.drain(15)
    st, hh, _ = sc.request("HEAD", "/srcb/adm.txt")
    assert hh.get("x-amz-replication-status") == "COMPLETED"

def _multiset_engine(tmp_path, n_keys=40):
    """Engine over a TWO-set pool with n_keys hash-distributed,
    unstamped (pre-config) objects — the shape where a shared resync
    checkpoint across sets silently skips keys."""
    from minio_tpu.object.sets import ErasureSets
    sets = [ErasureSet([LocalStorage(str(tmp_path / f"p{s}d{i}"))
                        for i in range(4)]) for s in range(2)]
    ess = ErasureSets(
        sets, deployment_id="8d7a41f2-9b33-4c55-a0ef-3c1d2e4f5a6b")
    ess.make_bucket("srcb")
    meta = ess.get_bucket_meta("srcb")
    meta["config:replication"] = REPL_XML.decode()
    meta["config:remote-target"] = json.dumps(
        {"endpoint": "127.0.0.1:1", "accessKey": "a", "secretKey": "s",
         "bucket": "dstb"})
    ess.set_bucket_meta("srcb", meta)
    keys = [f"k{i:03d}" for i in range(n_keys)]
    for k in keys:
        ess.put_object("srcb", k, b"x")
    by_set = {0: [], 1: []}
    for k in keys:
        by_set[ess.set_index(k)].append(k)
    # Both sets populated, and set 1 holds keys sorting BEFORE set 0's
    # last key — the exact layout a shared checkpoint would skip.
    assert by_set[0] and by_set[1]
    assert min(by_set[1]) < max(by_set[0])
    return ess, ReplicationEngine(ess, workers=0), keys


def _wait_resync(eng, bucket, timeout=15):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        doc = eng.resync_status(bucket)
        if doc and doc.get("state") not in (None, "running"):
            return doc
        time.sleep(0.05)
    return eng.resync_status(bucket)


def test_resync_covers_all_sets(tmp_path):
    """Full-bucket resync walks EVERY erasure set from its own key
    cursor: set 1's walk must not start at set 0's (lexically late)
    final checkpoint, or hash-distributed keys in later sets are
    silently skipped."""
    ess, eng, keys = _multiset_engine(tmp_path)
    try:
        eng.start_resync("srcb")
        doc = _wait_resync(eng, "srcb")
        assert doc["state"] == "done"
        assert doc["queued"] == len(keys)
        assert doc["scanned"] == len(keys)
        assert eng.stats()["pending"] == len(keys)
    finally:
        eng.stop()


def test_resync_failed_sweep_resumes_checkpoint(tmp_path):
    """Re-kicking a FAILED sweep resumes at its persisted (set,
    checkpoint) instead of restarting at set 0 / '' — and a done sweep
    re-kicks from scratch."""
    ess, eng, keys = _multiset_engine(tmp_path)
    try:
        # Prior sweep failed after finishing set 0 and walking set 1
        # past every key: the resumed sweep has nothing left to queue.
        eng._resyncs["srcb"] = {
            "bucket": "srcb", "state": "failed", "set": 1,
            "checkpoint": "zzz", "scanned": 0, "queued": 0,
            "started": 0.0, "finished": 0.0}
        eng.start_resync("srcb")
        doc = _wait_resync(eng, "srcb")
        assert doc["state"] == "done"
        assert doc["queued"] == 0
        # A fresh kick over the now-done sweep starts over and queues
        # the whole bucket.
        eng.start_resync("srcb")
        doc = _wait_resync(eng, "srcb")
        assert doc["state"] == "done"
        assert doc["queued"] == len(keys)
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Two-cluster chaos convergence matrix (real server processes)
# ---------------------------------------------------------------------------


def _pair_up(tmp_path, scanner_interval=0.5, env=None):
    """Two single-node real-process clusters: source replicating to
    target.  Returns (src_cluster, dst_cluster, src_client,
    dst_client)."""
    from tests.cluster import Cluster
    src = Cluster(tmp_path / "src", nodes=1, drives_per_node=4,
                  scanner_interval=scanner_interval, env=env).start()
    dst = Cluster(tmp_path / "dst", nodes=1, drives_per_node=4,
                  scanner_interval=0).start()
    sc, dc = src.client(0), dst.client(0)
    assert sc.request("PUT", "/srcb")[0] == 200
    assert dc.request("PUT", "/dstb")[0] == 200
    st, _, b = sc.request("PUT", "/minio/admin/v3/set-remote-target",
                          query={"bucket": "srcb"},
                          body=json.dumps({
                              "endpoint": dst.address(0),
                              "accessKey": "minioadmin",
                              "secretKey": "minioadmin",
                              "bucket": "dstb"}).encode())
    assert st == 200, b
    st, _, b = sc.request("PUT", "/srcb", query={"replication": ""},
                          body=REPL_XML)
    assert st == 200, b
    return src, dst, sc, dc


def _list_keys(client, bucket):
    st, _, body = client.request("GET", f"/{bucket}")
    assert st == 200, body
    return set(re.findall(rb"<Key>([^<]+)</Key>", body))


def _assert_converged(sc, dc, expect: dict, timeout=60):
    """Eventual byte-identity: every expected key's latest bytes match
    on both sides (None = deleted on both), and the target has ZERO
    divergent (extra) objects."""
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        diverged = []
        for key, want in expect.items():
            ss, _, sb = sc.request("GET", f"/srcb/{key}")
            ds, _, db = dc.request("GET", f"/dstb/{key}")
            if want is None:
                if not (ss == 404 and ds == 404):
                    diverged.append((key, ss, ds))
            elif not (ss == 200 and ds == 200 and sb == db == want):
                diverged.append((key, ss, ds))
        if not diverged:
            extra = _list_keys(dc, "dstb") - \
                {k.encode() for k, v in expect.items() if v is not None}
            if not extra:
                return
            diverged = [("extra-on-target", sorted(extra))]
        last = diverged
        time.sleep(0.5)
    raise AssertionError(f"divergent objects after chaos: {last}; "
                         f"source says {_repl_status(sc)}")


def _repl_status(client) -> dict:
    st, _, body = client.request("GET",
                                 "/minio/admin/v3/replication-status")
    assert st == 200, body
    doc = json.loads(body)
    doc.pop("lag_hist", None)
    return doc


def test_chaos_target_kill_restart_converges(tmp_path):
    """Kill the target mid-replication; keep writing; restart it: the
    scanner resync + breaker-parked lanes converge to byte-identity
    with zero divergent objects."""
    src, dst, sc, dc = _pair_up(tmp_path)
    expect = {}
    try:
        for i in range(6):
            body = f"pre-kill-{i}".encode() * 50
            assert sc.request("PUT", f"/srcb/k{i}",
                              body=body)[0] == 200
            expect[f"k{i}"] = body
        dst.kill(0)                      # crash mid-replication
        for i in range(6, 12):
            body = f"during-outage-{i}".encode() * 50
            assert sc.request("PUT", f"/srcb/k{i}",
                              body=body)[0] == 200
            expect[f"k{i}"] = body
        # A delete during the outage must also converge.
        assert sc.request("DELETE", "/srcb/k0")[0] == 204
        expect["k0"] = None
        # The target comes back only once the source has met the outage
        # (its lane's breaker has opened: deliveries are parked or
        # burning retries), however long that takes on a loaded box.
        until(lambda: sum(ln["breaker_opens"]
                          for ln in _repl_status(sc)["lanes"]) >= 1,
              "the source's lane breaker opened")
        dst.restart(0)
        dc = dst.client(0)
        _assert_converged(sc, dc, expect, timeout=90)
    finally:
        src.stop()
        dst.stop()


def test_chaos_source_sigkill_wal_replays(tmp_path):
    """SIGKILL the source with a loaded WAL (target down, intents
    queued): the restarted source replays its WAL / resyncs stamped
    versions and converges — v1 lost every queued intent here."""
    src, dst, sc, dc = _pair_up(tmp_path)
    expect = {}
    try:
        dst.kill(0)                      # target down: intents pile up
        for i in range(8):
            body = f"wal-loaded-{i}".encode() * 40
            assert sc.request("PUT", f"/srcb/w{i}",
                              body=body)[0] == 200
            expect[f"w{i}"] = body
        src.kill(0)                      # SIGKILL with the WAL loaded
        dst.restart(0)
        src.restart(0)
        sc, dc = src.client(0), dst.client(0)
        _assert_converged(sc, dc, expect, timeout=90)
    finally:
        src.stop()
        dst.stop()


@pytest.mark.slow
def test_chaos_matrix_full(tmp_path):
    """The full matrix: foreground writes + deletes churning while the
    target flaps twice and the source crashes once — eventual
    byte-identity, zero divergent objects."""
    src, dst, sc, dc = _pair_up(tmp_path)
    expect = {}
    try:
        def put(i, tag):
            body = f"{tag}-{i}".encode() * 64
            assert sc.request("PUT", f"/srcb/m{i}", body=body)[0] == 200
            expect[f"m{i}"] = body

        for i in range(5):
            put(i, "phase0")
        dst.kill(0)
        for i in range(5, 10):
            put(i, "outage1")
        sc.request("DELETE", "/srcb/m1")
        expect["m1"] = None
        dst.restart(0)
        dc = dst.client(0)
        _assert_converged(sc, dc, expect, timeout=90)
        # Second flap + source crash while loaded.
        dst.kill(0)
        for i in range(10, 15):
            put(i, "outage2")
        src.kill(0)
        dst.restart(0)
        src.restart(0)
        sc, dc = src.client(0), dst.client(0)
        for i in range(15, 18):
            put(i, "post-restart")
        _assert_converged(sc, dc, expect, timeout=120)
    finally:
        src.stop()
        dst.stop()

"""Elastic fleet under fire: live pool expansion, chaos-proof online
rebalance/decommission, zero-downtime drain.

Single-process tests cover the new migration machinery directly —
merge-dedup listings during a migration, the coherence bump ordering
inside migrate_key, the admission governor (yield-to-foreground +
parallel workers), the coordinator lease, and the elastic janitor's
crashed-vs-paused distinction. The cluster tests (tests/cluster.py
harness, real server processes) then prove the fleet-wide story: a
remote node's cache never serves a migrated-away copy, a SIGKILLed
rebalance coordinator is replaced by a surviving node resuming from
the checkpoint, a drain converges through a network partition, and a
live node drains out with zero failed foreground requests before its
removal from the topology.
"""

import json
import os
import threading
import time

import pytest

from minio_tpu.grid.dsync import LocalLocker, LockServer
from minio_tpu.object import decom, rebalance
from minio_tpu.object.erasure_object import ErasureSet
from minio_tpu.object.pools import ServerPools
from minio_tpu.object.sets import ErasureSets
from minio_tpu.object.types import PutOptions
from minio_tpu.storage.local import LocalStorage
from minio_tpu.topology.ellipses import parse_pools

DEP = "00000000-0000-0000-0000-0000e1a50000"


def _pool(tmp_path, name, n=4):
    disks = [LocalStorage(str(tmp_path / name / f"d{i}")) for i in range(n)]
    return ErasureSets([ErasureSet(disks)], deployment_id=DEP)


@pytest.fixture
def layer(tmp_path):
    lay = ServerPools([_pool(tmp_path, "p0"), _pool(tmp_path, "p1")])
    lay.make_bucket("db")
    return lay


def _pool_is_empty(pool, bucket) -> bool:
    page = pool.list_objects(bucket, max_keys=10, include_versions=True)
    return not page.objects


# -- pool expansion CLI (topology/ellipses comma form) ----------------------

def test_parse_pools_comma_forms():
    # A comma-separated argument is its OWN pool of exactly those
    # endpoints (ports and drives advancing together can't be written
    # as one cartesian ellipses pattern).
    assert parse_pools(["a,b", "c", "d"]) == [["a", "b"], ["c", "d"]]
    # Commas compose with ellipses: each segment expands in place.
    assert parse_pools(
        ["http://h:9000/d{1...2},http://h:9001/d{1...2}"]) == [
        ["http://h:9000/d1", "http://h:9000/d2",
         "http://h:9001/d1", "http://h:9001/d2"]]
    # Trailing comma keeps a single-endpoint pool separate from the
    # plain-argument pool.
    assert parse_pools(["solo,", "x", "y"]) == [["solo"], ["x", "y"]]
    with pytest.raises(ValueError):
        parse_pools([","])


# -- merge-dedup listings during a migration --------------------------------

def test_listing_never_doubly_visible_mid_migration(layer):
    """The mid-migration window where BOTH pools hold the same version
    stack (restore landed, source cleanup not yet): plain and
    versioned listings show each (key, version) exactly once."""
    body = os.urandom(9_000)
    layer.pools[0].put_object("db", "dup", body,
                              PutOptions(versioned=True))
    src_set = layer.pools[0].set_for("dup")
    dst_set = layer.pools[1].set_for("dup")
    for fi in src_set.list_versions_all("db", "dup"):
        from minio_tpu.object.types import GetOptions
        _, data = src_set.get_object(
            "db", "dup", GetOptions(version_id=fi.version_id))
        dst_set.restore_version("db", "dup", fi, data)
    layer.decommissioning.add(0)        # drain in progress: dst-first

    page = layer.list_objects("db", max_keys=10)
    assert [o.name for o in page.objects] == ["dup"]
    vpage = layer.list_objects("db", max_keys=10, include_versions=True)
    vkeys = [(o.name, o.version_id) for o in vpage.objects]
    assert len(vkeys) == len(set(vkeys)) == 1, vkeys
    _, got = layer.get_object("db", "dup")
    assert got == body


# -- coherence bump ordering in migrate_key ---------------------------------

def test_migrate_key_bumps_coherence_before_source_cleanup(layer):
    """The bucket-generation bump (the funnel that invalidates every
    node's fi_cache/metacache) must fire while the SOURCE copy still
    exists — a peer re-filling its cache in the gap resolves
    destination-first and is already correct; bumping after the
    cleanup would leave a window serving the deleted copy."""
    body = os.urandom(12_345)
    layer.pools[0].put_object("db", "bump", body)
    src_set = layer.pools[0].set_for("bump")
    calls = []
    orig = src_set.metacache.bump

    def spy(bucket, *a, **kw):
        try:
            src_has = bool(src_set.list_versions_all("db", "bump"))
        except Exception:  # noqa: BLE001 - absent == cleaned up
            src_has = False
        calls.append((bucket, src_has))
        return orig(bucket, *a, **kw)

    src_set.metacache.bump = spy
    moved = decom.migrate_key(layer, 0, "db", "bump", lambda: 1)
    assert moved == len(body)
    mig = [c for c in calls if c[0] == "db"]
    assert mig, "migrate_key never bumped the bucket generation"
    assert mig[0][1], "first bump fired AFTER the source cleanup"
    assert _pool_is_empty(layer.pools[0], "db")
    _, got = layer.get_object("db", "bump")
    assert got == body


# -- admission governor: migration yields to foreground ---------------------

def test_drain_yields_to_foreground_pressure(layer, monkeypatch):
    monkeypatch.setenv("MTPU_REBALANCE_YIELD_MS", "5")
    bodies = {f"y{i}": os.urandom(4_000) for i in range(6)}
    for k, b in bodies.items():
        layer.pools[0].put_object("db", k, b)
    busy = threading.Event()
    busy.set()
    layer.migration_pressure = busy.is_set

    d = layer.start_decommission(0)
    time.sleep(0.3)
    # Gated: nothing migrates while the front end queues, and the
    # pause is accounted.
    assert d.state["migrated"] == 0
    assert d.state["yields"] >= 1
    busy.clear()
    assert d.wait(60)
    st = layer.decommission_status()
    assert st["status"] == "complete", st
    assert st["migrated"] == len(bodies)
    assert st["bytes_moved"] == sum(len(b) for b in bodies.values())
    for k, b in bodies.items():
        _, got = layer.get_object("db", k)
        assert got == b


def test_parallel_drain_workers(layer, monkeypatch):
    monkeypatch.setenv("MTPU_REBALANCE_WORKERS", "4")
    bodies = {f"w{i:02d}": os.urandom(5_000 + i) for i in range(12)}
    for k, b in bodies.items():
        layer.pools[0].put_object("db", k, b)
    d = layer.start_decommission(0)
    assert d.wait(60)
    st = layer.decommission_status()
    assert st["status"] == "complete", st
    assert st["migrated"] == len(bodies) and st["failed"] == 0
    assert _pool_is_empty(layer.pools[0], "db")
    for k, b in bodies.items():
        _, got = layer.get_object("db", k)
        assert got == b
    # ... and lists exactly once: with the bytes above, the drained
    # namespace is identical to the one that went in
    vpage = layer.list_objects("db", max_keys=100, include_versions=True)
    assert sorted(o.name for o in vpage.objects) == sorted(bodies)


# -- coordinator lease ------------------------------------------------------

def test_coordinator_lease_admits_single_driver(layer):
    layer.lockers = [LocalLocker(LockServer(ttl=60))]
    held = decom.coordinator_lease(layer, "decom")
    assert held is not None and held.lock(write=True, timeout=2)
    try:
        layer.pools[0].put_object("db", "lease", b"x" * 2048)
        # Another would-be coordinator (same layer = same lockers)
        # cannot start the drain while the lease is held...
        with pytest.raises(decom.LeaseHeld):
            layer.start_decommission(0)
        assert 0 not in layer.decommissioning   # no half-started state
    finally:
        held.unlock()
    # ...and proceeds normally once it lapses.
    d = layer.start_decommission(0)
    assert d.wait(60)
    assert layer.decommission_status()["status"] == "complete"


def test_coordinator_lease_none_without_lockers(layer):
    assert decom.coordinator_lease(layer, "decom") is None


# -- elastic janitor: crashed resumes, paused stays paused ------------------

def _seed(layer, n=40, size=4_000):
    bodies = {f"j{i:03d}": os.urandom(size) for i in range(n)}
    for k, b in bodies.items():
        layer.pools[0].put_object("db", k, b)
    return bodies


def test_janitor_resumes_crashed_drain(layer):
    bodies = _seed(layer)
    d = layer.start_decommission(0, checkpoint_every=4)
    deadline = time.time() + 60
    while d.state["migrated"] < 6 and time.time() < deadline:
        time.sleep(0.005)
    d.stop()
    st = decom.load_state(layer)
    if st["status"] == "draining":
        # Model the CRASH (a SIGKILLed coordinator never writes the
        # explicit-pause flag a clean stop leaves behind).
        st.pop("paused", None)
        decom._save_state(layer, st)
        lay2 = ServerPools(list(layer.pools))
        assert lay2.elastic_janitor_tick() == ["decom"]
        assert lay2._decom.wait(120)
        final = lay2
    else:
        final = layer                   # drain outran the stop signal
    assert decom.load_state(final)["status"] == "complete"
    assert _pool_is_empty(final.pools[0], "db")
    for k, b in bodies.items():
        _, got = final.get_object("db", k)
        assert got == b


def test_janitor_skips_operator_paused_walks(layer):
    _seed(layer, n=30)
    d = layer.start_decommission(0, checkpoint_every=4)
    deadline = time.time() + 60
    while d.state["migrated"] < 4 and time.time() < deadline:
        time.sleep(0.005)
    d.stop()                            # explicit pause
    st = decom.load_state(layer)
    if st["status"] != "draining":
        pytest.skip("drain outran the stop signal on this box")
    assert st.get("paused") is True
    lay2 = ServerPools(list(layer.pools))
    assert lay2.elastic_janitor_tick() == []
    assert lay2._decom is None
    # The explicit resume path (operator/boot) still works on a
    # paused record — and clears the flag.
    d2 = lay2.resume_decommission()
    assert d2 is not None and d2.wait(120)
    assert decom.load_state(lay2)["status"] == "complete"


def test_janitor_resumes_crashed_rebalance(tmp_path):
    lay = ServerPools([_pool(tmp_path, "p0"), _pool(tmp_path, "p1")])
    lay.make_bucket("db")
    bodies = {f"r{i:03d}": os.urandom(6_000) for i in range(40)}
    for k, b in bodies.items():
        lay.pools[0].put_object("db", k, b)
    rb = lay.start_rebalance(checkpoint_every=4)
    deadline = time.time() + 60
    while time.time() < deadline:
        recs = rb.state.get("pools", {})
        if sum(r.get("migrated", 0) for r in recs.values()) >= 4:
            break
        time.sleep(0.005)
    rb.stop()
    st = rebalance.load_state(lay)
    if st["status"] == "rebalancing":
        st.pop("paused", None)
        st["rev"] = st.get("rev", 0) + 1
        blob = json.dumps(st, sort_keys=True).encode()
        from minio_tpu.storage.local import SYS_VOL
        for s in lay.pools[0].sets:
            for dsk in s.disks:
                dsk.write_all(SYS_VOL, rebalance.REBAL_PATH, blob)
        lay2 = ServerPools(list(lay.pools))
        assert lay2.elastic_janitor_tick() == ["rebalance"]
        assert lay2._rebalance.wait(120)
        final = lay2
    else:
        final = lay
    assert rebalance.load_state(final)["status"] == "complete"
    for k, b in bodies.items():
        _, got = final.get_object("db", k)
        assert got == b
    vpage = final.list_objects("db", max_keys=100, include_versions=True)
    vkeys = [(o.name, o.version_id) for o in vpage.objects]
    assert len(vkeys) == len(set(vkeys)) == len(bodies)


# -- observability: rebalance/decom metrics + admin info --------------------

def test_rebalance_metrics_and_admin_info(tmp_path):
    from minio_tpu.s3.server import S3Server
    from tests.s3client import S3Client

    lay = ServerPools([_pool(tmp_path, "p0"), _pool(tmp_path, "p1")])
    lay.make_bucket("db")
    for i in range(20):
        lay.pools[0].put_object("db", f"m{i:02d}", os.urandom(6_000))
    srv = S3Server(lay, address="127.0.0.1:0")
    srv.start()
    try:
        rb = lay.start_rebalance()
        assert rb.wait(60)
        assert lay.rebalance_status()["status"] == "complete"
        d = lay.start_decommission(0)
        assert d.wait(60)

        cli = S3Client(srv.address)
        st, _, body = cli.request("GET", "/minio/v2/metrics/cluster")
        assert st == 200
        text = body.decode()
        for name in ("minio_tpu_rebalance_migrated_total",
                     "minio_tpu_rebalance_bytes_moved_total",
                     "minio_tpu_rebalance_failed_total",
                     "minio_tpu_rebalance_pool_fill_fraction",
                     "minio_tpu_rebalance_yields_total",
                     "minio_tpu_rebalance_checkpoint_age_seconds",
                     "minio_tpu_rebalance_active",
                     "minio_tpu_decom_bytes_moved_total",
                     "minio_tpu_decom_yields_total",
                     "minio_tpu_decom_checkpoint_age_seconds",
                     "minio_tpu_decommission_migrated_total"):
            assert f"\n{name}" in text or text.startswith(name), name
        # Something actually moved and the gauges read sane.
        moved = sum(
            float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
            if ln.startswith("minio_tpu_rebalance_bytes_moved_total{"))
        assert moved > 0
        assert "minio_tpu_rebalance_active 0" in text

        st, _, body = cli.request("GET", "/minio/admin/v3/info")
        assert st == 200
        info = json.loads(body)
        node = info["nodes"][0] if "nodes" in info else info
        assert node["rebalance"]["status"] == "complete"
        assert node["decommission"]["status"] == "complete"
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# multi-process cluster tests (tests/cluster.py harness)
# ---------------------------------------------------------------------------

from tests.cluster import Cluster  # noqa: E402


def _put_retry(cli, path, body, deadline_s=45):
    deadline = time.time() + deadline_s
    while True:
        try:
            st, _, b = cli.request("PUT", path, body=body)
        except Exception as e:  # noqa: BLE001 - conn reset mid-failover
            st, b = 0, str(e).encode()
        if st == 200:
            return
        assert time.time() < deadline, f"PUT {path}: {st} {b[:300]}"
        time.sleep(1)


def _admin(cli, verb, method="GET", query=None):
    st, _, body = cli.request(method, f"/minio/admin/v3/{verb}",
                              query=query or {})
    return st, body


def _wait_status(cli, verb, want, deadline_s, key="status"):
    """Poll an elastic status admin verb until the persisted/live state
    reaches one of `want`; returns the final doc."""
    deadline = time.time() + deadline_s
    doc = None
    while time.time() < deadline:
        try:
            st, body = _admin(cli, verb)
            if st == 200 and body and body != b"null":
                doc = json.loads(body)
                if doc and doc.get(key) in want:
                    return doc
        except Exception:  # noqa: BLE001 - node mid-failover
            pass
        time.sleep(0.5)
    raise AssertionError(f"{verb} never reached {want}: {doc}")


def _disk_holds(cluster, node, pool, key) -> bool:
    """True when any drive dir of (node, pool) holds `key`'s xl.meta —
    ground-truth placement, independent of any server's view."""
    for d in range(64):
        p = cluster.pool_drive_dir(node, pool, d)
        if not os.path.isdir(p):
            break
        for dirpath, _dirs, files in os.walk(p):
            if key in dirpath.split(os.sep) and "xl.meta" in files:
                return True
    return False


def test_cluster_migrated_key_never_served_from_stale_cache(tmp_path):
    """Satellite 1, fleet-wide: nodes 1 and 2 warm their fi_cache /
    metacache against the SOURCE copy of a key; pool 0 then drains.
    migrate_key's coherence bump broadcasts BEFORE the source copy is
    destroyed, so the remote nodes' cached GET/HEAD must keep serving
    the (now migrated) bytes — never a 404, never the deleted copy —
    and listings show the key exactly once."""
    body = os.urandom(64 * 1024)
    with Cluster(tmp_path, nodes=3, pools=[2, 2]) as c:
        c0, c1, c2 = c.client(0), c.client(1), c.client(2)
        assert c0.request("PUT", "/ebkt")[0] == 200
        _put_retry(c0, "/ebkt/mig", body)
        holder = 0 if any(_disk_holds(c, n, 0, "mig")
                          for n in range(3)) else 1
        # Warm every node's caches against the source copy.
        for cli in (c1, c2):
            st, _, got = cli.request("GET", "/ebkt/mig")
            assert st == 200 and got == body
            assert cli.request("HEAD", "/ebkt/mig")[0] == 200

        st, b = _admin(c0, "decommission", "POST",
                       {"pool": str(holder)})
        assert st == 200, b
        # Any-node status: poll node 1, not the starting node.
        doc = _wait_status(c1, "decommission-status", ("complete",), 90)
        assert doc["failed"] == 0, doc

        for cli in (c1, c2):
            st, _, got = cli.request("GET", "/ebkt/mig")
            assert st == 200, "stale cache served the migrated-away copy"
            assert got == body
            assert cli.request("HEAD", "/ebkt/mig")[0] == 200
        st, _, lst = c2.request("GET", "/ebkt")
        assert st == 200 and lst.count(b"<Key>mig</Key>") == 1
        # Ground truth: the drained pool's drives are empty of the key.
        assert not any(_disk_holds(c, n, holder, "mig") for n in range(3))


@pytest.mark.slow
def test_cluster_sigkill_coordinator_rebalance_resumes(tmp_path):
    """The tentpole chaos acceptance: SIGKILL the node driving a
    rebalance mid-walk. Its dsync lease stops refreshing, expires
    after MTPU_GRID_LOCK_TTL, and a surviving node's elastic janitor
    wins the lock and resumes from the persisted checkpoint — no
    object lost, none doubly visible."""
    env = {"MTPU_GRID_LOCK_TTL": "4", "MTPU_ELASTIC_JANITOR_S": "1",
           "MTPU_REBALANCE_PACE_MS": "250"}
    bodies = {f"k{i:03d}": os.urandom(6_000 + i) for i in range(48)}
    with Cluster(tmp_path, nodes=4, pools=[2, 2], env=env) as c:
        c0, c1 = c.client(0), c.client(1)
        assert c0.request("PUT", "/rbkt")[0] == 200
        for k, b in bodies.items():
            _put_retry(c0, f"/rbkt/{k}", b)

        st, b = _admin(c0, "rebalance-start", "POST")
        assert st == 200, b
        # Let the walk make real progress, then crash the coordinator.
        deadline = time.time() + 60
        while time.time() < deadline:
            st, body = _admin(c0, "rebalance-status")
            doc = json.loads(body) if st == 200 and body else None
            moved = sum(r.get("migrated", 0)
                        for r in (doc or {}).get("pools", {}).values())
            if moved >= 2:
                break
            time.sleep(0.2)
        assert moved >= 2, f"rebalance made no progress: {doc}"
        c.kill(0)

        # A survivor resumes from the checkpoint and completes.
        doc = _wait_status(c1, "rebalance-status", ("complete",), 120)
        recs = doc.get("pools", {})
        assert sum(r.get("failed", 0) for r in recs.values()) == 0, doc
        assert sum(r.get("migrated", 0) for r in recs.values()) >= 2

        # Post-chaos byte identity + single-visibility for EVERY key.
        for k, b in bodies.items():
            st, _, got = c1.request("GET", f"/rbkt/{k}")
            assert st == 200 and got == b, f"{k}: lost or torn"
        st, _, lst = c1.request("GET", "/rbkt",
                                query={"max-keys": "1000"})
        assert st == 200
        for k in bodies:
            assert lst.count(f"<Key>{k}</Key>".encode()) == 1, k


@pytest.mark.slow
def test_cluster_partition_during_decommission_converges(tmp_path):
    """Partition a non-coordinator node mid-drain: the walk keeps
    going on remaining quorum (EC 4+4 tolerates 2 of 8 drives dark),
    completes, and after the node rejoins every key reads back
    byte-identical from every node — including the rejoined one."""
    env = {"MTPU_REBALANCE_PACE_MS": "150"}
    bodies = {f"p{i:03d}": os.urandom(5_000) for i in range(24)}
    with Cluster(tmp_path, nodes=4, pools=[2, 2], env=env) as c:
        c0, c2 = c.client(0), c.client(2)
        assert c0.request("PUT", "/pbkt")[0] == 200
        for k, b in bodies.items():
            _put_retry(c0, f"/pbkt/{k}", b)
        holder = 0 if any(_disk_holds(c, n, 0, "p000")
                          for n in range(4)) else 1

        st, b = _admin(c0, "decommission", "POST",
                       {"pool": str(holder)})
        assert st == 200, b
        deadline = time.time() + 60
        doc = None
        while time.time() < deadline:
            st, body = _admin(c0, "decommission-status")
            doc = json.loads(body) if st == 200 and body else None
            if doc and doc.get("migrated", 0) >= 2:
                break
            time.sleep(0.2)
        assert doc and doc.get("migrated", 0) >= 2, doc
        c.partition(1)
        try:
            doc = _wait_status(c0, "decommission-status",
                               ("complete", "failed"), 120)
        finally:
            c.rejoin(1)
        if doc.get("status") == "failed":
            # Keys that landed on the partitioned node's drives below
            # read quorum fail their migrate and are retried once the
            # partition heals — kick the resume and re-converge.
            st, b = _admin(c0, "decommission", "POST",
                           {"pool": str(holder)})
            assert st == 200, b
            doc = _wait_status(c0, "decommission-status",
                               ("complete",), 120)
        assert doc["status"] == "complete", doc

        for k, b in bodies.items():
            st, _, got = c2.request("GET", f"/pbkt/{k}")
            assert st == 200 and got == b, f"{k}: lost or torn"
        # The rejoined node converges too (its caches invalidate or
        # expire; never the deleted source copy).
        c1 = c.client(1)
        deadline = time.time() + 30
        for k, b in bodies.items():
            while True:
                st, _, got = c1.request("GET", f"/pbkt/{k}")
                if st == 200 and got == b:
                    break
                assert time.time() < deadline, f"{k} via rejoined node"
                time.sleep(0.5)


@pytest.mark.slow
def test_cluster_drain_and_remove_live_node(tmp_path):
    """Zero-downtime node removal: node 3 exclusively hosts pool 1;
    drain it while foreground PUT/GET traffic runs (zero failures
    allowed), then SHRINK the topology — reboot as a 3-node cluster
    without node 3 or its pool — and prove byte identity of every
    object through the new fleet."""
    bodies = {f"d{i:03d}": os.urandom(8_000) for i in range(16)}
    ports = None
    fg_bodies = {}
    failures = []
    with Cluster(tmp_path, nodes=4,
                 pools=[([0, 1, 2], 2), ([3], 12)]) as c:
        ports = list(c.ports)
        c0, c1 = c.client(0), c.client(1)
        assert c0.request("PUT", "/dbkt")[0] == 200
        for k, b in bodies.items():
            _put_retry(c0, f"/dbkt/{k}", b)
        # Pool 1 (12 drives, most free space) took the writes — the
        # shape under test: the node-to-remove holds the data.
        assert _disk_holds(c, 3, 1, "d000")

        st, b = _admin(c0, "decommission", "POST", {"pool": "1"})
        assert st == 200, b
        # Placement now excludes pool 1 cluster-wide; foreground
        # traffic through ANOTHER node must see zero failures for the
        # whole drain window.
        stop = threading.Event()

        def foreground():
            i = 0
            while not stop.is_set():
                k, body = f"fg{i:03d}", os.urandom(2_000)
                try:
                    st, _, b = c1.request("PUT", f"/dbkt/{k}", body=body)
                    if st != 200:
                        failures.append(f"PUT {k}: {st} {b[:200]}")
                    else:
                        fg_bodies[k] = body
                        st, _, got = c1.request("GET", f"/dbkt/{k}")
                        if st != 200 or got != body:
                            failures.append(f"GET {k}: {st}")
                except Exception as e:  # noqa: BLE001 - recorded
                    failures.append(f"{k}: {e}")
                i += 1
                time.sleep(0.05)

        t = threading.Thread(target=foreground)
        t.start()
        try:
            doc = _wait_status(c0, "decommission-status",
                               ("complete",), 120)
        finally:
            stop.set()
            t.join()
        assert doc["failed"] == 0, doc
        assert not failures, failures[:5]
        assert fg_bodies, "foreground loop never completed a PUT"
        # Ground truth: node 3's pool-1 drives hold nothing anymore.
        assert not any(_disk_holds(c, 3, 1, k) for k in bodies)

    # The operator removes the node: same drives, topology without
    # pool 1 or node 3. The persisted decom record names the drained
    # pool by SIGNATURE, so the shrunk boot ignores it cleanly.
    with Cluster(tmp_path, nodes=3, ports=ports[:3], pools=[2]) as c:
        cli = c.client(1)
        for k, b in {**bodies, **fg_bodies}.items():
            st, _, got = cli.request("GET", f"/dbkt/{k}")
            assert st == 200 and got == b, f"{k}: lost after removal"
        st, _, lst = cli.request("GET", "/dbkt",
                                 query={"max-keys": "1000"})
        assert st == 200
        for k in bodies:
            assert lst.count(f"<Key>{k}</Key>".encode()) == 1, k

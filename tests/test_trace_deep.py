"""Deep tracing: span trees through drives/engine/kernels, typed trace
streaming (incl. cross-worker over the pre-forked control pipes),
last-minute latency windows, per-drive histograms, and the slow-op log
(reference: TraceHandler internal trace types + pubsub,
cmd/last-minute.gen.go, metrics-v3 histograms)."""

import datetime
import hashlib
import hmac as hmac_mod
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import types as types_mod

import pytest

from minio_tpu.object.erasure_object import ErasureSet
from minio_tpu.s3 import sigv4
from minio_tpu.s3.server import S3Server
from minio_tpu.s3.trace import AuditLogger, TraceBroadcaster, make_entry
from minio_tpu.storage.health import wrap_disks
from minio_tpu.storage.local import LocalStorage
from minio_tpu.utils import latency, tracing
from tests.s3client import S3Client

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# broadcaster: typed subscriptions + slow-subscriber drop-oldest
# ---------------------------------------------------------------------------

def test_broadcaster_typed_subscription_filters():
    b = TraceBroadcaster()
    qs3 = b.subscribe()                       # default: s3 only
    qst = b.subscribe(types={"storage", "kernel"})
    assert tracing.ACTIVE, "internal subscriber must arm span collection"
    b.publish({"trace_type": "s3", "i": 1})
    b.publish({"trace_type": "storage", "i": 2})
    b.publish({"trace_type": "kernel", "i": 3})
    b.publish({"trace_type": "grid", "i": 4})  # nobody wants grid
    b.publish({"i": 5})                        # untyped = s3
    assert [qs3.get_nowait()["i"] for _ in range(2)] == [1, 5]
    assert qs3.empty()
    assert [qst.get_nowait()["i"] for _ in range(2)] == [2, 3]
    assert qst.empty()
    b.unsubscribe(qst)
    assert not tracing.ACTIVE or tracing.slow_ms() > 0, \
        "last internal subscriber gone must disarm"
    b.unsubscribe(qs3)
    assert not b.active


def test_broadcaster_slow_subscriber_drops_oldest():
    b = TraceBroadcaster()
    q = b.subscribe(types={"storage"})
    try:
        for i in range(1500):               # over queue depth of 1000
            b.publish({"trace_type": "storage", "i": i})
        got = []
        while not q.empty():
            got.append(q.get_nowait()["i"])
        assert len(got) == 1000
        assert got[-1] == 1499, "newest entry must survive"
        assert got[0] == 500, "oldest entries must be the ones dropped"
    finally:
        b.unsubscribe(q)


def test_broadcast_entries_bypass_type_filters():
    # The span-truncation marker (`broadcast`) must reach a
    # storage-only subscriber even though it is typed s3 — a filtered
    # stream still has to learn its span tree is incomplete.
    b = TraceBroadcaster()
    q = b.subscribe(types={"storage"})
    try:
        b.publish({"trace_type": "s3", "api": "trace.dropped",
                   "broadcast": True})
        b.publish({"trace_type": "s3", "api": "normal-root"})
        got = []
        while not q.empty():
            got.append(q.get_nowait()["api"])
        assert got == ["trace.dropped"]
    finally:
        b.unsubscribe(q)


def test_query_rpc_discards_stale_replies():
    # A reply landing AFTER its request timed out must not be served
    # as the answer to the next exchange on the same worker pipe.
    import socket as socket_mod
    from minio_tpu.io.workers import WorkerPool, _recv_msg, _send_msg
    pool = WorkerPool.__new__(WorkerPool)
    import itertools
    pool._rid = itertools.count(1)
    parent, child = socket_mod.socketpair()
    try:
        rec = {"worker": 0, "query": parent, "qmu": threading.Lock()}

        def responder():
            # Stale leftover from a timed-out earlier exchange...
            _send_msg(child, {"rid": 9999, "entries": ["stale"]})
            # ...then answer the real request properly.
            msg = _recv_msg(child, timeout=5.0)
            _send_msg(child, {"rid": msg["rid"], "stats": ["fresh"]})

        t = threading.Thread(target=responder, daemon=True)
        t.start()
        time.sleep(0.1)            # stale reply is already buffered
        reply = pool._query_rpc(rec, {"op": "stat"}, timeout=5.0)
        assert reply["stats"] == ["fresh"]
        t.join(timeout=5)
    finally:
        parent.close()
        child.close()


def test_broadcaster_remote_relay_arms_and_drains():
    b = TraceBroadcaster()
    b.arm_remote(["s3", "storage"])
    assert b.active and tracing.ACTIVE
    b.publish({"trace_type": "storage", "i": 1})
    b.publish({"trace_type": "kernel", "i": 2})   # not relayed
    b.publish({"trace_type": "s3", "i": 3})
    assert [e["i"] for e in b.drain_remote()] == [1, 3]
    assert b.drain_remote() == []
    b.disarm_remote()
    assert not b.active


def test_remote_relay_ttl_self_disarms():
    # A worker whose parent never delivered trace_stop (timeout,
    # respawn, parent death) must not stay armed forever: the relay
    # expires when no drain refreshes it within the TTL.
    b = TraceBroadcaster()
    b.arm_remote(["storage"])
    assert b.active and tracing.ACTIVE
    b._remote_deadline = time.monotonic() - 1     # simulate staleness
    b.publish({"trace_type": "storage", "i": 1})  # lazy expiry check
    assert not b.active
    assert b.drain_remote() == []
    assert not tracing.ACTIVE or tracing.slow_ms() > 0


# ---------------------------------------------------------------------------
# span tree over a real erasure PUT + GET
# ---------------------------------------------------------------------------

@pytest.fixture()
def traced_set(tmp_path):
    disks = wrap_disks([LocalStorage(str(tmp_path / f"d{i}"))
                        for i in range(4)])
    es = ErasureSet(disks)
    es.make_bucket("b")
    tracing.arm("test")
    yield es
    tracing.disarm("test")
    es.close()


def _stage_inside_a_drive_call(name):
    return name.startswith("disk.stream") or name == "disk.meta.sync"


def _span_index(ctx):
    return {s["span"]: s for s in ctx.spans}


def test_span_tree_linkage_put_get(traced_set):
    es = traced_set
    body = b"z" * (1 << 20)
    ctx_put = tracing.TraceContext()
    with tracing.bind(ctx_put):
        es.put_object("b", "k", body)
    ctx_get = tracing.TraceContext()
    with tracing.bind(ctx_get):
        _, got = es.get_object("b", "k")
    assert got == body

    from minio_tpu import native
    for ctx, kernel_name in ((ctx_put, "mtpu_put_frame"),
                             (ctx_get, "mtpu_get_frame")):
        by_id = _span_index(ctx)
        engine = [s for s in ctx.spans if s["name"] == "engine.op"]
        # `disk.<op>` is a drive call; `disk.stream*` and
        # `disk.meta.sync` are the stages inside one (storage/local.py)
        disk = [s for s in ctx.spans if s["name"].startswith("disk.")
                and not _stage_inside_a_drive_call(s["name"])]
        inside = [s for s in ctx.spans
                  if _stage_inside_a_drive_call(s["name"])]
        assert engine and disk, ctx.spans
        # Engine spans hang off the root, or off the GET stage that
        # fanned them out (get.prepare's xl.meta reads, get.fetch's
        # shard reads); every disk op is a child of an engine span on
        # the SAME drive queue, and carries the queue-wait split in its
        # parent's tags.
        for s in engine:
            assert s["parent"] == 0 or by_id[s["parent"]]["name"] in (
                "get.prepare", "get.fetch"), s
            assert "queue_wait_ms" in s["tags"]
        for s in disk:
            parent = by_id[s["parent"]]
            assert parent["name"] == "engine.op", s
        for s in inside:
            up = by_id[s["parent"]]
            while up in inside:
                up = by_id[up["parent"]]
            assert up in disk, s
        if ctx is ctx_put:
            assert {s["name"] for s in inside} >= {
                "disk.stream", "disk.stream.open", "disk.stream.write",
                "disk.stream.sync", "disk.meta.sync"}
        if native.load() is not None:
            kernels = [s for s in ctx.spans if s["type"] == "kernel"]
            assert [s["name"] for s in kernels] == [kernel_name]
            # the GET's kernel runs inside its window's stage
            parent = kernels[0]["parent"]
            assert parent == 0 if ctx is ctx_put \
                else by_id[parent]["name"] == "get.window"
        # Span ids unique, parents resolve inside the same trace.
        assert len(by_id) == len(ctx.spans)
        for s in ctx.spans:
            assert s["parent"] == 0 or s["parent"] in by_id


def test_slow_op_log_names_ancestry(traced_set):
    es = traced_set
    before = tracing.slow_total
    tracing.set_slow_ms(0.0001)        # everything is "slow"
    try:
        with tracing.bind(tracing.TraceContext()):
            es.put_object("b", "slowk", b"s" * 200_000)
    finally:
        tracing.set_slow_ms(0.0)
    assert tracing.slow_total > before
    disk_ops = [o for o in tracing.slow_ops()
                if o["name"].startswith("disk.") and o.get("slow")]
    assert disk_ops, "per-drive slow records expected"
    rec = disk_ops[-1]
    assert rec["ancestry"] == ["<root>", "engine.op"], rec
    assert rec["threshold_ms"] == 0.0001
    assert rec["tags"]["drive"], "slow op must name its drive"


def test_grid_call_and_stream_spans(tmp_path):
    from minio_tpu.grid.client import GridClient
    from minio_tpu.grid.server import GridServer
    gs = GridServer(0, host="127.0.0.1")
    gs.register("echo", lambda p: p)
    gs.register_stream("count", lambda p: iter(range(p)))
    gs.start()
    tracing.arm("test-grid")
    try:
        cli = GridClient("127.0.0.1", gs.port)
        ctx = tracing.TraceContext()
        with tracing.bind(ctx):
            with tracing.span("storage", "disk.remote_op"):
                assert cli.call("echo", {"x": 1}) == {"x": 1}
            assert list(cli.stream("count", 3)) == [0, 1, 2]
        cli.close()
        grid = [s for s in ctx.spans if s["type"] == "grid"]
        # Armed grid calls now propagate the trace to the peer and
        # stitch its subtree back under an explicit wire span (one per
        # round-trip) carrying the serialize/transit/peer timing split.
        assert {s["name"] for s in grid} == {"grid.echo", "grid.count",
                                             "wire"}
        by_name = {s["name"]: s for s in grid}
        # The unary call nested under the storage span; the stream span
        # hangs off the root and counted its chunks.
        parent = [s for s in ctx.spans if s["name"] == "disk.remote_op"]
        assert by_name["grid.echo"]["parent"] == parent[0]["span"]
        assert by_name["grid.count"]["tags"]["chunks"] == 3
        wires = [s for s in grid if s["name"] == "wire"]
        assert len(wires) == 2
        assert {w["parent"] for w in wires} == {
            by_name["grid.echo"]["span"], by_name["grid.count"]["span"]}
    finally:
        tracing.disarm("test-grid")
        gs.stop()


# ---------------------------------------------------------------------------
# histograms + last-minute windows
# ---------------------------------------------------------------------------

def test_latency_histogram_and_percentiles():
    h = latency.Histogram()
    for ms in (1, 2, 30, 30, 30, 400):
        h.observe(ms / 1000.0)
    st = h.state()
    assert st["count"] == 6
    cum = dict(latency.Histogram.cumulative(st))
    assert cum["+Inf"] == 6
    assert cum["0.05"] == 5          # all but the 400 ms one
    merged = latency.Histogram.merge([st, st])
    assert merged["count"] == 12

    lm = latency.LastMinute()
    now = time.time()
    for _ in range(90):
        lm.observe(0.004, now=now)
    for _ in range(10):
        lm.observe(0.8, now=now)
    s = lm.stats(now=now)
    assert s["count"] == 100
    assert s["p50"] == 0.005         # bucket upper bound containing 4 ms
    assert s["p99"] >= 0.5           # rank 99 lands in the slow tail
    assert s["max"] == 0.8
    # Entries age out of the trailing minute.
    assert lm.stats(now=now + 120)["count"] == 0

    # Quantiles landing in the +Inf bucket report the tracked max,
    # not a silent cap — a 60 s stall must read as 60 s.
    stall = latency.LastMinute()
    for _ in range(10):
        stall.observe(60.0, now=now)
    s2 = stall.stats(now=now)
    assert s2["p50"] == 60.0 and s2["p99"] == 60.0 and s2["max"] == 60.0


def test_per_drive_histogram_and_last_minute_in_metrics(traced_set):
    es = traced_set
    for i in range(4):
        es.put_object("b", f"m-{i}", b"q" * 4096)
    from minio_tpu.s3.metrics import Metrics
    m = Metrics()
    m.record("PUT:object", 200, 0.004)
    m.record("PUT:object", 200, 0.004)
    text = m.render(object_layer=es)
    # Per-drive histogram buckets + last-minute p99 rendered per drive.
    assert re.search(r'minio_tpu_drive_op_duration_seconds_bucket'
                     r'\{set="0",drive="0",le="\+Inf"\} [1-9]', text)
    drive_p99 = re.findall(
        r'minio_tpu_drive_last_minute_seconds'
        r'\{set="0",drive="\d+",q="p99"\} ([0-9.]+)', text)
    assert len(drive_p99) == 4 and all(float(v) > 0 for v in drive_p99)
    assert re.search(r'minio_tpu_drive_queue_wait_last_minute_seconds'
                     r'\{set="0",drive="0",q="p99"\} [0-9.]+', text)
    # Per-API histogram + last-minute.
    assert re.search(r'minio_tpu_api_request_duration_seconds_bucket'
                     r'\{api="PUT:object",le="0.005"\} 2', text)
    assert re.search(r'minio_tpu_api_last_minute_seconds'
                     r'\{api="PUT:object",q="p99"\} 0\.005', text)
    assert 'minio_tpu_api_last_minute_requests{api="PUT:object"} 2' in text
    # Last-minute merging across (simulated) workers doubles counts —
    # per-API and PER-DRIVE (each worker ships labelled engine rows;
    # the scrape merges the fleet, not its own 1/N slice).
    st = m.state()
    engine_rows = []
    for si, s in enumerate([es]):
        for di, est in enumerate(s.io.stats()):
            engine_rows.append({"set": si, "drive": di, **est})
    peers = [{"metrics": st, "engine": engine_rows},
             {"metrics": st, "engine": engine_rows}]
    text2 = m.render(object_layer=es, peer_states=peers)
    assert 'minio_tpu_api_last_minute_requests{api="PUT:object"} 4' in text2
    assert re.search(r'minio_tpu_api_request_duration_seconds_bucket'
                     r'\{api="PUT:object",le="0.005"\} 4', text2)
    one = int(re.search(r'minio_tpu_drive_op_duration_seconds_count'
                        r'\{set="0",drive="0"\} (\d+)', text).group(1))
    two = int(re.search(r'minio_tpu_drive_op_duration_seconds_count'
                        r'\{set="0",drive="0"\} (\d+)', text2).group(1))
    assert two == 2 * one, (one, two)
    assert re.search(r'minio_tpu_drive_last_minute_seconds'
                     r'\{set="0",drive="0",q="p99"\} [0-9.]+', text2)


# ---------------------------------------------------------------------------
# make_entry precision + audit counters
# ---------------------------------------------------------------------------

def test_make_entry_millisecond_timestamps():
    e = make_entry("GET:object", "GET", "/b/k", "b", "k", 200, 0.01,
                   "127.0.0.1", "ak")
    assert re.match(r"^\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}\.\d{3}Z$",
                    e["time"]), e["time"]
    # Two entries in one burst sort (strictly or equal, never coarser
    # than a millisecond apart when >= 1 ms elapsed).
    t0 = make_entry("a", "GET", "/", "", "", 200, 0, "", "")["time"]
    time.sleep(0.002)
    t1 = make_entry("a", "GET", "/", "", "", 200, 0, "", "")["time"]
    assert t1 > t0


def test_audit_drop_counters_surface():
    # Unreachable target: deliveries fail, retries exhaust, drops count.
    log = AuditLogger("http://127.0.0.1:1/audit", timeout=0.2)
    log._MAX_ATTEMPTS = 1
    try:
        log.submit(make_entry("PUT:object", "PUT", "/b/k", "b", "k", 200,
                              0.01, "127.0.0.1", "ak"))
        deadline = time.time() + 10
        while log.dropped == 0 and time.time() < deadline:
            time.sleep(0.05)
        st = log.stats()
        assert st["dropped"] >= 1 and st["sent"] == 0
        # Exported in Prometheus text via the server hook.
        from minio_tpu.s3.metrics import Metrics
        fake_server = types_mod.SimpleNamespace(audit=log)
        text = Metrics().render(server=fake_server)
        assert re.search(r"minio_tpu_audit_dropped_total [1-9]", text)
        assert "minio_tpu_audit_sent_total 0" in text
        assert "minio_tpu_audit_pending" in text
    finally:
        log.stop()


# ---------------------------------------------------------------------------
# admin trace over HTTP: typed internal spans, linkage, admin info
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def srv(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("deeptr")
    disks = wrap_disks([LocalStorage(str(tmp / f"d{i}"))
                        for i in range(4)])
    es = ErasureSet(disks)
    server = S3Server(es, address="127.0.0.1:0")
    server.start()
    yield server
    server.stop()


def _stream_trace(address, query: dict, out: list):
    """One raw signed GET of /minio/admin/v3/trace, de-chunked, JSON
    lines appended to `out` (the S3Client can't stream)."""
    now = datetime.datetime.now(datetime.timezone.utc)
    amz_date = now.strftime("%Y%m%dT%H%M%SZ")
    date = amz_date[:8]
    scope = f"{date}/us-east-1/s3/aws4_request"
    payload_hash = hashlib.sha256(b"").hexdigest()
    hdrs = {"host": address, "x-amz-date": amz_date,
            "x-amz-content-sha256": payload_hash}
    signed = sorted(hdrs)
    q = {k: [v] for k, v in query.items()}
    canon = sigv4.canonical_request("GET", "/minio/admin/v3/trace", q,
                                    hdrs, signed, payload_hash)
    sts = sigv4.string_to_sign(amz_date, scope, canon)
    skey = sigv4.signing_key("minioadmin", date, "us-east-1")
    sig = hmac_mod.new(skey, sts.encode(), hashlib.sha256).hexdigest()
    qs = "&".join(f"{k}={v}" for k, v in sorted(query.items()))
    conn = http.client.HTTPConnection(address, timeout=30)
    conn.request("GET", f"/minio/admin/v3/trace?{qs}", headers={
        **hdrs,
        "Authorization": f"{sigv4.ALGORITHM} "
        f"Credential=minioadmin/{scope}, "
        f"SignedHeaders={';'.join(signed)}, Signature={sig}"})
    resp = conn.getresponse()
    body = resp.read()              # http.client de-chunks
    conn.close()
    for line in body.splitlines():
        if line.strip():
            out.append(json.loads(line))


def test_admin_trace_internal_types_and_linkage(srv):
    cli = S3Client(srv.address)
    assert cli.request("PUT", "/deep")[0] == 200
    entries: list = []
    # the count holds the PUT's and the GET's whole span trees (a
    # parent span is streamed after its children)
    t = threading.Thread(target=_stream_trace,
                         args=(srv.address, {"types": "all", "count": "120"},
                               entries),
                         daemon=True)
    t.start()
    deadline = time.time() + 5
    while not tracing.ACTIVE and time.time() < deadline:
        time.sleep(0.05)            # types=all subscriber arms spans
    assert tracing.ACTIVE
    body = os.urandom(300_000)
    assert cli.request("PUT", "/deep/one", body=body)[0] == 200
    st, _, got = cli.request("GET", "/deep/one")
    assert st == 200 and got == body
    # Pad with s3-only requests so the count limit is reached and the
    # stream closes regardless of per-request span counts.
    for _ in range(120):
        cli.request("GET", "/minio/health/live", sign=False)
        if not t.is_alive():
            break
        time.sleep(0.05)
    t.join(timeout=20)
    assert not t.is_alive() and entries

    puts = [e for e in entries
            if e.get("trace_type") == "s3" and e["api"] == "PUT:object"]
    gets = [e for e in entries
            if e.get("trace_type") == "s3" and e["api"] == "GET:object"]
    assert puts and gets, entries[:5]
    for root in (puts[0], gets[0]):
        tid = root["trace"]
        assert root["span"] == 0
        kids = [e for e in entries if e.get("trace") == tid
                and e is not root]
        storage = [e for e in kids if e["trace_type"] == "storage"]
        assert storage, f"no storage spans for {root['api']}"
        ids = {e["span"] for e in kids} | {0}
        for e in kids:
            assert e["parent"] in ids, e
        # Every span streams exactly once (slow-op marking must not
        # double-publish a span under the same trace/span id).
        assert len(ids) == len(kids) + 1
        engine_ids = {e["span"] for e in kids if e["api"] == "engine.op"}
        # the drive calls; the stages inside one (`disk.stream*`,
        # `disk.meta.sync`) hang below them
        disk = [e for e in kids if e["api"].startswith("disk.")
                and not _stage_inside_a_drive_call(e["api"])]
        assert disk and all(e["parent"] in engine_ids for e in disk)


def test_admin_trace_default_excludes_internal(srv):
    cli = S3Client(srv.address)
    entries: list = []
    t = threading.Thread(target=_stream_trace,
                         args=(srv.address, {"count": "3"}, entries),
                         daemon=True)
    t.start()
    time.sleep(0.4)
    cli.request("PUT", "/deft")
    cli.request("PUT", "/deft/o", body=b"1")
    cli.request("GET", "/deft/o")
    t.join(timeout=15)
    assert len(entries) == 3
    assert all(e.get("trace_type", "s3") == "s3" for e in entries)
    apis = [e["api"] for e in entries]
    assert apis == ["PUT:bucket", "PUT:object", "GET:object"]


def test_admin_info_surfaces_last_minute_and_slow_ops(srv):
    cli = S3Client(srv.address)
    cli.request("PUT", "/obsb")
    cli.request("PUT", "/obsb/k", body=b"x" * 1000)
    st, _, raw = cli.request("GET", "/minio/admin/v3/info")
    assert st == 200
    info = json.loads(raw)
    assert "PUT:object" in info["last_minute"]
    assert info["last_minute"]["PUT:object"]["count"] >= 1
    assert info["last_minute"]["PUT:object"]["p99"] > 0
    assert "slow_ops" in info and "total" in info["slow_ops"]


# ---------------------------------------------------------------------------
# cross-worker trace streaming (2 pre-forked workers)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def worker_server(tmp_path_factory):
    """A 2-worker pre-forked server on shared drives (subprocess: the
    pytest process has JAX loaded, and fork-after-JAX is unsafe)."""
    root = tmp_path_factory.mktemp("trworkers")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", MTPU_HTTP_WORKERS="2")
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


def test_cross_worker_trace_stream(worker_server):
    """A trace stream served by ONE worker must carry entries for
    requests the kernel routed to EVERY worker (parent control-pipe
    relay, io/workers.py trace pump)."""
    addr = worker_server
    n_req = 14
    entries: list = []
    t = threading.Thread(
        target=_stream_trace,
        args=(addr, {"types": "all", "count": str(40 * n_req)}, entries),
        daemon=True)
    t.start()
    time.sleep(1.2)                 # subscription + fleet arming settle
    body = os.urandom(200_000)
    cli = S3Client(addr)
    assert cli.request("PUT", "/xwb")[0] == 200
    for i in range(n_req):
        # Fresh connection per request: the kernel spreads them.
        assert S3Client(addr).request("PUT", f"/xwb/o{i}",
                                      body=body)[0] == 200
    deadline = time.time() + 25
    j = 0
    while t.is_alive() and time.time() < deadline:
        # Keep traffic flowing until the count limit closes the stream.
        # Overwriting PUTs (not GETs): a repeat GET can be a hot-tier
        # hit tracing as a single root entry, while every PUT emits the
        # full storage/engine span fan-out the count budget assumes.
        S3Client(addr).request("PUT", f"/xwb/o{j % n_req}", body=body)
        j += 1
        time.sleep(0.1)
    roots = [e for e in entries if e.get("trace_type") == "s3"
             and e.get("api") in ("PUT:object", "GET:object")]
    assert roots, f"no s3 roots in {len(entries)} entries"
    workers_seen = {e.get("worker") for e in roots}
    assert len(workers_seen) >= 2, \
        f"entries only from workers {workers_seen}"
    # Internal spans relay cross-worker too, linked to their roots.
    tids = {e["trace"] for e in roots}
    storage = [e for e in entries if e.get("trace_type") == "storage"
               and e.get("trace") in tids]
    assert storage, "no storage spans relayed from the fleet"
    if t.is_alive():
        # Stream still open (count not reached): one last burst.
        for k in range(10):
            S3Client(addr).request("PUT", f"/xwb/o{k % n_req}",
                                   body=body)
        t.join(timeout=10)

"""Event-loop connection plane (s3/eventloop.py): adversarial
connection behavior the epoll front end must absorb.

  * kill-switch — MTPU_HTTP_EVENTLOOP=off reverts wholesale to the
    thread-per-connection path; the same e2e surface runs green both
    ways (parametrized fixture);
  * slowloris — partial request heads never occupy an executor thread
    and are reaped by the idle deadline while parked;
  * mid-body client death — a 1k-connection churn storm of partial
    heads, half-sent bodies, and instant disconnects leaves bufpool
    leases net zero and the connection table empty;
  * pipelining — back-to-back requests buffered in one segment are
    served on one dispatch;
  * idle-timeout parity — MTPU_HTTP_KEEPALIVE_S closes idle keep-alive
    connections under the loop exactly as under the thread path;
  * connection-level backpressure — accepts past MTPU_MAX_CONNS are
    answered 503 + Retry-After before any byte is read;
  * EAGAIN tail offload — a response's final write against a slow
    reader parks on the loop's EPOLLOUT drain instead of pinning the
    executor thread;
  * parked-idle memory model — idle keep-alive connections hold ZERO
    pooled recv buffers (hibernated leases);
  * sendfile short-circuit — whole-object plaintext GETs of a
    tier-resident version go file->socket in-kernel and stamp the
    response-path split.
"""

import os
import select
import socket
import time

import pytest

from minio_tpu.io.bufpool import global_pool
from minio_tpu.object.erasure_object import ErasureSet
from minio_tpu.s3 import eventloop
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.local import LocalStorage
from tests.batcher_rig import until
from tests.s3client import S3Client, ramp_get

pytestmark = pytest.mark.skipif(not hasattr(select, "epoll"),
                                reason="epoll front end is Linux-only")


def _make_server(tmp_path, name, env=None, drives=4):
    """S3Server over fresh local drives with `env` latched for the
    construction window (the front-end class and its knobs are read
    once, at bind time)."""
    saved = {}
    for k, v in (env or {}).items():
        saved[k] = os.environ.get(k)
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    try:
        disks = [LocalStorage(str(tmp_path / name / f"d{i}"))
                 for i in range(drives)]
        srv = S3Server(ErasureSet(disks), address="127.0.0.1:0")
        srv.start()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return srv


def _raw_conn(srv, timeout=10):
    host, _, port = srv.address.rpartition(":")
    s = socket.create_connection((host, int(port)), timeout=timeout)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


def _wait(cond, timeout=30, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# ---------------------------------------------------------------------------
# kill-switch + both-ways e2e surface
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=["loop", "threads"])
def srv(request, tmp_path_factory):
    """One server per front end: the epoll loop and the
    MTPU_HTTP_EVENTLOOP=off thread path must be observably identical."""
    env = {} if request.param == "loop" else {"MTPU_HTTP_EVENTLOOP": "off"}
    server = _make_server(tmp_path_factory.mktemp(f"el-{request.param}"),
                          request.param, env)
    server._front = request.param
    yield server
    server.stop()


@pytest.fixture(scope="module")
def cli(srv):
    c = S3Client(srv.address)
    assert c.request("PUT", "/evloop")[0] == 200
    return c


def test_front_end_selection(srv):
    cls = type(srv.httpd).__name__
    if srv._front == "loop":
        assert cls == "EventLoopServer"
        assert srv.eventloop_stats()["enabled"] is True
    else:
        assert cls != "EventLoopServer"
        assert srv.eventloop_stats() is None


def test_e2e_roundtrip_both_front_ends(srv, cli):
    body = os.urandom(300_000)
    st, _, _ = cli.request("PUT", "/evloop/obj", body=body,
                           headers={"x-amz-meta-k": "v"})
    assert st == 200
    st, h, got = cli.request("GET", "/evloop/obj")
    assert st == 200 and got == body and h.get("x-amz-meta-k") == "v"
    st, h, got = cli.request("GET", "/evloop/obj",
                             headers={"Range": "bytes=1000-2999"})
    assert st == 206 and got == body[1000:3000]
    st, _, _ = cli.request("PUT", "/evloop/chunked", body=body,
                           chunked=True)
    assert st == 200
    st, _, got = cli.request("GET", "/evloop/chunked")
    assert st == 200 and got == body
    st, _, got = cli.request("GET", "/evloop/missing-key")
    assert st == 404


def test_e2e_keepalive_reuse_both_front_ends(srv):
    ka = S3Client(srv.address, keepalive=True)
    base = srv.metrics.http_conn_stats()["keepalive_reuses"]
    for _ in range(4):
        assert ka.request("GET", "/minio/health/live", sign=False)[0] == 200
    assert srv.metrics.http_conn_stats()["keepalive_reuses"] >= base + 3
    ka.close()


def test_e2e_ramp_driver_both_front_ends(srv, cli):
    body = os.urandom(64 << 10)
    assert cli.request("PUT", "/evloop/ramp", body=body)[0] == 200
    r = ramp_get(srv.address, "/evloop/ramp", len(body), connections=8,
                 duration_s=0.5)
    assert r["errors"] == 0 and r["ops"] >= 8, r
    assert r["bytes"] == r["ops"] * len(body)


def test_pipelined_requests(srv):
    """Two requests in one TCP segment: under the loop the second head
    is already buffered at dispatch and must be served back-to-back on
    the same executor turn; under threads the hot loop handles it."""
    sock = _raw_conn(srv)
    try:
        sock.sendall(b"GET /minio/health/live HTTP/1.1\r\nHost: x\r\n\r\n"
                     b"GET /minio/health/live HTTP/1.1\r\nHost: x\r\n"
                     b"Connection: close\r\n\r\n")
        raw = bytearray()
        while True:
            try:
                got = sock.recv(65536)
            except OSError:
                break
            if not got:
                break
            raw += got
        assert raw.count(b"HTTP/1.1 200") == 2, raw[:200]
    finally:
        sock.close()


# ---------------------------------------------------------------------------
# idle deadline: slowloris + keep-alive parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=["loop", "threads"])
def reap_srv(request, tmp_path_factory):
    env = {"MTPU_HTTP_KEEPALIVE_S": "1"}
    if request.param == "threads":
        env["MTPU_HTTP_EVENTLOOP"] = "off"
    server = _make_server(tmp_path_factory.mktemp(f"reap-{request.param}"),
                          request.param, env)
    server._front = request.param
    yield server
    server.stop()


def _closed_within(sock, seconds) -> bool:
    sock.settimeout(seconds)
    try:
        return sock.recv(4096) == b""
    except socket.timeout:
        return False
    except OSError:
        return True


def test_slowloris_partial_head_reaped(reap_srv):
    """A drip-fed request head must never graduate to an executor
    thread and must die on the idle deadline (same MTPU_HTTP_KEEPALIVE_S
    budget the thread path applies via settimeout)."""
    stats0 = reap_srv.eventloop_stats()
    sock = _raw_conn(reap_srv)
    try:
        sock.sendall(b"GET /minio/health/live HTTP/1.1\r\nHo")
        assert _closed_within(sock, 8), \
            "slowloris connection survived the idle deadline"
    finally:
        sock.close()
    if reap_srv._front == "loop":
        assert _wait(lambda: reap_srv.eventloop_stats()["reaped_idle_total"]
                     > stats0["reaped_idle_total"], timeout=5)
        # The partial head was parked, not dispatched.
        assert reap_srv.eventloop_stats()["dispatch_total"] == \
            stats0["dispatch_total"]


def test_idle_keepalive_timeout_parity(reap_srv):
    """An idle keep-alive connection (one complete request served) is
    closed by the same deadline either way."""
    sock = _raw_conn(reap_srv)
    try:
        sock.sendall(b"GET /minio/health/live HTTP/1.1\r\nHost: x\r\n\r\n")
        sock.settimeout(10)
        head = sock.recv(65536)
        assert head.startswith(b"HTTP/1.1 200"), head[:64]
        t0 = time.monotonic()
        assert _closed_within(sock, 8), \
            "idle keep-alive connection survived the deadline"
        # The deadline is ~1s; anything past a few seconds means a
        # different (wrong) timer closed it.
        assert time.monotonic() - t0 < 6
    finally:
        sock.close()


# ---------------------------------------------------------------------------
# churn storm: leases net zero, table empty
# ---------------------------------------------------------------------------

def _signed_put_head(address, path, clen) -> bytes:
    """A correctly signed PUT head declaring `clen` body bytes (body
    signed UNSIGNED-PAYLOAD so partial delivery is the only sin)."""
    import datetime
    import hashlib
    import hmac

    from minio_tpu.s3 import sigv4
    now = datetime.datetime.now(datetime.timezone.utc)
    amz_date = now.strftime("%Y%m%dT%H%M%SZ")
    date = amz_date[:8]
    scope = f"{date}/us-east-1/s3/aws4_request"
    lower = {"host": address, "x-amz-date": amz_date,
             "x-amz-content-sha256": sigv4.UNSIGNED_PAYLOAD,
             "content-length": str(clen)}
    signed = sorted(lower)
    canon = sigv4.canonical_request("PUT", path, {}, lower, signed,
                                    sigv4.UNSIGNED_PAYLOAD)
    sts = sigv4.string_to_sign(amz_date, scope, canon)
    key = sigv4.signing_key("minioadmin", date, "us-east-1")
    sig = hmac.new(key, sts.encode(), hashlib.sha256).hexdigest()
    return (f"PUT {path} HTTP/1.1\r\nHost: {address}\r\n"
            f"x-amz-date: {amz_date}\r\n"
            f"x-amz-content-sha256: {sigv4.UNSIGNED_PAYLOAD}\r\n"
            f"Content-Length: {clen}\r\n"
            f"Authorization: {sigv4.ALGORITHM} "
            f"Credential=minioadmin/{scope}, "
            f"SignedHeaders={';'.join(signed)}, Signature={sig}\r\n"
            "\r\n").encode()


def test_churn_storm_leases_net_zero(srv, cli):
    """1k-connection churn storm of adversarial disconnects: instant
    close, partial head then close, and signed PUT dying mid-body.
    Afterwards the connection table drains to the fixture's own clients
    and the bufpool holds not one more outstanding lease than before —
    the leak the recv-buffer/body-lease plumbing must never have."""
    pool = global_pool()
    # Settle: let any prior test's connections finish dying first.
    time.sleep(0.5)
    base_outstanding = pool.stats()["outstanding"]
    put_head = _signed_put_head(srv.address, "/evloop/churn-victim",
                                64 << 10)
    n = 0
    for round_ in range(100):
        socks = []
        try:
            for kind in range(10):
                s = _raw_conn(srv, timeout=5)
                if kind % 3 == 1:
                    s.sendall(b"GET /x HTTP/1.1\r\nHo")       # partial head
                elif kind % 3 == 2:
                    s.sendall(put_head + b"\x00" * 1024)      # mid-body die
                socks.append(s)
                n += 1
        finally:
            for s in socks:
                # Abortive close (RST where the stack allows): the
                # nastiest client exit there is.
                try:
                    s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                 b"\x01\x00\x00\x00\x00\x00\x00\x00")
                except OSError:
                    pass
                s.close()
    assert n == 1000
    if srv._front == "loop":
        assert _wait(lambda: srv.eventloop_stats()["parked"]
                     + srv.eventloop_stats()["active"] <= 1,
                     timeout=60), srv.eventloop_stats()
    assert _wait(lambda: pool.stats()["outstanding"] <= base_outstanding,
                 timeout=60), \
        (base_outstanding, pool.stats())
    # The server still serves.
    assert cli.request("GET", "/minio/health/live", sign=False)[0] == 200


# ---------------------------------------------------------------------------
# connection-level backpressure
# ---------------------------------------------------------------------------

def test_accept_shed_503(tmp_path):
    server = _make_server(tmp_path, "shed", {"MTPU_MAX_CONNS": "8"})
    try:
        assert server.eventloop_stats()["max_conns"] == 8
        parked = []
        try:
            for _ in range(8):
                parked.append(_raw_conn(server))
            assert _wait(lambda: server.eventloop_stats()["parked"] == 8,
                         timeout=10), server.eventloop_stats()
            extra = _raw_conn(server)
            extra.settimeout(10)
            got = extra.recv(4096)
            assert got.startswith(b"HTTP/1.1 503"), got[:80]
            assert b"Retry-After" in got
            assert extra.recv(4096) == b""          # closed after shed
            extra.close()
            assert server.eventloop_stats()["shed_total"] >= 1
            # Freeing one slot re-opens admission.
            parked.pop().close()
            assert _wait(lambda: server.eventloop_stats()["parked"] == 7,
                         timeout=10)
            ok = _raw_conn(server)
            ok.sendall(b"GET /minio/health/live HTTP/1.1\r\n"
                       b"Host: x\r\n\r\n")
            ok.settimeout(10)
            assert ok.recv(4096).startswith(b"HTTP/1.1 200")
            ok.close()
        finally:
            for s in parked:
                s.close()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# EAGAIN tail offload + parked-idle memory model
# ---------------------------------------------------------------------------

def test_final_write_offloads_to_loop(srv, cli):
    """A slow reader on a response's final write must park the tail on
    the loop's EPOLLOUT drain, not pin the executor thread — and the
    bytes must still arrive intact."""
    if srv._front != "loop":
        pytest.skip("loop-owned response tails are event-loop machinery")
    body = os.urandom(512 << 10)
    assert cli.request("PUT", "/evloop/slowread", body=body)[0] == 200
    # Accepted sockets inherit the listener's buffers: shrink the send
    # side so a 512 KiB single-window response can never fit inline.
    srv.httpd.socket.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF,
                                65536)
    host, _, port = srv.address.rpartition(":")
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    # A tiny receive window guarantees the server's final gathered
    # write cannot complete inline.
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 8192)
    sock.connect((host, int(port)))
    url = cli.presign("GET", "/evloop/slowread")
    try:
        sock.sendall(f"GET {url} HTTP/1.1\r\nHost: {srv.address}\r\n"
                     "Connection: close\r\n\r\n".encode())
        # Don't read: the tail must be parked in _WRITING state.
        assert _wait(lambda: srv.eventloop_stats()["writing"] >= 1,
                     timeout=15), srv.eventloop_stats()
        sock.settimeout(60)
        raw = bytearray()
        while True:
            got = sock.recv(65536)
            if not got:
                break
            raw += got
            time.sleep(0.001)           # stay slow; the loop drains
        head_end = raw.find(b"\r\n\r\n")
        assert raw.startswith(b"HTTP/1.1 200"), raw[:64]
        assert bytes(raw[head_end + 4:]) == body
    finally:
        sock.close()


def test_parked_idle_connections_hold_no_leases(tmp_path):
    """The idle-connection memory model the tentpole charters: parked
    keep-alive connections hibernate their pooled recv buffer, so N
    idle connections hold ZERO leases (fds + small objects only)."""
    server = _make_server(tmp_path, "park", {})
    pool = global_pool()
    conns = []
    try:
        time.sleep(0.3)
        base = pool.stats()["outstanding"]
        for _ in range(100):
            s = _raw_conn(server)
            s.sendall(b"GET /minio/health/live HTTP/1.1\r\n"
                      b"Host: x\r\n\r\n")
            conns.append(s)
        for s in conns:
            s.settimeout(10)
            assert s.recv(65536).startswith(b"HTTP/1.1 200")
        assert _wait(lambda: server.eventloop_stats()["parked"] == 100,
                     timeout=15), server.eventloop_stats()
        assert _wait(lambda: pool.stats()["outstanding"] <= base,
                     timeout=10), (base, pool.stats())
    finally:
        for s in conns:
            s.close()
        server.stop()


# ---------------------------------------------------------------------------
# sendfile short-circuit + connection-plane observability
# ---------------------------------------------------------------------------

@pytest.fixture
def tiered_srv(tmp_path):
    """A live server whose object layer has one FS-warm tier and one
    transitioned 3 MiB object (tb/logs/app)."""
    from minio_tpu.object.lifecycle import make_scanner_hook
    from minio_tpu.object.scanner import Scanner
    from minio_tpu.object.tier import TierRegistry
    from minio_tpu.object.types import PutOptions

    disks = [LocalStorage(str(tmp_path / "t" / f"d{i}")) for i in range(4)]
    es = ErasureSet(disks)
    es.make_bucket("tb")
    reg = TierRegistry([es])
    reg.add("COLD", {"type": "fs", "path": str(tmp_path / "cold")})
    es.tiers = reg
    meta = es.get_bucket_meta("tb")
    meta["config:lifecycle"] = (
        '<LifecycleConfiguration><Rule><ID>t</ID>'
        '<Status>Enabled</Status><Filter><Prefix></Prefix></Filter>'
        '<Transition><Days>1</Days><StorageClass>COLD</StorageClass>'
        '</Transition></Rule></LifecycleConfiguration>')
    es.set_bucket_meta("tb", meta)
    body = os.urandom(3 << 20)
    es.put_object("tb", "logs/app", body, PutOptions())
    sc = Scanner([es], throttle=0)
    sc.on_object.append(
        make_scanner_hook(now_fn=lambda: time.time() + 2 * 86400))
    sc.scan_cycle()
    server = S3Server(es, address="127.0.0.1:0")
    server.start()
    yield server, body
    server.stop()


def test_sendfile_short_circuit_tier_get(tiered_srv):
    server, body = tiered_srv
    cli = S3Client(server.address)

    def paths():
        return server.metrics.http_conn_stats()["response_path"]

    st, _, got = cli.request("GET", "/tb/logs/app")
    assert st == 200 and got == body
    # The path is stamped after the final send returns: the client can
    # hold the whole body before the server has counted it. Wait for
    # the count, then hold it to exactly one.
    until(lambda: paths().get("sendfile", 0) >= 1, "sendfile stamped")
    assert paths()["sendfile"] == 1, paths()
    # Ranged reads leave the sendfile fast path; since the first GET
    # admitted the object to the hot read tier, the range is sliced
    # from the RAM copy (falls back to pooled windows when it isn't).
    st, _, got = cli.request("GET", "/tb/logs/app",
                             headers={"Range": "bytes=100-199"})
    assert st == 206 and got == body[100:200]
    until(lambda: paths().get("hotcache", 0) + paths().get("pooled", 0) >= 1,
          "the ranged GET's path stamped")
    assert paths()["sendfile"] == 1, paths()
    # The split is exported.
    text = server.metrics.render()
    assert 'minio_tpu_http_response_path_total{path="sendfile"} 1' in text


def test_connection_plane_metrics_exported(srv, cli):
    text = srv.metrics.render(server=srv)
    for name in ("minio_tpu_http_eventloop_enabled",
                 "minio_tpu_http_parked_connections",
                 "minio_tpu_http_dispatched_connections",
                 "minio_tpu_http_conns_accepted_total",
                 "minio_tpu_http_conns_shed_total",
                 "minio_tpu_http_conn_reparks_total",
                 "minio_tpu_http_idle_reaped_total",
                 "minio_tpu_http_response_path_total"):
        assert name in text, name
    if srv._front == "loop":
        assert "minio_tpu_http_eventloop_enabled 1" in text
        assert "minio_tpu_http_loop_lag_seconds" in text
    else:
        assert "minio_tpu_http_eventloop_enabled 0" in text


def test_admin_info_connections_section(srv):
    from minio_tpu.s3 import metrics as metrics_mod
    info = metrics_mod.node_info(srv)
    if srv._front == "loop":
        conns = info["connections"]
        for k in ("parked", "active", "max_conns", "accepted_total",
                  "shed_total", "reparks_total", "reaped_idle_total"):
            assert k in conns, k
        assert "loop_lag_ms" in conns
    else:
        assert "connections" not in info


# ---------------------------------------------------------------------------
# 2-worker pre-forked fleet, both front ends
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", params=["loop", "threads"])
def fleet(request, tmp_path_factory):
    """A 2-worker pre-forked fleet per front end (subprocess: the
    pytest process has JAX loaded and fork-after-JAX is unsafe) — the
    ISSUE's 2-worker conformance subset, green both ways."""
    import signal
    import subprocess
    import sys

    root = tmp_path_factory.mktemp(f"fleet-{request.param}")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", MTPU_HTTP_WORKERS="2")
    if request.param == "threads":
        env["MTPU_HTTP_EVENTLOOP"] = "off"
    else:
        env.pop("MTPU_HTTP_EVENTLOOP", None)
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
            if S3Client(address).request(
                    "GET", "/minio/health/live", sign=False)[0] == 200:
                ready = True
                break
        except OSError:
            time.sleep(0.4)
    if not ready:
        out = proc.stdout.read().decode(errors="replace") \
            if proc.stdout else ""
        proc.kill()
        pytest.skip(f"worker fleet failed to boot: {out[-800:]}")
    yield address, request.param
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=25)
    except subprocess.TimeoutExpired:
        proc.kill()


def test_fleet_conformance_subset_both_front_ends(fleet):
    """Object CRUD + listings + ranged GET across 2 pre-forked workers,
    each request on a FRESH connection so the kernel spreads accepts
    over both workers' listeners."""
    addr, _front = fleet
    assert S3Client(addr).request("PUT", "/flb")[0] == 200
    body = os.urandom(300_000)
    assert S3Client(addr).request("PUT", "/flb/obj", body=body)[0] == 200
    st, _, got = S3Client(addr).request("GET", "/flb/obj")
    assert st == 200 and got == body
    st, _, part = S3Client(addr).request(
        "GET", "/flb/obj", headers={"Range": "bytes=100-299"})
    assert st == 206 and part == body[100:300]
    for _ in range(4):
        st, _, lst = S3Client(addr).request("GET", "/flb")
        assert st == 200 and b"obj" in lst
    ka = S3Client(addr, keepalive=True)
    for i in range(4):
        assert ka.request("PUT", f"/flb/ka-{i}", body=b"x" * 1024)[0] \
            == 200
    ka.close()
    assert S3Client(addr).request("DELETE", "/flb/obj")[0] == 204
    st, _, lst = S3Client(addr).request("GET", "/flb")
    assert b"<Key>obj</Key>" not in lst


def test_fleet_connections_admin_and_metrics(fleet):
    """Any worker's admin-info/metrics scrape reports the FLEET's
    connection plane (io/workers.py carries each worker's loop snapshot
    in its control-plane stat)."""
    import json

    addr, front = fleet
    st, _, raw = S3Client(addr).request("GET", "/minio/admin/v3/info")
    assert st == 200
    info = json.loads(raw)
    assert len(info.get("workers", [])) == 2
    if front == "loop":
        conns = info.get("connections")
        assert conns, "fleet admin info missing connections section"
        assert conns["accepted_total"] >= 1
        assert conns["max_conns"] > 0
        assert "loop_lag_ms" in conns
    else:
        assert "connections" not in info
    st, _, text = S3Client(addr).request(
        "GET", "/minio/v2/metrics/cluster")
    assert st == 200
    text = text.decode()
    want = "minio_tpu_http_eventloop_enabled 1" if front == "loop" \
        else "minio_tpu_http_eventloop_enabled 0"
    assert want in text


def test_merge_loop_stats_fleet_view():
    from minio_tpu.s3.metrics import merge_loop_stats
    a = eventloop.EventLoopServer(("127.0.0.1", 0), _DummyHandler,
                                  workers=1)
    b = eventloop.EventLoopServer(("127.0.0.1", 0), _DummyHandler,
                                  workers=1)
    a.accepted_total, b.accepted_total = 3, 4
    a.loop_lag.observe(0.001)
    b.loop_lag.observe(0.002)
    merged = merge_loop_stats([a.stats(), b.stats(), None, "junk"])
    assert merged["enabled"] and merged["accepted_total"] == 7
    assert merged["loop_lag"]["count"] == 2
    a.server_close()
    b.server_close()
    for fd in (a._wr, a._ww, b._wr, b._ww):
        os.close(fd)
    a._epoll.close()
    b._epoll.close()


class _DummyHandler:
    loop_native_lib = None
    loop_keepalive_s = 75.0

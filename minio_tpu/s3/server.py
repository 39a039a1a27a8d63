"""S3 HTTP front-end: router + handlers over the object layer.

The analogue of the reference's api-router + object/bucket handlers
(cmd/api-router.go:253, cmd/object-handlers.go, cmd/bucket-handlers.go):
SigV4-authenticated REST mapping onto the ObjectLayer-equivalent
(ErasureSet / server pools). Stdlib threading HTTP server — one OS
thread per request, the Python shape of the reference's
goroutine-per-request model.
"""

from __future__ import annotations

import datetime
import email.utils
import hashlib
import time as _time_mod
import os
import queue as _queue_mod
import socket as socket_mod
import threading
import urllib.parse
import xml.etree.ElementTree as ET
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from minio_tpu.object.types import (DeleteOptions, GetOptions, InvalidArgument,
                                    ObjectNotFound, PutOptions)
from minio_tpu.s3 import hotloop, sigv4
from minio_tpu.s3.admission import AdmissionController, AdmissionShed
from minio_tpu.s3.admission import class_for as admission_class_for
from minio_tpu.s3.admission import path_class as admission_path_class
from minio_tpu.s3.errors import S3Error, from_exception
from minio_tpu.utils import deadline as deadline_mod
from minio_tpu.utils import tracing as tracing_mod
from minio_tpu.s3.metrics import Metrics, layer_sets as _layer_sets, \
    node_info, probe_disks as _probe_disks
from minio_tpu.utils.streams import (HashingReader, HttpChunkedReader,
                                     LimitedReader, Payload)

XMLNS = "http://s3.amazonaws.com/doc/2006-03-01/"
MAX_OBJECT_SIZE = 5 * (1 << 40)


def _rfc1123(ns: int) -> str:
    return email.utils.formatdate(ns / 1e9, usegmt=True)


def _iso8601(ns: int) -> str:
    return datetime.datetime.fromtimestamp(
        ns / 1e9, tz=datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%S.%f")[:-3] + "Z"


def _xml(root: ET.Element) -> bytes:
    return b'<?xml version="1.0" encoding="UTF-8"?>\n' + ET.tostring(root)


def _el(parent, tag, text=None):
    e = ET.SubElement(parent, tag)
    if text is not None:
        e.text = str(text)
    return e


# Sentinel: a bucket policy exists on disk but cannot be compiled; the
# authorizer fails closed on it (distinct from None = no policy).
_BAD_POLICY = object()


class Credentials:
    """Root credentials + optional IAM store behind one resolver.

    With an IAMSys attached, non-root access keys resolve through the
    store (users and service accounts) and per-request authorization
    runs against their policies; without one, only root exists."""

    def __init__(self, access_key: str = "", secret_key: str = "",
                 iam=None):
        self.access_key = access_key or os.environ.get(
            "MTPU_ROOT_USER", "minioadmin")
        self.secret_key = secret_key or os.environ.get(
            "MTPU_ROOT_PASSWORD", "minioadmin")
        self.iam = iam

    def secret_for(self, access_key: str):
        if access_key == self.access_key:
            return self.secret_key
        if self.iam is not None:
            return self.iam.secret_for(access_key)
        return None

    def is_allowed(self, access_key: str, action: str, resource: str) -> bool:
        if access_key == self.access_key:
            return True
        if self.iam is not None:
            return self.iam.is_allowed(access_key, action, resource)
        return False

    def decide(self, access_key: str, action: str, resource: str,
               context=None):
        """Tri-state identity decision; without an IAM store every
        non-root signed identity is unknown -> None (not Deny), so a
        bucket policy may still grant it."""
        if access_key == self.access_key:
            return "Allow"
        if self.iam is not None:
            return self.iam.decide(access_key, action, resource, context)
        return None


class _ReusePortHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer that joins an SO_REUSEPORT group: every
    pre-forked worker (io/workers.py) binds the same (host, port) and
    the kernel spreads accepted connections across their independent
    accept queues — no proxy hop, no shared listener lock."""

    def server_bind(self):
        self.socket.setsockopt(socket_mod.SOL_SOCKET,
                               socket_mod.SO_REUSEPORT, 1)
        super().server_bind()


class S3Server:
    def __init__(self, object_layer, address: str = "127.0.0.1:9000",
                 credentials: Credentials | None = None,
                 reuse_port: bool | None = None):
        self.object_layer = object_layer
        self.credentials = credentials or Credentials()
        # Hot-object read tier (object/hotcache.py): frequency-admitted
        # whole-object RAM cache. Hits are served straight off the epoll
        # loop (the handler class exports loop_hot_probe below) or from
        # the handler GET path; invalidation rides the metacache bump /
        # coherence funnel the layer already maintains. MTPU_HOT_CACHE=off
        # disables it wholesale.
        from minio_tpu.object.hotcache import HotObjectCache
        self.hot_cache = HotObjectCache()
        self.hot_cache.attach_layer(object_layer)
        host, _, port = address.rpartition(":")
        handler = _make_handler(self)
        if reuse_port is None:
            reuse_port = os.environ.get("MTPU_REUSE_PORT", "") \
                in ("1", "on", "true")
        # Event-loop connection plane (s3/eventloop.py): epoll accept/
        # dispatch, idle connections parked fd-cheap, bounded executor.
        # MTPU_HTTP_EVENTLOOP=off reverts wholesale to thread-per-
        # connection (and non-Linux platforms take it automatically).
        from minio_tpu.s3 import eventloop as eventloop_mod
        if eventloop_mod.loop_enabled():
            self.httpd = eventloop_mod.EventLoopServer(
                (host or "127.0.0.1", int(port)), handler,
                reuse_port=reuse_port,
                keepalive_s=handler.loop_keepalive_s)
        else:
            server_cls = _ReusePortHTTPServer if reuse_port \
                else ThreadingHTTPServer
            self.httpd = server_cls((host or "127.0.0.1", int(port)),
                                    handler)
        self.httpd.daemon_threads = True
        # Pre-forked worker identity (io/workers.py attaches these;
        # single-process mode is worker 0 of 1). cluster_stats, when
        # set, answers every worker's control-plane snapshot so
        # metrics/admin info aggregate across the fleet.
        self.worker_id = 0
        self.worker_total = 1
        self.cluster_stats = None
        # Self-declared node identity (distributed boot sets it; empty
        # on single-node deployments). Labels cluster-merged telemetry.
        self.node_id = ""
        # Fleet-wide trace subscription hub (io/workers.WorkerContext);
        # None = single-process mode, admin trace subscribes locally.
        self.cluster_trace = None
        self._thread: threading.Thread | None = None
        # Serializes read-modify-write of bucket metadata (policy /
        # tagging / versioning toggles) within this process; cross-node
        # serialization would ride the dsync namespace lock.
        self.bucket_meta_lock = threading.Lock()
        self.metrics = Metrics()
        # Continuous SLO engine (utils/slo.py): declared objectives
        # evaluated against the rolling windows above; None when
        # MTPU_SLO=off.
        from minio_tpu.utils.slo import SLOEngine
        self.slo = SLOEngine.from_env()
        if self.slo is not None:
            self.slo.start(metrics=self.metrics)
        # Admission control: bounded in-flight requests with per-class
        # gates and the per-request deadline budget
        # (MTPU_API_REQUESTS_MAX / _DEADLINE / _TIMEOUT; s3/admission.py).
        self.admission = AdmissionController.from_env()
        # Admin-triggered heal sweeps run in this background slot.
        self.heal_status: dict = {"state": "idle"}
        self._heal_thread: threading.Thread | None = None
        self._heal_lock = threading.Lock()
        # Drive lifecycle manager (object/drive_heal.DriveHealManager):
        # hot-replacement detection + checkpointed bulk heals. Wired by
        # minio_tpu.server boot; None = feature idle (tests, bare sets).
        self.drive_heal = None
        # Event notifier (events.EventNotifier); None = no targets.
        self.notifier = None
        # KMS for SSE-S3 (None until configured via MTPU_KMS_SECRET_KEY).
        from minio_tpu.crypto.kms import KMS
        self.kms = KMS.from_env()
        # Live request tracing + optional audit webhook. Background
        # spans (scanner/heal) and slow-op records publish through the
        # module hook straight into this broadcaster.
        from minio_tpu.s3.trace import TraceBroadcaster
        self.tracer = TraceBroadcaster()
        tracing_mod.set_publisher(self.tracer.publish)
        self.audit = None
        # Async bucket replication engine (replication.ReplicationEngine).
        self.replicator = None
        # Transparent compression for eligible content (off by default;
        # --compression enables).
        self.compression = False
        # Peer control plane fan-out: callable(kind, bucket="") set by
        # the distributed boot (grid.peers.PeerNotifier.broadcast);
        # None on single-node deployments.
        self.peer_notify = None
        # Warm-tier registry (object/tier.TierRegistry), created on
        # first admin use or at boot.
        self.tiers = None
        # OpenID validator for AssumeRoleWithWebIdentity; built lazily
        # from the config subsystem, reset on config change.
        self.oidc = None
        # Admin profiling (s3/profiling.py); peer grid clients are set
        # by the distributed boot so bundles cover every node.
        from minio_tpu.s3.profiling import Profiler
        self.profiler = Profiler()
        self.profile_peers = []            # [(name, grid client)]
        # Batch-job manager (object/batch.BatchJobs), ditto.
        self.batch = None
        # Site replicator (replication/site.SiteReplicator); None until
        # sites are registered.
        self.site = None
        # In-flight request count (stop() drains to zero before
        # closing the layer). Guarded: bare += across handler threads
        # can lose updates and either close the layer under a live
        # request or burn the full drain deadline.
        self._inflight = 0
        self._inflight_mu = threading.Lock()
        # Bucket-quota usage cache: bucket -> [stamp, bytes]. Seeded by
        # a live walk (TTL'd), advanced by committed writes so quota
        # enforcement reacts between scanner cycles (reference:
        # cmd/bucket-quota.go enforces from the data-usage cache).
        self.scanner = None
        self._quota_usage: dict = {}
        self._quota_mu = threading.Lock()

    @property
    def address(self) -> str:
        h, p = self.httpd.server_address[:2]
        return f"{h}:{p}"

    def eventloop_stats(self):
        """Connection-plane snapshot of the epoll front end, or None
        under the thread-per-connection path (metrics/admin surface)."""
        stats = getattr(self.httpd, "stats", None)
        return stats() if stats is not None else None

    def start(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        # Drain in-flight requests before tearing down anything they
        # use (shutdown() only stops the accept loop; an accepted large
        # PUT must finish cleanly, not 500 on a closed executor).
        # Counted explicitly: socketserver does NOT track daemon
        # handler threads (_Threads.append returns early for them).
        deadline = _time_mod.monotonic() + 10
        while self._inflight > 0 and _time_mod.monotonic() < deadline:
            _time_mod.sleep(0.05)
        # Workers that consume the object layer stop BEFORE the layer
        # closes — a replication/notification worker mid-delivery must
        # not hit a shut-down executor (and their threads must not
        # outlive the server: the leak harness counts them).
        if self.slo is not None:
            self.slo.stop()
        if self.site is not None:
            self.site.stop()
        if self.replicator is not None:
            self.replicator.stop()
        if self.notifier is not None:
            stop = getattr(self.notifier, "stop", None)
            if stop is not None:
                stop()
        if self.batch is not None:
            self.batch.shutdown()
        close = getattr(self.object_layer, "close", None)
        if close is not None:
            close()


def _keepalive_seconds():
    """MTPU_HTTP_KEEPALIVE_S: idle keep-alive deadline, shared by the
    thread path (settimeout around the head parse) and the event
    loop's parked-connection reaper. None = no idle timeout."""
    try:
        keepalive_s = float(
            os.environ.get("MTPU_HTTP_KEEPALIVE_S", "") or 75.0)
    except ValueError:
        keepalive_s = 75.0
    if keepalive_s <= 0:
        # <= 0 means "no idle timeout" — settimeout(0) would flip the
        # socket non-blocking and drop every slow-arriving head.
        return None
    return keepalive_s


def _make_handler(server: S3Server):
    # Native serve hot loop (s3/hotloop.py): request heads framed
    # GIL-free out of a pooled per-connection recv buffer, kept hot
    # across keep-alive requests. MTPU_HTTP_NATIVE=off (or a missing
    # native lib) keeps the stock BaseHTTPRequestHandler parse path.
    native_lib = hotloop.lib() if hotloop.native_enabled() else None
    keepalive_s = _keepalive_seconds()
    from minio_tpu.object import hotcache as hotcache_mod

    # Hot-cache short circuit (object/hotcache.py), run ON the event
    # loop thread before dispatch: a plain signed whole-object GET whose
    # object is resident in the hot read tier is answered from the
    # entry's captured header template (Date re-spliced) + pinned body —
    # no executor thread, no object-layer call, no erasure fan-out, no
    # journal read. Anything the probe declines dispatches to the full
    # handler unchanged, so declined requests are byte-identical to a
    # cache-off server. Admission gates are deliberately bypassed: a hit
    # is a RAM copy on the loop thread with none of the drive/CPU
    # fan-out the per-class admission slots exist to bound.
    _HOT_DECLINE = ("transfer-encoding", "expect",
                    "range", "if-match", "if-none-match",
                    "if-modified-since", "if-unmodified-since",
                    "x-amz-checksum-mode", "x-amz-security-token",
                    "x-amz-server-side-encryption-customer-algorithm",
                    "x-amz-server-side-encryption-customer-key")

    def _hot_probe(handler, head):
        """(bufs, close_connection) for a servable hot GET, else None.

        Only the root credential short-circuits: root bypasses policy
        evaluation legitimately (see _authorize); any other identity
        needs the bucket/IAM policy walk, so the full handler runs.
        Auth failures also decline — the handler then produces the
        exact error a cache-off server would."""
        hc = server.hot_cache
        if hc is None or not hc.enabled:
            return None
        d, method, target, version, http11 = head
        if method != "GET" or "?" in target:
            return None
        if "authorization" not in d:
            return None
        # A GET carrying a body would desynchronize the framed stream
        # (we never read bodies here); an explicit zero length is fine.
        if d.get("content-length", "0").strip() not in ("", "0"):
            return None
        for hk in _HOT_DECLINE:
            if hk in d:
                return None
        t0 = _time_mod.perf_counter()
        parts = urllib.parse.unquote(target).lstrip("/").split("/", 1)
        if len(parts) < 2 or not parts[0] or not parts[1]:
            return None
        bucket, key = parts[0], parts[1]
        entry = hc.get(bucket, key)
        if entry is None or entry.head_prefix is None:
            return None
        try:
            auth = sigv4.verify_request("GET", target, {}, d,
                                        server.credentials.secret_for)
        except Exception:  # noqa: BLE001 - any auth failure: full handler
            return None
        if auth.anonymous or auth.credential is None \
                or auth.credential.access_key \
                != server.credentials.access_key:
            return None
        body = entry.body
        bufs = [entry.head_prefix, hotcache_mod.date_bytes(),
                entry.head_suffix, body]
        conntype = d.get("connection", "").lower()
        if conntype == "close":
            close = True
        elif http11:
            close = False
        else:
            close = conntype != "keep-alive"
        # The loop path never enters _route: replicate its per-request
        # accounting (metrics, path split, keep-alive reuse, trace and
        # audit) so hot hits are observable like every other response.
        handler._count_request()
        dt = _time_mod.perf_counter() - t0
        server.metrics.record("GET:object", 200, dt, rx=0, tx=len(body))
        server.metrics.response_path("hotcache")
        if server.tracer.active or server.audit is not None:
            from minio_tpu.s3.trace import make_entry
            te = make_entry(
                "GET:object", "GET", target, bucket, key, 200, dt,
                handler.client_address[0] if handler.client_address
                else "", auth.credential.access_key, rx=0, tx=len(body))
            te["worker"] = server.worker_id
            server.tracer.publish(te)
            if server.audit is not None:
                server.audit.submit(te)
        return bufs, close

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        server_version = "MinIO-TPU"
        # Event-loop dispatcher hooks (s3/eventloop.py): the loop frames
        # heads with the same native lib and enforces the same idle
        # deadline the thread path applies via settimeout.
        loop_native_lib = native_lib
        loop_keepalive_s = keepalive_s
        loop_hot_probe = staticmethod(_hot_probe)

        # -- plumbing ---------------------------------------------------

        def log_message(self, fmt, *args):  # quiet; tracing subsystem logs
            pass

        def setup(self):
            super().setup()
            self._requests_served = 0
            self._h_lower = None
            self._conn = None
            self._body_reader = None
            self._defer_head = False
            self._deferred_head = None
            # Per-response override for the response-path counter
            # ("hotcache" when _get_object served from the hot tier);
            # None = the transport default (pooled/legacy/sendfile).
            self._path_kind = None
            # Set by the event-loop dispatcher (s3/eventloop.py _Conn);
            # None under the thread-per-connection front end.
            self._loop_conn = None
            if native_lib is not None:
                # The pooled ConnReader replaces the per-connection
                # BufferedReader for EVERY parser (the Python fallback
                # reads lines from the same buffer), so fast path and
                # fallback see one byte stream.
                try:
                    conn = hotloop.ConnReader(self.connection)
                except Exception:  # noqa: BLE001 - pool/alloc failure
                    conn = None
                if conn is not None:
                    try:
                        self.rfile.close()
                    except OSError:
                        pass
                    self.rfile = conn
                    self._conn = conn
            server.metrics.conn_open()

        def finish(self):
            try:
                super().finish()
            finally:
                if self._conn is not None:
                    self._conn.close()
                server.metrics.conn_close()

        def handle_one_request(self):
            """Native fast path: frame the head out of the connection
            buffer in one GIL-free scan; dispatch straight to do_*.
            Anything the framer rejects is re-parsed by the stock
            Python path from the SAME buffered bytes (counted)."""
            self._h_lower = None
            conn = self._conn
            if conn is None:
                return self._stock_request()
            try:
                # Idle keep-alive connections time out between requests
                # (stock behavior blocks forever); mid-head timeouts
                # close too — the deadline budget governs the rest of
                # the request, not the socket.
                self.connection.settimeout(keepalive_s)
                try:
                    head = conn.parse_head(native_lib)
                finally:
                    self.connection.settimeout(None)
            except hotloop._Fallback:
                server.metrics.parse_fallback()
                return self._stock_request()
            except (socket_mod.timeout, ConnectionError):
                self.close_connection = True
                return
            except OSError:
                self.close_connection = True
                return
            if head is None:                  # clean close between requests
                self.close_connection = True
                return
            self._dispatch_head(head)

        def _dispatch_head(self, head):
            """Serve ONE natively-framed request head: shared by the
            thread path above and the event-loop dispatcher
            (s3/eventloop.py), which frames heads on the loop and hands
            them here on an executor thread."""
            self._h_lower = None
            d, method, target, version, http11 = head
            self.command = method
            self.path = target
            self.request_version = version
            self.requestline = f"{method} {target} {version}"
            self.headers = hotloop.FastHeaders(d)
            conntype = d.get("connection", "").lower()
            if conntype == "close":
                self.close_connection = True
            elif http11:
                self.close_connection = False
            else:
                self.close_connection = conntype != "keep-alive"
            if http11 and d.get("expect", "").lower() == "100-continue":
                self.wfile.write(b"HTTP/1.1 100 Continue\r\n\r\n")
            self._count_request()
            mname = "do_" + method
            if not hasattr(self, mname):
                self.send_error(501, f"Unsupported method ({method!r})")
                return
            try:
                getattr(self, mname)()
                self.wfile.flush()
            except (socket_mod.timeout, ConnectionError):
                self.close_connection = True

        def _count_request(self):
            self._requests_served += 1
            if self._requests_served > 1:
                server.metrics.keepalive_reuse()

        def _stock_request(self):
            """Stock parse path (MTPU_HTTP_NATIVE=off or native-framer
            fallback) with the same connection accounting as the fast
            path: a non-empty request line means the connection served
            one more request, so keepalive_reuses_total stays truthful
            with the native framer disabled."""
            self.raw_requestline = b""
            rv = super().handle_one_request()
            if getattr(self, "raw_requestline", b""):
                self._count_request()
            return rv

        def flush_headers(self):
            """Deferred-head hook for gathered writes: while
            _defer_head is set the formatted header block is stashed so
            the response path can sendmsg it WITH the first body bytes
            in one syscall instead of a separate write."""
            buf = b"".join(getattr(self, "_headers_buffer", []))
            self._headers_buffer = []
            if self._defer_head:
                self._deferred_head = buf
                self._defer_head = False
            else:
                self.wfile.write(buf)

        def _take_head(self) -> bytes:
            head, self._deferred_head = self._deferred_head, None
            self._defer_head = False
            return head or b""

        def _send_bufs(self, bufs, final: bool = False) -> None:
            """Gathered zero-copy write: one sendmsg for head + body
            views (pooled GET windows go to the wire as memoryviews,
            no Python-level joins). Falls back to wfile on platforms
            without sendmsg.

            `final` marks a response's LAST write: under the event loop
            a full socket buffer then hands the remainder to the loop's
            EPOLLOUT drain (the executor thread goes back to the pool
            instead of blocking on a slow reader); it also stamps the
            per-response path-split counter exactly once."""
            lc = self._loop_conn
            if final and lc is not None:
                self.server.offload_final(lc, bufs)
                server.metrics.response_path(self._path_kind or "pooled")
                return
            try:
                hotloop.send_gathered(self.connection, bufs)
                if final:
                    server.metrics.response_path(self._path_kind
                                                 or "pooled")
            except (AttributeError, NotImplementedError):
                sent = 0
                try:
                    for b in bufs:
                        if len(b):
                            self.wfile.write(b)
                            sent += len(b)
                    if final:
                        server.metrics.response_path(self._path_kind
                                                     or "legacy")
                except Exception as e:  # noqa: BLE001 - annotate progress
                    e.mtpu_sent = sent
                    raise

        def _sendfile_body(self, head: bytes, fd: int, offset: int,
                           length: int) -> None:
            """Whole-object zero-copy GET body: the header block goes
            out via the gathered write, then the body moves file->socket
            entirely in-kernel (os.sendfile) — no userspace byte, no
            pooled window. Blocking-socket context only (the event
            loop's executor and the thread path both hold the socket
            blocking while a handler runs); the caller's finally owns
            the fd."""
            sent = 0
            try:
                # Span the in-kernel copy so the short-circuit shows up
                # in internal traces and the slow-op log like every
                # other response path (it never touches the pooled
                # windows the engine spans cover).
                with tracing_mod.span("http", "sendfile",
                                      {"bytes": length}) \
                        if tracing_mod.ACTIVE else tracing_mod.NOOP:
                    self._send_bufs([head])
                    sfd = self.connection.fileno()
                    while sent < length:
                        n = os.sendfile(sfd, fd, offset + sent,
                                        min(length - sent, 1 << 24))
                        if n == 0:      # truncated source: cut short
                            break
                        sent += n
                self._sent_bytes = getattr(self, "_sent_bytes", 0) + sent
            except OSError:
                # Headers (a 200) may already be on the wire: all we
                # can do is cut the connection so the client sees a
                # truncated transfer, never a silently short body.
                sent = -1
            if sent == length:
                server.metrics.response_path("sendfile")
            else:
                self.close_connection = True

        def _headers_lower(self) -> dict[str, str]:
            h = self.headers
            d = getattr(h, "d", None)      # FastHeaders: already lowercase
            if d is not None:
                return d
            if self._h_lower is None:
                low: dict[str, str] = {}
                for k, v in h.items():
                    k = k.lower()
                    # Repeats fold with a comma, matching both the
                    # native framer and SigV4 canonicalization — the
                    # two parse paths must verify identically.
                    low[k] = low[k] + "," + v if k in low else v
                self._h_lower = low
            return self._h_lower

        def _parse(self):
            parsed = urllib.parse.urlsplit(self.path)
            raw_path = parsed.path          # still percent-encoded: signed
            path = urllib.parse.unquote(raw_path)
            query = urllib.parse.parse_qs(parsed.query,
                                          keep_blank_values=True)
            parts = path.lstrip("/").split("/", 1)
            bucket = parts[0] if parts[0] else ""
            key = parts[1] if len(parts) > 1 else ""
            return raw_path, query, bucket, key

        def _read_body(self) -> bytes:
            te = self._headers_lower().get("transfer-encoding", "")
            if "chunked" in te.lower():
                out = bytearray()
                while True:
                    line = self.rfile.readline().strip()
                    try:
                        size = int(line.split(b";")[0], 16)
                    except ValueError:
                        raise S3Error("IncompleteBody") from None
                    if size == 0:
                        self.rfile.readline()
                        break
                    if len(out) + size > MAX_OBJECT_SIZE:
                        raise S3Error("EntityTooLarge")
                    out += self.rfile.read(size)
                    self.rfile.readline()
                return bytes(out)
            length = int(self.headers.get("Content-Length") or 0)
            if length > MAX_OBJECT_SIZE:
                raise S3Error("EntityTooLarge")
            return self.rfile.read(length) if length else b""

        def _auth(self, method, path, query) -> sigv4.ParsedAuth:
            return sigv4.verify_request(
                method, path, query, self._headers_lower(),
                server.credentials.secret_for)

        def _auth_context(self, access_key: str, query: dict,
                          h: dict) -> dict:
            """Condition-key context for policy evaluation (reference:
            cmd/auth-handler.go getConditionValues). Keys are stored
            lowercase; Statement.conditions_met folds case."""
            ctx = {
                "aws:sourceip": self.client_address[0]
                if self.client_address else "",
                "aws:securetransport": "false",
                "aws:useragent": h.get("user-agent", ""),
                "aws:referer": h.get("referer", ""),
                "aws:username": access_key,
                "aws:userid": access_key,
            }
            for qk in ("prefix", "delimiter", "max-keys", "versionId"):
                v = query.get(qk, [""])[0]
                if v:
                    ctx[f"s3:{qk.lower()}"] = v
            for hk, hv in h.items():
                if hk.startswith("x-amz-"):
                    ctx[f"s3:{hk}"] = hv
            return ctx

        def _bucket_policy(self, bucket: str):
            """Compiled bucket policy, None when absent, or _BAD_POLICY
            when a stored document fails to compile — the caller must
            fail CLOSED on that (returning None would silently drop the
            document's Deny statements)."""
            if not bucket or bucket == "*":
                return None
            import json as _json
            try:
                stored = server.object_layer.get_bucket_meta(bucket).get(
                    "config:policy")
            except Exception:  # noqa: BLE001 - bucket missing / offline
                return None
            if not stored:
                return None
            try:
                from minio_tpu.iam.policy import compile_policy
                return compile_policy(_json.loads(stored))
            except Exception:  # noqa: BLE001 - legacy/corrupt document
                return _BAD_POLICY

        def _authorize(self, ak: str, anonymous: bool, action: str,
                       resource: str, ctx: dict) -> bool:
            """Merge identity and bucket-policy decisions, deny-wins
            (reference: cmd/auth-handler.go:433-449,758): root always
            passes; anonymous requires an explicit bucket-policy Allow;
            signed identities pass if either side allows and neither
            explicitly denies."""
            if ak == server.credentials.access_key:
                return True
            from minio_tpu.iam.policy import decide
            bp = self._bucket_policy(resource.split("/", 1)[0])
            if bp is _BAD_POLICY:
                # A policy exists but cannot be evaluated: every
                # non-owner request to the bucket is refused rather
                # than guessing what it said.
                return False
            bp_decision = None if bp is None else decide(
                [bp], action, resource, ctx,
                ak if not anonymous else None, require_principal=True)
            if bp_decision == "Deny":
                return False
            if anonymous:
                return bp_decision == "Allow"
            id_decision = server.credentials.decide(ak, action, resource,
                                                    ctx)
            if id_decision == "Deny":
                return False
            return id_decision == "Allow" or bp_decision == "Allow"

        def _make_payload(self, auth) -> Payload:
            """Sized streaming payload for object-data PUTs: the body is
            never materialized; content verification (sha256 or chunk
            signatures) runs incrementally and rejects before commit."""
            h = self._headers_lower()
            te = h.get("transfer-encoding", "")
            if auth.payload_hash in (sigv4.STREAMING_PAYLOAD,
                                     sigv4.STREAMING_PAYLOAD_TRAILER,
                                     sigv4.STREAMING_UNSIGNED_TRAILER):
                declared = h.get("x-amz-decoded-content-length")
                if declared is None:
                    raise S3Error("MissingContentLength")
                declared = int(declared)
                if declared > MAX_OBJECT_SIZE:
                    raise S3Error("EntityTooLarge")
                if "chunked" in te.lower():
                    # aws-chunked inside HTTP TE-chunked (SDK pattern
                    # for unknown-length streams): strip the transfer
                    # framing incrementally first.
                    raw = HttpChunkedReader(self.rfile)
                else:
                    encoded_len = int(h.get("content-length") or 0)
                    raw = LimitedReader(self.rfile, encoded_len)
                secret = server.credentials.secret_for(
                    auth.credential.access_key)
                # Native-scan pooled decoder when available (byte-
                # identical to ChunkedPayloadReader, golden-tested);
                # tracked on the handler so its recv-buffer lease
                # returns deterministically even on error paths.
                reader = sigv4.chunked_reader(
                    raw, auth, secret,
                    verify_signatures=auth.payload_hash
                    != sigv4.STREAMING_UNSIGNED_TRAILER)
                self._body_reader = reader
                return Payload(reader, declared, finish=reader.finalize)
            if "chunked" in te.lower():
                # Plain HTTP chunked TE (no declared size): buffer it —
                # rare for S3 clients; bounded by MAX_OBJECT_SIZE.
                body = self._read_body()
                if auth.payload_hash != sigv4.UNSIGNED_PAYLOAD and \
                        hashlib.sha256(body).hexdigest() != auth.payload_hash:
                    raise S3Error("XAmzContentSHA256Mismatch")
                return Payload.wrap(body)
            length = int(h.get("content-length") or 0)
            if length > MAX_OBJECT_SIZE:
                raise S3Error("EntityTooLarge")
            raw = LimitedReader(self.rfile, length)
            if auth.payload_hash == sigv4.UNSIGNED_PAYLOAD:
                return Payload(raw, length)
            hasher = HashingReader(raw)
            want = auth.payload_hash

            def fin():
                if hasher.hexdigest() != want:
                    raise S3Error("XAmzContentSHA256Mismatch")
            return Payload(hasher, length, finish=fin)

        def _send(self, status: int, body: bytes = b"",
                  headers: dict | None = None, content_type="application/xml"):
            self._defer_head = True
            self.send_response(status)
            self.send_header("x-amz-request-id", "0")
            if body or status not in (204, 304):
                self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            head = self._take_head()
            if body and self.command != "HEAD":
                self._send_bufs([head, body], final=True)
                self._sent_bytes = getattr(self, "_sent_bytes", 0) \
                    + len(body)
            else:
                self._send_bufs([head], final=True)

        # Shed-path body drain cap: reading the remnant is cheap
        # network receive (the resource being protected is CPU/disk,
        # not the NIC), but it must stay bounded — a multi-GiB upload
        # is closed on instead (SDKs retry on connection errors).
        _DRAIN_CAP = 8 << 20

        def _drain_unread_body(self) -> None:
            """Discard the request body AFTER an early error response,
            bounded by _DRAIN_CAP and a read timeout. Only safe where
            NOTHING of the body has been consumed yet (the admission
            path runs before any body read); Content-Length framing
            only — chunked bodies just close (framing-position
            unknown). The shape of Go http.Server's pre-close drain."""
            try:
                h = self._headers_lower()
                if "chunked" in h.get("transfer-encoding", "").lower():
                    return
                remaining = int(h.get("content-length") or 0)
            except ValueError:
                return
            if remaining <= 0 or remaining > self._DRAIN_CAP:
                return
            try:
                self.connection.settimeout(2.0)
                while remaining > 0:
                    chunk = self.rfile.read(min(65536, remaining))
                    if not chunk:
                        return
                    remaining -= len(chunk)
            except OSError:
                pass        # stalled/gone client; the close handles it

        def _send_error(self, e: Exception, bucket="", key=""):
            # The request body may be partially or fully unread (auth runs
            # before body consumption): close the connection rather than
            # letting keep-alive parse leftover body bytes as a request.
            self.close_connection = True
            err = from_exception(e)
            if err.code == "RequestTimeout":
                server.admission.record_deadline_exceeded()
            root = ET.Element("Error")
            _el(root, "Code", err.code)
            _el(root, "Message", err.message)
            _el(root, "BucketName", err.bucket or bucket)
            _el(root, "Key", err.key or key)
            _el(root, "Resource", self.path)
            _el(root, "RequestId", "0")
            self._send(err.status, _xml(root),
                       headers=getattr(err, "headers", None))

        # -- dispatch ---------------------------------------------------

        def send_response(self, code, message=None):
            self._last_status = code
            super().send_response(code, message)

        def _api_label(self, method, raw_path, bucket, key,
                       pc=None) -> str:
            if pc is None:
                pc = admission_path_class(raw_path)
            if pc != "s3":
                return f"{method}:{pc}"
            scope = "object" if key else ("bucket" if bucket else "service")
            return f"{method}:{scope}"

        def _route(self, method: str):
            raw_path, query, bucket, key = self._parse()
            # Classify the path ONCE per request; admission gating,
            # dispatch, and the metrics label all consume this instead
            # of re-running the pattern checks (the hot loop's
            # "admission without re-entering the router slow path").
            pc = admission_path_class(raw_path)
            api = self._api_label(method, raw_path, bucket, key, pc)
            self._last_status = 0
            self._sent_bytes = 0
            self._auth_key = ""
            self._path_kind = None
            t0 = _time_mod.perf_counter()
            with server._inflight_mu:
                server._inflight += 1
            gate = None
            tctx = None
            try:
                # Admission: bounded in-flight slots per request class
                # BEFORE any auth/body work — a saturated server sheds
                # with 503 + Retry-After instead of queueing unbounded
                # (reference: maxClients, cmd/generic-handlers.go).
                try:
                    gate = server.admission.enter(
                        admission_class_for(pc))
                except AdmissionShed as shed:
                    err = S3Error("SlowDown", str(shed))
                    err.headers = {"Retry-After": str(shed.retry_after)}
                    self._send_error(err, bucket, key)
                    # A shed PUT's client is mid-upload: discard its
                    # body (bounded) so it can finish sending and READ
                    # the 503 + Retry-After instead of dying on a
                    # connection reset when we close under its write.
                    self._drain_unread_body()
                    return
                # Per-request deadline budget: every layer below (fan-
                # outs, drive deadlines, grid calls) consumes from it,
                # so one hung drive bounds the request, not the stack
                # of per-layer timeouts.
                dl = None
                if server.admission.request_timeout > 0:
                    dl = deadline_mod.Deadline(
                        server.admission.request_timeout)
                # Span context: armed only while somebody watches (a
                # trace subscriber wanting internal types, a remote
                # worker relay, or a slow-op threshold) — disarmed,
                # requests pay one attribute check. It rides the same
                # binding channel the deadline budget rides.
                if tracing_mod.ACTIVE:
                    tctx = tracing_mod.TraceContext()
                # The whole request on the profiler's clock, under the
                # API's label, holding its stages' seconds until it ends
                # (the request's record is the trace root published
                # below; its seconds are api_request_duration_seconds).
                with deadline_mod.bind(dl), tracing_mod.bind(tctx), \
                        server.profiler.request_profile(), \
                        tracing_mod.request_root("s3." + api):
                    self._route_inner(method, raw_path, query, bucket, key,
                                      pc)
            finally:
                if gate is not None:
                    gate.leave()
                reader = getattr(self, "_body_reader", None)
                if reader is not None:
                    self._body_reader = None
                    close = getattr(reader, "close", None)
                    if close is not None:
                        close()
                with server._inflight_mu:
                    server._inflight -= 1
                try:
                    rx = int(self.headers.get("Content-Length") or 0)
                except ValueError:
                    rx = 0
                dt = _time_mod.perf_counter() - t0
                status = self._last_status or 500
                server.metrics.record(api, status, dt,
                                      rx=rx, tx=self._sent_bytes)
                if server.slo is not None:
                    server.slo.observe(api, status)
                if server.tracer.active or server.audit is not None:
                    from minio_tpu.s3.trace import make_entry
                    entry = make_entry(
                        api, method, raw_path, bucket, key, status, dt,
                        self.client_address[0] if self.client_address
                        else "", self._auth_key, rx=rx,
                        tx=self._sent_bytes)
                    entry["worker"] = server.worker_id
                    if server.node_id:
                        entry["node"] = server.node_id
                    if tctx is not None:
                        # The request record IS the trace root: span 0,
                        # every internal span parents (transitively)
                        # under it.
                        entry["trace_type"] = "s3"
                        entry["trace"] = tctx.trace_id
                        entry["span"] = 0
                        server.tracer.publish(entry)
                        if server.tracer.wants_internal():
                            for se in tracing_mod.entries_from(
                                    tctx, worker=server.worker_id):
                                server.tracer.publish(se)
                    else:
                        server.tracer.publish(entry)
                    if server.audit is not None:
                        server.audit.submit(entry)

        def _route_inner(self, method, raw_path, query, bucket, key,
                         pc=None):
            if pc is None:
                pc = admission_path_class(raw_path)
            try:
                # Unauthenticated endpoints: health probes and metrics
                # (reference: cmd/healthcheck-handler.go is authless;
                # metrics here follow suit for scrape simplicity).
                # (path_class in s3/admission.py is the shared pattern
                # source for these operator endpoints; keep dispatch
                # and classification in lockstep.)
                if raw_path == "/minio/health/live":
                    return self._send(200)
                if raw_path == "/minio/health/ready":
                    return self._health_ready()
                if pc == "metrics":
                    with tracing_mod.stage("s3.metrics_render",
                                           count=False):
                        # Worker mode: whichever worker the kernel handed
                        # this scrape to aggregates the whole fleet via
                        # the parent control pipe (io/workers.py).
                        peers = None
                        if server.cluster_stats is not None:
                            try:
                                peers = server.cluster_stats()
                            except Exception:  # noqa: BLE001 - serve own
                                peers = None
                        # Cluster federation: pull every peer NODE's
                        # telemetry over the grid (peer.metrics verb) so a
                        # scrape of any node reports the whole cluster
                        # with per-node labels. ?cluster=false opts out
                        # (per-node scrape configs avoiding N^2 fan-out).
                        nodes = None
                        want_cluster = (query.get("cluster", [""])[0]
                                        or "").lower() not in (
                            "false", "0", "off", "no")
                        if server.profile_peers and want_cluster:
                            nodes = self._cluster_metrics_states()
                        text = server.metrics.render(
                            object_layer=server.object_layer,
                            scanner=getattr(server.object_layer, "scanner",
                                            None),
                            server=server, peer_states=peers,
                            node_states=nodes)
                    return self._send(200, text.encode(),
                                      content_type="text/plain; "
                                      "version=0.0.4")
                ctype = self._headers_lower().get("content-type", "")
                if method == "POST" and bucket and not key \
                        and "multipart/form-data" in ctype:
                    # Browser POST-policy upload: credentials live in
                    # the form fields, not the Authorization header.
                    return self._post_object(bucket, self._read_body(),
                                             ctype)
                # Verify the signature from headers first; the declared
                # payload hash is part of the signed canonical request, so
                # the body is only hashed afterwards when the mode calls
                # for it (streaming modes verify per chunk instead). The
                # RAW request path is signed — never a re-encoding of it.
                # Requests with no credentials at all are anonymous and
                # authorized purely by bucket policy (reference:
                # cmd/auth-handler.go:433-449 authTypeAnonymous ->
                # globalPolicySys.IsAllowed).
                h = self._headers_lower()
                with tracing_mod.stage("s3.auth"):
                    if "authorization" not in h \
                            and "X-Amz-Signature" not in query \
                            and "Signature" not in query:
                        auth = sigv4.anonymous_auth()
                    else:
                        auth = self._auth(method, raw_path, query)
                    self._auth_key = auth.credential.access_key
                    # STS credentials must present their session token on
                    # every request (reference: cmd/auth-handler.go's
                    # getSessionToken check); permanent keys have none.
                    if not auth.anonymous and \
                            server.credentials.iam is not None:
                        tok = server.credentials.iam.session_token_for(
                            auth.credential.access_key)
                        if tok is not None:
                            presented = h.get("x-amz-security-token", "") or \
                                query.get("X-Amz-Security-Token", [""])[0]
                            if presented != tok:
                                raise S3Error("AccessDenied",
                                              "invalid session token")
                    if pc != "admin":
                        # Per-request policy authorization (reference:
                        # checkRequestAuthType -> IsAllowed): root passes, IAM
                        # identities evaluate their policies merged deny-wins
                        # with the bucket policy; anonymous identities need an
                        # explicit bucket-policy Allow.
                        ak = auth.credential.access_key
                        ctx = self._auth_context(ak, query, h)
                        for action, resource in _required_permissions(
                                method, bucket, key, query, h):
                            if not self._authorize(ak, auth.anonymous, action,
                                                   resource, ctx):
                                raise S3Error("AccessDenied", bucket=bucket,
                                              key=key)
                if pc == "admin":
                    if auth.anonymous:
                        raise S3Error("AccessDenied")
                    return self._admin_op(method, raw_path, query, auth)
                body = b""
                payload = None
                # Object-data PUTs stream O(window); every other body
                # (bucket XML, multipart-complete XML, ...) is small and
                # buffered with upfront content verification.
                data_put = method == "PUT" and bool(key)
                if data_put:
                    payload = self._make_payload(auth)
                elif method in ("PUT", "POST"):
                    body = self._read_body()
                    if auth.payload_hash in (
                            sigv4.STREAMING_PAYLOAD,
                            sigv4.STREAMING_PAYLOAD_TRAILER,
                            sigv4.STREAMING_UNSIGNED_TRAILER):
                        secret = server.credentials.secret_for(
                            auth.credential.access_key)
                        body = sigv4.decode_chunked_payload(body, auth, secret)
                    elif auth.payload_hash != sigv4.UNSIGNED_PAYLOAD:
                        if hashlib.sha256(body).hexdigest() != auth.payload_hash:
                            raise S3Error("XAmzContentSHA256Mismatch")

                if not bucket:
                    if method == "GET":
                        return self._list_buckets()
                    if method == "POST":
                        # STS rides POST / with a form body (reference:
                        # cmd/sts-handlers.go router).
                        return self._sts_op(auth, body)
                    raise S3Error("MethodNotAllowed")
                try:
                    if not key:
                        return self._bucket_op(method, bucket, query, body)
                    return self._object_op(method, bucket, key, query, body,
                                           payload)
                finally:
                    # A handler that did not drain the request body (copy
                    # object, errors) leaves bytes on the socket: close
                    # rather than let keep-alive misparse them.
                    if payload is not None and payload.remaining:
                        self.close_connection = True
            except Exception as e:  # noqa: BLE001 - rendered as S3 error XML
                self._send_error(e, bucket, key)

        def do_GET(self):
            self._route("GET")

        def do_PUT(self):
            self._route("PUT")

        def do_POST(self):
            self._route("POST")

        def do_DELETE(self):
            self._route("DELETE")

        def do_HEAD(self):
            self._route("HEAD")

        # -- service / bucket ops --------------------------------------

        def _sts_op(self, auth, body: bytes):
            """POST / — STS (reference: cmd/sts-handlers.go:61-65):
            AssumeRole (any authenticated USER identity mints temporary
            credentials scoped to its own permissions, optionally
            narrowed by a session policy) and
            AssumeRoleWithWebIdentity (an OIDC JWT from a configured
            IdP mints credentials mapped from its policy claim — no
            local user needed, no SigV4 on the request)."""
            import json as _json
            form = dict(urllib.parse.parse_qsl(
                body.decode("utf-8", "replace")))
            action = form.get("Action", "")
            if action not in ("AssumeRole", "AssumeRoleWithWebIdentity"):
                raise S3Error("NotImplemented", f"STS action {action!r}")
            iam = server.credentials.iam
            if iam is None:
                raise S3Error("NotImplemented", "no IAM store")
            duration = None
            if form.get("DurationSeconds"):
                try:
                    duration = int(form["DurationSeconds"])
                except ValueError:
                    raise S3Error("InvalidArgument",
                                  "bad DurationSeconds") from None
            from minio_tpu.iam import IAMError
            from minio_tpu.iam.policy import PolicyError
            if action == "AssumeRoleWithWebIdentity":
                rec = self._sts_web_identity(iam, form, duration)
            else:
                if auth.anonymous:
                    raise S3Error("AccessDenied")
                policy = None
                if form.get("Policy"):
                    try:
                        policy = _json.loads(form["Policy"])
                    except ValueError:
                        raise S3Error("MalformedPolicy") from None
                try:
                    rec = iam.assume_role(auth.credential.access_key,
                                          duration, policy)
                except PolicyError as e:
                    raise S3Error("MalformedPolicy", str(e)) from None
                except IAMError as e:
                    raise S3Error("AccessDenied", str(e)) from None
            root = ET.Element(
                f"{action}Response",
                xmlns="https://sts.amazonaws.com/doc/2011-06-15/")
            res = _el(root, f"{action}Result")
            if action == "AssumeRoleWithWebIdentity" and rec.get("subject"):
                _el(res, "SubjectFromWebIdentityToken", rec["subject"])
            creds = _el(res, "Credentials")
            _el(creds, "AccessKeyId", rec["access_key"])
            _el(creds, "SecretAccessKey", rec["secret_key"])
            _el(creds, "SessionToken", rec["session_token"])
            _el(creds, "Expiration", _iso8601(rec["expiry_ns"]))
            self._send(200, _xml(root))

        def _sts_web_identity(self, iam, form: dict, duration):
            """Validate the WebIdentityToken against the configured
            OIDC provider and mint claim-mapped credentials."""
            from minio_tpu.iam import IAMError
            from minio_tpu.iam.oidc import OIDCError, OpenIDValidator
            token = form.get("WebIdentityToken", "")
            if not token:
                raise S3Error("InvalidArgument",
                              "WebIdentityToken is required")
            validator = server.oidc
            if validator is None:
                from minio_tpu.s3 import config as cfg_mod
                cfg = cfg_mod.load_config(server.object_layer)
                try:
                    validator = OpenIDValidator.from_config(cfg)
                except OIDCError as e:
                    raise S3Error("NotImplemented", str(e)) from None
                if validator is None:
                    raise S3Error("NotImplemented",
                                  "no OpenID provider configured")
                server.oidc = validator
            session_policy = None
            if form.get("Policy"):
                import json as _json
                try:
                    session_policy = _json.loads(form["Policy"])
                except ValueError:
                    raise S3Error("MalformedPolicy") from None
            try:
                claims = validator.validate(token)
                names = validator.policies_from(claims)
                rec = iam.assume_role_web_identity(
                    claims.get("sub", ""), names, duration,
                    session_policy)
            except OIDCError as e:
                raise S3Error("AccessDenied", str(e)) from None
            except IAMError as e:
                raise S3Error("AccessDenied", str(e)) from None
            rec["subject"] = claims.get("sub", "")
            return rec

        def _list_buckets(self):
            buckets = server.object_layer.list_buckets()
            root = ET.Element("ListAllMyBucketsResult", xmlns=XMLNS)
            owner = _el(root, "Owner")
            _el(owner, "ID", "minio-tpu")
            _el(owner, "DisplayName", "minio-tpu")
            bl = _el(root, "Buckets")
            for b in buckets:
                be = _el(bl, "Bucket")
                _el(be, "Name", b.name)
                _el(be, "CreationDate", _iso8601(b.created))
            self._send(200, _xml(root))

        # Bucket sub-configurations persisted in bucket metadata
        # (reference: cmd/bucket-metadata-sys.go keeps policy/lifecycle/
        # tagging/... documents in one quorum-replicated record):
        # meta key -> (absent-error, validator).
        # meta key -> (absent-error or None for empty-doc GET, validator).
        _BUCKET_CONFIGS = {
            "policy": ("NoSuchBucketPolicy", "_validate_policy_json"),
            "lifecycle": ("NoSuchLifecycleConfiguration",
                          "_validate_lifecycle_xml"),
            "tagging": ("NoSuchTagSet", "_validate_xml_doc"),
            "cors": ("NoSuchCORSConfiguration", "_validate_xml_doc"),
            "encryption": ("ServerSideEncryptionConfigurationNotFoundError",
                           "_validate_xml_doc"),
            "notification": (None, "_validate_notification_xml"),
            "replication": ("ReplicationConfigurationNotFoundError",
                            "_validate_replication_xml"),
        }

        def _validate_policy_json(self, body: bytes) -> None:
            import json as _json
            try:
                doc = _json.loads(body)
            except ValueError:
                raise S3Error("MalformedPolicy") from None
            if not isinstance(doc, dict) or "Statement" not in doc:
                raise S3Error("MalformedPolicy")
            # Full compile: unsupported condition operators and bad
            # principals are rejected HERE, not silently ignored at
            # evaluation time (ignoring a condition would over-grant).
            from minio_tpu.iam.policy import Policy, PolicyError
            try:
                pol = Policy.from_json(doc)
            except PolicyError as e:
                raise S3Error("MalformedPolicy", str(e)) from None
            # Bucket policies are principal-scoped by definition; a
            # statement without one is an identity-policy document
            # pasted in the wrong place (AWS rejects these too).
            if any(s.principals is None for s in pol.statements):
                raise S3Error("MalformedPolicy",
                              "bucket policy statements need a Principal")

        def _validate_xml_doc(self, body: bytes) -> None:
            try:
                ET.fromstring(body)
            except ET.ParseError:
                raise S3Error("MalformedXML") from None

        def _validate_lifecycle_xml(self, body: bytes) -> None:
            """Semantic validation, not just well-formedness: a config
            the scanner cannot evaluate must be rejected at PUT, never
            accepted and silently ignored."""
            from minio_tpu.object.lifecycle import (LifecycleError,
                                                    parse_lifecycle)
            try:
                parse_lifecycle(body)
            except LifecycleError as e:
                raise S3Error("MalformedXML", str(e)) from None

        def _validate_notification_xml(self, body: bytes) -> None:
            from minio_tpu.events import parse_notification_xml
            from minio_tpu.events.notify import EventError
            try:
                parse_notification_xml(body)
            except EventError as e:
                raise S3Error("MalformedXML", str(e)) from None

        def _validate_replication_xml(self, body: bytes) -> None:
            from minio_tpu.replication import (ReplicationError,
                                               parse_replication_xml)
            try:
                parse_replication_xml(body)
            except ReplicationError as e:
                raise S3Error("MalformedXML", str(e)) from None

        def _bucket_config(self, method, bucket, name, query, body):
            ol = server.object_layer
            ol.get_bucket_info(bucket)
            absent_err, validator = self._BUCKET_CONFIGS[name]
            meta_key = f"config:{name}"
            if method == "PUT":
                getattr(self, validator)(body)
                with server.bucket_meta_lock:
                    meta = ol.get_bucket_meta(bucket)
                    meta[meta_key] = body.decode("utf-8", "replace")
                    ol.set_bucket_meta(bucket, meta)
                self._site_enqueue("bucket-meta", bucket)
                return self._send(200)
            if method == "DELETE":
                with server.bucket_meta_lock:
                    meta = ol.get_bucket_meta(bucket)
                    if meta.pop(meta_key, None) is not None:
                        ol.set_bucket_meta(bucket, meta)
                self._site_enqueue("bucket-meta", bucket)
                return self._send(204)
            stored = ol.get_bucket_meta(bucket).get(meta_key)
            if stored is None:
                if absent_err is None:
                    # Unset notification config answers an empty
                    # document, per S3.
                    root = ET.Element("NotificationConfiguration",
                                      xmlns=XMLNS)
                    return self._send(200, _xml(root))
                raise S3Error(absent_err, bucket=bucket)
            ctype = "application/json" if name == "policy" \
                else "application/xml"
            return self._send(200, stored.encode(), content_type=ctype)

        def _notify(self, event_name, bucket, key, size=0, etag="",
                    version_id=""):
            if server.notifier is not None:
                server.notifier.notify(event_name, bucket, key, size=size,
                                       etag=etag, version_id=version_id)

        def _bucket_op(self, method, bucket, query, body):
            ol = server.object_layer
            for name in self._BUCKET_CONFIGS:
                if name in query:
                    return self._bucket_config(method, bucket, name, query,
                                               body)
            if "object-lock" in query:
                return self._object_lock_config(method, bucket, body)
            if "acl" in query:
                return self._acl(method, bucket, "", body)
            if method == "PUT":
                if "versioning" in query:
                    return self._put_versioning(bucket, body)
                _validate_bucket_name(bucket)
                ol.make_bucket(bucket)
                self._site_enqueue("bucket-make", bucket)
                if self._headers_lower().get(
                        "x-amz-bucket-object-lock-enabled", "").lower() \
                        == "true":
                    # Lock-enabled buckets are born versioned with the
                    # lock flag set atomically-enough (no objects can
                    # exist yet) — reference: cmd/bucket-handlers.go
                    # PutBucketHandler's objectLockEnabled path.
                    from minio_tpu.object import objectlock as olock
                    with server.bucket_meta_lock:
                        meta = ol.get_bucket_meta(bucket)
                        meta["versioning"] = True
                        meta[olock.BUCKET_META_KEY] = {"enabled": True}
                        ol.set_bucket_meta(bucket, meta)
                    self._site_enqueue("bucket-meta", bucket)
                return self._send(200, headers={"Location": f"/{bucket}"})
            if method == "HEAD":
                ol.get_bucket_info(bucket)
                return self._send(200)
            if method == "DELETE":
                ol.delete_bucket(bucket)
                self._site_enqueue("bucket-delete", bucket)
                return self._send(204)
            if method == "POST" and "delete" in query:
                return self._delete_objects(bucket, body)
            if method == "GET" and "uploads" in query:
                return self._list_uploads(bucket, query)
            if method == "GET":
                if "location" in query:
                    root = ET.Element("LocationConstraint", xmlns=XMLNS)
                    return self._send(200, _xml(root))
                if "versioning" in query:
                    return self._get_versioning(bucket)
                if "versions" in query:
                    return self._list_versions(bucket, query)
                return self._list_objects(bucket, query)
            raise S3Error("MethodNotAllowed")

        def _lock_config(self, bucket) -> dict:
            """The bucket's object-lock config ({} when lock-less).
            Read failures PROPAGATE: returning {} on a transient error
            would fail every lock check open (new versions without
            default retention, versioning suspendable mid-outage)."""
            from minio_tpu.object import objectlock as olock
            return server.object_layer.get_bucket_meta(bucket).get(
                olock.BUCKET_META_KEY) or {}

        def _object_attributes(self, bucket, key, query):
            """GET ?attributes — GetObjectAttributes (reference:
            cmd/object-handlers.go GetObjectAttributesHandler): the
            caller names the attributes it wants in
            x-amz-object-attributes."""
            h = self._headers_lower()
            wanted = {w.strip() for w in
                      h.get("x-amz-object-attributes", "").split(",")
                      if w.strip()}
            if not wanted:
                raise S3Error("InvalidArgument",
                              "x-amz-object-attributes is required")
            vid = query.get("versionId", [""])[0]
            info = server.object_layer.get_object_info(
                bucket, key, GetOptions(version_id=vid))
            root = ET.Element("GetObjectAttributesOutput", xmlns=XMLNS)
            if "ETag" in wanted:
                _el(root, "ETag", info.etag)
            if "Checksum" in wanted:
                from minio_tpu.s3 import checksum as ck
                stored = ck.response_headers(info.internal_metadata)
                if stored:
                    ce = _el(root, "Checksum")
                    for hname, v in stored.items():
                        algo = hname[len(ck.H_PREFIX):]
                        _el(ce, f"Checksum{algo.upper()}", v)
            if "ObjectParts" in wanted and info.parts and \
                    len(info.parts) > 1:
                pe = _el(root, "ObjectParts")
                _el(pe, "TotalPartsCount", len(info.parts))
                _el(pe, "IsTruncated", "false")
                for p in info.parts:
                    part = _el(pe, "Part")
                    _el(part, "PartNumber", p.number)
                    _el(part, "Size", p.actual_size)
            if "StorageClass" in wanted:
                _el(root, "StorageClass", info.storage_class or "STANDARD")
            if "ObjectSize" in wanted:
                _el(root, "ObjectSize", info.size)
            headers = {"Last-Modified": _rfc1123(info.mod_time)}
            if info.version_id:
                headers["x-amz-version-id"] = info.version_id
            return self._send(200, _xml(root), headers=headers)

        def _acl(self, method, bucket, key, body):
            """GET/PUT ?acl — the MinIO-parity ACL surface (reference:
            cmd/acl-handlers.go): ACLs are a legacy AWS mechanism; only
            'private' exists, GET always answers the owner's
            FULL_CONTROL, and any attempt to grant something else is
            refused (policies are the real authorization surface)."""
            if not key:
                server.object_layer.get_bucket_info(bucket)
            if method == "GET":
                root = ET.Element("AccessControlPolicy", xmlns=XMLNS)
                owner = _el(root, "Owner")
                _el(owner, "ID", "minio-tpu")
                _el(owner, "DisplayName", "minio-tpu")
                grants = _el(root, "AccessControlList")
                g = _el(grants, "Grant")
                grantee = _el(g, "Grantee")
                grantee.set("xmlns:xsi",
                            "http://www.w3.org/2001/XMLSchema-instance")
                grantee.set("xsi:type", "CanonicalUser")
                _el(grantee, "ID", "minio-tpu")
                _el(g, "Permission", "FULL_CONTROL")
                return self._send(200, _xml(root))
            if method != "PUT":
                raise S3Error("MethodNotAllowed")
            h = self._headers_lower()
            canned = h.get("x-amz-acl", "")
            if canned and canned != "private":
                raise S3Error("NotImplemented",
                              "only the 'private' canned ACL exists; "
                              "use bucket policies")
            if body:
                try:
                    root = ET.fromstring(body)
                except ET.ParseError:
                    raise S3Error("MalformedACLError") from None
                perms = [e.text for e in root.iter()
                         if e.tag.endswith("Permission")]
                if any(p != "FULL_CONTROL" for p in perms):
                    raise S3Error("NotImplemented",
                                  "only FULL_CONTROL grants exist; use "
                                  "bucket policies")
            return self._send(200)

        def _object_lock_config(self, method, bucket, body):
            """GET/PUT ?object-lock (reference: cmd/bucket-handlers.go
            GetBucketObjectLockConfigHandler /
            PutBucketObjectLockConfigHandler)."""
            from minio_tpu.object import objectlock as olock
            ol = server.object_layer
            ol.get_bucket_info(bucket)
            if method == "GET":
                cfg = self._lock_config(bucket)
                if not cfg.get("enabled"):
                    raise S3Error("ObjectLockConfigurationNotFoundError",
                                  bucket=bucket)
                return self._send(200, olock.lock_config_xml(cfg))
            if method != "PUT":
                raise S3Error("MethodNotAllowed")
            try:
                cfg = olock.parse_lock_config_xml(body)
            except olock.ObjectLockError as e:
                raise S3Error(e.code, str(e)) from None
            with server.bucket_meta_lock:
                meta = ol.get_bucket_meta(bucket)
                # Enabling lock on an existing bucket requires (and
                # then pins) versioning.
                meta["versioning"] = True
                meta[olock.BUCKET_META_KEY] = cfg
                ol.set_bucket_meta(bucket, meta)
            self._site_enqueue("bucket-meta", bucket)
            return self._send(200)

        def _list_versions(self, bucket, query):
            """GET ?versions — ListObjectVersions (reference:
            cmd/bucket-listobjects-handlers.go ListObjectVersionsHandler).

            A version-id-marker resumes WITHIN the marker key: its
            remaining (older) versions are emitted first, then the
            listing continues past the key."""
            def q(name, default=""):
                return query.get(name, [default])[0]
            prefix = q("prefix")
            delimiter = q("delimiter")
            key_marker = q("key-marker")
            vid_marker = q("version-id-marker")
            max_keys = int(q("max-keys", "1000") or 1000)
            entries = []
            if key_marker and vid_marker:
                from minio_tpu.object.erasure_object import ErasureSet
                try:
                    versions = server.object_layer.list_versions_all(
                        bucket, key_marker)
                except Exception:  # noqa: BLE001 - marker key deleted
                    versions = []
                emit = False
                for v in versions:           # latest-first journal order
                    if emit:
                        entries.append(ErasureSet._to_object_info(
                            bucket, key_marker, v))
                    elif (v.version_id or "null") == vid_marker:
                        emit = True
            info = server.object_layer.list_objects(
                bucket, prefix=prefix, marker=key_marker,
                delimiter=delimiter, max_keys=max_keys,
                include_versions=True)
            combined = entries + info.objects
            truncated = info.is_truncated
            if len(combined) > max_keys:
                combined = combined[:max_keys]
                truncated = True
            root = ET.Element("ListVersionsResult", xmlns=XMLNS)
            _el(root, "Name", bucket)
            _el(root, "Prefix", prefix)
            _el(root, "KeyMarker", key_marker)
            if vid_marker:
                _el(root, "VersionIdMarker", vid_marker)
            _el(root, "MaxKeys", max_keys)
            _el(root, "IsTruncated", "true" if truncated else "false")
            if truncated and combined:
                _el(root, "NextKeyMarker", combined[-1].name)
                _el(root, "NextVersionIdMarker",
                    combined[-1].version_id or "null")
            for o in combined:
                tag = "DeleteMarker" if o.delete_marker else "Version"
                ve = _el(root, tag)
                _el(ve, "Key", o.name)
                _el(ve, "VersionId", o.version_id or "null")
                _el(ve, "IsLatest", "true" if o.is_latest else "false")
                _el(ve, "LastModified", _iso8601(o.mod_time))
                if not o.delete_marker:
                    _el(ve, "ETag", f'"{o.etag}"')
                    _el(ve, "Size", o.size)
                    _el(ve, "StorageClass", o.storage_class)
            for p in info.prefixes:
                ce = _el(root, "CommonPrefixes")
                _el(ce, "Prefix", p)
            self._send(200, _xml(root))

        def _get_versioning(self, bucket):
            ol = server.object_layer
            ol.get_bucket_info(bucket)
            state = _versioning_state(ol, bucket)
            root = ET.Element("VersioningConfiguration", xmlns=XMLNS)
            if state:
                _el(root, "Status", state)
            self._send(200, _xml(root))

        def _put_versioning(self, bucket, body):
            ol = server.object_layer
            ol.get_bucket_info(bucket)
            try:
                status = ET.fromstring(body).findtext(
                    f"{{{XMLNS}}}Status") or ET.fromstring(body).findtext("Status")
            except ET.ParseError:
                raise S3Error("MalformedXML") from None
            if status not in ("Enabled", "Suspended"):
                raise S3Error("MalformedXML",
                              "Status must be Enabled or Suspended")
            with server.bucket_meta_lock:
                # Lock-config check INSIDE the metadata lock: checked
                # outside, a concurrent PutObjectLockConfiguration could
                # commit between check and write, leaving a WORM bucket
                # unversioned. WORM guarantee: a lock-enabled bucket can
                # never stop versioning (reference:
                # cmd/bucket-handlers.go PutBucketVersioningHandler).
                if status != "Enabled" and self._lock_config(bucket).get(
                        "enabled"):
                    raise S3Error("InvalidBucketState",
                                  "object lock requires versioning",
                                  bucket=bucket)
                # Suspension is a distinct state, not versioning-off:
                # null-versionId writes replace the null version while
                # older real versions survive (reference:
                # internal/bucket/versioning/versioning.go:36,76). The
                # layer setter manages both meta keys consistently.
                setter = getattr(ol, "set_bucket_versioning", None)
                if setter is None:
                    raise S3Error("NotImplemented")
                setter(bucket, status)
            self._site_enqueue("bucket-meta", bucket)
            self._send(200)

        def _list_objects(self, bucket, query):
            def q(name, default=""):
                return query.get(name, [default])[0]
            v2 = q("list-type") == "2"
            prefix = q("prefix")
            delimiter = q("delimiter")
            max_keys = int(q("max-keys", "1000") or 1000)
            if v2:
                marker = q("start-after")
                token = q("continuation-token")
                if token:
                    marker = _b64d(token)
            else:
                marker = q("marker")
            info = server.object_layer.list_objects(
                bucket, prefix=prefix, marker=marker, delimiter=delimiter,
                max_keys=max_keys)
            root = ET.Element("ListBucketResult", xmlns=XMLNS)
            _el(root, "Name", bucket)
            _el(root, "Prefix", prefix)
            if delimiter:
                _el(root, "Delimiter", delimiter)
            _el(root, "MaxKeys", max_keys)
            _el(root, "IsTruncated", "true" if info.is_truncated else "false")
            if v2:
                _el(root, "KeyCount", len(info.objects) + len(info.prefixes))
                if info.is_truncated:
                    _el(root, "NextContinuationToken", _b64e(info.next_marker))
            else:
                _el(root, "Marker", marker)
                if info.is_truncated:
                    _el(root, "NextMarker", info.next_marker)
            for o in info.objects:
                c = _el(root, "Contents")
                _el(c, "Key", o.name)
                _el(c, "LastModified", _iso8601(o.mod_time))
                _el(c, "ETag", f'"{o.etag}"')
                _el(c, "Size", o.size)
                _el(c, "StorageClass", o.storage_class)
            for p in info.prefixes:
                cp = _el(root, "CommonPrefixes")
                _el(cp, "Prefix", p)
            self._send(200, _xml(root))

        def _list_uploads(self, bucket, query):
            prefix = query.get("prefix", [""])[0]
            uploads = server.object_layer.list_multipart_uploads(bucket,
                                                                 prefix)
            root = ET.Element("ListMultipartUploadsResult", xmlns=XMLNS)
            _el(root, "Bucket", bucket)
            _el(root, "Prefix", prefix)
            _el(root, "IsTruncated", "false")
            for rec in uploads:
                ue = _el(root, "Upload")
                _el(ue, "Key", rec.get("object", ""))
                _el(ue, "UploadId", rec.get("upload_id", ""))
                _el(ue, "Initiated", _iso8601(rec.get("initiated", 0)))
                _el(ue, "StorageClass", "STANDARD")
            self._send(200, _xml(root))

        def _delete_objects(self, bucket, body):
            try:
                tree = ET.fromstring(body)
            except ET.ParseError:
                raise S3Error("MalformedXML") from None
            ns = f"{{{XMLNS}}}"
            objs = tree.findall(f"{ns}Object") or tree.findall("Object")
            quiet = (tree.findtext(f"{ns}Quiet") or
                     tree.findtext("Quiet") or "") == "true"
            root = ET.Element("DeleteResult", xmlns=XMLNS)
            state = _versioning_state(server.object_layer, bucket)
            h = self._headers_lower()
            for obj in objs[:1000]:
                key = obj.findtext(f"{ns}Key") or obj.findtext("Key") or ""
                vid = obj.findtext(f"{ns}VersionId") or obj.findtext("VersionId") or ""
                try:
                    self._check_version_deletable(bucket, key, vid, h)
                    deleted = server.object_layer.delete_object(
                        bucket, key,
                        DeleteOptions(version_id=vid,
                                      versioned=state == "Enabled",
                                      null_marker=state == "Suspended"
                                      and not vid))
                    if not vid:
                        # Bulk deletes mirror to peer sites like single
                        # DELETEs (version-targeted prunes stay local).
                        self._site_enqueue("delete", bucket, key)
                    self._notify(
                        "s3:ObjectRemoved:DeleteMarkerCreated"
                        if deleted.delete_marker
                        else "s3:ObjectRemoved:Delete", bucket, key,
                        version_id=deleted.delete_marker_version_id
                        if deleted.delete_marker else vid)
                    if not quiet:
                        de = _el(root, "Deleted")
                        _el(de, "Key", key)
                        if vid:
                            _el(de, "VersionId", vid)
                        if deleted.delete_marker:
                            _el(de, "DeleteMarker", "true")
                            _el(de, "DeleteMarkerVersionId",
                                deleted.delete_marker_version_id)
                except Exception as e:  # noqa: BLE001 - per-key result
                    err = from_exception(e)
                    ee = _el(root, "Error")
                    _el(ee, "Key", key)
                    _el(ee, "Code", err.code)
                    _el(ee, "Message", err.message)
            self._send(200, _xml(root))

        # -- object ops -------------------------------------------------

        def _object_op(self, method, bucket, key, query, body, payload=None):
            _validate_object_name(key)
            if method == "POST" and "select" in query:
                return self._select_object(bucket, key, query, body)
            if method == "POST" and "uploads" in query:
                return self._initiate_multipart(bucket, key)
            if method == "POST" and "uploadId" in query:
                return self._complete_multipart(bucket, key, query, body)
            if method == "PUT" and "partNumber" in query:
                return self._put_part(bucket, key, query, payload,
                                      self._headers_lower())
            if method == "DELETE" and "uploadId" in query:
                server.object_layer.abort_multipart_upload(
                    bucket, key, query["uploadId"][0])
                return self._send(204)
            if method == "GET" and "uploadId" in query:
                return self._list_parts(bucket, key, query)
            if "tagging" in query:
                return self._object_tagging(method, bucket, key, query,
                                            payload)
            if method == "GET" and "attributes" in query:
                return self._object_attributes(bucket, key, query)
            if "acl" in query:
                body_acl = payload.read_all() if method == "PUT" and \
                    payload is not None else b""
                server.object_layer.get_object_info(
                    bucket, key,
                    GetOptions(version_id=query.get("versionId",
                                                    [""])[0]))
                return self._acl(method, bucket, key, body_acl)
            if "retention" in query:
                return self._object_retention(method, bucket, key, query,
                                              payload)
            if "legal-hold" in query:
                return self._object_legal_hold(method, bucket, key, query,
                                               payload)
            if method == "PUT":
                return self._put_object(bucket, key, query, payload)
            if method in ("GET", "HEAD"):
                return self._get_object(method, bucket, key, query)
            if method == "DELETE":
                return self._delete_object(bucket, key, query)
            raise S3Error("MethodNotAllowed")

        def _select_object(self, bucket, key, query, body):
            """POST ?select&select-type=2 — SQL over one object
            (reference: internal/s3select; the SelectObjectContent API).
            Records STREAM through the engine in O(record) memory; the
            SSE/compression transforms reuse the GET path's plaintext
            chunk generators, version-pinned so params and data come
            from one snapshot."""
            from minio_tpu.s3select import SelectError, run_select
            h = self._headers_lower()
            vid = query.get("versionId", [""])[0]
            # ONE open: the stream's own info decides the transform
            # branch. Version-pinned buckets are fully race-free; on
            # unversioned buckets the transform re-open below keeps
            # the same small overwrite window the plain GET path has
            # (and the reference shares).
            info, chunks = server.object_layer.get_object_stream(
                bucket, key, GetOptions(version_id=vid))
            imeta = info.internal_metadata
            if imeta.get("x-internal-sse-alg"):
                chunks.close()
                self._sse_check_head(h, info)
                info, chunks, _, _ = self._get_encrypted(
                    bucket, key, vid or info.version_id, None, h, info)
            elif imeta.get("x-internal-comp"):
                chunks.close()
                info, chunks, _, _ = self._get_compressed(
                    bucket, key, vid or info.version_id, None, info)
            try:
                resp = run_select(chunks, body)
            except SelectError as e:
                raise S3Error("InvalidArgument", str(e)) from None
            self._send(200, resp,
                       content_type="application/octet-stream")

        def _object_tagging(self, method, bucket, key, query, payload):
            """GET/PUT/DELETE ?tagging on an object (reference:
            cmd/object-handlers.go PutObjectTagsHandler et al.)."""
            vid = query.get("versionId", [""])[0]
            if method == "GET":
                info = server.object_layer.get_object_info(
                    bucket, key, GetOptions(version_id=vid))
                root = ET.Element("Tagging", xmlns=XMLNS)
                ts = _el(root, "TagSet")
                for kv in urllib.parse.parse_qsl(info.user_tags):
                    te = _el(ts, "Tag")
                    _el(te, "Key", kv[0])
                    _el(te, "Value", kv[1])
                return self._send(200, _xml(root))
            if method == "PUT":
                body = payload.read_all() if payload is not None else b""
                tags = _parse_tagging_xml(body)
                server.object_layer.update_object_tags(bucket, key, vid,
                                                       tags)
                return self._send(200)
            if method == "DELETE":
                server.object_layer.update_object_tags(bucket, key, vid,
                                                       None)
                return self._send(204)
            raise S3Error("MethodNotAllowed")

        def _object_lock_put_meta(self, bucket, h) -> dict:
            """Lock metadata for a new version: explicit request
            headers win; otherwise the bucket's default-retention rule
            applies (reference: cmd/api-headers.go +
            cmd/bucket-object-lock.go defaults at PutObject)."""
            from minio_tpu.object import objectlock as olock
            cfg = self._lock_config(bucket)
            now = _time_mod.time_ns()
            try:
                explicit = olock.headers_to_meta(h, cfg.get("enabled", False),
                                                 now)
            except olock.ObjectLockError as e:
                raise S3Error(e.code, str(e)) from None
            # Merge: the bucket default supplies retention unless the
            # request set its own mode — a legal-hold-only header must
            # not suppress the default-retention rule.
            out = olock.default_retention_meta(cfg, now)
            out.update(explicit)
            return out

        def _site_enqueue(self, kind, bucket, key="", vid=""):
            """Mirror a change to peer sites — unless the change ITSELF
            arrived from a site (replica markers break the ping-pong)."""
            if server.site is None:
                return
            from minio_tpu.replication.site import H_SITE_REPLICA
            h = self._headers_lower()
            if h.get(H_SITE_REPLICA) or "x-amz-meta-mtpu-replica" in h:
                return
            server.site.enqueue(kind, bucket, key, vid)

        def _layer_sets(self):
            ol = server.object_layer
            if hasattr(ol, "pools"):
                return ol.pools[0].sets
            if hasattr(ol, "sets"):
                return ol.sets
            return [ol]

        def _batch_jobs(self):
            if server.batch is None:
                from minio_tpu.object.batch import BatchJobs
                ol = server.object_layer
                if hasattr(ol, "pools"):
                    sets = ol.pools[0].sets
                elif hasattr(ol, "sets"):
                    sets = ol.sets
                else:
                    sets = [ol]
                server.batch = BatchJobs(ol, sets)
                server.batch.kms = server.kms
            return server.batch

        def _tier_registry(self):
            """The server's tier registry, created on first use and
            attached to every erasure set (the read/transition paths
            resolve backends through set.tiers)."""
            if server.tiers is None:
                from minio_tpu.object.tier import TierRegistry
                ol = server.object_layer
                if hasattr(ol, "pools"):
                    reg_sets = ol.pools[0].sets
                    all_sets = [s for p in ol.pools for s in p.sets]
                elif hasattr(ol, "sets"):
                    reg_sets = all_sets = ol.sets
                else:
                    reg_sets = all_sets = [ol]
                server.tiers = TierRegistry(reg_sets)
                for s in all_sets:
                    s.tiers = server.tiers
            return server.tiers

        def _can_bypass_governance(self, bucket, key, h) -> bool:
            """Governance bypass needs BOTH the explicit header and the
            s3:BypassGovernanceRetention permission (reference:
            cmd/bucket-object-lock.go enforceRetentionBypassForDelete)."""
            if h.get(
                    "x-amz-bypass-governance-retention", "").lower() != "true":
                return False
            ak = self._auth_key
            return self._authorize(ak, ak == "",
                                   "s3:BypassGovernanceRetention",
                                   f"{bucket}/{key}",
                                   self._auth_context(ak, {}, h))

        def _object_retention(self, method, bucket, key, query, payload):
            """GET/PUT ?retention (reference: cmd/object-handlers.go
            GetObjectRetentionHandler / PutObjectRetentionHandler:2705)."""
            from minio_tpu.object import objectlock as olock
            vid = query.get("versionId", [""])[0]
            # Consistent gate for every verb: retention APIs only exist
            # on lock-enabled buckets (checked before any object read).
            if not self._lock_config(bucket).get("enabled"):
                raise S3Error("InvalidRequest", "bucket is missing "
                              "ObjectLockConfiguration", bucket=bucket)
            if method == "GET":
                info = server.object_layer.get_object_info(
                    bucket, key, GetOptions(version_id=vid))
                if not info.internal_metadata.get(olock.META_MODE):
                    raise S3Error("NoSuchObjectLockConfiguration",
                                  bucket=bucket, key=key)
                return self._send(200, olock.retention_xml(
                    info.internal_metadata))
            if method != "PUT":
                raise S3Error("MethodNotAllowed")
            body = payload.read_all() if payload is not None else b""
            h = self._headers_lower()
            try:
                mode, until = olock.parse_retention_xml(body)
                now = _time_mod.time_ns()
                if until and olock.parse_iso8601(until) <= now:
                    raise S3Error("InvalidArgument",
                                  "RetainUntilDate must be in the future")
                info = server.object_layer.get_object_info(
                    bucket, key, GetOptions(version_id=vid))
                denial = olock.check_retention_change(
                    info.internal_metadata, mode, until, now,
                    self._can_bypass_governance(bucket, key, h))
            except olock.ObjectLockError as e:
                raise S3Error(e.code, str(e)) from None
            if denial:
                raise S3Error(denial, "existing retention forbids this "
                              "change", bucket=bucket, key=key)

            def mutate(meta):
                if mode:
                    meta[olock.META_MODE] = mode
                    meta[olock.META_UNTIL] = until
                else:
                    meta.pop(olock.META_MODE, None)
                    meta.pop(olock.META_UNTIL, None)
            server.object_layer.update_version_metadata(bucket, key, vid,
                                                        mutate)
            return self._send(200)

        def _object_legal_hold(self, method, bucket, key, query, payload):
            """GET/PUT ?legal-hold (reference: cmd/object-handlers.go
            GetObjectLegalHoldHandler / PutObjectLegalHoldHandler:2862)."""
            from minio_tpu.object import objectlock as olock
            vid = query.get("versionId", [""])[0]
            if not self._lock_config(bucket).get("enabled"):
                raise S3Error("InvalidRequest", "bucket is missing "
                              "ObjectLockConfiguration", bucket=bucket)
            if method == "GET":
                info = server.object_layer.get_object_info(
                    bucket, key, GetOptions(version_id=vid))
                return self._send(200, olock.legal_hold_xml(
                    info.internal_metadata))
            if method != "PUT":
                raise S3Error("MethodNotAllowed")
            body = payload.read_all() if payload is not None else b""
            try:
                status = olock.parse_legal_hold_xml(body)
            except olock.ObjectLockError as e:
                raise S3Error(e.code, str(e)) from None
            server.object_layer.update_version_metadata(
                bucket, key, vid,
                lambda meta: meta.__setitem__(olock.META_HOLD, status))
            return self._send(200)

        def _check_version_deletable(self, bucket, key, vid, h):
            """Refuse destroying a retained/held version (reference:
            enforceRetentionForDeletion via DeleteObjectHandler). Only
            version-targeted deletes destroy data; marker stacking is
            always allowed."""
            if not vid:
                return
            # Lock can never be disabled once enabled, so a bucket whose
            # (TTL-cached) config lacks it holds no retained versions —
            # skip the per-version quorum metadata read on the common
            # path (bulk version deletes would otherwise double their
            # metadata I/O).
            if not self._lock_config(bucket).get("enabled"):
                return
            from minio_tpu.object import objectlock as olock
            from minio_tpu.object.types import (MethodNotAllowed as _MNA,
                                                ObjectNotFound as _ONF,
                                                VersionNotFound as _VNF)
            try:
                info = server.object_layer.get_object_info(
                    bucket, key, GetOptions(version_id=vid))
            except (_ONF, _VNF, _MNA):
                return          # absent or a delete marker: nothing held
            imeta = info.internal_metadata
            if not (imeta.get(olock.META_MODE) or imeta.get(olock.META_HOLD)):
                return
            denial = olock.check_version_deletable(
                imeta, _time_mod.time_ns(),
                self._can_bypass_governance(bucket, key, h))
            if denial:
                raise S3Error(denial, "object version is WORM-protected",
                              bucket=bucket, key=key)

        # -- multipart --------------------------------------------------

        def _initiate_multipart(self, bucket, key):
            h = self._headers_lower()
            from minio_tpu.crypto import sse as sse_mod
            meta = {k[len("x-amz-meta-"):]: v for k, v in h.items()
                    if k.startswith("x-amz-meta-")}
            opts = PutOptions(
                versioned=_versioned(server.object_layer, bucket),
                user_metadata=meta,
                content_type=h.get("content-type", ""),
                storage_class=h.get("x-amz-storage-class", "STANDARD"))
            opts.internal_metadata.update(
                self._object_lock_put_meta(bucket, h))
            # SSE multipart: choose/seal the object data key NOW and
            # persist the params with the upload; each part becomes its
            # own DARE stream under a per-part derived key (reference:
            # cmd/encryption-v1.go:643 part-boundary crypto).
            sse_headers = {}
            try:
                customer = sse_mod.parse_sse_c(h)
                enc_cfg = None
                if customer is None:
                    # A metadata read failure PROPAGATES: guessing "no
                    # default encryption" on a transient error would
                    # silently store the whole object as plaintext.
                    enc_cfg = server.object_layer.get_bucket_meta(
                        bucket).get("config:encryption")
                if customer is not None or sse_mod.wants_sse_s3(h, enc_cfg):
                    _, _, imeta = sse_mod.encrypt_metadata(
                        bucket, key, 0, server.kms, customer)
                    imeta[sse_mod.META_MULTIPART] = "1"
                    opts.internal_metadata.update(imeta)
                    if customer is not None:
                        sse_headers = {sse_mod.H_C_ALG: "AES256",
                                       sse_mod.H_C_MD5: customer[1]}
                    else:
                        sse_headers = {sse_mod.H_SSE: "AES256"}
            except sse_mod.SSEError as e:
                raise S3Error(e.code, str(e)) from None
            uid = server.object_layer.new_multipart_upload(bucket, key, opts)
            root = ET.Element("InitiateMultipartUploadResult", xmlns=XMLNS)
            _el(root, "Bucket", bucket)
            _el(root, "Key", key)
            _el(root, "UploadId", uid)
            self._send(200, _xml(root), headers=sse_headers)

        def _part_sse_wrap(self, bucket, key, uid, part_num, payload, h):
            """Encrypt one part's payload when the upload was initiated
            with SSE: an independent DARE stream under the per-part
            derived key (crypto/sse.part_key) and a FRESH random base
            nonce persisted with the part — a re-uploaded part number
            must never reuse an AES-GCM (key, nonce, seq) tuple on
            different plaintext. Returns (payload, actual_size|None,
            part nonce b64, response headers). Errors reading the
            upload record PROPAGATE: silently storing an SSE part as
            plaintext is the one unacceptable failure mode."""
            from minio_tpu.crypto import (EncryptingPayload,
                                          encrypt_stream_size)
            from minio_tpu.crypto import sse as sse_mod
            rec = server.object_layer.get_multipart_upload(bucket, key, uid)
            imeta = rec.get("internal_metadata") or {}
            if not imeta.get(sse_mod.META_ALG):
                return payload, None, "", {}
            try:
                customer = sse_mod.parse_sse_c(h)
                data_key, _ = sse_mod.decrypt_params(
                    bucket, key, imeta, server.kms, customer)
            except sse_mod.SSEError as e:
                raise S3Error(e.code, str(e)) from None
            part_nonce = os.urandom(12)
            plain = payload.size
            enc = EncryptingPayload(payload,
                                    sse_mod.part_key(data_key, part_num),
                                    part_nonce)
            # The inner payload runs its own finish (signature/trailer
            # verification) as the encryptor drains its last byte.
            out = Payload(enc, encrypt_stream_size(plain))
            if customer is not None:
                hdrs = {sse_mod.H_C_ALG: "AES256",
                        sse_mod.H_C_MD5: customer[1]}
            else:
                hdrs = {sse_mod.H_SSE: "AES256"}
            import base64 as _b64
            return out, plain, _b64.b64encode(part_nonce).decode(), hdrs

        def _put_part(self, bucket, key, query, payload, h):
            try:
                part_num = int(query["partNumber"][0])
            except (ValueError, KeyError):
                raise S3Error("InvalidArgument") from None
            uid = query.get("uploadId", [""])[0]
            if payload is not None:
                self._check_quota(bucket, payload.size)
            if "x-amz-copy-source" in h:
                # UploadPartCopy: source bytes become the part payload.
                src = urllib.parse.unquote(h["x-amz-copy-source"]).lstrip("/")
                src_vid = ""
                if "?versionId=" in src:
                    src, _, src_vid = src.partition("?versionId=")
                if "/" not in src:
                    raise S3Error("InvalidArgument", "bad copy source")
                sbucket, skey = src.split("/", 1)
                spec = _range_spec(h.get("x-amz-copy-source-range", "")
                                   .replace("bytes=", "bytes=")
                                   ) if h.get("x-amz-copy-source-range") else None
                # Decrypting fetch: an SSE source must contribute
                # PLAINTEXT part bytes (range in plaintext space too).
                _, body = self._read_source_plain(sbucket, skey, src_vid,
                                                  spec, h)
                cpay, actual, pnonce, sse_hdrs = self._part_sse_wrap(
                    bucket, key, uid, part_num, Payload.wrap(body), h)
                part = server.object_layer.put_object_part(
                    bucket, key, uid, part_num, cpay, actual_size=actual,
                    nonce=pnonce)
                root = ET.Element("CopyPartResult", xmlns=XMLNS)
                _el(root, "ETag", f'"{part.etag}"')
                _el(root, "LastModified", _iso8601(part.mod_time))
                return self._send(200, _xml(root), headers=sse_hdrs)
            # Per-part checksums (boto3 >= 1.36 declares one on every
            # UploadPart by default): verified before commit; composite
            # object-level checksums are not assembled in v1.
            ck_opts = PutOptions()
            payload, ck_hdrs = self._apply_checksums(payload, h, ck_opts)
            payload, actual, pnonce, sse_hdrs = self._part_sse_wrap(
                bucket, key, uid, part_num, payload, h)
            part = server.object_layer.put_object_part(
                bucket, key, uid, part_num, payload, actual_size=actual,
                nonce=pnonce)
            self._send(200, headers={"ETag": f'"{part.etag}"', **sse_hdrs,
                                     **ck_hdrs})

        def _complete_multipart(self, bucket, key, query, body):
            uid = query["uploadId"][0]
            try:
                tree = ET.fromstring(body)
            except ET.ParseError:
                raise S3Error("MalformedXML") from None
            ns = f"{{{XMLNS}}}"
            parts = []
            for pe in tree.findall(f"{ns}Part") or tree.findall("Part"):
                num = pe.findtext(f"{ns}PartNumber") or pe.findtext("PartNumber")
                etag = pe.findtext(f"{ns}ETag") or pe.findtext("ETag") or ""
                try:
                    parts.append((int(num), etag))
                except (TypeError, ValueError):
                    raise S3Error("MalformedXML") from None
            info = server.object_layer.complete_multipart_upload(
                bucket, key, uid, parts)
            self._replicate_after_write(bucket, key, info.version_id,
                                        self._headers_lower())
            self._site_enqueue("put", bucket, key, info.version_id)
            self._notify("s3:ObjectCreated:CompleteMultipartUpload",
                         bucket, key, size=info.size, etag=info.etag,
                         version_id=info.version_id)
            root = ET.Element("CompleteMultipartUploadResult", xmlns=XMLNS)
            _el(root, "Location", f"/{bucket}/{key}")
            _el(root, "Bucket", bucket)
            _el(root, "Key", key)
            _el(root, "ETag", f'"{info.etag}"')
            headers = {}
            if info.version_id:
                headers["x-amz-version-id"] = info.version_id
            self._send(200, _xml(root), headers=headers)

        def _list_parts(self, bucket, key, query):
            uid = query["uploadId"][0]
            try:
                marker = int(query.get("part-number-marker", ["0"])[0] or 0)
                max_parts = int(query.get("max-parts", ["1000"])[0] or 1000)
            except ValueError:
                raise S3Error("InvalidArgument") from None
            parts = server.object_layer.list_parts(bucket, key, uid,
                                                   marker, max_parts)
            root = ET.Element("ListPartsResult", xmlns=XMLNS)
            _el(root, "Bucket", bucket)
            _el(root, "Key", key)
            _el(root, "UploadId", uid)
            _el(root, "PartNumberMarker", marker)
            _el(root, "MaxParts", max_parts)
            _el(root, "IsTruncated", "false")
            for p in parts:
                pe = _el(root, "Part")
                _el(pe, "PartNumber", p["number"])
                _el(pe, "ETag", f'"{p["etag"]}"')
                _el(pe, "Size", p["size"])
                _el(pe, "LastModified", _iso8601(p["mod_time"]))
            self._send(200, _xml(root))

        def _copy_object(self, bucket, key, h):
            src = urllib.parse.unquote(h["x-amz-copy-source"])
            src_vid = ""
            if "?versionId=" in src:
                src, _, src_vid = src.partition("?versionId=")
            src = src.lstrip("/")
            if "/" not in src:
                raise S3Error("InvalidArgument", "bad copy source")
            sbucket, skey = src.split("/", 1)
            sinfo, payload = self._read_source_plain(sbucket, skey,
                                                     src_vid, None, h)
            if any(c in h for c in ("x-amz-copy-source-if-match",
                                    "x-amz-copy-source-if-none-match",
                                    "x-amz-copy-source-if-modified-since",
                                    "x-amz-copy-source-if-unmodified-since")):
                self._check_conditions(h, sinfo, for_read=False,
                                       prefix="x-amz-copy-source-")
            directive = h.get("x-amz-metadata-directive", "COPY").upper()
            if directive == "REPLACE":
                meta = {k2[len("x-amz-meta-"):]: v for k2, v in h.items()
                        if k2.startswith("x-amz-meta-")}
                ctype = h.get("content-type", sinfo.content_type)
            else:
                meta = dict(sinfo.user_metadata)
                ctype = sinfo.content_type
            tag_directive = h.get("x-amz-tagging-directive", "COPY").upper()
            tags = h.get("x-amz-tagging", "") if tag_directive == "REPLACE" \
                else sinfo.user_tags
            opts = PutOptions(
                versioned=_versioned(server.object_layer, bucket),
                user_metadata=meta, content_type=ctype, tags=tags)
            # Copies into a lock-enabled bucket honor lock headers and
            # the default-retention rule like any other new version.
            opts.internal_metadata.update(
                self._object_lock_put_meta(bucket, h))
            self._check_quota(bucket, len(payload))
            out_payload, sse_headers = self._apply_sse(
                bucket, key, Payload.wrap(payload), h, opts)
            info = server.object_layer.put_object(
                bucket, key, out_payload, opts)
            self._note_quota_write(bucket, len(payload))
            self._replicate_after_write(bucket, key, info.version_id, h)
            self._site_enqueue("put", bucket, key, info.version_id)
            self._notify("s3:ObjectCreated:Copy", bucket, key,
                         size=len(payload), etag=info.etag,
                         version_id=info.version_id)
            root = ET.Element("CopyObjectResult", xmlns=XMLNS)
            _el(root, "ETag", f'"{info.etag}"')
            _el(root, "LastModified", _iso8601(info.mod_time))
            headers = dict(sse_headers)
            if info.version_id:
                headers["x-amz-version-id"] = info.version_id
            self._send(200, _xml(root), headers=headers)

        def _put_object(self, bucket, key, query, payload):
            h = self._headers_lower()
            if "x-amz-copy-source" in h:
                return self._copy_object(bucket, key, h)
            # Everything between the signature and the body's first byte
            # is one stage: conditions, versioning / object-lock / quota /
            # SSE / replication decisions — each a bucket-metadata lookup
            # that fans out to the drives when its TTL cache has run out.
            with tracing_mod.stage("s3.put_prepare"):
                if "if-match" in h or "if-none-match" in h:
                    # Conditional write (create-only / replace-exact): check
                    # the current version before accepting the body. Only a
                    # definitive not-found counts as absent — a transient
                    # read failure must NOT let a create-only PUT overwrite.
                    from minio_tpu.object.types import (
                        MethodNotAllowed as _MNA, ObjectNotFound as _ONF,
                        VersionNotFound as _VNF)
                    try:
                        cur = server.object_layer.get_object_info(
                            bucket, key, GetOptions())
                    except (_ONF, _VNF, _MNA):
                        cur = None
                    if cur is None:
                        if "if-match" in h:
                            raise S3Error("NoSuchKey", bucket=bucket, key=key)
                    else:
                        self._check_conditions(h, cur, for_read=False)
                meta = {k[len("x-amz-meta-"):]: v for k, v in h.items()
                        if k.startswith("x-amz-meta-")}
                opts = PutOptions(
                    versioned=_versioned(server.object_layer, bucket),
                    user_metadata=meta,
                    content_type=h.get("content-type", ""),
                    storage_class=h.get("x-amz-storage-class", "STANDARD"),
                    tags=h.get("x-amz-tagging", ""))
                opts.internal_metadata.update(
                    self._object_lock_put_meta(bucket, h))
                self._check_quota(bucket, payload.size)
                fused = self._fused_put_prepare(bucket, key, payload, h, opts)
                if fused is not None:
                    # Fused single-pass plane: the raw LOGICAL body goes to
                    # the object layer with a TransformSpec — etag md5,
                    # declared checksums, compression, and DARE all run as
                    # ONE native pass next to the framer
                    # (object/transform.py). Checksum verification runs
                    # pre-commit via the spec's verify hook.
                    payload, sse_headers, checksum_hdrs, plain_size = fused
                else:
                    from minio_tpu.object import transform as _tf
                    from minio_tpu.object.erasure_object import \
                        STREAM_THRESHOLD as _ST
                    if payload.size <= _ST:
                        _tf.note_put("legacy", payload.size)
                    payload, checksum_hdrs = self._apply_checksums(payload, h,
                                                                   opts)
                    plain_size = payload.size
                    # Compression BEFORE encryption: the block scheme sees
                    # plaintext (ciphertext is incompressible), so
                    # compressed+encrypted objects store DARE(compressed)
                    # — the same layering the fused pass produces.
                    payload = self._apply_compression(key, payload, opts)
                    payload, sse_headers = self._apply_sse(bucket, key,
                                                           payload, h, opts)
                # Replicate only after the SSE decision: encrypted objects
                # do not replicate in v1 (their keys bind to this cluster),
                # and an incoming REPLICA must not ping-pong back in
                # active-active setups (the mtpu-replica marker).
                replicate = (server.replicator is not None
                             and "x-amz-meta-mtpu-replica" not in h
                             and not opts.internal_metadata.get(
                                 "x-internal-sse-alg")
                             and server.replicator.should_replicate(bucket,
                                                                    key))
                if replicate:
                    from minio_tpu.replication import REPL_STATUS_KEY
                    opts.internal_metadata[REPL_STATUS_KEY] = "PENDING"
            info = server.object_layer.put_object(bucket, key, payload, opts)
            self._note_quota_write(bucket, plain_size)
            if replicate:
                server.replicator.enqueue(bucket, key, info.version_id,
                                          "put",
                                          mod_time=getattr(info,
                                                           "mod_time", 0))
            self._site_enqueue("put", bucket, key, info.version_id)
            self._notify("s3:ObjectCreated:Put", bucket, key,
                         size=plain_size, etag=info.etag,
                         version_id=info.version_id)
            headers = {"ETag": f'"{info.etag}"', **sse_headers,
                       **checksum_hdrs}
            if info.version_id:
                headers["x-amz-version-id"] = info.version_id
            self._send(200, headers=headers)

        def _replicate_after_write(self, bucket, key, version_id, h):
            """Post-hoc replication marking for write paths that cannot
            stamp PENDING before commit (multipart complete, copy): one
            metadata update, then enqueue — so the scanner resync also
            covers them after a crash."""
            r = server.replicator
            if r is None or "x-amz-meta-mtpu-replica" in h \
                    or not r.should_replicate(bucket, key):
                return
            from minio_tpu.replication import REPL_STATUS_KEY
            mod_time = 0
            try:
                info = server.object_layer.update_version_metadata(
                    bucket, key, version_id,
                    lambda m: None if m.get("x-internal-sse-alg")
                    else m.__setitem__(REPL_STATUS_KEY, "PENDING"))
                if info.internal_metadata.get("x-internal-sse-alg"):
                    return            # SSE objects do not replicate (v1)
                mod_time = getattr(info, "mod_time", 0)
            except Exception:  # noqa: BLE001 - stamping is advisory
                pass
            r.enqueue(bucket, key, version_id, "put", mod_time=mod_time)

        _QUOTA_TTL = 5.0

        def _bucket_quota(self, bucket) -> int:
            """Configured hard quota bytes (0 = none)."""
            import json as _json
            raw = server.object_layer.get_bucket_meta(bucket) \
                .get("config:quota")
            if not raw:
                return 0
            try:
                cfg = _json.loads(raw) if isinstance(raw, str) else raw
            except ValueError:
                return 0
            if cfg.get("quotatype", "hard") != "hard":
                return 0
            return int(cfg.get("quota") or 0)

        def _bucket_usage_bytes(self, bucket) -> float:
            """Current bucket size: the scanner's accounting when
            available, else a TTL'd live walk; committed writes advance
            the cached figure between refreshes (_note_quota_write).
            Single-flight: exactly one thread refreshes an expired
            entry — concurrent PUTs after TTL expiry must not each
            repeat the O(objects) walk."""
            now = _time_mod.monotonic()
            with server._quota_mu:
                ent = server._quota_usage.get(bucket)
                if ent is not None and (now - ent[0] < self._QUOTA_TTL
                                        or len(ent) > 2):
                    return ent[1]       # fresh, or someone refreshing
                if ent is None:
                    ent = server._quota_usage[bucket] = [now, 0]
                ent.append("refreshing")
            size = ent[1]               # prior figure if refresh fails
            try:
                sc = server.scanner
                if sc is not None and bucket in getattr(
                        sc.usage, "buckets", {}):
                    size = sc.usage.buckets[bucket].size
                else:
                    from minio_tpu.object.rebalance import \
                        bucket_used_bytes
                    size = bucket_used_bytes(server.object_layer, bucket)
            finally:
                with server._quota_mu:
                    server._quota_usage[bucket] = [
                        _time_mod.monotonic(), size]
            return size

        def _check_quota(self, bucket, incoming: int) -> None:
            """Hard-quota gate for every write path (reference:
            cmd/bucket-quota.go:32 enforceBucketQuotaHard on PutObject,
            parts and copies)."""
            quota = self._bucket_quota(bucket)
            if not quota:
                return
            if self._bucket_usage_bytes(bucket) + incoming > quota:
                raise S3Error("XMinioAdminBucketQuotaExceeded",
                              bucket=bucket)

        def _note_quota_write(self, bucket, nbytes: int) -> None:
            with server._quota_mu:
                ent = server._quota_usage.get(bucket)
                if ent is not None:
                    ent[1] += nbytes

        def _fused_put_prepare(self, bucket, key, payload, h, opts):
            """Plan the fused single-pass data plane for a buffered
            PUT: returns (logical bytes, sse response headers, checksum
            response headers, plain size) with opts.transform set — or
            None when the fused plane cannot take this request (kill
            switch, no native kernel, streaming-size body) and the
            layered pipeline should run instead."""
            from minio_tpu.crypto import sse as sse_mod
            from minio_tpu.object import transform as _tf
            from minio_tpu.object.erasure_object import STREAM_THRESHOLD
            from minio_tpu.s3 import checksum as ck
            if not _tf.fused_put_enabled() \
                    or payload.size > STREAM_THRESHOLD:
                return None
            try:
                declared = dict(ck.declared_algos(h))
                t_algos = ck.trailer_algos(h)
                algos = ck.single_algo(declared, t_algos)
            except ck.ChecksumError as e:
                raise S3Error(e.code, str(e)) from None
            # SSE decision (same gates as transform.sse_payload, minus
            # the payload wrap — the erasure layer seals in-pass).
            try:
                customer = sse_mod.parse_sse_c(h)
                enc_key = enc_nonce = b""
                sse_headers = {}
                if customer is not None or sse_mod.wants_sse_s3(
                        h, server.object_layer.get_bucket_meta(bucket)
                        .get("config:encryption")):
                    enc_key, enc_nonce, imeta = sse_mod.encrypt_metadata(
                        bucket, key, payload.size, server.kms, customer)
                    opts.internal_metadata.update(imeta)
                    sse_headers = ({sse_mod.H_C_ALG: "AES256",
                                    sse_mod.H_C_MD5: customer[1]}
                                   if customer is not None
                                   else {sse_mod.H_SSE: "AES256"})
            except sse_mod.SSEError as e:
                raise S3Error(e.code, str(e)) from None
            from minio_tpu.crypto import compress as comp
            compress = bool(server.compression and payload.size
                            and comp.eligible(key, opts.content_type))
            raw = getattr(payload, "_reader", None)   # trailer source
            # Reading the body drives the SigV4/chunk-signature checks
            # and the trailer parse — the single ingest walk the
            # layered path also pays; every digest after this point
            # comes out of the ONE fused native pass.
            data = payload.read_all()
            checksum_hdrs: dict = {}

            def verify(sp):
                expected = dict(declared)
                trailers = getattr(raw, "trailers", {}) or {}
                for a in t_algos:
                    expected.setdefault(a,
                                        trailers.get(ck.H_PREFIX + a))
                if not expected:
                    return
                try:
                    meta = ck.verify_and_meta(
                        ck.DigestValues(sp.digests), expected)
                except ck.ChecksumError as e:
                    raise S3Error(e.code, str(e)) from None
                opts.internal_metadata.update(meta)
                checksum_hdrs.update(ck.response_headers(meta))

            opts.transform = _tf.TransformSpec(
                algos=tuple(algos), compress=compress, enc_key=enc_key,
                enc_nonce=enc_nonce, verify=verify)
            return data, sse_headers, checksum_hdrs, len(data)

        def _apply_checksums(self, payload, h, opts):
            """Wrap the LOGICAL payload in checksum computation when
            the request declares x-amz-checksum-* values (headers, or
            aws-chunked trailers — the SDK default). Verification runs
            in the payload's finish hook, i.e. before commit; verified
            values land in internal metadata. Returns (payload,
            response-header dict that fills in post-verify)."""
            from minio_tpu.s3 import checksum as ck
            try:
                declared = dict(ck.declared_algos(h))
                t_algos = ck.trailer_algos(h)
                algos = ck.single_algo(declared, t_algos)
            except ck.ChecksumError as e:
                raise S3Error(e.code, str(e)) from None
            if not algos:
                return payload, {}
            raw = getattr(payload, "_reader", None)   # trailer source
            reader = ck.ChecksumingReader(payload, algos)
            hdrs: dict = {}

            def fin():
                # Zero-byte bodies: the outer payload finishes without
                # ever pulling the inner one, whose own finish parses
                # the trailers — drive it explicitly (idempotent for
                # non-empty bodies, whose finish already ran).
                payload.read(1)
                expected = dict(declared)
                trailers = getattr(raw, "trailers", {}) or {}
                for a in t_algos:
                    expected.setdefault(a,
                                        trailers.get(ck.H_PREFIX + a))
                try:
                    meta = ck.verify_and_meta(reader, expected)
                except ck.ChecksumError as e:
                    raise S3Error(e.code, str(e)) from None
                opts.internal_metadata.update(meta)
                hdrs.update(ck.response_headers(meta))

            return Payload(reader, payload.size, finish=fin), hdrs

        def _apply_sse(self, bucket, key, payload, h, opts):
            """Wrap a put payload in DARE encryption when the request
            (SSE-C / SSE-S3 headers) or the bucket's default encryption
            config asks for it (shared put-side seam:
            object/transform.py). Returns (payload, response headers)."""
            from minio_tpu.crypto import sse as sse_mod
            from minio_tpu.object import transform
            try:
                return transform.sse_payload(server.object_layer,
                                             server.kms, bucket, key,
                                             payload, opts, h)
            except sse_mod.SSEError as e:
                raise S3Error(e.code, str(e)) from None

        def _apply_compression(self, key, payload, opts):
            """Compress eligible buffered-size plaintext objects
            (reference: cmd/object-api-utils.go compression gate —
            never for incompressible payloads). Runs BEFORE the SSE
            wrap, so encrypted eligible objects store DARE over the
            compressed block stream — the fused pass's layering."""
            from minio_tpu.crypto import compress as comp
            from minio_tpu.object.erasure_object import STREAM_THRESHOLD
            if not server.compression \
                    or payload.size == 0 \
                    or payload.size > STREAM_THRESHOLD \
                    or not comp.eligible(key, opts.content_type):
                return payload
            data = payload.read_all()
            result = comp.compress(data)
            if result is None:           # incompressible: store as-is
                return Payload.wrap(data)
            stored, meta = result
            opts.internal_metadata.update(meta)
            # ETag must hash the LOGICAL bytes (single-PUT clients
            # verify ETag == md5(body)), not the compressed stream.
            opts.etag = hashlib.md5(data).hexdigest()
            return Payload.wrap(stored)

        def _get_compressed(self, bucket, key, vid, spec, info):
            """Ranged read of a compressed object (shared transform
            seam: object/transform.py)."""
            from minio_tpu.crypto import compress as comp
            from minio_tpu.object import transform
            try:
                return transform.get_compressed(server.object_layer,
                                                bucket, key, vid, spec,
                                                info)
            except comp.CompressionError as e:
                raise S3Error("InternalError", str(e)) from None

        def _sse_response_headers(self, h, info) -> dict:
            from minio_tpu.crypto import sse as sse_mod
            alg = info.internal_metadata.get(sse_mod.META_ALG, "")
            if alg == sse_mod.ALG_SSE_S3:
                return {sse_mod.H_SSE: "AES256"}
            if alg == sse_mod.ALG_SSE_C:
                return {sse_mod.H_C_ALG: "AES256",
                        sse_mod.H_C_MD5:
                        info.internal_metadata.get(sse_mod.META_KEY_MD5,
                                                   "")}
            return {}

        def _sse_check_head(self, h, info):
            """HEAD/GET of an SSE-C object requires the matching key."""
            from minio_tpu.crypto import sse as sse_mod
            from minio_tpu.object import transform
            try:
                transform.sse_check_head(h, info)
            except sse_mod.SSEError as e:
                raise S3Error(e.code, str(e)) from None

        def _read_source_plain(self, sbucket, skey, src_vid, spec, h):
            """Copy-source fetch in PLAINTEXT space: decrypts SSE
            sources (using x-amz-copy-source-...-customer-* headers for
            SSE-C) and resolves ranges against the logical size."""
            sinfo = server.object_layer.get_object_info(
                sbucket, skey, GetOptions(version_id=src_vid))
            # SSE first: a compressed+encrypted source must decrypt
            # before inflating (get_encrypted handles the combined
            # layering; the comp branch alone would inflate ciphertext).
            if sinfo.internal_metadata.get("x-internal-comp") \
                    and not sinfo.internal_metadata.get(
                        "x-internal-sse-alg"):
                sinfo, chunks, _, _ = self._get_compressed(
                    sbucket, skey, src_vid or sinfo.version_id, spec,
                    sinfo)
                return sinfo, b"".join(chunks)
            if not sinfo.internal_metadata.get("x-internal-sse-alg"):
                return server.object_layer.get_object(
                    sbucket, skey, GetOptions(version_id=src_vid,
                                              range_spec=spec))
            from minio_tpu.crypto import sse as sse_mod
            src_h = {}
            pfx = "x-amz-copy-source-server-side-encryption-customer-"
            for tail, name in (("algorithm", sse_mod.H_C_ALG),
                               ("key", sse_mod.H_C_KEY),
                               ("key-md5", sse_mod.H_C_MD5)):
                v = h.get(pfx + tail)
                if v is not None:
                    src_h[name] = v
            # The GET-side decryptor handles both single-stream and
            # per-part multipart DARE layouts.
            sinfo, chunks, _, _ = self._get_encrypted(
                sbucket, skey, src_vid or sinfo.version_id, spec, src_h,
                sinfo)
            return sinfo, b"".join(chunks)

        def _get_encrypted(self, bucket, key, vid, spec, h, info):
            """Ranged decrypting GET (shared transform seam:
            object/transform.py; reference: cmd/encryption-v1.go:643)."""
            from minio_tpu.crypto import sse as sse_mod
            from minio_tpu.object import transform
            try:
                return transform.get_encrypted(server.object_layer,
                                               server.kms, bucket, key,
                                               vid, spec, h, info)
            except sse_mod.SSEError as e:
                raise S3Error(e.code, str(e)) from None

        def _check_conditions(self, h, info, for_read: bool,
                              prefix: str = "") -> bool:
            """RFC 7232 / S3 conditional requests. Returns True when a
            read should answer 304 Not Modified; raises
            PreconditionFailed for failed write/read preconditions.
            prefix selects copy-source variants (x-amz-copy-source-if-*).
            """
            def g(name):
                return h.get(prefix + name)

            def etag_matches(val):
                vals = [v.strip().strip('"') for v in val.split(",")]
                return "*" in vals or info.etag in vals

            def parse_http_date(val):
                try:
                    dt = email.utils.parsedate_to_datetime(val)
                    return dt.timestamp()
                except (TypeError, ValueError):
                    return None

            # Whole-second comparison: Last-Modified is served at second
            # granularity, so sub-second mod times must truncate or
            # revalidation (If-Modified-Since echoing our own header)
            # could never match (RFC 7232).
            mod_secs = info.mod_time // 1_000_000_000
            im, inm = g("if-match"), g("if-none-match")
            ims = parse_http_date(g("if-modified-since") or "")
            ius = parse_http_date(g("if-unmodified-since") or "")
            if im is not None:
                if not etag_matches(im):
                    raise S3Error("PreconditionFailed", bucket=info.bucket,
                                  key=info.name)
            elif ius is not None and mod_secs > ius:
                raise S3Error("PreconditionFailed", bucket=info.bucket,
                              key=info.name)
            if inm is not None:
                if etag_matches(inm):
                    if for_read:
                        return True          # 304
                    raise S3Error("PreconditionFailed", bucket=info.bucket,
                                  key=info.name)
            elif ims is not None and mod_secs <= ims:
                if for_read:
                    return True
                # Copy-source semantics: "only copy if modified since"
                # fails hard when the source has not changed.
                raise S3Error("PreconditionFailed", bucket=info.bucket,
                              key=info.name)
            return False

        def _send_not_modified(self, info):
            self.send_response(304)
            self.send_header("ETag", f'"{info.etag}"')
            self.send_header("Last-Modified", _rfc1123(info.mod_time))
            self.end_headers()

        def _get_object(self, method, bucket, key, query):
            h = self._headers_lower()
            vid = query.get("versionId", [""])[0]
            rng = h.get("range", "")
            spec = _range_spec(rng)
            chunks = None
            send_fd = None
            if any(c in h for c in ("if-match", "if-none-match",
                                    "if-modified-since",
                                    "if-unmodified-since")):
                pre = server.object_layer.get_object_info(
                    bucket, key, GetOptions(version_id=vid))
                if self._check_conditions(h, pre, for_read=True):
                    return self._send_not_modified(pre)
            hot = getattr(server, "hot_cache", None)
            hot_entry = None
            hot_token = None
            hot_admit = False
            hot_head = None
            if method == "HEAD":
                # HEAD: metadata fan-out only, no shard reads.
                info = server.object_layer.get_object_info(
                    bucket, key, GetOptions(version_id=vid))
                self._sse_check_head(h, info)
                start, length = (_resolve_head_range(spec, info.size)
                                 if spec else (0, info.size))
            elif hot is not None and not vid \
                    and (hot_entry := hot.get(bucket, key)) is not None:
                # Hot-tier RAM hit (object/hotcache.py): serve the
                # pinned plaintext body with ZERO object-layer work.
                # The shared header-assembly + send code below runs
                # unchanged on the cached ObjectInfo, so the response
                # is byte-identical to a miss (and to a
                # MTPU_HOT_CACHE=off server). Cheap ranges resolve
                # against the resident whole object.
                info = hot_entry.info
                start, length = (_resolve_head_range(spec, info.size)
                                 if spec else (0, info.size))
                chunks = (w for w in
                          (memoryview(hot_entry.body)
                           [start:start + length],))
                self._path_kind = "hotcache"
            else:
                # One streaming read, rerouted on the returned info when
                # the object carries a transform (SSE grows the offset
                # space, compression shrinks it). A plaintext range
                # exceeding a COMPRESSED stored size raises InvalidRange
                # at the open — only then fall back to an info-first
                # read; spec=None can never take that path. Version
                # pinning keeps params and data from the same generation
                # (unversioned buckets keep a small overwrite race, as
                # does the reference).
                from minio_tpu.object.types import InvalidRange as _IR
                if hot is not None and hot.enabled and not vid:
                    # Hot-tier token BEFORE the read fan-out (the
                    # fi_cache contract): a mutation racing this read
                    # bumps the bucket generation, and put() below
                    # refuses the stale insert.
                    hot_token = hot.token(bucket)
                info = chunks = None
                try:
                    info, chunks = \
                        server.object_layer.get_object_stream(
                            bucket, key, GetOptions(version_id=vid,
                                                    range_spec=spec))
                except _IR:
                    info = server.object_layer.get_object_info(
                        bucket, key, GetOptions(version_id=vid))
                    if not info.internal_metadata.get("x-internal-comp"):
                        raise      # genuinely out of range
                imeta = info.internal_metadata
                if imeta.get("x-internal-sse-alg"):
                    if chunks is not None:
                        chunks.close()
                    self._sse_check_head(h, info)
                    info, chunks, start, length = self._get_encrypted(
                        bucket, key, vid or info.version_id, spec, h,
                        info)
                elif imeta.get("x-internal-comp"):
                    if chunks is not None:
                        chunks.close()
                    info, chunks, start, length = self._get_compressed(
                        bucket, key, vid or info.version_id, spec, info)
                else:
                    start, length = info.range_start, info.range_length
                    # Hot-tier admission (tinyLFU): only plaintext
                    # whole-object reads under the size cap are
                    # candidates; the sketch decides whether buffering
                    # this body beats the would-be eviction victim.
                    if hot_token is not None and spec is None and length:
                        hot_admit = hot.admit(bucket, key, length)
                    # Whole-object plaintext sendfile short-circuit:
                    # a tier-resident (FS-warm) version's stored bytes
                    # live contiguously in one local file, so the body
                    # can go socket-ward entirely in-kernel. Erasure-
                    # resident objects never qualify (shard files are
                    # bitrot-framed). The probe is gated on the tier
                    # marker so the hot erasure GET path pays nothing.
                    if spec is None and length \
                            and imeta.get("x-internal-tier-name"):
                        gof = getattr(server.object_layer,
                                      "get_object_file", None)
                        sf = None
                        if gof is not None:
                            # The stream's read lock is still held and
                            # `info` is resolved for this exact version:
                            # the probe skips a second quorum fan-out.
                            try:
                                sf = gof(bucket, key, GetOptions(
                                    version_id=vid or info.version_id),
                                    info=info)
                            except Exception:  # noqa: BLE001 - fall back
                                sf = None
                        if sf is not None:
                            chunks.close()
                            chunks = None
                            info, send_fd, start, length = sf
            if spec and info.size == 0 and spec[0] is None:
                spec = None  # suffix range on empty object: plain 200 (AWS)
            headers = {
                "ETag": f'"{info.etag}"',
                "Last-Modified": _rfc1123(info.mod_time),
                "Accept-Ranges": "bytes",
            }
            headers.update(self._sse_response_headers(h, info))
            from minio_tpu.object import objectlock as olock
            headers.update(olock.meta_to_headers(info.internal_metadata))
            if h.get("x-amz-checksum-mode", "").upper() == "ENABLED":
                from minio_tpu.s3 import checksum as ck
                headers.update(ck.response_headers(
                    info.internal_metadata))
            repl = info.internal_metadata.get("x-internal-repl-status")
            if repl:
                headers["x-amz-replication-status"] = repl
            if info.version_id:
                headers["x-amz-version-id"] = info.version_id
            for mk, mv in info.user_metadata.items():
                headers[f"x-amz-meta-{mk}"] = mv
            ctype = info.content_type or "application/octet-stream"
            status = 206 if spec else 200
            if spec:
                headers["Content-Range"] = \
                    f"bytes {start}-{start + length - 1}/{info.size}"
            try:
                self._defer_head = True
                self.send_response(status)
                self.send_header("x-amz-request-id", "0")
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(length))
                for k2, v2 in headers.items():
                    self.send_header(k2, v2)
                self.end_headers()
                head = self._take_head()
                if method == "HEAD":
                    return self._send_bufs([head], final=True)
                if hot is not None and spec is None \
                        and h.get("x-amz-checksum-mode",
                                  "").upper() != "ENABLED":
                    # A plain whole-object GET's header block is the
                    # canonical response every later hit must replay
                    # byte-identically; checksum-mode requests shape
                    # extra headers, so their head never becomes the
                    # template (their body may still be admitted).
                    if hot_entry is not None:
                        hot.set_head(bucket, key, info.etag,
                                     info.version_id or "", head)
                    elif hot_admit:
                        hot_head = head
                if send_fd is not None:
                    self._sendfile_body(head, send_fd, start, length)
                    if hot_admit and not self.close_connection:
                        # Tier-resident hit went out in-kernel; admit
                        # the same bytes from the already-open fd.
                        try:
                            hbody = os.pread(send_fd, length, start)
                        except OSError:
                            hbody = b""
                        if len(hbody) == length:
                            hot.put(bucket, key, info, hbody, hot_head,
                                    hot_token)
                    return
                sent = 0
                hot_buf = bytearray() if hot_admit else None
                try:
                    # Gathered zero-copy streaming: the header block
                    # rides the FIRST window's sendmsg; every window is
                    # a pooled-buffer memoryview straight from the
                    # engine's readahead (released when the generator
                    # advances) — no Python-level joins or re-buffering.
                    # The LAST window is the response's final write:
                    # under the event loop an EAGAIN remainder there is
                    # handed to the loop instead of blocking the
                    # executor on a slow reader.
                    for chunk in chunks:
                        last = sent + len(chunk) >= length
                        if hot_buf is not None:
                            # Copy BEFORE the send: pooled windows are
                            # recycled when the generator advances.
                            hot_buf += chunk
                        with tracing_mod.stage("get.send", cpu=False):
                            if head is not None:
                                self._send_bufs([head, chunk], final=last)
                                head = None
                            else:
                                self._send_bufs([chunk], final=last)
                        sent += len(chunk)
                        self._sent_bytes = getattr(
                            self, "_sent_bytes", 0) + len(chunk)
                    if head is not None:      # zero-length body
                        self._send_bufs([head], final=True)
                        head = None
                except Exception as exc:  # noqa: BLE001 - headers may be sent
                    if head is not None and \
                            not getattr(exc, "mtpu_sent", 0):
                        # Nothing hit the wire yet (the FIRST window's
                        # produce failed, or its send died before any
                        # byte went out): surface a proper S3 error
                        # instead of a truncated 200. A partially-sent
                        # first window (mtpu_sent > 0) must NOT re-raise
                        # — a second full response after partial 200
                        # bytes is protocol corruption; cut instead.
                        raise
                    # Mid-stream failure (quorum loss, drive death) after
                    # the status line went out: all we can do is cut the
                    # connection short so the client sees a failed
                    # (truncated) transfer, never a silently short 200.
                    sent = -1
                if sent != length:
                    self.close_connection = True
                elif hot_buf is not None:
                    hot.put(bucket, key, info, bytes(hot_buf), hot_head,
                            hot_token)
            finally:
                if chunks is not None:
                    chunks.close()
                if send_fd is not None:
                    os.close(send_fd)

        def _post_object(self, bucket, body, ctype):
            """Browser-form POST-policy upload (reference:
            cmd/post-policy.go PostPolicyBucketHandler): multipart form
            with a base64 policy document signed by the uploader's key;
            the object is the `file` part."""
            import base64
            import email.parser as _ep
            import email.policy as _epol
            import hmac as _hmac
            import json as _json
            import re as _re

            raw = b"Content-Type: " + ctype.encode() + b"\r\n\r\n" + body
            msg = _ep.BytesParser(policy=_epol.default).parsebytes(raw)
            if not msg.is_multipart():
                raise S3Error("MalformedPOSTRequest")
            fields: dict[str, str] = {}
            file_data = None
            file_name = ""
            for part in msg.iter_parts():
                cd = part.get("Content-Disposition", "")
                m = _re.search(r'name="([^"]*)"', cd)
                if not m:
                    continue
                name = m.group(1).lower()
                data = part.get_payload(decode=True) or b""
                if name == "file":
                    file_data = data
                    fm = _re.search(r'filename="([^"]*)"', cd)
                    file_name = fm.group(1) if fm else ""
                    fields.setdefault("content-type",
                                      part.get_content_type())
                else:
                    fields[name] = data.decode("utf-8", "replace")
            if file_data is None:
                raise S3Error("InvalidArgument", "POST form missing file")
            policy_b64 = fields.get("policy", "")
            sig = fields.get("x-amz-signature", "")
            cred_str = fields.get("x-amz-credential", "")
            # A form with no credentials at all is an anonymous upload,
            # authorized purely by bucket policy below (reference:
            # cmd/post-policy.go treats a missing policy as anonymous).
            anonymous = not policy_b64 and not sig and not cred_str
            if anonymous:
                access_key = ""
                pol = {}
            else:
                if not policy_b64 or not sig or not cred_str:
                    raise S3Error("AccessDenied")
                cred = sigv4.Credential.parse(cred_str)
                access_key = cred.access_key
                self._auth_key = access_key   # audit/trace attribution
                secret = server.credentials.secret_for(access_key)
                if secret is None:
                    raise S3Error("InvalidAccessKeyId")
                skey = sigv4.signing_key(secret, cred.date, cred.region,
                                         cred.service)
                want = _hmac.new(skey, policy_b64.encode(),
                                 hashlib.sha256).hexdigest()
                if not _hmac.compare_digest(want, sig):
                    raise S3Error("SignatureDoesNotMatch")
                # STS keys must present their session token in the form
                # (same invariant as header-authorized requests).
                if server.credentials.iam is not None:
                    tok = server.credentials.iam.session_token_for(
                        access_key)
                    if tok is not None and \
                            fields.get("x-amz-security-token", "") != tok:
                        raise S3Error("AccessDenied",
                                      "invalid session token")
                try:
                    pol = _json.loads(base64.b64decode(policy_b64))
                except ValueError:
                    raise S3Error("MalformedPOSTRequest") from None
            exp = pol.get("expiration", "")
            if exp:
                try:
                    exp_dt = datetime.datetime.fromisoformat(
                        exp.replace("Z", "+00:00"))
                    if exp_dt.tzinfo is None:
                        exp_dt = exp_dt.replace(
                            tzinfo=datetime.timezone.utc)
                except (ValueError, TypeError):
                    raise S3Error("MalformedPOSTRequest") from None
                if exp_dt < datetime.datetime.now(datetime.timezone.utc):
                    raise S3Error("AccessDenied", "policy expired")
            key = fields.get("key", "")
            if not key:
                raise S3Error("InvalidArgument", "POST form missing key")
            key = key.replace("${filename}", file_name)
            # Enforce the policy's own conditions (eq / starts-with /
            # content-length-range) against the submitted form.
            form_view = dict(fields)
            form_view["bucket"] = bucket
            form_view["key"] = key
            for cond in pol.get("conditions", []):
                if isinstance(cond, dict):
                    items = [("eq", f"${k}", v) for k, v in cond.items()]
                elif isinstance(cond, list) and len(cond) == 3:
                    items = [tuple(cond)]
                else:
                    continue
                for op, field, val in items:
                    op = str(op).lower()
                    if op == "content-length-range":
                        continue
                    fname = str(field).lstrip("$").lower()
                    got = form_view.get(fname, "")
                    if op == "eq" and got != val:
                        raise S3Error("AccessDenied",
                                      f"policy condition failed: {fname}")
                    if op == "starts-with" and not got.startswith(val):
                        raise S3Error("AccessDenied",
                                      f"policy condition failed: {fname}")
                if isinstance(cond, list) and \
                        str(cond[0]).lower() == "content-length-range":
                    lo, hi = int(cond[1]), int(cond[2])
                    if not lo <= len(file_data) <= hi:
                        raise S3Error("EntityTooLarge"
                                      if len(file_data) > hi
                                      else "EntityTooSmall")
            # Same deny-wins identity + bucket-policy merge as every
            # header-authorized request (was a plain IAM check, which
            # bypassed bucket-policy Deny statements).
            ctx = self._auth_context(access_key, {}, self._headers_lower())
            if not self._authorize(access_key, anonymous, "s3:PutObject",
                                   f"{bucket}/{key}", ctx):
                raise S3Error("AccessDenied", bucket=bucket, key=key)
            meta = {k[len("x-amz-meta-"):]: v for k, v in fields.items()
                    if k.startswith("x-amz-meta-")}
            opts = PutOptions(
                versioned=_versioned(server.object_layer, bucket),
                user_metadata=meta,
                content_type=fields.get("content-type", ""),
                tags=fields.get("tagging", ""))
            # Form fields carry the same x-amz-object-lock-* names as
            # headers; lock metadata and bucket defaults apply equally.
            opts.internal_metadata.update(
                self._object_lock_put_meta(bucket, fields))
            # Bucket default encryption applies to form uploads too
            # (explicit SSE form fields ride the same header names).
            self._check_quota(bucket, len(file_data))
            post_payload, _ = self._apply_sse(
                bucket, key, Payload.wrap(file_data),
                {sse_key: v for sse_key, v in fields.items()
                 if sse_key.startswith("x-amz-server-side-encryption")},
                opts)
            info = server.object_layer.put_object(bucket, key,
                                                  post_payload, opts)
            self._note_quota_write(bucket, len(file_data))
            self._site_enqueue("put", bucket, key, info.version_id)
            self._notify("s3:ObjectCreated:Post", bucket, key,
                         size=len(file_data), etag=info.etag,
                         version_id=info.version_id)
            status = fields.get("success_action_status", "204")
            if status == "201":
                root = ET.Element("PostResponse")
                _el(root, "Location", f"/{bucket}/{key}")
                _el(root, "Bucket", bucket)
                _el(root, "Key", key)
                _el(root, "ETag", f'"{info.etag}"')
                return self._send(201, _xml(root))
            return self._send(200 if status == "200" else 204)

        def _health_ready(self):
            """Readiness: honest about degradation. 503 with a JSON
            body NAMING the degraded sets when any erasure set is below
            write quorum or still bulk-healing a replaced drive —
            orchestrators keep traffic off a node that would fail or
            slow-path writes (reference: ClusterCheckHandler,
            cmd/healthcheck-handler.go, plus the maintenance probe's
            healing awareness)."""
            import json as _json
            sets = _layer_sets(server.object_layer)
            if not sets:
                return self._send(503, _json.dumps(
                    {"ready": False, "reason": "no erasure sets"}
                ).encode(), content_type="application/json")
            probes = _probe_disks(server.object_layer)
            degraded = []
            for si, s in enumerate(sets):
                infos = [di for psi, _, di in probes if psi == si]
                ok = sum(1 for di in infos if di is not None)
                healing = sum(1 for di in infos
                              if di is not None
                              and getattr(di, "healing", False))
                n = len(s.disks)
                parity = getattr(s, "default_parity", 0)
                k = n - parity
                write_quorum = max(k + (1 if k == parity else 0),
                                   n // 2 + (1 if n > 1 else 0))
                if ok < write_quorum or healing:
                    degraded.append({
                        "set": si, "drives_online": ok, "drives": n,
                        "write_quorum": write_quorum,
                        "healing_drives": healing,
                    })
            if degraded:
                return self._send(503, _json.dumps(
                    {"ready": False, "degraded_sets": degraded}
                ).encode(), content_type="application/json")
            return self._send(200, _json.dumps({"ready": True}).encode(),
                              content_type="application/json")

        def _admin_speedtest(self, q1):
            """Self-measured object throughput (reference: `mc admin
            speedtest`, cmd/perf-tests.go): timed PUTs then GETs of
            synthetic objects through the full object layer, cleaned up
            afterwards."""
            import json as _json
            import os as _os
            import time as _time
            try:
                size = int(q1.get("size", str(4 << 20)))
                count = int(q1.get("count", "8"))
            except ValueError:
                raise S3Error("InvalidArgument") from None
            size = max(1 << 10, min(size, 256 << 20))
            count = max(1, min(count, 64))
            ol = server.object_layer
            bucket = "mtpu-speedtest-tmp"
            from minio_tpu.object.types import BucketExists
            try:
                ol.make_bucket(bucket)
            except BucketExists:
                pass        # shared across runs; keys are run-unique
            body = _os.urandom(size)
            run = _os.urandom(6).hex()    # concurrent runs never collide
            keys = [f"obj-{run}-{i}" for i in range(count)]
            try:
                t0 = _time.perf_counter()
                for k2 in keys:
                    ol.put_object(bucket, k2, body, PutOptions())
                put_s = _time.perf_counter() - t0
                t0 = _time.perf_counter()
                for k2 in keys:
                    ol.get_object(bucket, k2)
                get_s = _time.perf_counter() - t0
            finally:
                # Mid-run failures must not strand synthetic data.
                for k2 in keys:
                    try:
                        ol.delete_object(bucket, k2)
                    except Exception:  # noqa: BLE001 - best effort
                        pass
                try:
                    ol.delete_bucket(bucket)
                except Exception:  # noqa: BLE001 - other runs active
                    pass
            total = size * count
            result = {
                "object_size": size,
                "objects": count,
                "put_seconds": round(put_s, 4),
                "get_seconds": round(get_s, 4),
                "put_mibps": round(total / put_s / (1 << 20), 2),
                "get_mibps": round(total / get_s / (1 << 20), 2),
            }
            self._send(200, _json.dumps(result).encode(),
                       content_type="application/json")

        def _cluster_metrics_states(self):
            """Fleet-federated telemetry: the local node's merged
            snapshot (all pre-forked workers, one level down) plus one
            grid `peer.metrics` call per peer node — the same merge
            shape io/workers.py applies to workers, lifted to nodes.
            Down peers yield an `unreachable` stub so the scrape still
            reports them (as minio_tpu_cluster_node_up 0)."""
            from minio_tpu.s3.metrics import peer_metrics_state
            local = peer_metrics_state(server)
            local["local"] = True
            nodes = [local]
            mu = threading.Lock()

            def _fetch(name, client):
                try:
                    st = client.call("peer.metrics", {}, timeout=3)
                    if not isinstance(st, dict):
                        raise ValueError("bad peer snapshot")
                except Exception:  # noqa: BLE001 - peer down
                    st = {"node": name, "states": [],
                          "unreachable": True}
                st.setdefault("node", name)
                with mu:
                    nodes.append(st)

            # Concurrent fan-out: serial calls would stack one timeout
            # per DOWN peer onto every scrape.
            ts = [threading.Thread(target=_fetch, args=(n, c),
                                   daemon=True)
                  for n, c in server.profile_peers]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=4)
            return nodes

        def _admin_trace(self, query):
            """Live trace stream: chunked JSON lines until the client
            disconnects (reference: TraceHandler + pubsub; the `mc
            admin trace` shape). ?count=N stops after N entries;
            ?types=storage,grid,... filters (default `s3` — the
            top-level request records; `all` = every type including
            internal storage/grid/kernel/scanner/heal spans).

            In pre-forked worker mode this request lands on ONE worker
            while requests spread over ALL of them: the handler
            subscribes fleet-wide through the parent control pipe
            (io/workers.py trace pump) instead of its local
            broadcaster, so entries from every sibling stream here.

            ?cluster=true lifts the same merge one level up: the
            subscription fans out over every peer NODE as a grid
            `trace.stream` and the relays funnel into this response,
            so one connection tails the whole deployment (entries
            carry their origin `node`)."""
            import json as _json
            import queue as _queue
            limit = 0
            try:
                limit = int(query.get("count", ["0"])[0] or 0)
            except ValueError:
                pass
            raw = (query.get("types", [""])[0] or "").strip()
            if not raw:
                types = {"s3"}
            elif raw == "all":
                types = set(tracing_mod.TRACE_TYPES)
            else:
                types = {t.strip() for t in raw.split(",") if t.strip()} \
                    & set(tracing_mod.TRACE_TYPES)
                if not types:
                    types = {"s3"}

            relay_q = relay_stop = None
            if server.profile_peers and \
                    (query.get("cluster", [""])[0] or "").lower() in (
                        "true", "1", "yes", "on"):
                relay_q = _queue.Queue(maxsize=4096)
                relay_stop = threading.Event()
                for name, client in server.profile_peers:
                    threading.Thread(
                        target=self._trace_relay,
                        args=(name, client, sorted(types), relay_q,
                              relay_stop),
                        daemon=True).start()

            hub = getattr(server, "cluster_trace", None)
            sub = sub_id = None
            if hub is not None:
                try:
                    sub_id = hub.trace_sub(sorted(types))
                except Exception:  # noqa: BLE001 - control plane down
                    hub = None
            if hub is None:
                sub = server.tracer.subscribe(types)
            try:
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                sent = 0
                idle_since = _time_mod.monotonic()
                while not limit or sent < limit:
                    entries = []
                    if hub is not None:
                        entries = hub.trace_poll(sub_id)
                    else:
                        try:
                            entries = [sub.get(timeout=0.2)]
                        except _queue.Empty:
                            pass
                    if relay_q is not None:
                        try:
                            while len(entries) < 1024:
                                entries.append(relay_q.get_nowait())
                        except _queue.Empty:
                            pass
                    if not entries:
                        if _time_mod.monotonic() - idle_since > 1.0:
                            # Heartbeat chunk: on an idle server this
                            # is the only way a disconnected client
                            # surfaces (EPIPE) — without it the thread
                            # and subscriptions leak.
                            self.wfile.write(b"1\r\n\n\r\n")
                            self.wfile.flush()
                            idle_since = _time_mod.monotonic()
                        if hub is not None:
                            _time_mod.sleep(0.2)
                        continue
                    idle_since = _time_mod.monotonic()
                    for entry in entries:
                        line = _json.dumps(entry).encode() + b"\n"
                        self.wfile.write(b"%x\r\n" % len(line) + line
                                         + b"\r\n")
                        sent += 1
                        if limit and sent >= limit:
                            break
                    self.wfile.flush()
                self.wfile.write(b"0\r\n\r\n")
            except OSError:
                pass        # client went away
            finally:
                if relay_stop is not None:
                    relay_stop.set()
                if hub is not None:
                    try:
                        hub.trace_unsub(sub_id)
                    except Exception:  # noqa: BLE001 - best effort
                        pass
                else:
                    server.tracer.unsubscribe(sub)
                self.close_connection = True

        def _trace_relay(self, name, client, types, out_q, stop):
            """?cluster=true peer relay: one grid trace.stream per peer
            node, batches funneled into the merge queue. Dies with its
            stream on peer failure — the merged response keeps serving
            the surviving nodes. Backpressure drops (full queue) are
            acceptable for a diagnostics tail."""
            try:
                for batch in client.stream("trace.stream",
                                           {"types": types},
                                           timeout=10.0):
                    if stop.is_set():
                        break
                    for entry in batch or []:
                        if isinstance(entry, dict):
                            entry.setdefault("node", name)
                        try:
                            out_q.put_nowait(entry)
                        except _queue_mod.Full:
                            pass
            except Exception:  # noqa: BLE001 - peer gone / stream cut
                pass

        def _admin_info(self):
            import json as _json
            info = node_info(server)
            # Cluster view: each peer contributes its own node summary
            # over the grid (reference: cmd/notification.go ServerInfo
            # fan-out) — admin info reports the whole deployment, not
            # just the node that answered the HTTP call.
            if server.profile_peers:
                nodes = {"local": dict(info)}

                def _fetch(name, client):
                    try:
                        nodes[name] = client.call("peer.info", {},
                                                  timeout=3)
                    except Exception:  # noqa: BLE001 - peer down
                        nodes[name] = {"mode": "offline"}

                # Concurrent fan-out: serial calls would stack one
                # timeout per DOWN peer onto every info request.
                ts = [threading.Thread(target=_fetch, args=(n, c),
                                       daemon=True)
                      for n, c in server.profile_peers]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=4)
                info["nodes"] = nodes
                info["nodes_online"] = sum(
                    1 for n in nodes.values()
                    if n.get("mode") == "online")
                info["nodes_offline"] = len(nodes) - info["nodes_online"]
            self._send(200, _json.dumps(info).encode(),
                       content_type="application/json")

        def _admin_heal(self, query):
            """Trigger a global heal sweep in the background; poll with
            GET heal (reference: cmd/admin-heal-ops.go heal sequences)."""
            import json as _json
            deep = query.get("deep", [""])[0] in ("true", "1")

            def run():
                from minio_tpu.object.scanner import heal_set
                total = {"buckets": 0, "objects": 0, "healed": 0,
                         "failures": 0}
                try:
                    for s in _layer_sets(server.object_layer):
                        r = heal_set(s, deep=deep)
                        for k2 in total:
                            total[k2] += r.get(k2, 0)
                    server.heal_status = {"state": "done", **total}
                except Exception as e:  # noqa: BLE001 - surfaced in status
                    server.heal_status = {"state": "failed",
                                          "error": str(e)[:300]}

            with server._heal_lock:
                if server._heal_thread is None or \
                        not server._heal_thread.is_alive():
                    server.heal_status = {"state": "running", "deep": deep}
                    server._heal_thread = threading.Thread(target=run,
                                                           daemon=True)
                    server._heal_thread.start()
            return self._send(200, _json.dumps(
                self._heal_payload()).encode(),
                content_type="application/json")

        def _heal_payload(self):
            """Admin heal status: the sweep slot plus, when the drive
            lifecycle manager is wired, per-drive bulk-heal progress
            (scanned/healed/failed/bytes/ETA + checkpoint). In
            pre-forked mode the bulk heal lives in worker 0 while this
            request may land on any worker, so the fleet's snapshots
            are merged when the control plane is up."""
            payload = dict(server.heal_status)
            merged = None
            if server.cluster_stats is not None:
                try:
                    agg = {"formats_restored": 0, "drives": []}
                    found = False
                    for p in server.cluster_stats():
                        pst = p.get("drive_heal")
                        if isinstance(pst, dict):
                            found = True
                            agg["formats_restored"] += \
                                pst.get("formats_restored", 0)
                            agg["drives"].extend(pst.get("drives", []))
                    if found:
                        merged = agg
                except Exception:  # noqa: BLE001 - control plane down
                    merged = None
            if merged is None and server.drive_heal is not None:
                try:
                    merged = server.drive_heal.status()
                except Exception:  # noqa: BLE001 - status best effort
                    merged = None
            if merged is not None:
                payload["drive_heal"] = merged
            return payload

        # -- admin API (/minio/admin/v3/...) ---------------------------

        def _admin_op(self, method, raw_path, query, auth):
            """IAM management endpoints, root-only (reference:
            cmd/admin-handlers-users.go; bodies are plain JSON rather
            than the reference's madmin-encrypted payloads)."""
            import json as _json
            ak = auth.credential.access_key
            if not server.credentials.is_allowed(ak, "admin:*", "*"):
                raise S3Error("AccessDenied")
            op = raw_path[len("/minio/admin/v3/"):] \
                if raw_path.startswith("/minio/admin/v3/") else ""
            if op == "info" and method == "GET":
                return self._admin_info()
            if op == "trace" and method == "GET":
                return self._admin_trace(query)
            if op == "heal" and method == "POST":
                return self._admin_heal(query)
            if op == "heal" and method == "GET":
                return self._send(200,
                                  _json.dumps(self._heal_payload()).encode(),
                                  content_type="application/json")
            body = self._read_body()
            q1 = {k: v[0] for k, v in query.items()}

            def ok(payload=None):
                blob = _json.dumps(payload).encode() \
                    if payload is not None else b""
                self._send(200, blob, content_type="application/json")

            if op == "speedtest" and method == "POST":
                return self._admin_speedtest(q1)

            # Config subsystem: persisted KV with hot apply (reference:
            # admin SetConfigKV/GetConfigKV over internal/config).
            if op == "get-config" and method == "GET":
                from minio_tpu.s3 import config as cfg_mod
                return ok(cfg_mod.load_config(server.object_layer))
            if op == "set-config" and method == "PUT":
                from minio_tpu.s3 import config as cfg_mod
                try:
                    updates = _json.loads(body)
                    if not isinstance(updates, dict):
                        raise ValueError("config must be an object")
                    cfg_mod.validate(updates)
                except ValueError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                except cfg_mod.ConfigError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                try:
                    # Lock the read-modify-write so two concurrent
                    # set-configs cannot drop each other's keys. Hot
                    # apply reaches THIS node; peers reload over the
                    # control plane (TTL/reboot as the fallback).
                    with server.bucket_meta_lock:
                        prev = cfg_mod.load_config(server.object_layer)
                        cfg = dict(prev)
                        cfg.update(updates)
                        cfg_mod.save_config(server.object_layer, cfg,
                                            prev=prev)
                except cfg_mod.ConfigError as e:
                    # Persistence failure is a SERVICE error, not a bad
                    # request.
                    raise S3Error("InternalError", str(e)) from None
                # Apply only what THIS request changed.
                applied = cfg_mod.apply_config(server, updates)
                if server.peer_notify is not None:
                    server.peer_notify("config")
                return ok({"applied": applied})

            # Site replication (reference: cmd/site-replication.go).
            if op in ("site-replication-add", "site-replication-info",
                      "site-replication-remove",
                      "site-import-bucket-meta", "site-import-iam"):
                from minio_tpu.replication.site import (SiteError,
                                                        SiteReplicator,
                                                        hook_iam_changes)
                try:
                    if op == "site-replication-add" and method == "POST":
                        cfg = SiteReplicator.validate(_json.loads(body))
                        new_site = SiteReplicator(
                            server.object_layer, self._layer_sets(), cfg,
                            iam=server.credentials.iam)
                        try:
                            # Persist BEFORE arming: a failed save must
                            # not leave an active replicator running a
                            # config a restart will silently drop.
                            new_site.save()
                        except SiteError:
                            new_site.stop()
                            raise
                        if server.site is not None:
                            server.site.stop()
                        server.site = new_site
                        hook_iam_changes(server)
                        server.site.bootstrap()
                        return ok()
                    if op == "site-replication-info" and method == "GET":
                        return ok(server.site.info()
                                  if server.site else None)
                    if op == "site-replication-remove" and \
                            method == "POST":
                        if server.site is not None:
                            # Persist the removal BEFORE stopping: if
                            # the save fails quorum, the replicator
                            # keeps running its (intact) config rather
                            # than leaving a dead replicator armed and
                            # an on-disk config that re-arms at boot.
                            old_cfg = dict(server.site.config)
                            server.site.config = {"peers": []}
                            try:
                                server.site.save()
                            except SiteError:
                                server.site.config = old_cfg
                                raise
                            server.site.stop()
                            server.site = None
                        return ok()
                    if op == "site-import-bucket-meta" and method == "PUT":
                        # Receiving side of a peer's bucket-meta push:
                        # applied directly (never re-broadcast).
                        bkt = q1.get("bucket", "")
                        meta = _json.loads(body)
                        if not isinstance(meta, dict):
                            raise S3Error("InvalidArgument", "bad meta")
                        from minio_tpu.object.types import BucketExists
                        try:
                            server.object_layer.make_bucket(bkt)
                        except BucketExists:
                            pass
                        with server.bucket_meta_lock:
                            server.object_layer.set_bucket_meta(bkt, meta)
                        return ok()
                    if op == "site-import-iam" and method == "PUT":
                        # Receiving side of a peer's IAM mirror: applied
                        # directly; import_doc never fires on_change, so
                        # the change cannot ping-pong back.
                        doc = _json.loads(body)
                        if not isinstance(doc, dict):
                            raise S3Error("InvalidArgument", "bad doc")
                        iam = server.credentials.iam
                        if iam is None:
                            raise S3Error("NotImplemented",
                                          "no IAM store")
                        iam.import_doc(doc)
                        return ok()
                except SiteError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                except ValueError:
                    raise S3Error("MalformedXML") from None
                raise S3Error("MethodNotAllowed")

            # Batch jobs (reference: cmd/batch-handlers.go).
            if op in ("start-batch-job", "batch-job-status",
                      "list-batch-jobs", "cancel-batch-job"):
                from minio_tpu.object.batch import BatchError
                mgr = self._batch_jobs()
                try:
                    if op == "start-batch-job" and method == "POST":
                        return ok({"id": mgr.start(_json.loads(body))})
                    if op == "batch-job-status" and method == "GET":
                        st2 = mgr.status(q1.get("id", ""))
                        if st2 is None:
                            raise S3Error("InvalidArgument",
                                          "no such job")
                        return ok(st2)
                    if op == "list-batch-jobs" and method == "GET":
                        return ok(mgr.list_jobs())
                    if op == "cancel-batch-job" and method == "POST":
                        mgr.cancel(q1.get("id", ""))
                        return ok()
                except BatchError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                except ValueError:
                    raise S3Error("MalformedXML") from None
                raise S3Error("MethodNotAllowed")

            # Warm-tier management (reference: cmd/admin-handlers-tiers).
            if op in ("add-tier", "remove-tier", "list-tiers"):
                from minio_tpu.object.tier import TierError
                reg = self._tier_registry()
                try:
                    if op == "add-tier" and method == "PUT":
                        doc = _json.loads(body)
                        reg.add(doc.get("name", ""), doc.get("config", {}))
                        return ok()
                    if op == "remove-tier" and method == "DELETE":
                        name = q1.get("name", "")
                        # In-use guard: a lifecycle rule referencing
                        # the tier means transitions (and transitioned
                        # versions) depend on it; removal would make
                        # their data unreachable in one call. Parsed,
                        # not substring-matched — namespaced or
                        # whitespace-styled XML must not slip past.
                        from minio_tpu.object.lifecycle import (
                            LifecycleError, parse_lifecycle)
                        for bi in server.object_layer.list_buckets():
                            doc = server.object_layer.get_bucket_meta(
                                bi.name).get("config:lifecycle", "")
                            if not doc:
                                continue
                            try:
                                rules = parse_lifecycle(doc)
                            except LifecycleError:
                                continue
                            if any(name in (r.transition_tier,
                                            r.noncurrent_transition_tier)
                                   for r in rules):
                                raise S3Error(
                                    "InvalidArgument",
                                    f"tier {name!r} is referenced by "
                                    f"bucket {bi.name!r}'s lifecycle")
                        reg.remove(name)
                        return ok()
                    if op == "list-tiers" and method == "GET":
                        return ok(reg.list())
                except TierError as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                except ValueError:
                    raise S3Error("MalformedXML") from None
                raise S3Error("MethodNotAllowed")

            # Pool decommission / rebalance admin verbs — served from
            # ANY node (reference: cmd/admin-handlers-pools.go).
            # Starts work everywhere because the checkpoint doc lives
            # on cluster-readable drives and the dsync coordinator
            # lease keeps a single driver; status fans IN a live
            # coordinator's counters (fresher than the checkpoint);
            # stop fans OUT so it reaches whichever node drives the
            # walk (grid elastic.status/elastic.stop, wired at boot).
            def _elastic_live_peer(kind):
                for _n, cli in getattr(server, "profile_peers",
                                       None) or []:
                    try:
                        r = cli.call("elastic.status", None, timeout=3.0)
                    except Exception:  # noqa: BLE001 - peer down
                        continue
                    if isinstance(r, dict) and r.get(f"{kind}_live") \
                            and r.get(kind):
                        # At most one live driver exists (the lease),
                        # so the first live answer is THE coordinator.
                        return r[kind]
                return None

            def _elastic_stop_peers(kind):
                for _n, cli in getattr(server, "profile_peers",
                                       None) or []:
                    try:
                        cli.call("elastic.stop", {"kind": kind},
                                 timeout=5.0)
                    except Exception:  # noqa: BLE001 - peer down
                        continue

            if op == "decommission" and method == "POST":
                ol = server.object_layer
                if not hasattr(ol, "start_decommission"):
                    raise S3Error("NotImplemented", "single-pool layout")
                from minio_tpu.object.decom import DecomError
                try:
                    ol.start_decommission(int(q1.get("pool", "-1")))
                except (DecomError, ValueError) as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                return ok()
            if op == "decommission-status" and method == "GET":
                ol = server.object_layer
                fn = getattr(ol, "decommission_status", None)
                st = fn() if fn else None
                d = getattr(ol, "_decom", None)
                if d is None or d.wait(timeout=0):
                    peer = _elastic_live_peer("decommission")
                    if peer is not None:
                        st = peer
                return ok(st)
            if op == "decommission-cancel" and method == "POST":
                fn = getattr(server.object_layer, "cancel_decommission",
                             None)
                if fn:
                    fn()
                _elastic_stop_peers("decommission")
                return ok()

            if op == "rebalance-start" and method == "POST":
                ol = server.object_layer
                if not hasattr(ol, "start_rebalance"):
                    raise S3Error("NotImplemented", "single-pool layout")
                from minio_tpu.object.rebalance import (LeaseHeld,
                                                        RebalanceError)
                try:
                    ol.start_rebalance()
                except (LeaseHeld, RebalanceError) as e:
                    raise S3Error("InvalidArgument", str(e)) from None
                return ok()
            if op == "rebalance-status" and method == "GET":
                ol = server.object_layer
                fn = getattr(ol, "rebalance_status", None)
                st = fn() if fn else None
                rb = getattr(ol, "_rebalance", None)
                if rb is None or rb.wait(timeout=0):
                    peer = _elastic_live_peer("rebalance")
                    if peer is not None:
                        st = peer
                return ok(st)
            if op == "rebalance-stop" and method == "POST":
                fn = getattr(server.object_layer, "stop_rebalance", None)
                if fn:
                    fn()
                _elastic_stop_peers("rebalance")
                return ok()

            # KMS key management (reference: cmd/kms-handlers.go
            # KMSCreateKey / KMSListKeys / KMSKeyStatus).
            if op in ("kms-key-create", "kms-key-list", "kms-key-status"):
                from minio_tpu.crypto.kms import KeyStore, KMSError
                try:
                    ks = getattr(server, "_kms_keystore", None)
                    if ks is None:
                        disks = [d for s in self._layer_sets()
                                 for d in s.disks]
                        ks = server._kms_keystore = KeyStore(
                            server.kms, disks)
                    ks.reload()
                    if op == "kms-key-create" and method == "POST":
                        ks.create(q1.get("key-id", ""))
                        return ok()
                    if op == "kms-key-list" and method == "GET":
                        return ok(ks.list())
                    if op == "kms-key-status" and method == "GET":
                        return ok(ks.status(q1.get("key-id", "")))
                except KMSError as e:
                    raise S3Error("InvalidRequest", str(e)) from None
                raise S3Error("MethodNotAllowed")

            # Profiling (reference: cmd/admin-handlers.go:1021
            # StartProfilingHandler / DownloadProfilingDataHandler).
            if op == "start-profiling" and method == "POST":
                from minio_tpu.s3.profiling import ProfileError
                kind = q1.get("profilerType", "") or "cpu"
                try:
                    server.profiler.start(kind)
                except ProfileError as e:
                    raise S3Error("InvalidRequest", str(e)) from None
                # A trace is of the local node alone: the process that
                # holds the device.
                for _name, client in (server.profile_peers
                                      if kind == "cpu" else ()):
                    try:
                        client.call("peer.profile", {"action": "start"},
                                    timeout=5)
                    except Exception:  # noqa: BLE001 - peer down
                        pass
                return ok({"started": True, "profilerType": kind})
            if op == "download-profiling" and method == "GET":
                import base64 as _b64

                from minio_tpu.s3 import profiling as prof_mod
                from minio_tpu.s3.profiling import ProfileError
                per_node = {}
                try:
                    per_node["local"] = server.profiler.stop()
                except ProfileError as e:
                    raise S3Error("InvalidRequest", str(e)) from None
                for name, client in server.profile_peers:
                    try:
                        rec = client.call("peer.profile",
                                          {"action": "stop"}, timeout=10)
                        if rec.get("ok"):
                            per_node[name] = {
                                "stats": _b64.b64decode(
                                    rec.get("stats_b64", "")),
                                "text": rec.get("text", "")}
                    except Exception:  # noqa: BLE001 - peer down
                        pass
                return self._send(200, prof_mod.bundle(per_node),
                                  content_type="application/zip")

            # Bucket quotas (reference: cmd/admin-bucket-handlers.go
            # SetBucketQuotaConfigHandler / GetBucketQuotaConfigHandler,
            # enforced by cmd/bucket-quota.go).
            if op == "set-bucket-quota" and method == "PUT":
                bkt = q1.get("bucket", "")
                server.object_layer.get_bucket_info(bkt)
                try:
                    cfg = _json.loads(body) if body else {}
                    quota = int(cfg.get("quota") or 0)
                except (ValueError, TypeError):
                    raise S3Error("InvalidArgument",
                                  "malformed quota configuration") \
                        from None
                if quota < 0:
                    raise S3Error("InvalidArgument",
                                  "quota must be non-negative")
                with server.bucket_meta_lock:
                    meta = server.object_layer.get_bucket_meta(bkt)
                    if quota == 0:
                        meta.pop("config:quota", None)
                    else:
                        meta["config:quota"] = _json.dumps(
                            {"quota": quota,
                             "quotatype": cfg.get("quotatype", "hard")})
                    server.object_layer.set_bucket_meta(bkt, meta)
                return ok()
            if op == "get-bucket-quota" and method == "GET":
                bkt = q1.get("bucket", "")
                server.object_layer.get_bucket_info(bkt)
                raw = server.object_layer.get_bucket_meta(bkt) \
                    .get("config:quota")
                if not raw:
                    raise S3Error("XMinioAdminNoSuchQuotaConfiguration",
                                  bucket=bkt)
                return ok(_json.loads(raw) if isinstance(raw, str)
                          else raw)

            # Replication target management needs no IAM store.
            if op == "set-remote-target" and method == "PUT":
                doc = _json.loads(body)
                for field in ("endpoint", "accessKey", "secretKey"):
                    if not doc.get(field):
                        raise S3Error("InvalidArgument",
                                      f"missing {field}")
                bkt = q1.get("bucket", "")
                server.object_layer.get_bucket_info(bkt)
                with server.bucket_meta_lock:
                    meta = server.object_layer.get_bucket_meta(bkt)
                    meta["config:remote-target"] = _json.dumps(doc)
                    server.object_layer.set_bucket_meta(bkt, meta)
                return ok()
            if op == "get-remote-target" and method == "GET":
                bkt = q1.get("bucket", "")
                doc = server.object_layer.get_bucket_meta(bkt) \
                    .get("config:remote-target")
                rec = _json.loads(doc) if doc else None
                if rec:
                    rec.pop("secretKey", None)   # never echo secrets
                return ok(rec)
            if op == "replication-status" and method == "GET":
                r = server.replicator
                if r is None:
                    return ok(None)
                # Keep the v1 keys at top level; the full stats dict
                # (lanes, WAL, spill, lag) rides alongside them.
                doc = r.stats() if hasattr(r, "stats") else \
                    {"queued": r.queued, "completed": r.completed,
                     "failed": r.failed}
                return ok(doc)
            if op == "replication-resync" and method == "POST":
                r = server.replicator
                if r is None or not hasattr(r, "start_resync"):
                    raise S3Error("NotImplemented")
                bkt = q1.get("bucket", "")
                server.object_layer.get_bucket_info(bkt)
                return ok(r.start_resync(bkt))
            if op == "replication-resync" and method == "GET":
                r = server.replicator
                if r is None or not hasattr(r, "resync_status"):
                    raise S3Error("NotImplemented")
                return ok(r.resync_status(q1.get("bucket") or None))

            iam = server.credentials.iam
            if iam is None:
                raise S3Error("NotImplemented")

            try:
                if op == "add-user" and method == "PUT":
                    doc = _json.loads(body)
                    iam.add_user(q1.get("accessKey", ""),
                                 doc.get("secretKey", ""))
                    return ok()
                if op == "remove-user" and method == "DELETE":
                    iam.remove_user(q1.get("accessKey", ""))
                    return ok()
                if op == "list-users" and method == "GET":
                    return ok(iam.list_users())
                if op == "set-user-status" and method == "PUT":
                    iam.set_user_status(q1.get("accessKey", ""),
                                        q1.get("status", "") == "enabled")
                    return ok()
                if op == "add-canned-policy" and method == "PUT":
                    iam.set_policy(q1.get("name", ""), _json.loads(body))
                    return ok()
                if op == "remove-canned-policy" and method == "DELETE":
                    iam.delete_policy(q1.get("name", ""))
                    return ok()
                if op == "list-canned-policies" and method == "GET":
                    return ok(iam.list_policies())
                if op == "set-user-or-group-policy" and method == "PUT":
                    names = [n for n in
                             q1.get("policyName", "").split(",") if n]
                    iam.attach_policy(q1.get("userOrGroup", ""), names)
                    return ok()
                if op == "add-service-account" and method == "PUT":
                    doc = _json.loads(body)
                    iam.add_service_account(
                        doc.get("parent", server.credentials.access_key),
                        doc.get("accessKey", ""), doc.get("secretKey", ""),
                        doc.get("policy"))
                    return ok()
                if op == "update-group-members" and method == "PUT":
                    doc = _json.loads(body)
                    iam.update_group_members(
                        doc.get("group", ""),
                        list(doc.get("members") or []),
                        remove=bool(doc.get("remove")))
                    return ok()
                if op == "remove-group" and method == "DELETE":
                    iam.remove_group(q1.get("group", ""))
                    return ok()
                if op == "list-groups" and method == "GET":
                    return ok(iam.list_groups())
            except ValueError:
                raise S3Error("MalformedXML") from None
            except Exception as e:
                from minio_tpu.iam import IAMError
                if isinstance(e, IAMError):
                    raise S3Error("InvalidArgument", str(e)) from None
                raise
            raise S3Error("MethodNotAllowed")

        def _delete_object(self, bucket, key, query):
            vid = query.get("versionId", [""])[0]
            h = self._headers_lower()
            self._check_version_deletable(bucket, key, vid, h)
            state = _versioning_state(server.object_layer, bucket)
            # Only versionless deletes (which create markers) replicate;
            # pruning ONE old version must never destroy the replica's
            # live object (DeleteMarkerReplication semantics).  Deletes
            # arriving FROM a peer carry the replica marker header and
            # never re-replicate — an active-active pair would
            # otherwise ping-pong markers forever.
            replicate = (server.replicator is not None and not vid
                         and "x-amz-meta-mtpu-replica" not in h
                         and server.replicator.should_replicate(
                             bucket, key, delete=True))
            opts = DeleteOptions(
                version_id=vid,
                versioned=state == "Enabled",
                null_marker=state == "Suspended" and not vid)
            if not vid and opts.versioned \
                    and "x-amz-meta-mtpu-replica" in h:
                # Replicated delete: mint the marker with the SOURCE
                # marker's version id so active-active peers hold the
                # same marker version (re-delivery replaces in place
                # instead of stacking a second marker).  Only honored
                # on replica traffic, only for uuid-shaped ids — a
                # suspended source sends "null", which the target's own
                # versioning state governs instead.
                import uuid as _uuid
                from minio_tpu.replication.common import H_REPLICA_DM
                dmv = h.get(H_REPLICA_DM, "")
                if dmv and dmv != "null":
                    try:
                        _uuid.UUID(dmv)
                        opts.marker_version_id = dmv
                    except ValueError:
                        pass
            if replicate and (opts.versioned or opts.null_marker):
                # Stamp the marker PENDING at creation: the status
                # commits with the marker's quorum write, so a crash
                # before the enqueue still leaves the scanner a
                # resyncable trail.
                from minio_tpu.replication import REPL_STATUS_KEY
                opts.marker_metadata = {REPL_STATUS_KEY: "PENDING"}
            deleted = server.object_layer.delete_object(bucket, key, opts)
            if replicate:
                server.replicator.enqueue(
                    bucket, key,
                    deleted.delete_marker_version_id
                    if deleted.delete_marker else "",
                    op="delete", mod_time=_time_mod.time_ns())
            if not vid:
                self._site_enqueue("delete", bucket, key)
            self._notify("s3:ObjectRemoved:DeleteMarkerCreated"
                         if deleted.delete_marker
                         else "s3:ObjectRemoved:Delete", bucket, key,
                         version_id=deleted.delete_marker_version_id
                         if deleted.delete_marker else vid)
            headers = {}
            if deleted.delete_marker:
                headers["x-amz-delete-marker"] = "true"
                headers["x-amz-version-id"] = deleted.delete_marker_version_id
            elif vid:
                headers["x-amz-version-id"] = vid
            self._send(204, headers=headers)

    return Handler


def _parse_tagging_xml(body: bytes) -> str:
    """<Tagging><TagSet><Tag><Key>..</Key><Value>..</Value> -> URL-encoded
    tag string; validates count and uniqueness (reference:
    internal/bucket/object/tags)."""
    try:
        root = ET.fromstring(body)
    except ET.ParseError:
        raise S3Error("MalformedXML") from None
    ns = f"{{{XMLNS}}}"
    tags = []
    tagset = root.find(f"{ns}TagSet")
    if tagset is None:
        tagset = root.find("TagSet")
    if tagset is None:
        raise S3Error("MalformedXML")
    for te in list(tagset.findall(f"{ns}Tag")) + list(tagset.findall("Tag")):
        k = te.findtext(f"{ns}Key") or te.findtext("Key") or ""
        v = te.findtext(f"{ns}Value") or te.findtext("Value") or ""
        if not k or len(k) > 128 or len(v) > 256:
            raise S3Error("InvalidTag")
        tags.append((k, v))
    if len(tags) > 10:
        raise S3Error("InvalidTag", "too many tags")
    if len({k for k, _ in tags}) != len(tags):
        raise S3Error("InvalidTag", "duplicate tag key")
    return urllib.parse.urlencode(tags)


def _required_permissions(method: str, bucket: str, key: str, query: dict,
                          h: dict) -> list[tuple[str, str]]:
    """Map one S3 request to the (action, resource) pairs it needs
    (reference: cmd/api-router.go handler -> policy.Action wiring).
    Resources are `bucket` / `bucket/key` (ARN prefix already stripped,
    matching iam.policy's compiled patterns)."""
    if not bucket:
        return [("s3:ListAllMyBuckets", "*")] if method == "GET" else []
    perms: list[tuple[str, str]] = []
    if key and method == "PUT" and "x-amz-copy-source" in h:
        src = urllib.parse.unquote(h["x-amz-copy-source"]).lstrip("/")
        src = src.partition("?versionId=")[0]
        perms.append(("s3:GetObject", src))
    _CONFIG_ACTIONS = {
        "policy": "BucketPolicy", "lifecycle": "LifecycleConfiguration",
        "tagging": "BucketTagging", "cors": "BucketCORS",
        "encryption": "EncryptionConfiguration",
        "notification": "BucketNotification",
        "replication": "ReplicationConfiguration",
    }
    if not key:
        for q, stem in _CONFIG_ACTIONS.items():
            if q in query:
                verb = {"GET": "Get", "HEAD": "Get", "PUT": "Put",
                        "DELETE": "Delete"}.get(method, "Get")
                perms.append((f"s3:{verb}{stem}", bucket))
                return perms
        if "object-lock" in query:
            verb = "Put" if method == "PUT" else "Get"
            return [(f"s3:{verb}BucketObjectLockConfiguration", bucket)]
        if "acl" in query:
            verb = "Put" if method == "PUT" else "Get"
            return [(f"s3:{verb}BucketAcl", bucket)]
        if method == "PUT":
            perms.append(("s3:PutBucketVersioning", bucket)
                         if "versioning" in query
                         else ("s3:CreateBucket", bucket))
        elif method == "DELETE":
            perms.append(("s3:DeleteBucket", bucket))
        elif method == "HEAD":
            perms.append(("s3:ListBucket", bucket))
        elif method == "POST" and "delete" in query:
            perms.append(("s3:DeleteObject", f"{bucket}/*"))
        elif method == "GET":
            if "uploads" in query:
                perms.append(("s3:ListBucketMultipartUploads", bucket))
            elif "versioning" in query:
                perms.append(("s3:GetBucketVersioning", bucket))
            elif "versions" in query:
                perms.append(("s3:ListBucketVersions", bucket))
            elif "location" in query:
                perms.append(("s3:GetBucketLocation", bucket))
            else:
                perms.append(("s3:ListBucket", bucket))
        return perms
    res = f"{bucket}/{key}"
    if method == "POST" and "select" in query:
        return [("s3:GetObjectVersion" if query.get("versionId", [""])[0]
                 else "s3:GetObject", res)]
    if "tagging" in query:
        verb = {"GET": "Get", "PUT": "Put", "DELETE": "Delete"}.get(
            method, "Get")
        perms.append((f"s3:{verb}ObjectTagging", res))
        return perms
    if "acl" in query:
        verb = "Put" if method == "PUT" else "Get"
        return [(f"s3:{verb}ObjectAcl", res)]
    if "attributes" in query and method == "GET":
        # Attribute reads are data-class access; gating on the broad
        # GetObject(Version) keeps canned readonly policies working.
        return [("s3:GetObjectVersion"
                 if query.get("versionId", [""])[0] else "s3:GetObject",
                 res)]
    if "retention" in query:
        verb = "Put" if method == "PUT" else "Get"
        return [(f"s3:{verb}ObjectRetention", res)]
    if "legal-hold" in query:
        verb = "Put" if method == "PUT" else "Get"
        return [(f"s3:{verb}ObjectLegalHold", res)]
    if method in ("GET", "HEAD"):
        if "uploadId" in query:
            perms.append(("s3:ListMultipartUploadParts", res))
        elif query.get("versionId", [""])[0]:
            perms.append(("s3:GetObjectVersion", res))
        else:
            perms.append(("s3:GetObject", res))
    elif method == "PUT":
        perms.append(("s3:PutObject", res))
    elif method == "DELETE":
        perms.append(("s3:AbortMultipartUpload", res)
                     if "uploadId" in query else ("s3:DeleteObject", res))
    elif method == "POST":
        perms.append(("s3:PutObject", res))
    return perms


def _b64e(s: str) -> str:
    import base64
    return base64.urlsafe_b64encode(s.encode()).decode()


def _b64d(s: str) -> str:
    import base64
    try:
        return base64.urlsafe_b64decode(s.encode()).decode()
    except Exception:
        raise S3Error("InvalidArgument", "bad continuation token") from None


def _versioned(ol, bucket: str) -> bool:
    fn = getattr(ol, "bucket_versioning", None)
    return bool(fn(bucket)) if fn else False


def _versioning_state(ol, bucket: str) -> str:
    """"" (never enabled) | "Enabled" | "Suspended" — the reference
    keeps Suspended as a REAL state (internal/bucket/versioning/
    versioning.go:36,76): suspended buckets write null-versionId
    objects replacing the previous null version while Enabled-era
    versions survive."""
    meta = getattr(ol, "get_bucket_meta", lambda b: {})(bucket)
    if meta.get("versioning"):
        return "Enabled"
    if meta.get("versioning-suspended"):
        return "Suspended"
    return ""


def _range_spec(rng: str):
    """Range header -> (start|None, end|None) spec, or None if absent."""
    if not rng:
        return None
    if not rng.startswith("bytes="):
        raise S3Error("InvalidArgument")
    spec = rng[len("bytes="):]
    if "," in spec:
        raise S3Error("NotImplemented", "multiple ranges")
    lo, _, hi = spec.partition("-")
    try:
        if lo == "":
            return (None, int(hi))
        return (int(lo), int(hi) if hi else None)
    except ValueError:
        raise S3Error("InvalidArgument") from None


def _resolve_head_range(spec, size: int):
    from minio_tpu.object.erasure_object import _resolve_range
    return _resolve_range(spec, size, "", "")


def _validate_bucket_name(name: str) -> None:
    import re
    if not (3 <= len(name) <= 63) or \
            not re.fullmatch(r"[a-z0-9][a-z0-9.-]*[a-z0-9]", name):
        raise S3Error("InvalidBucketName", bucket=name)


def _validate_object_name(key: str) -> None:
    if not key or len(key.encode()) > 1024 or "\x00" in key:
        raise S3Error("InvalidObjectName", key=key)
    for seg in key.split("/"):
        # Empty segments ("a//b", trailing "/") would alias to a different
        # key after path normalization on disk — reject them.
        if seg in ("", ".", ".."):
            raise S3Error("InvalidObjectName", key=key)

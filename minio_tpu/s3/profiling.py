"""Admin profiling: start/stop CPU profiles, bundle results.

The analogue of the reference's profiling handlers
(cmd/admin-handlers.go:1021 StartProfilingHandler /
DownloadProfilingDataHandler): an admin starts a profile, load runs,
and the download returns a zip bundle of per-node profile data. The
reference captures Go pprof profiles; the runtime here is Python, so
the capture is cProfile — the zip carries both the raw marshaled stats
(loadable with pstats.Stats) and a rendered text summary per node.
`profilerType=trace` (upstream: `mc admin profile --type trace`) is
the JAX profiler's trace of the local node instead: device operations
and the program's stages on one clock.

In distributed mode the start/stop fan out over the grid
(PROFILE_HANDLER) so the bundle covers every peer, the way the
reference's NotificationSys collects remote profiles.
"""

from __future__ import annotations

import contextlib
import cProfile
import io
import marshal
import os
import pstats
import shutil
import tempfile
import threading
import time
import zipfile

PROFILE_HANDLER = "peer.profile"
KINDS = ("cpu", "trace")


class ProfileError(Exception):
    pass


class Profiler:
    """One node's profile capture: `cpu` (cProfile, one request at a
    time) or `trace` (the JAX profiler's device + host trace, in the
    process that holds the device)."""

    # Per-request capture cap: an admin who forgets to stop a profile
    # on a busy server must not accumulate profiles without bound.
    _MAX_REQUEST_PROFILES = 4096

    def __init__(self):
        self._mu = threading.Lock()
        self._kind = ""                       # "" = nothing running
        # One capture at a time: since Python 3.12 a second
        # cProfile.Profile().enable() anywhere in the process raises
        # ("Another profiling tool is already active"), so a request
        # that finds the slot taken runs unprofiled — a running admin
        # profile must never fail a request.
        self._slot = threading.Lock()
        self._request_profs: list[cProfile.Profile] = []
        self._trace_dir = ""
        self._started_ns = 0

    def start(self, kind: str = "cpu") -> None:
        if kind not in KINDS:
            raise ProfileError(f"unknown profilerType {kind!r} "
                               f"(have: {', '.join(KINDS)})")
        with self._mu:
            if self._kind:
                raise ProfileError("a profile is already running")
            if kind == "trace":
                self._trace_dir = _start_device_trace()
            self._request_profs = []
            self._started_ns = time.time_ns()
            self._kind = kind

    @contextlib.contextmanager
    def request_profile(self):
        """Per-request capture on the HANDLER thread, merged into the
        bundle at stop. Whatever goes wrong in here, the request
        runs."""
        # Lock-free fast path: this wraps EVERY request's dispatch,
        # and profiling is almost always off — a single attribute read
        # (atomic in CPython) must not become a shared-lock point.
        if self._kind != "cpu" or not self._slot.acquire(blocking=False):
            yield
            return
        p = None
        try:
            if len(self._request_profs) < self._MAX_REQUEST_PROFILES:
                p = cProfile.Profile()
                try:
                    p.enable()
                except ValueError:      # another tool holds the hook
                    p = None
            yield
        finally:
            try:
                if p is not None:
                    p.disable()
                    with self._mu:
                        if self._kind == "cpu":
                            self._request_profs.append(p)
            finally:
                self._slot.release()

    def stop(self) -> dict:
        """Stop and return {"stats": marshaled pstats bytes, "text":
        rendered summary, "duration_s"} for a cpu profile, {"trace":
        {relative path: bytes}, "duration_s"} for a trace."""
        with self._mu:
            if not self._kind:
                raise ProfileError("no profile is running")
            kind, self._kind = self._kind, ""
            request_profs, self._request_profs = self._request_profs, []
            trace_dir, self._trace_dir = self._trace_dir, ""
        duration_s = (time.time_ns() - self._started_ns) / 1e9
        if kind == "trace":
            return {"trace": _stop_device_trace(trace_dir),
                    "duration_s": duration_s}
        out = io.StringIO()
        stats = pstats.Stats(stream=out)
        for p in request_profs:
            try:
                stats.add(p)
            except Exception:  # noqa: BLE001 - one bad capture != no bundle
                continue
        stats.sort_stats("cumulative").print_stats(60)
        return {
            "stats": marshal.dumps(stats.stats),
            "text": out.getvalue(),
            "duration_s": duration_s,
        }

    @property
    def kind(self) -> str:
        """"cpu" | "trace" | "" (nothing running)."""
        return self._kind


def _start_device_trace() -> str:
    """jax.profiler.start_trace into a fresh temp dir, in the process
    that holds the device; -> the dir. The program's stages are in the
    trace as host spans (utils/tracing.stage)."""
    from minio_tpu.ops import device
    if not device.held():
        raise ProfileError("profilerType=trace needs the process that "
                           "holds the device (a device backend serves "
                           "there); this one runs the host codec")
    import jax
    # device events and TraceMe spans only: the Python call tracer
    # (level 1 by default) stalls a one-process server for the seconds
    # it takes to write every call out
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    trace_dir = tempfile.mkdtemp(prefix="mtpu-trace-")
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    except Exception as e:  # noqa: BLE001 - said to the admin as a 400
        shutil.rmtree(trace_dir, ignore_errors=True)
        raise ProfileError(f"profiler session did not start: "
                           f"{type(e).__name__}: {e}") from e
    return trace_dir


def _stop_device_trace(trace_dir: str) -> dict[str, bytes]:
    import jax
    try:
        jax.profiler.stop_trace()
        files = {}
        for root, _dirs, names in os.walk(trace_dir):
            for name in names:
                path = os.path.join(root, name)
                with open(path, "rb") as f:
                    files[os.path.relpath(path, trace_dir)] = f.read()
        return files
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def bundle(per_node: dict[str, dict]) -> bytes:
    """zip bytes: <node>/profile.pstats + <node>/profile.txt per node
    (the shape of the reference's profiling zip download), or the
    profiler's own directory under <node>/trace/ for a trace."""
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
        for node, rec in per_node.items():
            if "trace" in rec:
                for rel, data in sorted(rec["trace"].items()):
                    z.writestr(f"{node}/trace/{rel}", data)
                continue
            z.writestr(f"{node}/profile.pstats", rec.get("stats", b""))
            z.writestr(f"{node}/profile.txt", rec.get("text", ""))
    return buf.getvalue()


def make_profile_handler(profiler: Profiler):
    """Grid handler: peers start/stop their local profiler on request
    (the receiving half of the cluster-wide fan-out)."""

    def handler(payload):
        action = (payload or {}).get("action", "")
        if action == "start":
            try:
                profiler.start()
            except ProfileError:
                pass                      # already running: converged
            return {"ok": True}
        if action == "stop":
            if profiler.kind != "cpu":
                # nothing running — or this node's own trace, which
                # only its own admin's download ends
                return {"ok": False}
            try:
                rec = profiler.stop()
            except ProfileError:
                return {"ok": False}
            import base64
            return {"ok": True, "text": rec["text"],
                    "duration_s": rec["duration_s"],
                    "stats_b64": base64.b64encode(rec["stats"]).decode()}
        return {"ok": False}

    return handler

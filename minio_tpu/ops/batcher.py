"""Device-resident cross-request stripe batching on the sharded codec.

The blueprint's most TPU-native idea (BASELINE.json: "shard batches from
parallelWriter ... are coalesced into HBM-resident tensors so a full
erasure set's stripes encode in one pmap"): stripe windows from MANY
concurrent PutObject calls coalesce into ONE device step — the batch
dimension becomes "stripes from many requests" — and completions
demultiplex back to the waiting writers, whose per-drive shard writes
then ride the io/engine drive queues exactly like solo PUTs. The
reference's analogue is the opposite trade (each goroutine encodes its
own blocks on its own core, cmd/erasure-encode.go:27 multiWriter); on a
TPU the accelerator is one big shared mesh, so batching across requests
is what fills it.

What makes the batch DEVICE-resident (ops/hh_device.make_mesh_framer):
the coalesced window is staged into ONE pooled bufpool buffer — pooled
for every bucket on every route: io/bufpool's classes reach past the
largest dispatch (256 blocks: 256 MiB on put, 268.5 MB on get) and keep
two idle buffers of that size, the dispatcher's depth, so a loaded
batcher copies into memory that has been written before and never
page-faults a fresh mapping per dispatch — padded to
a fixed power-of-two bucket, and dispatched as one jitted step placed
by ops/device.batch_placement: on one chip as it stands, on several
with the batch dim cut over the chips (`P("stripe")`) and the staged
batch donated, so data flows host -> HBM -> parity with no defensive
copy. One compiled executable exists per (bucket, EC config), never per
concurrency level. All device dispatches in the process serialize
through the shared io/engine kernel lane (the chip is one resource, like
a drive), which also yields wait-vs-service attribution for free.

Dispatch policy is MEASURED, not assumed: a one-time background probe
times the device round trip (host->HBM transfer + fused kernel +
readback) against the host codec for the same bytes. Where the device
round trip wins, batches route to the device; where it loses,
everything stays on the host codec and the batcher degrades to a
pass-through. Losing on time is a verdict; a device function that
RAISES is a fault — counted and logged with its traceback
(ops/device.record_fault), and an error rather than a route when the
operator asked for the device (device.required()). A lone
PUT with no concurrency never waits: frame() bypasses the queue
entirely unless other requests are already in flight. The accumulation
window is ADAPTIVE: it opens at the measured base wait, stretches while
bursts keep filling whole buckets, shrinks toward zero while traffic is
sparse, dispatches early the moment one mesh-filling batch is pending,
and never holds a member past its request deadline (members whose
budget is already spent fail alone — they are culled before dispatch
and cannot poison batch-mates).

The dispatcher thread never waits for the lane. A dispatch is three
steps — stage (copy the members into the staging buffer), submit (the
device call, on the kernel lane's own thread) and finish (lease back,
rows demultiplexed, members released) — and the dispatcher does the
first two and goes back for the next batch, while a finisher thread of
its own does the third the moment the rows are back. The depth is two
by construction: one batch in the lane, one being staged; before it
submits, the dispatcher waits for its previous batch's lane call to
have returned (the counted stage `batcher.lane_wait`: what it still
waits for the device); the finish is the counted stage
`batcher.finish`, on every route. So the cycle of a loaded dispatcher is
max(stage, lane) instead of their sum, results come back in order, and
at most two staging buffers are alive. A lone window (frame()'s solo
path) runs the three steps on its own thread, one after the other.

Every batched dispatch is also one `kernel` span FANNED into each
member request's span tree (utils/tracing.record_into): a traced PUT
shows the shared dispatch it rode — batch size, bucket, mesh width,
its own coalescing wait — not a gap.

The same machinery runs the READ path in reverse (the decode mirror,
PR "device-resident read path"): a batcher carries a `route` —
  * "put"          — encode+frame windows (the original),
  * "get"          — framed-window bitrot verification (the device
                     de-framer, hh_device.make_mesh_deframer; members
                     are [B, k, 32+shard] stacked on-disk frames),
  * "reconstruct"  — batched GF decode-matrix application for degraded
                     reads / heal rebuilds (rs_device.make_mesh_matrix;
                     members are [B, k, shard] survivor stripes).
  * "transform"    — the fused single-pass data plane's frame stage
                     (object/transform.py): stored windows that already
                     ran digest/compress/DARE through the native
                     transform kernel coalesce here, calibrated and
                     forceable independently of raw PUT windows.
Routes calibrate INDEPENDENTLY (one batcher instance per route and
config): a host whose device link wins on encode but loses on decode —
or vice versa — routes each direction on its own measurement, and
MTPU_BATCH_FORCE accepts per-route pins. Non-put routes plug in a
`split_fn` that demultiplexes the shared dispatch result back to
member-sized results (the PUT-specific digest/block re-pointing stays
the default), and a `concat_fn` that splices oversized windows'
chunked results. Members whose trailing shapes differ (e.g. heal
verify batches from objects of different EC configs through one
verifier) never share a staging buffer: the dispatcher drains
same-shape runs per batch.

Environment:
  MTPU_BATCH_FORCE    device|host|auto (default auto): pin the
                      calibration verdict — a reproducible route in
                      tests/CI instead of a probe-dependent one.
                      Accepts per-route pins as a comma list, e.g.
                      "put=device,get=host" (unnamed routes stay auto).
  MTPU_BATCH_WAIT_MS  base accumulation window in ms (default 2).
  MTPU_GET_BATCH_WAIT_MS
                      base window for the get/reconstruct routes
                      (default: MTPU_BATCH_WAIT_MS) — read latency
                      budgets are tighter than write ones, so the
                      decode coalescing window tunes separately.
"""

from __future__ import annotations

import os
import queue as queue_mod
import threading
import time
import weakref
from concurrent.futures import Future
from typing import Callable, Optional

import numpy as np

from minio_tpu.io.engine import EngineSaturated, kernel_lane
from minio_tpu.ops import device
from minio_tpu.utils import deadline as deadline_mod
from minio_tpu.utils import tracing
from minio_tpu.utils.deadline import DeadlineExceeded
from minio_tpu.utils.latency import Histogram

# Batch-dim padding buckets: one compiled device shape per bucket, not
# one per distinct concurrency level. Powers of two so every bucket
# divides evenly across a power-of-two chip mesh (ops/device
# mesh_batch_devices) with zero per-chip remainder shapes.
_BUCKETS = (8, 16, 32, 64, 128, 256)
# Base accumulation window (first window of a burst); the adaptive
# controller moves the live value between _MIN_WAIT_S and this.
_MAX_WAIT_S = 0.002
_MIN_WAIT_S = 0.00025
# Cap per dispatched device batch (VMEM/HBM bound upstream anyway).
_MAX_BATCH_BLOCKS = 256
# Stripe blocks per chip that saturate one chip's fused pipeline: the
# accumulation window stops waiting the moment the pending total can
# feed the whole mesh at this depth.
_PER_CHIP_BLOCKS = 32
# A member must dispatch at least this long before its deadline — the
# device round trip plus demux must fit in what remains.
_DEADLINE_SLACK_S = 0.005


def _bucket(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    return _BUCKETS[-1]


def _env_wait_s(route: str = "put") -> float:
    raw = os.environ.get("MTPU_BATCH_WAIT_MS", "")
    if route in ("get", "reconstruct"):
        raw = os.environ.get("MTPU_GET_BATCH_WAIT_MS", "") or raw
    try:
        return max(0.0, float(raw or 2.0)) / 1000.0
    except ValueError:
        return _MAX_WAIT_S


def batch_force_mode(route: str = "put") -> str:
    """The MTPU_BATCH_FORCE verdict for `route`: "device", "host", or
    "auto". A bare value pins every route; a comma list of
    `route=value` pairs pins each independently (the encode/decode
    small-fix: a host that wins on encode but loses on decode — or the
    reverse — must be forceable per direction, and the auto
    calibration already measures each route's own device_fn/host_fn
    rivalry)."""
    v = os.environ.get("MTPU_BATCH_FORCE", "auto").strip().lower()
    if "=" in v:
        out = "auto"
        for part in v.split(","):
            r, _, m = part.partition("=")
            if r.strip() == route and m.strip() in ("device", "host",
                                                    "auto"):
                out = m.strip()
        return out
    return v if v in ("device", "host") else "auto"


class DeviceRouteError(RuntimeError):
    """The device was asked for and this route's device function
    raised: the request fails instead of quietly riding the host."""


def _default_concat(rows, chunk):
    """Oversized-window splice for the PUT rows contract: per-drive
    lists of per-block piece tuples concatenate drive-wise."""
    return [r + c for r, c in zip(rows, chunk)]


class _Pending:
    __slots__ = ("stacked", "count", "rows", "exc", "event", "expires_at",
                 "tctx", "tparent", "t_enq", "route_taken")

    def __init__(self, stacked: np.ndarray,
                 dl: Optional[deadline_mod.Deadline]):
        self.stacked = stacked
        self.count = stacked.shape[0]
        self.rows = None
        self.exc: Optional[BaseException] = None
        self.event = threading.Event()
        self.expires_at = dl.expires_at if dl is not None else None
        self.tctx, self.tparent = tracing.capture() if tracing.ACTIVE \
            else (None, 0)
        self.t_enq = time.perf_counter()
        self.route_taken = "host"      # resolved by _finish


class _Batch:
    """One dispatch, from the members the dispatcher took to their
    release: what staging, the lane call and the finish hand on to each
    other, on whichever threads they run."""
    __slots__ = ("live", "total", "bucket", "route", "t_wall", "t0",
                 "lease", "stacked", "t_lane", "overlapped", "done")

    def __init__(self, live: list, total: int, bucket: int, route: str):
        self.live = live
        self.total = total
        self.bucket = bucket
        self.route = route
        self.t_wall = time.time()
        self.t0 = time.perf_counter()
        self.lease = None
        self.stacked = None
        self.t_lane = 0.0
        # Staging began while the dispatcher's previous batch was still
        # in the lane.
        self.overlapped = False
        # Set when the finish is over: the lane call has returned, the
        # staging lease is back and every member is released.
        self.done = threading.Event()


# Live batchers, for fleet-wide occupancy metrics (s3/metrics.py
# renders minio_tpu_batcher_* from aggregate_stats()).
_REGISTRY: "weakref.WeakSet[StripeBatcher]" = weakref.WeakSet()


ROUTES = ("put", "get", "reconstruct", "transform")


def _route_zero() -> dict:
    return {
        "dispatches": {"device": 0, "host": 0},
        "overlapped": 0,
        "requests": {"device": 0, "host": 0, "bypass": 0},
        "buckets": {},
        "batched_blocks": 0,
        "capacity_blocks": 0,
        "deadline_failures": 0,
        "wait_hist": None,
        "fill_ratio": 0.0,
    }


def aggregate_stats() -> dict:
    """Occupancy stats across every live batcher, summed PER ROUTE
    (put|get|reconstruct): dispatch/path/bucket counters, fill
    accounting, the coalescing wait histogram, deadline culls — plus
    the decode-route kernel-lane service histogram (the get/
    reconstruct dispatches' share of the shared accelerator lane)."""
    out = {
        "routes": {r: _route_zero() for r in ROUTES},
        "mesh_devices": 0,
        "forced": {r: batch_force_mode(r) for r in ROUTES},
        "decode_lane_hist": None,
        # One entry per live batcher: what its probe decided and why.
        "calibration": [],
    }
    hists: dict[str, list] = {r: [] for r in ROUTES}
    decode_lane = []
    for sb in list(_REGISTRY):
        st = sb.stats()
        route = st.get("route", "put")
        out["calibration"].append({"name": st["name"], "route": route,
                                   **st["calibration"]})
        agg = out["routes"].setdefault(route, _route_zero())
        for key in ("device", "host"):
            agg["dispatches"][key] += st["dispatches"][key]
        agg["overlapped"] += st["overlapped"]
        for key in ("device", "host", "bypass"):
            agg["requests"][key] += st["requests"][key]
        for b, v in st["buckets"].items():
            agg["buckets"][b] = agg["buckets"].get(b, 0) + v
        agg["batched_blocks"] += st["batched_blocks"]
        agg["capacity_blocks"] += st["capacity_blocks"]
        agg["deadline_failures"] += st["deadline_failures"]
        out["mesh_devices"] = max(out["mesh_devices"], st["mesh_devices"])
        hists.setdefault(route, []).append(st["wait_hist"])
        if route in ("get", "reconstruct"):
            decode_lane.append(st["lane_hist"])
    for r, agg in out["routes"].items():
        hs = hists.get(r, [])
        agg["wait_hist"] = Histogram.merge(hs) if hs \
            else Histogram().state()
        cap = agg["capacity_blocks"]
        agg["fill_ratio"] = (agg["batched_blocks"] / cap) if cap else 0.0
    out["decode_lane_hist"] = Histogram.merge(decode_lane) \
        if decode_lane else Histogram().state()
    return out


class StripeBatcher:
    """Coalesces concurrent frame() calls of one EC config.

    device_fn(stacked [B, k, L] u8) -> per-drive rows (the
    make_mesh_framer / make_encode_framer run() contract);
    host_fn(stacked) -> same rows via the host codec. Both must be
    thread-safe. `pool` (io/bufpool.BufferPool) backs the coalesced
    staging buffer — its lease is RETAINED for the whole dispatch, so a
    donated host buffer can never be recycled under an in-flight
    host->HBM transfer.
    """

    def __init__(self, device_fn: Callable, host_fn: Callable,
                 probe_fn: Optional[Callable] = None,
                 min_device_blocks: int = 8,
                 max_wait_s: Optional[float] = None,
                 pool=None, name: str = "", route: str = "put",
                 split_fn: Optional[Callable] = None,
                 concat_fn: Optional[Callable] = None):
        self._device_fn = device_fn
        self._host_fn = host_fn
        self._min_device_blocks = min_device_blocks
        self._max_wait = _env_wait_s(route) if max_wait_s is None \
            else max_wait_s
        self._cur_wait = self._max_wait
        self._pool = pool
        self.name = name
        self.route = route
        # split_fn(result, off, count, member_stacked) -> member result:
        # how one coalesced dispatch's output demultiplexes back to a
        # member (None = the PUT per-drive rows contract). concat_fn
        # splices chunked oversized-window results back together.
        self._split_fn = split_fn
        self._concat = concat_fn if concat_fn is not None \
            else _default_concat
        self.mesh_devices = max(1, int(getattr(device_fn, "mesh_devices",
                                               1) or 1))
        self._mu = threading.Condition()
        self._pending: list[_Pending] = []
        self._deadline = 0.0            # current window's dispatch-by time
        self._inflight = 0              # frame() calls currently active
        self._dispatcher: Optional[threading.Thread] = None
        # The dispatcher's last submitted batch: the next one is staged
        # while this one is in the lane, and submitted once it is back.
        self._in_lane: Optional[_Batch] = None
        self._closed = False
        # Calibration: None = unknown (host until probed), True/False.
        self._device_ok: Optional[bool] = None
        self._probe_fn = probe_fn
        self._probe_started = False
        # What the probe measured: (device_s, host_s) once it ran, and
        # the exception when the device function raised instead.
        self._probe_times: Optional[tuple[float, float]] = None
        self.probe_error: Optional[BaseException] = None
        forced = batch_force_mode(route)
        # Pinned = the verdict came from MTPU_BATCH_FORCE or force(),
        # not from a probe.
        self._pinned = forced != "auto"
        if self._pinned:
            self._probe_started = True
            self._device_ok = forced == "device"
        # Occupancy stats (own lock: the dispatcher holds _mu at the
        # moments hot paths want to count).
        self._stat_mu = threading.Lock()
        self._dispatches = {"device": 0, "host": 0}
        # Device dispatches whose staging began while the batch before
        # was still in the lane.
        self._overlapped = 0
        self._requests = {"device": 0, "host": 0, "bypass": 0}
        # Calibrated-host bypass count: bumped WITHOUT _stat_mu on the
        # zero-overhead pass-through, folded into stats() reads.
        self._bypass_approx = 0
        self._bucket_dispatches: dict[int, int] = {}
        self._batched_blocks = 0
        self._capacity_blocks = 0
        self._deadline_failures = 0
        self._wait_hist = Histogram()
        # Per-calling-thread record of the last frame() dispatch path
        # (device|host|bypass): callers with their own fused host
        # kernel read last_route() to keep path metrics honest — a
        # coalesced batch below min_device_blocks resolves to the host
        # fallback even under a device calibration, and that must not
        # be counted as a device window.
        self._local = threading.local()
        # Kernel-lane service time of this batcher's device dispatches
        # (submit-to-result through io/engine.kernel_lane). For decode
        # routes this is the read path's share of the shared
        # accelerator — exported as the decode-route lane histogram.
        self._lane_hist = Histogram()
        _REGISTRY.add(self)

    # -- calibration ----------------------------------------------------

    def _default_probe(self, sample: np.ndarray) -> bool:
        """Time device vs host on one representative batch (the first
        request's config, widened to a device-worthy block count);
        True when the device round trip wins. A device function that
        raises propagates — that is a fault, not a slow device."""
        stacked = np.zeros(
            (_bucket(max(self._min_device_blocks, self.mesh_devices)),)
            + sample.shape[1:], dtype=np.uint8)
        with device.batch_of(0):               # zeros: all padding
            self._device_fn(stacked)           # compile
            t0 = time.perf_counter()
            self._device_fn(stacked)
            t_dev = time.perf_counter() - t0
        t0 = time.perf_counter()
        self._host_fn(stacked)
        t_host = time.perf_counter() - t0
        self._probe_times = (t_dev, t_host)
        return t_dev < t_host

    def _ensure_probe(self, sample: np.ndarray) -> None:
        with self._mu:
            # Check-and-set under the lock: two first-users racing here
            # would otherwise run two probes whose device/host timings
            # pollute each other.
            if self._probe_started:
                return
            self._probe_started = True

        def probe():
            err = None
            try:
                if self._probe_fn is not None:
                    ok = bool(self._probe_fn())
                else:
                    ok = self._default_probe(sample)
            except Exception as e:  # noqa: BLE001 - thread boundary
                device.record_fault(f"probe:{self.route}", e)
                ok, err = False, e
            with self._mu:
                self.probe_error = err
                self._device_ok = ok

        # Non-daemon: a daemon probe mid-device-call at interpreter
        # exit aborts the process from inside the runtime (terminate
        # without rethrow); joining at exit costs at most one compile.
        threading.Thread(target=probe, daemon=False,
                         name="stripe-batcher-probe").start()

    def _check_probe_fault(self) -> None:
        """A probe that raised resolves to host only where nobody asked
        for the device; where the operator did, every window that would
        have consulted this route fails with the cause attached."""
        err = self.probe_error
        if err is not None and device.required():
            raise DeviceRouteError(
                f"{self.route} route {self.name!r}: device function "
                f"raised during calibration: {type(err).__name__}: "
                f"{err}") from err

    def wants_device(self) -> bool:
        """False only once calibration has RESOLVED to host — the
        caller can then skip the batcher entirely (its own host path is
        at least as good, without the queue/lock hop). Unprobed (None)
        answers True so traffic keeps flowing through frame() until the
        probe settles."""
        self._check_probe_fault()
        return self._device_ok is not False

    def worth_batching(self, blocks: int) -> bool:
        """True when frame(`blocks`) could plausibly take the device
        route RIGHT NOW: calibration has not resolved to host, and
        either the window alone is device-sized or other requests are
        in flight to coalesce with. Callers with a fused native host
        kernel of their own (the GET window's mtpu_get_frame) consult
        this before stacking a member — a solo sub-threshold window
        should ride the native kernel, not the batcher's generic host
        fallback."""
        if self._device_ok is False:
            self._check_probe_fault()
            return False
        return blocks >= self._min_device_blocks or self._inflight > 0 \
            or bool(self._pending)

    def force(self, device_ok: bool) -> None:
        """Pin the calibration verdict (tests): no probe runs,
        dispatch follows `device_ok` unconditionally. The env knob
        MTPU_BATCH_FORCE=device|host applies the same pin at
        construction (a slow-link probe must not silently turn a
        test of the device route into pass-through)."""
        with self._mu:
            self._probe_started = True
            self._pinned = True
            self.probe_error = None
            self._device_ok = bool(device_ok)

    def reset_calibration(self) -> None:
        """Back to the configured default (tests' cleanup after
        force()): unprobed under auto, re-pinned under a
        MTPU_BATCH_FORCE override."""
        with self._mu:
            self.probe_error = None
            self._probe_times = None
            forced = batch_force_mode(self.route)
            self._pinned = forced != "auto"
            if forced != "auto":
                self._probe_started = True
                self._device_ok = forced == "device"
            else:
                self._probe_started = False
                self._device_ok = None

    # -- observability --------------------------------------------------

    def calibration(self) -> dict:
        """The route's verdict and what it rests on: "device"/"host"
        from a timed probe (times attached), "raised" when the device
        function threw, "forced-*" under a pin, "probing" while the
        probe runs, "unprobed" before any device-worthy traffic."""
        forced = batch_force_mode(self.route)
        ok, err, times = self._device_ok, self.probe_error, self._probe_times
        if err is not None:
            verdict = "raised"
        elif ok is None:
            verdict = "probing" if self._probe_started else "unprobed"
        else:
            verdict = ("forced-" if self._pinned else "") \
                + ("device" if ok else "host")
        out = {"verdict": verdict, "pin": forced}
        if times is not None:
            out["device_ms"] = round(times[0] * 1e3, 3)
            out["host_ms"] = round(times[1] * 1e3, 3)
        if err is not None:
            out["error"] = f"{type(err).__name__}: {err}"[:300]
        return out

    def stats(self) -> dict:
        with self._stat_mu:
            requests = dict(self._requests)
            requests["bypass"] += self._bypass_approx
            return {
                "name": self.name,
                "route": self.route,
                "mesh_devices": self.mesh_devices,
                "dispatches": dict(self._dispatches),
                "overlapped": self._overlapped,
                "requests": requests,
                "buckets": dict(self._bucket_dispatches),
                "batched_blocks": self._batched_blocks,
                "capacity_blocks": self._capacity_blocks,
                "deadline_failures": self._deadline_failures,
                "wait_hist": self._wait_hist.state(),
                "lane_hist": self._lane_hist.state(),
                "window_s": self._cur_wait,
                "calibration": self.calibration(),
            }

    def _note_request(self, route: str, n: int = 1) -> None:
        with self._stat_mu:
            self._requests[route] += n

    # -- submission -----------------------------------------------------

    def frame(self, stacked: np.ndarray):
        """Frame one request's stripe window [B, k, L]; blocks until
        the (possibly coalesced) result is ready. Returns per-drive
        rows for exactly this window's blocks. Raises DeadlineExceeded
        without touching the device when the caller's budget is
        already spent."""
        if self._device_ok is False:
            # Calibration resolved to host: genuinely free pass-through
            # — no lock, no inflight bookkeeping, no condition-variable
            # hop, just the host codec (the unlocked read is safe: the
            # verdict transitions once, None -> True/False). The counter
            # bump is unlocked too — approximate under races, and the
            # only shared state this path touches.
            self._check_probe_fault()
            self._bypass_approx += 1
            self._local.route = "bypass"
            return self._host_fn(stacked)
        if stacked.shape[0] > _MAX_BATCH_BLOCKS:
            # An oversized window (whole-part framing of a huge
            # multipart/copy part can exceed the largest padding
            # bucket) must never reach _stage as one pending — the
            # staging buffer is at most _BUCKETS[-1] rows, and a mesh
            # dispatch needs a divisible batch. Dispatch bucket-sized
            # chunks through the same path (each rides the device or
            # host route on its own merits) and splice the per-drive
            # rows back together.
            rows = None
            routes = set()
            for off in range(0, stacked.shape[0], _MAX_BATCH_BLOCKS):
                chunk = self.frame(stacked[off:off + _MAX_BATCH_BLOCKS])
                routes.add(self.last_route())
                rows = chunk if rows is None else self._concat(rows, chunk)
            self._local.route = "device" if "device" in routes \
                else routes.pop()
            return rows
        dl = deadline_mod.current()
        if dl is not None and dl.expired():
            with self._stat_mu:
                self._deadline_failures += 1
            raise DeadlineExceeded("request deadline exceeded")
        big = stacked.shape[0] >= self._min_device_blocks
        with self._mu:
            self._inflight += 1
            solo = self._inflight == 1 and not self._pending
        try:
            if big or not solo:
                # Worth calibrating: either this window alone is
                # device-sized, or there is company to coalesce with.
                # (A lone small PUT never probes — the probe's device
                # compile would steal host CPU from a workload that is
                # not even a batching candidate.)
                self._ensure_probe(stacked)
            if solo:
                if big and self._device_ok:
                    # A single device-sized window (e.g. a streaming
                    # PUT's 32-block window) needs no queue — dispatch
                    # straight through the shared batch path (same
                    # staging, padding buckets, kernel lane, tracing).
                    p = _Pending(stacked, dl)
                    self._run_batch([p])
                    self._local.route = p.route_taken
                    if p.exc is not None:
                        raise p.exc
                    return p.rows
                self._note_request("bypass")
                self._local.route = "bypass"
                return self._host_fn(stacked)
            if self._device_ok is not True:
                self._note_request("host")
                self._local.route = "host"
                return self._host_fn(stacked)
            return self._enqueue(stacked, dl)
        finally:
            with self._mu:
                self._inflight -= 1

    def _enqueue(self, stacked: np.ndarray, dl):
        p = _Pending(stacked, dl)
        with self._mu:
            if not self._pending:
                self._deadline = time.monotonic() + self._cur_wait
            self._pending.append(p)
            # _dispatcher is cleared (under this lock) by the loop
            # BEFORE it exits, so is_alive() can never claim a thread
            # that has already decided to die with our entry unseen.
            if self._dispatcher is None or not self._dispatcher.is_alive():
                self._dispatcher = threading.Thread(
                    target=self._dispatch_loop, daemon=True,
                    name="stripe-batcher")
                self._dispatcher.start()
            # Always wake the dispatcher: if it is parked in its idle
            # 0.2 s poll, an un-notified append would stretch the
            # coalescing window into a 200 ms latency spike.
            self._mu.notify_all()
        p.event.wait()
        self._local.route = p.route_taken
        if p.exc is not None:
            raise p.exc
        return p.rows

    def last_route(self) -> str:
        """The dispatch path the CALLING thread's last frame() took:
        "device" (rode a device dispatch), "host" (served by the host
        fallback — calibration unresolved, or a coalesced batch below
        min_device_blocks), or "bypass" (calibrated-host pass-through
        / lone small window). Callers with a fused native kernel of
        their own use this to label path metrics honestly."""
        return getattr(self._local, "route", "host")

    # -- dispatch -------------------------------------------------------

    def _fill_target(self) -> int:
        """Pending blocks that saturate the mesh: stop accumulating
        the moment one dispatch can feed every chip at working depth."""
        return min(_MAX_BATCH_BLOCKS,
                   max(self._min_device_blocks,
                       self.mesh_devices * _PER_CHIP_BLOCKS))

    def _adapt_window(self, fill_ratio: float) -> None:
        """Depth-aware accumulation: buckets dispatching full mean the
        burst outruns the window — stretch it (more coalescing per
        compile is paying for itself); sparse dispatches mean waiting
        only adds latency — shrink toward pass-through."""
        if fill_ratio >= 0.75:
            self._cur_wait = min(self._max_wait, self._cur_wait * 1.5)
        elif fill_ratio < 0.25:
            self._cur_wait = max(_MIN_WAIT_S, self._cur_wait * 0.5)

    def _dispatch_loop(self) -> None:
        # The dispatcher never waits for the lane: it stages a batch,
        # submits it and comes back for the next, and its finisher
        # (alive as long as it is) releases each batch's members when
        # that batch's rows are back.
        finishes: queue_mod.SimpleQueue = queue_mod.SimpleQueue()
        threading.Thread(target=self._finish_loop, args=(finishes,),
                         daemon=True,
                         name="stripe-batcher-finish").start()
        try:
            self._dispatch_batches(finishes)
        finally:
            finishes.put(None)

    def _dispatch_batches(self, finishes: queue_mod.SimpleQueue) -> None:
        while True:
            with self._mu:
                while not self._pending and not self._closed:
                    self._mu.wait(timeout=0.2)
                    if not self._pending and self._inflight == 0:
                        # Idle: clear the handle BEFORE dying (still
                        # under the lock) so a racing _enqueue starts
                        # a fresh dispatcher instead of trusting a
                        # thread that will never look again.
                        self._dispatcher = None
                        return
                if self._closed and not self._pending:
                    self._dispatcher = None
                    return
                now = time.monotonic()
                total = sum(p.count for p in self._pending)
                # The window closes at the adaptive deadline, when the
                # mesh can be fed at full depth, or in time for the
                # EARLIEST member deadline — a coalesced batch must
                # respect the most impatient request riding it.
                bound = self._deadline
                expiries = [p.expires_at for p in self._pending
                            if p.expires_at is not None]
                if expiries:
                    bound = min(bound, min(expiries) - _DEADLINE_SLACK_S)
                if total < self._fill_target() and now < bound \
                        and not self._closed:
                    self._mu.wait(timeout=bound - now)
                    continue
                # Drain at most one bucket's worth per dispatch; the
                # remainder keeps its place for the next round (an
                # unbounded drain could exceed the largest pad bucket).
                batch, rest = [], []
                taken = 0
                for p in self._pending:
                    c = p.count
                    if batch and (taken + c > _MAX_BATCH_BLOCKS
                                  or p.stacked.shape[1:]
                                  != batch[0].stacked.shape[1:]):
                        # Over the bucket cap, or a DIFFERENT member
                        # geometry (heal verifies of mixed EC configs
                        # share one route batcher): staging copies
                        # members into one [bucket, *trail] buffer, so
                        # a batch is one trailing shape — the rest
                        # keeps its place for the next round.
                        rest.append(p)
                    else:
                        batch.append(p)
                        taken += c
                self._pending = rest
                if rest:
                    self._deadline = now      # no extra wait for them
            self._dispatch(batch, finishes)

    def _stage(self, live: list[_Pending], bucket: int):
        """(lease, stacked [bucket, k, L]): members copied into ONE
        pooled staging buffer, zero-padded to the bucket. Pooled
        whatever the bucket: the lease's size alone picks the pool's
        class (io/bufpool keeps two idle buffers of the classes a
        large dispatch needs), so from its third dispatch on a loaded
        batcher writes into pages that are already resident. The lease
        is held by the caller for the whole dispatch — donation safety:
        the buffer the device is still reading can never be recycled
        into a new lease mid-transfer. Returns (None, member array)
        when a lone member already fills the bucket exactly."""
        if len(live) == 1 and live[0].count == bucket:
            return None, live[0].stacked
        shape = (bucket,) + live[0].stacked.shape[1:]
        lease = None
        stacked = None
        if self._pool is not None:
            try:
                lease = self._pool.lease(int(np.prod(shape)))
                stacked = lease.ndarray(shape)
            except Exception:  # noqa: BLE001 - pool pressure -> fresh
                lease = None
        if stacked is None:
            stacked = np.empty(shape, dtype=np.uint8)
        off = 0
        for p in live:
            stacked[off:off + p.count] = p.stacked
            off += p.count
        if off < bucket:
            # Zero the pad rows: a recycled pool buffer carries stale
            # bytes, and deterministic pads keep batched output
            # byte-stable run to run (the pad rows' parity/digests are
            # sliced off either way).
            stacked[off:] = 0
        return lease, stacked

    def _launch(self, b: _Batch, prev: Optional[_Batch] = None) -> Future:
        """Stage `b` and hand its device call to the process-wide kernel
        lane (serialized device access + wait/service attribution; a
        direct call if the lane is saturated or closed). The Future
        holds the device rows, or whatever raised on the way there,
        the copy included. `prev` is the dispatcher's previous batch:
        its lane call must have returned before this one is submitted,
        so one batch of this batcher is in the lane and one being
        staged, never more — two staging buffers alive at most, and
        `_lane_hist` never times a wait behind our own batch."""
        fut: Future = Future()
        try:
            with tracing.stage("batcher.stage", type_="kernel"):
                b.lease, b.stacked = self._stage(b.live, b.bucket)
            if prev is not None:
                with tracing.stage("batcher.lane_wait", type_="kernel"):
                    prev.done.wait()

            def call():
                # The first `total` rows are the members', the rest
                # bucket padding: said to the device function on the
                # thread that calls it (ops/device.batch_of).
                with device.batch_of(b.total):
                    return self._device_fn(b.stacked)
            b.t_lane = time.perf_counter()
            try:
                return kernel_lane().submit(call)
            except EngineSaturated:
                fut.set_result(call())
        except BaseException as e:  # noqa: BLE001 - _finish delivers it
            fut.set_exception(e)
        return fut

    def _open(self, batch: list[_Pending]) -> Optional[_Batch]:
        """The batch's live members with its bucket and route; None when
        nobody is left to serve."""
        # Cull members whose budget is already spent: they fail ALONE
        # (DeadlineExceeded, counted) and never poison batch-mates —
        # the dispatch proceeds without them.
        now = time.monotonic()
        live, dead = [], []
        for p in batch:
            if p.expires_at is not None \
                    and now >= p.expires_at - 1e-9:
                dead.append(p)
            else:
                live.append(p)
        if dead:
            with self._stat_mu:
                self._deadline_failures += len(dead)
            for p in dead:
                p.exc = DeadlineExceeded(
                    "request deadline exceeded before batch dispatch")
                p.event.set()
        if not live:
            return None
        total = sum(p.count for p in live)
        # Never pick a bucket narrower than the mesh: the device run()
        # requires batch % mesh_devices == 0, and small dispatches on a
        # wide mesh (e.g. 8 blocks across 16 chips) would otherwise
        # fail every batch member.
        bucket = _bucket(max(total, self.mesh_devices))
        route = "device" if total >= self._min_device_blocks \
            and self._device_ok else "host"
        return _Batch(live, total, bucket, route)

    def _run_batch(self, batch: list[_Pending]) -> None:
        """One batch from start to finish on the calling thread (a lone
        window of frame())."""
        b = self._open(batch)
        if b is not None:
            fut = self._launch(b) if b.route == "device" else None
            self._finish(b, fut)

    def _dispatch(self, batch: list[_Pending],
                  finishes: queue_mod.SimpleQueue) -> None:
        """The dispatcher's share of one batch: stage it while the
        previous one is in the lane, submit it, and go back for the
        next — the finish runs on the finisher when the rows are back."""
        b = self._open(batch)
        if b is None:
            return
        if b.route != "device":
            self._finish(b, None)
            return
        prev = self._in_lane
        b.overlapped = prev is not None and not prev.done.is_set()
        fut = self._launch(b, prev)
        self._in_lane = b
        finishes.put((b, fut))

    def _finish_loop(self, finishes: queue_mod.SimpleQueue) -> None:
        """Finish the dispatcher's batches in the order their lane calls
        return (the lane is one FIFO worker), so members leave the
        moment their rows are back and not after the next batch's copy."""
        while True:
            item = finishes.get()
            if item is None:
                return
            self._finish(*item)
            # The Future's rows view the batch's staging buffer: not to
            # be kept alive while this thread waits for the next batch.
            del item

    def _finish(self, b: _Batch, fut: Optional[Future]) -> None:
        """Wait for the batch's lane call (`fut`; None on the host
        route), then, as the stage `batcher.finish` (from the rows'
        return to `done`: what the dispatcher's next submit waits for
        beyond the lane), give the staging buffer back, demultiplex
        the rows to the members, count, and release them."""
        live, total, bucket, route = b.live, b.total, b.bucket, b.route
        counts = [p.count for p in live]
        rows_all = failed = None
        if fut is not None:
            try:
                rows_all = fut.result()
            except BaseException as e:  # noqa: BLE001 - delivered below
                failed = e
        with tracing.stage("batcher.finish", type_="kernel", cpu=False):
            try:
                if fut is not None:
                    # The dispatch is synchronous through the readback
                    # (the framer returns host numpy), so the staging
                    # buffer is done feeding HBM here — and not before.
                    if b.t_lane:
                        self._lane_hist.observe(
                            time.perf_counter() - b.t_lane)
                    # Let go of the buffer here too: `_in_lane` keeps
                    # the batch until the next one is submitted, and the
                    # lease goes back to the pool now, for the batch
                    # after next to stage into.
                    lease, b.lease, b.stacked = b.lease, None, None
                    if lease is not None:
                        lease.release()
                    if failed is not None:
                        raise failed
                    if self._split_fn is not None:
                        # Route-specific demux (get: verdict slices + data
                        # views of the member's OWN window; reconstruct:
                        # rebuilt-row slices).
                        off = 0
                        for p, c in zip(live, counts):
                            p.rows = self._split_fn(rows_all, off, c,
                                                    p.stacked)
                            off += c
                    else:
                        k = live[0].stacked.shape[1]
                        staged = lease is not None or len(live) > 1
                        off = 0
                        for p, c in zip(live, counts):
                            rows = [drive[off:off + c] for drive in rows_all]
                            if staged:
                                # Demultiplex data drives back onto each
                                # member's OWN window: device rows view the
                                # shared staging buffer whose lease just
                                # returned to the pool; digests/parity are
                                # fresh device output and stay as-is.
                                for i in range(k):
                                    rows[i] = [(dig, p.stacked[bi, i])
                                               for bi, (dig, _blk)
                                               in enumerate(rows[i])]
                            p.rows = rows
                            off += c
                    with self._stat_mu:
                        self._dispatches["device"] += 1
                        self._overlapped += b.overlapped
                        self._requests["device"] += len(live)
                        self._bucket_dispatches[bucket] = \
                            self._bucket_dispatches.get(bucket, 0) + 1
                        self._batched_blocks += total
                        self._capacity_blocks += bucket
                    self._adapt_window(total / bucket)
                else:
                    for p in live:
                        p.rows = self._host_fn(p.stacked)
                    with self._stat_mu:
                        self._dispatches["host"] += 1
                        self._requests["host"] += len(live)
                    # Host-routed dispatches are the sparse case (total
                    # below min_device_blocks) — adapt here too, or light
                    # steady traffic pins _cur_wait at whatever a past
                    # burst stretched it to and every small PUT pays the
                    # full window forever.
                    self._adapt_window(total / bucket)
            except BaseException as e:  # noqa: BLE001 - deliver to waiters
                if route == "device" and isinstance(e, Exception):
                    device.record_fault(f"dispatch:{self.route}", e)
                for p in live:
                    p.exc = e
            finally:
                dur_ms = (time.perf_counter() - b.t0) * 1000.0
                for p in live:
                    p.route_taken = route
                    wait_s = max(0.0, b.t0 - p.t_enq)
                    self._wait_hist.observe(wait_s)
                    if p.tctx is not None:
                        # ONE kernel span fanned into each member's tree.
                        tracing.record_into(
                            p.tctx, p.tparent, "kernel", "batcher.dispatch",
                            b.t_wall, dur_ms,
                            tags={"blocks": p.count, "batch_blocks": total,
                                  "bucket": bucket, "members": len(live),
                                  "route": route,
                                  "mesh_devices": self.mesh_devices,
                                  "wait_ms": round(wait_s * 1000.0, 3)})
                    p.event.set()
                b.done.set()

    def close(self) -> None:
        """Stop accumulating: what is pending dispatches at once, and
        the call returns when the dispatcher has gone and its last
        batch has delivered."""
        with self._mu:
            self._closed = True
            self._mu.notify_all()
            dispatcher = self._dispatcher
        if dispatcher is not None:
            dispatcher.join()
        last = self._in_lane
        if last is not None:
            last.done.wait()

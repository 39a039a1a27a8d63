"""Drive health wrapper: latency tracking, op deadlines, circuit breaker.

The analogue of the reference's xlStorageDiskIDCheck wrapper
(cmd/xl-storage-disk-id-check.go): every StorageAPI call is timed and
deadline-bounded, consecutive infrastructure faults (timeouts, I/O
errors) trip a breaker that fails calls FAST while the drive is
considered offline, and a half-open probe re-admits it after a
cooldown. Quorum fan-outs over wrapped drives therefore stay bounded in
latency even when a drive hangs rather than dies — the failure mode
plain error handling never catches.

Domain errors (missing files/volumes, corrupt journals) are the
storage layer working CORRECTLY and never count against the drive.

Every call runs off the caller's thread, so that the caller can give up
on it at its deadline and leave it to finish unobserved. It is handed to
a worker at once — an idle one, or a new one (_DaemonPool) — and never
queues for one: a call that legitimately holds its worker for seconds
(the streaming PUT's create_file, parked on its request's next window)
must not stand in front of another request's rename_data or stat_vol.
No fixed number bounds the workers. The calls in flight do: the request
admission above the object layer bounds those on a healthy drive, and
on a hung one the breaker does — trip_after timed-out calls open it, and
from then on _admit() fails fast before any worker is asked for.
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout

from minio_tpu.io.engine import IDLE_EXIT_S
from minio_tpu.storage.local import (DiskAccessDenied, FaultyDisk,
                                     VolumeExists, VolumeNotEmpty,
                                     VolumeNotFound)
from minio_tpu.storage.meta import (FileNotFoundErr, MetaError,
                                    VersionNotFoundErr)
from minio_tpu.utils import deadline as deadline_mod
from minio_tpu.utils import tracing
from minio_tpu.utils.deadline import DeadlineExceeded

# Errors that mean "the drive answered correctly" — never breaker fuel.
# The BUILTIN FileNotFoundError is deliberately absent: LocalStorage
# converts every ordinary missing-object case to FileNotFoundErr, so a
# raw one means the drive root itself vanished (unmounted disk) — the
# reference maps that to disk-not-found, and so does this breaker.
_DOMAIN_ERRORS = (FileNotFoundErr, VersionNotFoundErr, MetaError,
                  VolumeNotFound, VolumeExists, VolumeNotEmpty,
                  DiskAccessDenied, IsADirectoryError,
                  NotADirectoryError, ValueError, KeyError)

# Bulk transfer ops get a longer deadline than metadata ops.
# commit_group is bulk: one call commits a whole coalesced batch (many
# members' journals + one WAL fsync) and must not be clipped by the
# single-op metadata timeout.
_BULK_OPS = {"create_file", "read_file", "rename_data", "commit_group"}
# Ops returning lazy iterators: each next() must go through the
# deadline/breaker machinery, not just the (instant) generator creation.
_GENERATOR_OPS = {"walk_dir", "walk_scan"}


class _CallStats:
    """Process-wide totals of the pools below, all drives together:
    what `minio_tpu_drive_call_*` exports (s3/metrics.py)."""

    def __init__(self):
        self._mu = threading.Lock()
        self.wait_seconds = 0.0     # submit -> worker's first instruction
        self.calls = 0
        self.workers = 0            # alive now
        self.workers_started = 0

    def worker(self, delta: int) -> None:
        with self._mu:
            self.workers += delta
            if delta > 0:
                self.workers_started += delta

    def waited(self, seconds: float) -> None:
        with self._mu:
            self.wait_seconds += seconds
            self.calls += 1

    def snapshot(self) -> dict:
        with self._mu:
            return {"wait_seconds": self.wait_seconds, "calls": self.calls,
                    "workers": self.workers,
                    "workers_started": self.workers_started}


CALL_STATS = _CallStats()


class _DaemonPool:
    """Executor whose jobs never wait for a worker: `submit` hands the
    job to an idle worker if there is one, else starts one; a worker
    idle for IDLE_EXIT_S exits. So the workers alive equal the calls in
    flight (plus a short idle tail), and a call parked in its worker for
    seconds — a streaming create_file waiting for its request's next
    window — delays nobody else's rename or lookup.

    Workers are DAEMON threads: a call hung on dead storage must never
    block interpreter shutdown (ThreadPoolExecutor joins its workers at
    exit)."""

    def __init__(self):
        self._q: "queue_mod.SimpleQueue" = queue_mod.SimpleQueue()
        self._mu = threading.Lock()
        self._idle = 0      # workers in get() that no submit has claimed
        self._closed = False

    def submit(self, fn, *args, **kwargs) -> Future:
        f: Future = Future()
        job = (f, fn, args, kwargs, time.perf_counter())
        with self._mu:
            claimed = self._idle > 0
            if claimed:
                self._idle -= 1
        if claimed:
            self._q.put(job)
            return f
        try:
            # The new worker takes this job as its first: nothing that
            # is already waiting on the queue can take it instead.
            threading.Thread(target=self._work, args=(job,), daemon=True,
                             name="drive-call").start()
        except RuntimeError as e:   # the process is out of threads
            f.set_exception(e)
        return f

    def _work(self, job) -> None:
        CALL_STATS.worker(+1)
        try:
            while job is not None:
                self._run(job)
                job = self._next()
        finally:
            CALL_STATS.worker(-1)

    @staticmethod
    def _run(job) -> None:
        f, fn, args, kwargs, t_sub = job
        CALL_STATS.waited(time.perf_counter() - t_sub)
        if not f.set_running_or_notify_cancel():
            return
        try:
            f.set_result(fn(*args, **kwargs))
        except BaseException as e:  # noqa: BLE001 - ferried to caller
            f.set_exception(e)

    def _next(self):
        """The next job, or None when this worker should exit (closed,
        or idle for IDLE_EXIT_S with no submit having claimed it)."""
        with self._mu:
            if self._closed:
                return None
            self._idle += 1
        while True:
            try:
                return self._q.get(timeout=IDLE_EXIT_S)
            except queue_mod.Empty:
                with self._mu:
                    if self._idle > 0:
                        self._idle -= 1
                        return None
                # Every waiting worker is claimed, this one included:
                # a job is on its way to the queue.

    def shutdown(self) -> None:
        with self._mu:
            self._closed = True
            idle, self._idle = self._idle, 0
        for _ in range(idle):
            self._q.put(None)


class DiskHealthWrapper:
    """Wraps any StorageAPI-shaped drive with deadlines + a breaker.

    op_timeout / bulk_timeout: per-call deadlines (seconds).
    trip_after: consecutive faults that open the breaker.
    cooldown: seconds the breaker stays open before a half-open probe.
    """

    def __init__(self, disk, op_timeout: float = 10.0,
                 bulk_timeout: float = 120.0, trip_after: int = 3,
                 cooldown: float = 5.0):
        self._disk = disk
        self._op_timeout = op_timeout
        self._bulk_timeout = bulk_timeout
        self._trip_after = trip_after
        self._cooldown = cooldown
        self._mu = threading.Lock()
        self._consecutive = 0
        self._open_since: float = 0.0     # 0 = closed
        self._half_open_probe = False
        # Consecutive budget-clamped expiries with a GENEROUS window
        # (see _SUSPICION_WINDOW): ambiguous individually, but a drive
        # that repeatedly cannot answer inside whole seconds is hung —
        # without this, any request deadline shorter than the op
        # timeout would classify every expiry as "the request's
        # problem" and a dead drive could never trip the breaker.
        self._clamped_streak = 0
        # op -> [count, errors, total_seconds]; small and bounded.
        self.op_stats: dict[str, list] = {}
        # A hung call keeps its worker until it returns, and the pool
        # starts another for the next call: the breaker, not the pool,
        # bounds the threads on a hung drive (trip_after faults open it
        # and _admit() fails fast; module docstring).
        self._pool = _DaemonPool()

    # -- introspection ---------------------------------------------------

    @property
    def wrapped(self):
        return self._disk

    @property
    def endpoint(self):
        return getattr(self._disk, "endpoint", "")

    @property
    def root(self):
        return getattr(self._disk, "root", None)

    def is_online(self) -> bool:
        with self._mu:
            return self._open_since == 0.0

    def health_info(self) -> dict:
        with self._mu:
            return {
                "online": self._open_since == 0.0,
                "consecutive_faults": self._consecutive,
                "ops": {op: {"count": s[0], "errors": s[1],
                             "avg_ms": round(1000 * s[2] / s[0], 3)
                             if s[0] else 0.0}
                        for op, s in self.op_stats.items()},
            }

    # -- call path -------------------------------------------------------

    def _admit(self) -> None:
        """Fail fast while the breaker is open; let one probe through
        after the cooldown (half-open)."""
        with self._mu:
            if self._open_since == 0.0:
                return
            if time.monotonic() - self._open_since < self._cooldown:
                raise FaultyDisk(f"drive {self.endpoint}: breaker open")
            if self._half_open_probe:
                raise FaultyDisk(
                    f"drive {self.endpoint}: breaker half-open, probing")
            self._half_open_probe = True

    def _record(self, op: str, seconds: float, failed: bool) -> None:
        with self._mu:
            s = self.op_stats.setdefault(op, [0, 0, 0.0])
            s[0] += 1
            s[1] += 1 if failed else 0
            s[2] += seconds

    def _fault(self) -> None:
        with self._mu:
            self._consecutive += 1
            self._half_open_probe = False
            if self._open_since != 0.0:
                # Failed half-open probe: restart the cooldown, or every
                # request after the first expiry would become a probe
                # and eat the full op timeout.
                self._open_since = time.monotonic()
            elif self._consecutive >= self._trip_after:
                self._open_since = time.monotonic()

    def _ok(self) -> None:
        with self._mu:
            self._consecutive = 0
            self._clamped_streak = 0
            self._open_since = 0.0
            self._half_open_probe = False

    # Clamped expiries only count toward suspicion when the drive had
    # at least this long to answer — a request with 50 ms left proves
    # nothing, but whole seconds of silence repeated trip_after times
    # in a row does.
    _SUSPICION_WINDOW = 1.0

    def _clamped_expiry(self, window: float) -> None:
        """A budget-clamped op expiry: release the probe slot, and
        accumulate generous-window expiries; a full streak is treated
        as a real fault episode and opens the breaker outright."""
        with self._mu:
            self._half_open_probe = False
            if window < self._SUSPICION_WINDOW:
                return
            self._clamped_streak += 1
            if self._clamped_streak >= self._trip_after:
                self._clamped_streak = 0
                self._consecutive = max(self._consecutive + 1,
                                        self._trip_after)
                self._open_since = time.monotonic()

    def _probe_inconclusive(self) -> None:
        """A half-open probe that ended for REQUEST reasons (deadline
        budget) proved nothing about the drive: release the probe slot
        so the next caller can probe, without touching fault state —
        otherwise the flag wedges and the drive stays offline forever."""
        with self._mu:
            self._half_open_probe = False

    def _call(self, op: str, fn, args, kwargs):
        # Every storage op becomes one span (drive + op name) — the
        # per-drive attribution layer of the trace tree — and one
        # annotation on the profiler's clock. The span covers admit +
        # the hand-off to a worker + the op itself; the engine-level
        # span above it carries the queue-wait split. No stage counter:
        # drive_op_duration_seconds already counts the op.
        tags = {"drive": str(self.endpoint or self.root or "")} \
            if tracing.ACTIVE else None
        with tracing.stage(f"disk.{op}", tags, type_="storage",
                           count=False):
            return self._call_inner(op, fn, args, kwargs)

    def _call_inner(self, op: str, fn, args, kwargs):
        # Deadline pre-check BEFORE _admit(): an already-exhausted
        # request must not consume the breaker's half-open probe slot.
        dl = deadline_mod.current()
        if dl is not None and dl.expired():
            raise DeadlineExceeded(
                f"request deadline exceeded before {op} on "
                f"{self.endpoint}")
        self._admit()
        base = self._bulk_timeout if op in _BULK_OPS else self._op_timeout
        # Clamp the op deadline to the REQUEST's remaining budget
        # (utils/deadline.py): a request with 200 ms left must not wait
        # a full op timeout on this drive. A single clamped expiry is
        # the request running out of time, not breaker fuel; only a
        # generous-window streak becomes suspicion (_clamped_expiry).
        timeout = base
        if dl is not None:
            timeout = min(base, dl.remaining())
        t0 = time.monotonic()
        tctx, tparent = tracing.capture() if tracing.ACTIVE else (None, 0)
        if dl is None and tctx is None:
            fut: Future = self._pool.submit(fn, *args, **kwargs)
        else:
            # Re-bind the budget (and the trace scope) inside the pool
            # worker so nested layers (remote drives -> grid calls)
            # keep consuming it / parenting under this op's span.
            def run(_dl=dl, _tc=tctx, _tp=tparent):
                with deadline_mod.bind(_dl), tracing.bind(_tc, _tp):
                    return fn(*args, **kwargs)
            fut = self._pool.submit(run)
        try:
            result = fut.result(timeout=timeout)
        except FutureTimeout:
            self._record(op, time.monotonic() - t0, failed=True)
            if timeout < base:
                # The REQUEST's budget expired first; one such expiry
                # proves nothing about drive health, but a streak of
                # generous-window ones does (see _clamped_expiry) —
                # otherwise a budget permanently shorter than the op
                # timeout would starve the breaker of evidence and a
                # dead drive could never fail fast.
                self._clamped_expiry(timeout)
                raise DeadlineExceeded(
                    f"request deadline exceeded during {op} on "
                    f"{self.endpoint}") from None
            self._fault()
            raise FaultyDisk(
                f"drive {self.endpoint}: {op} exceeded {timeout}s") from None
        except DeadlineExceeded:
            # Raised by a nested layer (e.g. a remote drive's grid
            # call): the request's problem, not this drive's.
            self._record(op, time.monotonic() - t0, failed=True)
            self._probe_inconclusive()
            raise
        except _DOMAIN_ERRORS:
            # The drive responded; the object/volume state is the news.
            self._record(op, time.monotonic() - t0, failed=False)
            self._ok()
            raise
        except Exception:
            self._record(op, time.monotonic() - t0, failed=True)
            self._fault()
            raise
        self._record(op, time.monotonic() - t0, failed=False)
        self._ok()
        return result

    _END = object()

    def _guarded_iter(self, name: str, attr, args, kwargs):
        """Deadline-bounded iteration of a generator op: creating the
        generator is instant, the I/O happens per next() — so every
        step runs through the breaker/deadline machinery."""
        it = iter(attr(*args, **kwargs))

        def step():
            try:
                return next(it)
            except StopIteration:
                return self._END

        while True:
            item = self._call(name, step, (), {})
            if item is self._END:
                return
            yield item

    def __getattr__(self, name: str):
        attr = getattr(self._disk, name)
        if not callable(attr):
            return attr
        cache = self.__dict__.setdefault("_bound_cache", {})
        hit = cache.get(name)
        if hit is not None:
            return hit

        if name in _GENERATOR_OPS:
            def bound(*args, **kwargs):
                return self._guarded_iter(name, attr, args, kwargs)
        else:
            def bound(*args, **kwargs):
                return self._call(name, attr, args, kwargs)
        cache[name] = bound
        return bound

    def close(self) -> None:
        self._pool.shutdown()


def wrap_disks(disks, **kwargs) -> list:
    """Health-wrap a drive list (OfflineDisk placeholders pass through —
    they already fail fast)."""
    out = []
    for d in disks:
        if d is None or type(d).__name__ == "OfflineDisk" \
                or isinstance(d, DiskHealthWrapper):
            out.append(d)
        else:
            out.append(DiskHealthWrapper(d, **kwargs))
    return out

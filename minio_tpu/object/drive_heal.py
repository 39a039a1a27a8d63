"""Drive lifecycle: hot replacement with a checkpointed bulk heal.

The analogue of the reference's new-disk healing stack
(cmd/background-newdisks-heal-ops.go): a drive that dies and is swapped
for a fresh one at RUNTIME is detected while serving, re-formatted with
its slot's identity (scanner.check_drive_formats, the analogue of
formatErasureFixV3), marked healing (storage/local.HEALING_FILE, the
analogue of .healing.bin), and repopulated by a throttled set-wide bulk
heal that walks every bucket/object through the standard heal_object
path.

Semantics while a drive is healing:
  * writes resume IMMEDIATELY — new data lands on the replaced drive
    the moment its format is restored, so the heal backlog only ever
    shrinks;
  * reads participate as reconstruct sources only in the natural
    sense: the drive was wiped, so it holds no stale data — objects it
    already carries (healed or newly written) serve normally, objects
    it misses return not-found and the erasure layer reconstructs from
    the other drives;
  * readiness (/minio/health/ready) reports the set degraded until the
    bulk heal finishes (s3/server._health_ready).

The bulk heal checkpoints its position (bucket, last completed object)
into the healing marker every few objects, so a process restart — or a
crash — resumes where it stopped instead of at 'a' (the reference
persists healingTracker the same way). It is worker-0-gated like the
scanner (n pre-forked workers bulk-healing the same drives would
multiply every heal by n) and sheds under admission pressure: when the
front end is queueing clients, background repair yields.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional, Sequence

from minio_tpu.storage.local import (clear_healing, read_healing,
                                     write_healing)

# Objects healed between checkpoint persists (reference:
# healingTracker.bucketsCompleted-style periodic saves).
CHECKPOINT_EVERY = 64


def new_tracker(set_index: int, disk_index: int,
                endpoint: str = "") -> dict:
    """A fresh healing tracker for a just-adopted replacement drive."""
    return {
        "started": time.time(),
        "set_index": set_index,
        "disk_index": disk_index,
        "endpoint": endpoint,
        "objects_scanned": 0,
        "objects_healed": 0,
        "objects_failed": 0,
        "bytes_healed": 0,
        "checkpoint_bucket": "",
        "checkpoint_object": "",
        "finished": False,
    }


def mark_healing(disk, set_index: int, disk_index: int,
                 endpoint: str = "") -> bool:
    """Write a fresh healing marker unless the drive already carries a
    live checkpoint (never clobber resume state). The indices are
    advisory/display — the manager re-stamps them from its own set
    list when it adopts the tracker. Returns True when written."""
    if read_healing(disk) is not None:
        return False
    write_healing(disk, new_tracker(set_index, disk_index, endpoint))
    return True


def admission_pressure(admission) -> bool:
    """True when the front end is visibly queueing or saturated — the
    bulk heal's yield signal. Reads the AdmissionController snapshot
    (s3/admission.py); absent/odd controllers mean no pressure."""
    if admission is None:
        return False
    try:
        snap = admission.snapshot()
    except Exception:  # noqa: BLE001 - controller mid-teardown
        return False
    for v in snap.values():
        if not isinstance(v, dict):
            continue
        if v.get("waiting", 0) > 0:
            return True
        limit = v.get("limit", 0)
        if limit and v.get("in_flight", 0) >= limit:
            return True
    return False


def bulk_heal_drive(es, disk_idx: int, tracker: dict,
                    stop: Optional[threading.Event] = None,
                    throttle: float = 0.0,
                    pressure: Optional[Callable[[], bool]] = None,
                    checkpoint_every: int = CHECKPOINT_EVERY) -> dict:
    """Set-wide bulk heal converging one replaced drive: every bucket
    volume, then every object (sorted, resumable), through heal_object
    (reference: cmd/global-heal.go healErasureSet driven by the
    new-disk flow). Mutates + persists `tracker` as it goes; returns it
    finished (or checkpointed, when `stop` fired mid-sweep).
    """
    from minio_tpu.object.healing import heal_bucket, heal_object
    from minio_tpu.object.scanner import _walk_all_drives
    from minio_tpu.storage.meta import XLMeta

    disk = es.disks[disk_idx]
    since_ckpt = 0

    def version_ids(copies) -> list:
        """EVERY version of the walked key, from any parseable journal
        copy — a replaced drive must get old versions and delete
        markers back too, not just the latest ("" falls back to
        latest-only when no copy parses)."""
        for _i, blob in copies:
            try:
                vids = [v.get("vid", "") for v in XLMeta.load(blob).versions]
                if vids:
                    return vids
            except Exception:  # noqa: BLE001 - corrupt copy: try next
                continue
        return [""]

    def save(bucket: str = "", obj: str = "") -> None:
        if bucket:
            tracker["checkpoint_bucket"] = bucket
            tracker["checkpoint_object"] = obj
        try:
            write_healing(disk, tracker)
        except Exception:  # noqa: BLE001 - drive hiccup: next checkpoint
            pass

    ckpt_bucket = tracker.get("checkpoint_bucket", "")
    ckpt_object = tracker.get("checkpoint_object", "")
    try:
        buckets = sorted(b.name for b in es.list_buckets())
    except Exception:  # noqa: BLE001 - set unreadable: retry next poll
        return tracker
    for bucket in buckets:
        if bucket < ckpt_bucket:
            continue
        try:
            heal_bucket(es, bucket)
        except Exception:  # noqa: BLE001 - bucket vanished mid-sweep
            continue
        forward = ckpt_object if bucket == ckpt_bucket else ""
        for path, copies in _walk_all_drives(es, bucket,
                                             forward_from=forward):
            if stop is not None and stop.is_set():
                save(bucket, path)
                return tracker
            while pressure is not None and pressure():
                # Shed: clients are queueing; background repair yields
                # until the front end drains (checkpoint stays warm).
                if stop is not None and stop.is_set():
                    save(bucket, path)
                    return tracker
                time.sleep(0.05)
            tracker["objects_scanned"] += 1
            key_healed = False
            for vid in version_ids(copies):
                try:
                    r = heal_object(es, bucket, path, vid)
                    if r.healed and disk_idx < len(r.after) \
                            and r.before[disk_idx] != r.after[disk_idx]:
                        key_healed = True
                        tracker["bytes_healed"] += r.size
                except Exception:  # noqa: BLE001 - scanner/MRF retries
                    tracker["objects_failed"] += 1
                    break
            if key_healed:
                tracker["objects_healed"] += 1
            since_ckpt += 1
            if since_ckpt >= checkpoint_every:
                since_ckpt = 0
                save(bucket, path)
            if throttle:
                time.sleep(throttle)
        ckpt_object = ""
    tracker["finished"] = True
    tracker["finished_at"] = time.time()
    clear_healing(disk)
    return tracker


class DriveHealManager:
    """Per-process drive lifecycle manager.

    poll_once() is one detection pass: restore formats of fresh drives
    appearing in previously-formatted slots (while serving), then start
    — or resume, after a restart, from the persisted checkpoint — a
    bulk heal thread for every drive carrying an unfinished healing
    marker. start() runs poll_once on an interval (worker 0 only, wired
    by minio_tpu.server).
    """

    def __init__(self, sets: Sequence, set_size: int = 0,
                 throttle: float = 0.001,
                 checkpoint_every: int = CHECKPOINT_EVERY,
                 pressure: Optional[Callable[[], bool]] = None,
                 total_hint: Optional[Callable[[], int]] = None):
        self.sets = list(sets)
        self.set_size = set_size or (len(self.sets[0].disks)
                                     if self.sets else 0)
        self.throttle = throttle
        self.checkpoint_every = checkpoint_every
        self.pressure = pressure
        self.total_hint = total_hint      # e.g. scanner usage.objects
        self.formats_restored = 0
        self._mu = threading.Lock()
        # (set_idx, disk_idx) -> {"tracker": dict, "thread": Thread}
        self._active: dict[tuple, dict] = {}
        # Finished trackers kept for status/metrics continuity.
        self._done: dict[tuple, dict] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- detection -------------------------------------------------------

    def poll_once(self) -> int:
        """One pass: format restore for fresh drives + bulk-heal
        start/resume for marked drives. Returns newly-started heals."""
        from minio_tpu.object.scanner import check_drive_formats
        try:
            self.formats_restored += check_drive_formats(self.sets,
                                                         self.set_size)
        except Exception:  # noqa: BLE001 - detection retries next poll
            pass
        started = 0
        for si, es in enumerate(self.sets):
            for di, d in enumerate(es.disks):
                tracker = read_healing(d)
                if tracker is None or tracker.get("finished"):
                    continue
                if self._ensure_heal(si, di, es, tracker):
                    started += 1
        return started

    def _ensure_heal(self, si: int, di: int, es, tracker: dict) -> bool:
        with self._mu:
            slot = self._active.get((si, di))
            if slot is not None and slot["thread"].is_alive():
                return False
            # Re-stamp identity from the manager's own topology: the
            # persisted indices are advisory (boot-time markers use the
            # pool-local row) and must not leak into live status keys.
            tracker["set_index"] = si
            tracker["disk_index"] = di
            tracker["endpoint"] = getattr(es.disks[di], "endpoint", "") \
                or tracker.get("endpoint", "")
            t = threading.Thread(
                target=self._run_heal, args=(si, di, es, tracker),
                daemon=True, name=f"drive-heal-{si}-{di}")
            self._active[(si, di)] = {"tracker": tracker, "thread": t}
        t.start()
        return True

    def _run_heal(self, si: int, di: int, es, tracker: dict) -> None:
        try:
            bulk_heal_drive(es, di, tracker, stop=self._stop,
                            throttle=self.throttle,
                            pressure=self.pressure,
                            checkpoint_every=self.checkpoint_every)
        except Exception:  # noqa: BLE001 - next poll resumes from ckpt
            pass
        if tracker.get("finished"):
            with self._mu:
                self._active.pop((si, di), None)
                self._done[(si, di)] = tracker

    # -- introspection ---------------------------------------------------

    def healing_drives(self) -> list[tuple]:
        with self._mu:
            return [k for k, v in self._active.items()
                    if v["thread"].is_alive()]

    def status(self) -> dict:
        """Admin-facing snapshot: one entry per healing (or recently
        finished) drive with progress counters and an ETA when a
        cluster object-count hint is available."""
        total = 0
        if self.total_hint is not None:
            try:
                # The hint (scanner usage) is CLUSTER-wide; a bulk heal
                # walks one set's share of the namespace, so scale it
                # down or the ETA never converges on multi-set layouts.
                total = int(self.total_hint()) // max(len(self.sets), 1)
            except Exception:  # noqa: BLE001 - hint optional
                total = 0
        drives = []
        with self._mu:
            live = [(k, dict(v["tracker"]), v["thread"].is_alive())
                    for k, v in self._active.items()]
            done = [(k, dict(t)) for k, t in self._done.items()]
        for (si, di), tracker, alive in live:
            entry = dict(tracker, set=si, drive=di,
                         state="healing" if alive else "paused")
            scanned = tracker.get("objects_scanned", 0)
            elapsed = max(time.time() - tracker.get("started", 0), 1e-6)
            rate = scanned / elapsed
            if total and rate > 0:
                entry["eta_seconds"] = round(
                    max(total - scanned, 0) / rate, 1)
            drives.append(entry)
        for (si, di), tracker in done:
            drives.append(dict(tracker, set=si, drive=di, state="done"))
        return {"formats_restored": self.formats_restored,
                "drives": drives}

    def wait(self, timeout: float = 30.0) -> bool:
        """Testing hook: block until every active bulk heal finishes."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            with self._mu:
                threads = [v["thread"] for v in self._active.values()]
            if not any(t.is_alive() for t in threads):
                return True
            time.sleep(0.02)
        return False

    # -- lifecycle -------------------------------------------------------

    def start(self, interval: float = 10.0) -> None:
        if self._thread is not None:
            return

        def run():
            while not self._stop.wait(interval):
                try:
                    self.poll_once()
                except Exception:  # noqa: BLE001 - manager must survive
                    continue

        self._thread = threading.Thread(target=run, daemon=True,
                                        name="drive-heal-manager")
        self._thread.start()

    def stop(self, timeout: float = 2.0) -> bool:
        """True when the manager and every bulk heal ended, each within
        `timeout` (a heal checkpoints at the stop event and returns) —
        False means one is still writing to its drive."""
        self._stop.set()
        mgr, self._thread = self._thread, None
        if mgr is not None:
            mgr.join(timeout=timeout)   # first: it is what starts heals
        with self._mu:
            threads = [v["thread"] for v in self._active.values()]
        for t in threads:
            t.join(timeout=timeout)
        if mgr is not None:
            threads.append(mgr)
        return not any(t.is_alive() for t in threads)

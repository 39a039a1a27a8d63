"""Background data scanner + heal drivers.

The process that looks at data unprompted — the analogue of the
reference's scanner stack:
  * cmd/data-scanner.go — low-priority cycles over every bucket/object,
    accumulating data-usage statistics and sampling objects for heal
    (1 in healObjectSelectProb=1024 gets a deep, bitrot-verifying pass);
  * cmd/background-newdisks-heal-ops.go — detect replaced/fresh drives
    and bring them back: restore format.json for the slot, then let the
    per-object heals repopulate it;
  * cmd/global-heal.go — a full-set heal sweep (every bucket, every
    object) used by the new-disk flow and the admin heal trigger.

Design: one Scanner owns all erasure sets of the server (pools ->
sets), walks EVERY drive's sorted journal listing per bucket and merges
by key, so presence is known per drive without extra stats; objects
missing anywhere (or hitting the deep-sample counter) route through
heal_object. Usage rolls up per bucket and persists quorum-style to the
system volume so restarts (and the admin API) can read it back.
"""

from __future__ import annotations

import dataclasses
import heapq
import json
import threading
import time
from typing import Callable, Optional, Sequence

SYS_VOL = ".mtpu.sys"
USAGE_PATH = "scanner/usage.json"
DEEP_EVERY = 1024     # reference healObjectSelectProb (data-scanner.go:59)


@dataclasses.dataclass
class BucketUsage:
    objects: int = 0
    versions: int = 0
    delete_markers: int = 0
    size: int = 0

    def to_json(self):
        return dataclasses.asdict(self)


@dataclasses.dataclass
class DataUsage:
    """Aggregate usage snapshot (reference: DataUsageInfo)."""
    buckets: dict = dataclasses.field(default_factory=dict)
    objects: int = 0
    versions: int = 0
    delete_markers: int = 0
    total_size: int = 0
    last_update: float = 0.0
    cycles: int = 0
    healed: int = 0
    heal_failures: int = 0

    def to_json(self):
        return {
            "buckets": {b: u.to_json() for b, u in self.buckets.items()},
            "objects": self.objects, "versions": self.versions,
            "delete_markers": self.delete_markers,
            "total_size": self.total_size,
            "last_update": self.last_update, "cycles": self.cycles,
            "healed": self.healed, "heal_failures": self.heal_failures,
        }

    @classmethod
    def from_json(cls, m: dict) -> "DataUsage":
        u = cls()
        for b, bu in (m.get("buckets") or {}).items():
            u.buckets[b] = BucketUsage(**bu)
        for f in ("objects", "versions", "delete_markers", "total_size",
                  "last_update", "cycles", "healed", "heal_failures"):
            setattr(u, f, m.get(f, 0))
        return u


def _walk_all_drives(es, bucket: str, forward_from: str = ""):
    """Merged sorted walk over ALL of the set's drives.

    Yields (path, [(disk_idx, xlmeta_blob), ...]) per key — presence per
    drive falls out of the merge, no extra stat calls. `forward_from`
    resumes the walk at a key (inclusive): checkpointed sweeps — the
    bulk drive heal — restart where they stopped instead of at 'a'."""
    def tagged(i, d):
        try:
            for path, blob in d.walk_dir(bucket, forward_from=forward_from):
                yield path, i, blob
        except Exception:  # noqa: BLE001 - offline drive: contributes nothing
            return

    iters = [tagged(i, d) for i, d in enumerate(es.disks)]
    merged = heapq.merge(*iters, key=lambda t: t[0])
    from itertools import groupby
    for path, grp in groupby(merged, key=lambda t: t[0]):
        yield path, [(i, blob) for _, i, blob in grp]


def walk_bucket_versions(es, bucket: str, forward_from: str = ""):
    """Full-fidelity (path, [FileInfo...]) walk of one set's bucket,
    resumable at a key — the driver for checkpointed background sweeps
    (replication resync).  Each key's versions parse from the first
    readable journal copy; keys with no readable copy are skipped
    (heal owns those)."""
    from minio_tpu.storage.meta import XLMeta
    for path, copies in _walk_all_drives(es, bucket,
                                         forward_from=forward_from):
        for _, blob in copies:
            try:
                versions = XLMeta.load(blob).list_versions(bucket, path)
            except Exception:  # noqa: BLE001 - corrupt journal copy
                continue
            if versions:
                yield path, versions
            break


def scan_set_bucket(es, bucket: str, usage: BucketUsage, state: dict,
                    heal: bool = True, throttle: float = 0.0,
                    on_object: Optional[Callable] = None) -> None:
    """One scanner pass over one bucket of one set: usage accounting,
    missing-shard detection, deep-heal sampling.

    Journal decoding rides the batched native summary scanner
    (storage/meta_scan.BlobScanner): keys accumulate into one pooled
    lease and decode in one GIL-free native call per batch instead of
    a full msgpack + XLMeta build per key — at 10M objects the
    interpreter time was the scanner's whole budget (ROADMAP item 4
    remainder). The full parser runs only for keys the scanner rejects
    or whose versions carry metadata beyond the captured set (the
    hooks need full fidelity there); both are counted in the shared
    minio_tpu_meta_scan_blobs_total{path=fallback} funnel, so the
    scanner's blobs show up in the same coverage metric listings use.
    """
    from minio_tpu.object.healing import heal_bucket, heal_object
    from minio_tpu.storage.meta import XLMeta
    from minio_tpu.storage.meta_scan import BlobScanner, summary_sufficient

    if heal:
        try:
            # Recreate the bucket volume on drives that miss it (fresh /
            # replaced disks) so they participate in the object heals.
            heal_bucket(es, bucket)
        except Exception:  # noqa: BLE001 - bucket gone everywhere
            return

    n = len(es.disks)
    alive = set()
    for i, d in enumerate(es.disks):
        try:
            d.stat_vol(bucket)
            alive.add(i)
        except Exception:  # noqa: BLE001 - offline or missing bucket
            continue

    def full_versions(path, copies):
        """Full-fidelity stack from the first parseable copy (the blob
        the BlobScanner carries back IS copies[0]'s bytes, so the
        copies list alone covers every candidate). None = nothing
        parseable anywhere."""
        for _, b in copies:
            try:
                return XLMeta.load(b).list_versions(bucket, path)
            except Exception:  # noqa: BLE001 - corrupt journal copy
                continue
        return None

    def handle(path, copies, vlist, blob):
        """Account + hook + heal one scanned key (post-flush)."""
        del blob
        if vlist is not None and (on_object is None
                                  or summary_sufficient(vlist)):
            # The listing stream's own trimmed-entry rebuild: scanner
            # hooks (ILM, replication resync) see FileInfos
            # field-identical to a full parse. Only summary-SUFFICIENT
            # keys take this path when hooks exist — their versions
            # carry no metadata beyond etag/content-type/tags by
            # construction, so tier/lock/replication-status probes
            # answer absent exactly as a full parse would.
            versions = es._entry_fileinfos(bucket, path, ("s", vlist))
        else:
            # Summary rejected, or a hook needs metadata the summary
            # does not carry: full parse (the counted fallback already
            # fired for rejected blobs inside the BlobScanner).
            versions = full_versions(path, copies)
        if versions is None:
            return
        # An EMPTY version stack still accounts and heals (a crash
        # mid-delete can leave zero-version journals on some drives —
        # the old per-key loop healed those too); only the hooks need
        # actual versions.
        usage.objects += 1
        usage.versions += len(versions)
        for v in versions:
            if v.deleted:
                usage.delete_markers += 1
            else:
                usage.size += v.size
        if on_object is not None and versions:
            try:
                on_object(bucket, path, versions)
            except Exception:  # noqa: BLE001 - hooks never stop the scan
                pass
        if not heal:
            return
        state["counter"] = state.get("counter", 0) + 1
        present = {i for i, _ in copies}
        missing = alive - present
        deep = state["counter"] % state.get("deep_every", DEEP_EVERY) == 0
        if missing or deep:
            try:
                heal_object(es, bucket, path, deep=deep)
                state["healed"] = state.get("healed", 0) + 1
            except Exception:  # noqa: BLE001 - next cycle retries
                state["failures"] = state.get("failures", 0) + 1
        if throttle:
            time.sleep(throttle)

    bs = BlobScanner()
    batch: list[tuple] = []          # (path, copies) in add order
    try:
        for path, copies in _walk_all_drives(es, bucket):
            bs.add_bytes(path, copies[0][1])
            batch.append((path, copies))
            if bs.full():
                for (path, copies), (_p, vlist, blob) in \
                        zip(batch, bs.flush()):
                    handle(path, copies, vlist, blob)
                batch = []
        for (path, copies), (_p, vlist, blob) in zip(batch, bs.flush()):
            handle(path, copies, vlist, blob)
    finally:
        bs.close()


def check_drive_formats(sets: Sequence, set_size: int = 0) -> int:
    """Runtime new-disk detection (reference:
    cmd/background-newdisks-heal-ops.go:563): a drive whose format.json
    vanished (replaced disk) gets its slot identity restored from a
    healthy peer's layout; the object heals then repopulate it via the
    normal scan. Returns the number of formats restored.

    Self-locating across pools: each pool has its own format layout, so
    the set's row in `layout.sets` comes from where the DONOR drive's
    own UUID sits, never from a global set index (which would cross
    pool boundaries)."""
    from minio_tpu.topology.format import FormatInfo

    healed = 0
    for es in sets:
        layout = None
        donor_pos = None          # (row, column) of the donor in its layout
        fresh: list[int] = []
        donor_q = None
        for q, d in enumerate(es.disks):
            try:
                layout_m = d.read_format()   # None = fresh (no format.json)
            except Exception:  # noqa: BLE001 - offline: neither fresh nor donor
                continue
            if layout_m is None:
                fresh.append(q)
                continue
            if layout is not None:
                continue
            try:
                cand = FormatInfo.from_json(layout_m)
            except Exception:  # noqa: BLE001 - corrupt format: skip
                continue
            for r, row in enumerate(cand.sets):
                if cand.this in row:
                    layout, donor_pos, donor_q = cand, (r, row.index(
                        cand.this)), q
                    break
        if not fresh or layout is None or donor_pos is None:
            continue
        row = layout.sets[donor_pos[0]]
        # The donor's column must line up with its position in es.disks
        # for positional identity restore to be sound.
        if donor_pos[1] != donor_q or len(row) != len(es.disks):
            continue
        for q in fresh:
            d = es.disks[q]
            try:
                fi = FormatInfo(deployment_id=layout.deployment_id,
                                sets=layout.sets, this=row[q])
                d.write_format(fi.to_json())
                healed += 1
            except Exception:  # noqa: BLE001 - still dead: next cycle
                continue
            # A replaced drive misses every object committed before the
            # swap: mark it healing so the drive lifecycle manager
            # (object/drive_heal) owns bringing it back with a
            # checkpointed bulk heal. Best effort — without the marker
            # the per-object scanner heals still converge, just without
            # resume/progress.
            try:
                from minio_tpu.object.drive_heal import mark_healing
                mark_healing(d, donor_pos[0], q,
                             getattr(d, "endpoint", ""))
            except Exception:  # noqa: BLE001 - marker is an optimization
                pass
    return healed


def heal_set(es, deep: bool = False) -> dict:
    """Global heal sweep of one erasure set (reference:
    cmd/global-heal.go:49 healErasureSet): every bucket volume, then
    every object, through the standard heal path."""
    from minio_tpu.object.healing import heal_bucket, heal_object

    stats = {"buckets": 0, "objects": 0, "healed": 0, "failures": 0}
    for b in es.list_buckets():
        try:
            heal_bucket(es, b.name)
            stats["buckets"] += 1
        except Exception:  # noqa: BLE001
            stats["failures"] += 1
        for path, _ in _walk_all_drives(es, b.name):
            stats["objects"] += 1
            try:
                r = heal_object(es, b.name, path, deep=deep)
                if r.healed:
                    stats["healed"] += 1
            except Exception:  # noqa: BLE001
                stats["failures"] += 1
    return stats


class Scanner:
    """The background walker: cycles over all sets at low priority.

    interval: seconds between full cycles; throttle: sleep per scanned
    object (the low-priority knob; reference scannerSleeper). on_object
    hooks receive (bucket, path, versions) per scanned object — the ILM
    evaluator registers here."""

    def __init__(self, sets: Sequence, set_size: int = 0,
                 interval: float = 60.0, throttle: float = 0.001,
                 deep_every: int = DEEP_EVERY):
        self.sets = list(sets)
        self.set_size = set_size or (len(self.sets[0].disks)
                                     if self.sets else 0)
        self.interval = interval
        self.throttle = throttle
        self.deep_every = deep_every
        self.usage = DataUsage()
        self.on_object: list[Callable] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._load_usage()

    # -- persistence ----------------------------------------------------

    def _load_usage(self) -> None:
        for es in self.sets:
            for d in es.disks:
                try:
                    blob = d.read_all(SYS_VOL, USAGE_PATH)
                    self.usage = DataUsage.from_json(json.loads(blob))
                    return
                except Exception:  # noqa: BLE001 - try next drive
                    continue

    def _save_usage(self) -> None:
        blob = json.dumps(self.usage.to_json()).encode()
        for es in self.sets:
            es._fanout([lambda d=d: d.write_all(SYS_VOL, USAGE_PATH, blob)
                        for d in es.disks])

    # -- one cycle ------------------------------------------------------

    def scan_cycle(self) -> DataUsage:
        """One full pass over every set: format checks, walk, heal,
        usage rollup, persist."""
        from minio_tpu.utils import tracing
        with tracing.op_span("scanner", "scanner.cycle",
                             {"sets": len(self.sets)}):
            return self._scan_cycle_inner()

    def _scan_cycle_inner(self) -> DataUsage:
        check_drive_formats(self.sets, self.set_size)
        usage = DataUsage()
        state = {"deep_every": self.deep_every,
                 "counter": self.usage.cycles * 31}   # decorrelate samples
        buckets = {}
        for es in self.sets:
            for b in es.list_buckets():
                buckets.setdefault(b.name, BucketUsage())
        for bucket, bu in buckets.items():
            for es in self.sets:
                def hook(bkt, path, versions):
                    for cb in self.on_object:
                        cb(es, bkt, path, versions)
                scan_set_bucket(es, bucket, bu, state,
                                throttle=self.throttle, on_object=hook)
        usage.buckets = buckets
        for bu in buckets.values():
            usage.objects += bu.objects
            usage.versions += bu.versions
            usage.delete_markers += bu.delete_markers
            usage.total_size += bu.size
        usage.cycles = self.usage.cycles + 1
        usage.healed = self.usage.healed + state.get("healed", 0)
        usage.heal_failures = self.usage.heal_failures \
            + state.get("failures", 0)
        usage.last_update = time.time()
        self.usage = usage
        try:
            self._save_usage()
        except Exception:  # noqa: BLE001 - stats loss is not fatal
            pass
        return usage

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.scan_cycle()
            except Exception:  # noqa: BLE001 - scanner must survive
                continue

    def stop(self, timeout: float = 2.0) -> bool:
        """True when the scan thread ended within `timeout` — False
        means a cycle (and any heal it started) is still running."""
        self._stop.set()
        t, self._thread = self._thread, None
        if t is None:
            return True
        t.join(timeout=timeout)
        return not t.is_alive()

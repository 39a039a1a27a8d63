"""Healing: reconstruct missing/corrupt shards onto bad drives, plus the
MRF ("most recently failed") retry queue.

The analogue of the reference's healing stack (cmd/erasure-healing.go:296
healObject; cmd/mrf.go MRF queue): classify per-drive state for the
quorum version, rebuild ALL n shards from any k readable ones
(reference: Erasure.Heal reconstructs data+parity,
cmd/erasure-decode.go:317), and commit the rebuilt shards to the bad
drives through the same staged rename path writes use. Partial writes
enqueue onto the MRF queue for immediate background repair, exactly the
reference's write-path MRF hook (cmd/erasure-object.go:1556-1594).
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Optional

import numpy as np

from minio_tpu.erasure.codec import ceil_frac
from minio_tpu.object.types import ObjectNotFound, ReadQuorumError
from minio_tpu.storage import bitrot
from minio_tpu.storage.meta import FileInfo, FileNotFoundErr, VersionNotFoundErr

DRIVE_STATE_OK = "ok"
DRIVE_STATE_OFFLINE = "offline"
DRIVE_STATE_MISSING = "missing"
DRIVE_STATE_OUTDATED = "outdated"
DRIVE_STATE_CORRUPT = "corrupt"


@dataclasses.dataclass
class HealResult:
    bucket: str
    object: str
    version_id: str = ""
    before: list = dataclasses.field(default_factory=list)
    after: list = dataclasses.field(default_factory=list)
    healed: int = 0
    data_blocks: int = 0
    parity_blocks: int = 0
    size: int = 0                 # logical object bytes (bulk-heal stats)


class HealError(Exception):
    pass


def heal_object(es, bucket: str, object_: str, version_id: str = "",
                deep: bool = False) -> HealResult:
    """Heal one version of one object across the set's drives.

    Serialized against put/delete/get via the namespace write lock
    (reference: healObject's NSLock, cmd/erasure-healing.go:323) so a
    background heal cannot race an in-flight write into purging freshly
    committed shards.

    `deep=False` (the scanner's normal mode) classifies shard files by
    stat (existence + exact framed size) without reading them;
    `deep=True` reads and bitrot-verifies every block (reference scanMode
    normal vs deep, cmd/erasure-healing.go:296).
    """
    from minio_tpu.utils import tracing
    with tracing.op_span("heal", "heal.object",
                         {"bucket": bucket, "object": object_,
                          "deep": int(deep)}), \
            es.ns.write(bucket, object_):
        result = _heal_object_locked(es, bucket, object_, version_id, deep)
    if result.healed:
        # Drive journals changed under this key: cached quorum
        # fileinfo (here and, via the shared generation, in sibling
        # pre-forked workers) must re-resolve or reads would keep an
        # out-of-date holder map past the heal.
        es.metacache.bump(bucket)
    return result


def _heal_object_locked(es, bucket: str, object_: str, version_id: str,
                        deep: bool) -> HealResult:
    from minio_tpu.object import erasure_object as eo

    fis, errors = es._read_version_all(bucket, object_, version_id,
                                       read_data=True)
    n = len(es.disks)
    not_found = sum(isinstance(e, (FileNotFoundErr, VersionNotFoundErr))
                    for e in errors)
    if not_found > n // 2:
        # Majority verdict: this version does not exist. Purge stale
        # copies only when they can NEVER satisfy read quorum again —
        # not-found must exceed the version's parity count, not just a
        # majority (reference deleteIfDangling's stricter criteria,
        # cmd/erasure-object.go:484: a quorum-thin but valid write must
        # heal, not vanish).
        stale = [i for i in range(n) if fis[i] is not None]
        purge = False
        if stale:
            # Parity bound from the most redundant DATA version held by
            # any stale drive (a delete marker has no erasure info and
            # must not collapse the bound to a bare majority).
            ks = [fis[i].erasure.data_blocks for i in stale
                  if not fis[i].deleted and fis[i].erasure.data_blocks]
            if ks:
                m = n - min(ks)
                purge = not_found > max(n // 2, m)
            else:
                # Only delete markers / metadata-only versions: majority
                # not-found is already decisive.
                purge = True
        if stale and purge:
            es._fanout([
                (lambda i=i: _purge_version(es.disks[i], bucket, object_,
                                            fis[i].version_id))
                if i in stale else None for i in range(n)])
            es.metacache.bump(bucket)
        result = HealResult(bucket=bucket, object=object_,
                            version_id=version_id)
        result.before = [DRIVE_STATE_OUTDATED if i in stale
                         else DRIVE_STATE_MISSING for i in range(n)]
        if purge:
            result.after = [DRIVE_STATE_MISSING] * n
            result.healed = len(stale)
        else:
            result.after = list(result.before)
        return result
    any_fi = next((f for f in fis if f is not None), None)
    if any_fi is None:
        raise ObjectNotFound(bucket, object_)
    quorum = max(any_fi.erasure.data_blocks, n // 2) \
        if any_fi.erasure.data_blocks else n // 2 + 1
    fi, _ = es._quorum_fileinfo(fis, quorum)
    if fi is None:
        raise ReadQuorumError(bucket, object_)
    if fi.deleted:
        # Delete markers heal by metadata replication only.
        return _heal_metadata_only(es, bucket, object_, fi, fis, errors)
    from minio_tpu.object.tier import META_TIER
    if (fi.metadata or {}).get(META_TIER):
        # Transitioned versions hold no local data — their shard files
        # were reclaimed at transition; only the metadata pointer
        # replicates (treating the absent data files as damage would
        # 'reconstruct' garbage or purge a healthy version).
        return _heal_metadata_only(es, bucket, object_, fi, fis, errors)

    from minio_tpu.storage.meta import ObjectPartInfo
    k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
    e = es._erasure(k, m)
    shard_size = e.shard_size()
    inline = fi.inline_data is not None
    dist = fi.erasure.distribution
    parts = fi.parts or [ObjectPartInfo(number=1, size=fi.size,
                                        actual_size=fi.size)]

    # Classify drives + load verified shards PER PART (multipart objects
    # store one independently-encoded shard file per part).
    states: list[str] = [DRIVE_STATE_OFFLINE] * n
    # part_shards[part_idx][shard_idx] -> bytes or None
    part_shards: list[list[Optional[np.ndarray]]] = \
        [[None] * (k + m) for _ in parts]

    def load_all_parts(disk_idx: int) -> Optional[list[np.ndarray]]:
        d = es.disks[disk_idx]
        dfi = fis[disk_idx]
        out = []
        try:
            for p in parts:
                plen = e.shard_file_size(p.size)
                if inline:
                    blob = dfi.inline_data or b""
                else:
                    blob = d.read_file(
                        bucket, f"{object_}/{fi.data_dir}/part.{p.number}")
                # Batched bitrot verify: all of this shard file's full
                # blocks hash in one pass, routed through the batched
                # device verify (the get-route batcher, k=1 members)
                # when this host's decode calibration picks the device
                # — deep heal reads whole shard files, the best-case
                # batch, and the drive-replacement bulk heal fans one
                # load per drive so shard files coalesce cross-drive.
                arr = es._verify_shard_blob(blob, shard_size, plen)
                if arr is None:
                    return None
                out.append(arr)
            return out
        except Exception:  # noqa: BLE001 - treat as corrupt
            return None

    def stat_all_parts(disk_idx: int) -> bool:
        """Non-deep check: every shard file exists with the exact
        bitrot-framed size (no data read, no hash verify)."""
        d = es.disks[disk_idx]
        dfi = fis[disk_idx]
        try:
            for p in parts:
                plen = e.shard_file_size(p.size)
                want = bitrot.shard_file_size(plen, shard_size)
                if inline:
                    if len(dfi.inline_data or b"") != want:
                        return False
                else:
                    st = d.stat_info_file(
                        bucket, f"{object_}/{fi.data_dir}/part.{p.number}")
                    if st.st_size != want:
                        return False
            return True
        except Exception:  # noqa: BLE001 - unstattable == corrupt
            return False

    for i in range(n):
        dfi = fis[i]
        if isinstance(errors[i], (FileNotFoundErr, VersionNotFoundErr)):
            states[i] = DRIVE_STATE_MISSING
            continue
        if dfi is None:
            states[i] = DRIVE_STATE_OFFLINE
            continue
        if (dfi.mod_time, dfi.data_dir) != (fi.mod_time, fi.data_dir) \
                or dfi.deleted != fi.deleted:
            states[i] = DRIVE_STATE_OUTDATED
            continue
        if fi.size == 0:
            states[i] = DRIVE_STATE_OK
            for ps in part_shards:
                ps[dist[i] - 1] = np.zeros(0, np.uint8)
            continue
        if deep:
            loaded = load_all_parts(i)
            if loaded is None:
                states[i] = DRIVE_STATE_CORRUPT
            else:
                states[i] = DRIVE_STATE_OK
                for pi, arr in enumerate(loaded):
                    part_shards[pi][dist[i] - 1] = arr
        else:
            states[i] = DRIVE_STATE_OK if stat_all_parts(i) \
                else DRIVE_STATE_CORRUPT

    result = HealResult(bucket=bucket, object=object_,
                        version_id=fi.version_id, before=list(states),
                        data_blocks=k, parity_blocks=m, size=fi.size)
    bad = [i for i in range(n) if states[i] in
           (DRIVE_STATE_MISSING, DRIVE_STATE_OUTDATED, DRIVE_STATE_CORRUPT)]
    if not bad:
        result.after = list(states)
        return result

    if fi.size > 0 and not deep:
        # Non-deep mode deferred the reads; pull verified shards from the
        # stat-OK drives now that a rebuild is actually needed. A drive
        # that passed stat but fails bitrot on read demotes to corrupt.
        ok_idxs = [i for i in range(n) if states[i] == DRIVE_STATE_OK]
        loads, _ = es._fanout([
            (lambda i=i: load_all_parts(i)) if i in ok_idxs else None
            for i in range(n)])
        for i in ok_idxs:
            loaded = loads[i]
            if loaded is None:
                states[i] = DRIVE_STATE_CORRUPT
                result.before[i] = DRIVE_STATE_CORRUPT
                bad.append(i)
            else:
                for pi, arr in enumerate(loaded):
                    part_shards[pi][dist[i] - 1] = arr

    if fi.size > 0:
        for ps in part_shards:
            if sum(1 for s in ps if s is not None) < k:
                raise ReadQuorumError(bucket, object_,
                                      "not enough shards to heal")
            # Rebuild ALL shards (data + parity) of this part.
            e.decode_data_and_parity_blocks(ps)

    # Write rebuilt shards to the bad drives via the staged commit path.
    def heal_one(disk_idx: int):
        d = es.disks[disk_idx]
        shard_idx = dist[disk_idx] - 1
        hfi = dataclasses.replace(
            fi, metadata=dict(fi.metadata), parts=list(fi.parts),
            erasure=dataclasses.replace(fi.erasure, index=shard_idx + 1),
            inline_data=None)
        if fi.size == 0:
            hfi.inline_data = b"" if inline else None
            d.write_metadata(bucket, object_, hfi)
            return
        if inline:
            hfi.inline_data = bitrot.frame_shard(
                part_shards[0][shard_idx], shard_size)
            d.write_metadata(bucket, object_, hfi)
        else:
            staging = eo.new_staging()
            for pi, p in enumerate(parts):
                framed = bitrot.frame_shard(part_shards[pi][shard_idx],
                                            shard_size)
                d.create_file(eo.SYS_VOL,
                              f"{staging}/{fi.data_dir}/part.{p.number}",
                              framed)
            d.rename_data(eo.SYS_VOL, staging, hfi, bucket, object_)

    _, herrs = es._fanout([
        (lambda i=i: heal_one(i)) if i in bad else None
        for i in range(n)])
    after = list(states)
    for i in bad:
        if herrs[i] is None:
            after[i] = DRIVE_STATE_OK
            result.healed += 1
    result.after = after
    return result


def _purge_version(disk, bucket: str, object_: str, version_id: str) -> None:
    try:
        disk.delete_version(bucket, object_, version_id)
    except Exception:  # noqa: BLE001 - best effort purge
        pass


def _heal_metadata_only(es, bucket, object_, fi: FileInfo, fis, errors):
    n = len(es.disks)
    states = []
    for i in range(n):
        if fis[i] is not None and fis[i].mod_time == fi.mod_time \
                and fis[i].deleted == fi.deleted:
            states.append(DRIVE_STATE_OK)
        elif isinstance(errors[i], (FileNotFoundErr, VersionNotFoundErr)):
            states.append(DRIVE_STATE_MISSING)
        else:
            states.append(DRIVE_STATE_OUTDATED if fis[i] is not None
                          else DRIVE_STATE_OFFLINE)
    result = HealResult(bucket=bucket, object=object_,
                        version_id=fi.version_id, before=list(states))
    bad = [i for i in range(n) if states[i] in (DRIVE_STATE_MISSING,
                                                DRIVE_STATE_OUTDATED)]
    _, herrs = es._fanout([
        (lambda i=i: es.disks[i].write_metadata(bucket, object_, fi))
        if i in bad else None for i in range(n)])
    after = list(states)
    for i in bad:
        if herrs[i] is None:
            after[i] = DRIVE_STATE_OK
            result.healed += 1
    result.after = after
    return result


def heal_bucket(es, bucket: str) -> dict:
    """Recreate the bucket volume on drives that miss it."""
    results, errors = es._fanout(
        [lambda d=d: d.stat_vol(bucket) for d in es.disks])
    missing = [i for i, r in enumerate(results) if r is None]
    if len(missing) == len(es.disks):
        raise ObjectNotFound(bucket, "")
    _, herrs = es._fanout([
        (lambda i=i: es.disks[i].make_vol_if_missing(bucket))
        if i in missing else None for i in range(len(es.disks))])
    return {"bucket": bucket, "missing": len(missing),
            "healed": sum(1 for i, e in enumerate(herrs)
                          if i in missing and e is None)}


MRF_PATH = "mrf/pending.json"


class MRFQueue:
    """Most-recently-failed heal queue: partial writes retry immediately
    in the background (reference: cmd/mrf.go, bounded queue + worker).

    Pending entries persist to the system volume (best-effort, across
    all drives) whenever the queue has been dirty for a moment, and are
    reloaded+replayed at boot — the reference saves its MRF queue on
    shutdown and re-queues it at startup (cmd/mrf.go:155 healMRFDir)."""

    _PERSIST_EVERY = 2.0

    def __init__(self, es, max_items: int = 100_000, retries: int = 3,
                 persist: bool = True):
        self.es = es
        self.q: "queue.Queue[tuple]" = queue.Queue(maxsize=max_items)
        self.retries = retries
        self.healed = 0
        # Two failure counters with very different severities:
        # `spilled` — bounded-queue overflow that parked the entry in
        # the persisted pending set (nothing lost, replays later);
        # `dropped` — retries exhausted, the heal is genuinely gone.
        # Exported separately so alerting on real loss is possible.
        self.spilled = 0
        self.dropped = 0
        self._persist = persist
        # (bucket, obj, vid) -> queued? False = overflow spill: the
        # entry could not enter the bounded queue but stays pending, so
        # it persists across save/boot cycles and re-feeds when the
        # queue drains — queue.Full must never silently lose a heal.
        self._pending: dict[tuple, bool] = {}
        self._dirty = False
        self._last_save = 0.0
        self._mu = threading.Lock()
        self._stop = threading.Event()
        if persist:
            self._load()
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    def enqueue(self, bucket: str, object_: str, version_id: str = "") -> None:
        key = (bucket, object_, version_id)
        with self._mu:
            self._pending[key] = True
            self._dirty = True
        try:
            self.q.put_nowait((bucket, object_, version_id, 0))
        except queue.Full:
            # Spill: stays in _pending (persisted, replayed when the
            # queue drains or at the next boot).
            self.spilled += 1
            with self._mu:
                self._pending[key] = False

    # -- persistence ----------------------------------------------------

    def _load(self) -> None:
        import json
        from minio_tpu.storage.local import SYS_VOL
        # Union across drives: a pending heal recorded by ANY healthy
        # drive replays (a flaky drive with a stale copy must not make
        # entries vanish — losing a heal is worse than re-running one,
        # and heals are idempotent).
        entries: dict[tuple, int] = {}
        for d in self.es.disks:
            try:
                items = json.loads(d.read_all(SYS_VOL, MRF_PATH))
            except Exception:  # noqa: BLE001 - absent / offline
                continue
            for it in items:
                try:
                    entries[(it["b"], it["o"], it.get("v", ""))] = 1
                except TypeError:
                    continue
        for (b, o, v) in entries:
            self._pending[(b, o, v)] = True
            try:
                self.q.put_nowait((b, o, v, 0))
            except queue.Full:
                self.spilled += 1
                self._pending[(b, o, v)] = False   # re-fed as q drains

    def _save(self) -> None:
        import json
        from minio_tpu.storage.local import SYS_VOL
        with self._mu:
            items = [{"b": b, "o": o, "v": v}
                     for (b, o, v) in self._pending]
            self._dirty = False
        blob = json.dumps(items).encode()

        def write(d):
            def go():
                try:
                    d.write_all(SYS_VOL, MRF_PATH, blob)
                except Exception:  # noqa: BLE001 - best effort
                    pass
            return go
        self.es._fanout([write(d) for d in self.es.disks])

    def _maybe_persist(self) -> None:
        if not self._persist:
            return
        now = time.time()
        if self._dirty and now - self._last_save >= self._PERSIST_EVERY:
            self._last_save = now
            self._save()

    def save_now(self) -> None:
        """Flush pending entries to disk (shutdown / testing hook)."""
        if self._persist:
            self._save()

    # -- worker ---------------------------------------------------------

    def _refill_one(self) -> None:
        """Promote one overflow-spilled pending entry into the bounded
        queue now that it has room."""
        with self._mu:
            key = next((k for k, queued in self._pending.items()
                        if not queued), None)
            if key is None:
                return
            self._pending[key] = True
        try:
            self.q.put_nowait((*key, 0))
        except queue.Full:
            with self._mu:
                if key in self._pending:
                    self._pending[key] = False

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self._maybe_persist()
            except Exception:  # noqa: BLE001 - e.g. pool torn down at exit
                pass
            try:
                bucket, object_, vid, attempt = self.q.get(timeout=0.2)
            except queue.Empty:
                self._refill_one()
                continue
            try:
                # MRF entries come from observed failures (degraded reads,
                # bitrot hits, partial writes), so verify deeply.
                heal_object(self.es, bucket, object_, vid, deep=True)
                self.healed += 1
                with self._mu:
                    self._pending.pop((bucket, object_, vid), None)
                    self._dirty = True
            except Exception:  # noqa: BLE001 - retry w/ backoff, then drop
                if attempt + 1 < self.retries and not self._stop.is_set():
                    time.sleep(min(2 ** attempt * 0.05, 1.0))
                    try:
                        self.q.put_nowait((bucket, object_, vid, attempt + 1))
                    except queue.Full:
                        # Spill back to pending: retried on a later
                        # boot/save cycle rather than silently lost.
                        self.spilled += 1
                        with self._mu:
                            if (bucket, object_, vid) in self._pending:
                                self._pending[(bucket, object_, vid)] = False
                else:
                    self.dropped += 1
                    with self._mu:
                        self._pending.pop((bucket, object_, vid), None)
                        self._dirty = True
            finally:
                self.q.task_done()

    def stats(self) -> dict:
        with self._mu:
            return {"healed": self.healed, "spilled": self.spilled,
                    "dropped": self.dropped,
                    "pending": len(self._pending)}

    def drain(self, timeout: float = 10.0) -> None:
        """Testing hook: wait until queued AND in-flight items finish."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.q.unfinished_tasks == 0:
                return
            time.sleep(0.02)

    def stop(self, timeout: float = 2.0) -> bool:
        """Take no more work; the heal in flight, if any, runs to its
        end. True when the worker ended within `timeout` — False means
        a heal is still writing to the drives."""
        self._stop.set()
        self._worker.join(timeout=timeout)
        if self._persist:
            try:
                self._save()
            except Exception:  # noqa: BLE001 - shutdown best effort
                pass
        return not self._worker.is_alive()

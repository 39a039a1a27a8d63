"""Local drive backend: the per-drive POSIX storage engine.

The analogue of the reference's xlStorage (cmd/xl-storage.go): one
instance manages one drive (a directory tree), storing each object as

    <root>/<volume>/<object>/xl.meta          version journal (meta.py)
    <root>/<volume>/<object>/<dataDir>/part.N shard files (bitrot-framed)
    <root>/.mtpu.sys/tmp/<uuid>               staging for crash-safe commits

Writes land in tmp and are atomically renamed into place with fsync
(reference: CreateFile cmd/xl-storage.go:2092, RenameData :2557) so a
crash never exposes a partial object. Small shards inline into xl.meta
instead of separate files (reference threshold semantics,
internal/config/storageclass/storage-class.go:278).

This layer is deliberately synchronous & thread-safe per path; the
erasure object layer above fans out across drives with a thread pool the
way the reference fans out goroutines.
"""

from __future__ import annotations

import errno
import json
import os
import shutil
import threading
import time
import uuid as uuid_mod
from dataclasses import dataclass, field
from typing import Iterator, Optional

from minio_tpu.storage import meta as metafmt
from minio_tpu.storage.meta import (FileInfo, FileNotFoundErr, MetaError,
                                    VersionNotFoundErr, XLMeta)
from minio_tpu.utils import tracing
from minio_tpu.utils.latency import BUCKETS, Histogram

SYS_VOL = ".mtpu.sys"
META_FILE = "xl.meta"
TMP_DIR = "tmp"
FORMAT_FILE = "format.json"
# Healing marker (the analogue of the reference's .healing.bin,
# cmd/background-newdisks-heal-ops.go): present on a drive that was
# re-formatted into its slot at runtime and has not finished its bulk
# heal. Holds the checkpointed HealingTracker JSON (object/drive_heal).
HEALING_FILE = "healing.json"

# Directory-entry fsync after rename commits. The reference syncs file
# CONTENTS (Fdatasync, cmd/xl-storage.go:2195) on every commit but syncs
# the parent directory only when MINIO_FS_OSYNC is set
# (cmd/common-main.go:745 defaults it off; cmd/xl-storage.go:1557
# globalSync) — on a journaling filesystem the rename itself orders with
# the journal, and a dir fsync per write costs more than the whole GF
# encode. Same default, same opt-in, here.
FS_OSYNC = os.environ.get("MTPU_FS_OSYNC", "").lower() in ("1", "on", "true")
# O_DIRECT for streaming shard writes (reference: disk.ODirectPlatform
# + globalAPIConfig.odirectEnabled, on by default where supported).
O_DIRECT_ENABLED = hasattr(os, "O_DIRECT") and \
    os.environ.get("MTPU_O_DIRECT", "on").lower() not in ("0", "off",
                                                          "false")


# A sync over this long is counted slow: the signature of a run in
# which the shard files' fdatasync piles up (PERF.md, PR 29: p90 7.5 s).
SLOW_SYNC_S = 1.0
# ... and its histogram keeps finite buckets past that tail.
SYNC_BUCKETS = BUCKETS + (30.0, 60.0)
SYNC_KINDS = ("shard", "meta")
STREAM_MODES = ("direct", "direct_dropped", "buffered")
_SYNC_STAGE = {"shard": "disk.stream.sync", "meta": "disk.meta.sync"}
# The syncs' clock, looked up at each call (tests step it).
_now = time.perf_counter


class _StreamStats:
    """Process-wide totals of the shard streams and the syncs below,
    all drives together: what `minio_tpu_drive_streams_*` and
    `_sync*` export (s3/metrics.py)."""

    def __init__(self):
        self._mu = threading.Lock()
        self.streams_open = 0           # create_file calls in flight
        self.syncs_in_flight = dict.fromkeys(SYNC_KINDS, 0)
        self.slow_syncs = dict.fromkeys(SYNC_KINDS, 0)
        self.streams = dict.fromkeys(STREAM_MODES, 0)
        self.sync_hist = {k: Histogram(SYNC_BUCKETS) for k in SYNC_KINDS}

    def stream(self, delta: int, mode: str = "") -> None:
        with self._mu:
            self.streams_open += delta
            if mode:
                self.streams[mode] += 1

    def sync_begin(self, disk, kind: str) -> tuple[int, int]:
        """-> (syncs in flight in the process, on this drive), before
        this one joins them."""
        with self._mu:
            peers = sum(self.syncs_in_flight.values())
            peers_drive = disk._syncs_in_flight
            self.syncs_in_flight[kind] += 1
            disk._syncs_in_flight += 1
        return peers, peers_drive

    def sync_end(self, disk, kind: str, seconds: float) -> None:
        with self._mu:
            self.syncs_in_flight[kind] -= 1
            disk._syncs_in_flight -= 1
            if seconds > SLOW_SYNC_S:
                self.slow_syncs[kind] += 1
        self.sync_hist[kind].observe(seconds)

    def snapshot(self) -> dict:
        with self._mu:
            out = {"streams_open": self.streams_open,
                   "syncs_in_flight": dict(self.syncs_in_flight),
                   "slow_syncs": dict(self.slow_syncs),
                   "streams": dict(self.streams)}
        out["sync_hist"] = {k: h.state() for k, h in self.sync_hist.items()}
        return out


STREAM_STATS = _StreamStats()


def merge_stream_stats(snapshots) -> dict:
    """The sum of several processes' STREAM_STATS.snapshot()s."""
    out = _StreamStats().snapshot()
    for field, total in out.items():
        for snap in snapshots:
            got = snap.get(field)
            if got is None or field == "sync_hist":
                continue
            if isinstance(total, dict):
                for k in total:
                    total[k] += got.get(k, 0)
            else:
                out[field] += got
    out["sync_hist"] = {
        k: Histogram.merge([snap.get("sync_hist", {}).get(k, {})
                            for snap in snapshots], SYNC_BUCKETS)
        for k in SYNC_KINDS}
    return out


class StorageError(Exception):
    pass


class VolumeNotFound(StorageError):
    pass


class VolumeExists(StorageError):
    pass


class VolumeNotEmpty(StorageError):
    pass


class DiskAccessDenied(StorageError):
    pass


class FaultyDisk(StorageError):
    pass


class PowerFault(StorageError):
    """Base of injected power-cut faults (storage/crashdisk.PowerCut):
    a dead node's fault must propagate WHOLESALE out of commit_group —
    recording it as one member's error would let batch-mates proceed
    on a node that no longer exists."""


@dataclass
class DiskInfo:
    total: int = 0
    free: int = 0
    used: int = 0
    root_disk: bool = False
    healing: bool = False
    endpoint: str = ""
    disk_id: str = ""
    error: str = ""


@dataclass
class VolInfo:
    name: str
    created: int = 0


def _read_raw(path: str) -> bytes:
    """Whole-file read through raw os.open — the io.open stack costs
    several times the syscall for the small files the group-commit hot
    loop reads (version journals)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        size = os.fstat(fd).st_size
        buf = os.read(fd, size)
        while len(buf) < size:
            chunk = os.read(fd, size - len(buf))
            if not chunk:
                break
            buf += chunk
        return buf
    finally:
        os.close(fd)


def _write_raw(path: str, blob: bytes) -> None:
    """Whole-file write through raw os.open (no fsync — callers that
    need durability sync explicitly)."""
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
    try:
        off = 0
        view = memoryview(blob)
        while off < len(blob):
            off += os.write(fd, view[off:])
    finally:
        os.close(fd)


def _is_valid_volname(vol: str) -> bool:
    return bool(vol) and vol not in (".", "..") and "/" not in vol and "\\" not in vol


class OfflineDisk:
    """Placeholder for a format position whose drive is missing/refused.

    Every operation fails with StorageError, which the erasure layer
    already tolerates up to parity (the reference models this as a nil
    StorageAPI slot in the set)."""

    def __init__(self, endpoint: str = "offline"):
        self.endpoint = endpoint

    def is_online(self) -> bool:
        return False

    def disk_id(self) -> str:
        return ""

    def read_format(self):
        return None

    def __getattr__(self, name: str):
        def fail(*a, **kw):
            raise StorageError(f"drive offline: {self.endpoint}")
        return fail


class LocalStorage:
    """One local drive. All paths are (volume, object-path) pairs."""

    def __init__(self, root: str, endpoint: str = ""):
        self.root = os.path.abspath(root)
        self.endpoint = endpoint or self.root
        self._disk_id: Optional[str] = None
        self._lock = threading.Lock()          # guards _path_locks
        self._path_locks: dict[str, threading.Lock] = {}
        # Group-commit WAL (commit_group): one append-mode file per
        # process, held open across batches; frames accumulate until a
        # checkpoint's sync truncates it (storage/group_commit).
        self._gc_mu = threading.Lock()
        self._gc_wal_fd: Optional[int] = None
        self._gc_wal_path = ""
        self._gc_dirty = 0                 # frames since last checkpoint
        import itertools
        self._gc_seq = itertools.count()   # tmp-name counter (hot loop)
        os.makedirs(os.path.join(self.root, SYS_VOL, TMP_DIR), exist_ok=True)

    def _path_lock(self, volume: str, path: str) -> threading.Lock:
        """Per-object lock serializing xl.meta read-modify-write cycles.

        Bounded: the map is pruned opportunistically (uncontended locks
        are dropped once the map grows past a soft cap)."""
        key = f"{volume}/{path}"
        with self._lock:
            lk = self._path_locks.get(key)
            if lk is None:
                if len(self._path_locks) > 4096:
                    for k in [k for k, v in self._path_locks.items()
                              if not v.locked()][:2048]:
                        del self._path_locks[k]
                lk = self._path_locks[key] = threading.Lock()
            return lk

    # ------------------------------------------------------------------
    # identity (format.json, reference: cmd/format-erasure.go)
    # ------------------------------------------------------------------

    def read_format(self) -> Optional[dict]:
        try:
            with open(os.path.join(self.root, SYS_VOL, FORMAT_FILE), "rb") as f:
                return json.loads(f.read())
        except FileNotFoundError:
            return None

    def write_format(self, fmt: dict) -> None:
        blob = json.dumps(fmt, indent=2).encode()
        self._atomic_write(os.path.join(self.root, SYS_VOL, FORMAT_FILE), blob)
        self._disk_id = fmt.get("xl", {}).get("this")

    def disk_id(self) -> str:
        if self._disk_id is None:
            fmt = self.read_format()
            self._disk_id = fmt.get("xl", {}).get("this", "") if fmt else ""
        return self._disk_id or ""

    def is_online(self) -> bool:
        return os.path.isdir(os.path.join(self.root, SYS_VOL))

    # ------------------------------------------------------------------
    # path helpers
    # ------------------------------------------------------------------

    def _vol_dir(self, volume: str) -> str:
        if not _is_valid_volname(volume):
            raise StorageError(f"invalid volume name {volume!r}")
        return os.path.join(self.root, volume)

    def _obj_dir(self, volume: str, path: str) -> str:
        base = self._vol_dir(volume)
        full = os.path.normpath(os.path.join(base, path))
        if not full.startswith(base + os.sep) and full != base:
            raise DiskAccessDenied(path)  # path escape
        return full

    def _tmp_path(self) -> str:
        return os.path.join(self.root, SYS_VOL, TMP_DIR, str(uuid_mod.uuid4()))

    @staticmethod
    def _fsync_dir(path: str) -> None:
        try:
            fd = os.open(path, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        except OSError:
            pass

    def _atomic_write(self, dest: str, data: bytes) -> None:
        """tmp + fdatasync + rename: the crash-consistency primitive.

        Directories are created on demand (ENOENT retry) rather than
        with an unconditional makedirs pair — two mkdir walks per
        commit cost real time on the hot path, and a hot-replaced
        drive's missing staging tree is the rare case, not the common
        one."""
        tmp = self._tmp_path()
        try:
            f = open(tmp, "wb")
        except FileNotFoundError:
            os.makedirs(os.path.dirname(tmp), exist_ok=True)
            f = open(tmp, "wb")
        with f:
            f.write(data)
            f.flush()
            self._fdatasync(f.fileno(), "meta", len(data))
        try:
            os.replace(tmp, dest)
        except FileNotFoundError:
            os.makedirs(os.path.dirname(dest), exist_ok=True)
            os.replace(tmp, dest)
        if FS_OSYNC:
            self._fsync_dir(os.path.dirname(dest))

    # ------------------------------------------------------------------
    # volumes
    # ------------------------------------------------------------------

    def make_vol(self, volume: str) -> None:
        d = self._vol_dir(volume)
        if os.path.isdir(d):
            raise VolumeExists(volume)
        os.makedirs(d)

    def make_vol_if_missing(self, volume: str) -> None:
        os.makedirs(self._vol_dir(volume), exist_ok=True)

    def list_vols(self) -> list[VolInfo]:
        out = []
        for name in sorted(os.listdir(self.root)):
            if name == SYS_VOL or not _is_valid_volname(name):
                continue
            st = os.stat(os.path.join(self.root, name))
            if os.path.isdir(os.path.join(self.root, name)):
                out.append(VolInfo(name=name, created=int(st.st_ctime_ns)))
        return out

    def stat_vol(self, volume: str) -> VolInfo:
        d = self._vol_dir(volume)
        if not os.path.isdir(d):
            raise VolumeNotFound(volume)
        return VolInfo(name=volume, created=int(os.stat(d).st_ctime_ns))

    def delete_vol(self, volume: str, force: bool = False) -> None:
        d = self._vol_dir(volume)
        if not os.path.isdir(d):
            raise VolumeNotFound(volume)
        if force:
            shutil.rmtree(d)
            return
        try:
            os.rmdir(d)
        except OSError as e:
            if e.errno in (errno.ENOTEMPTY, errno.EEXIST):
                raise VolumeNotEmpty(volume) from e
            raise

    # ------------------------------------------------------------------
    # raw file ops
    # ------------------------------------------------------------------

    def write_all(self, volume: str, path: str, data: bytes) -> None:
        self._atomic_write(self._obj_dir(volume, path), data)

    def read_all(self, volume: str, path: str) -> bytes:
        try:
            with open(self._obj_dir(volume, path), "rb") as f:
                return f.read()
        except FileNotFoundError:
            raise FileNotFoundErr(f"{volume}/{path}") from None
        except IsADirectoryError:
            raise FileNotFoundErr(f"{volume}/{path}") from None

    def delete(self, volume: str, path: str, recursive: bool = False) -> None:
        full = self._obj_dir(volume, path)
        try:
            if recursive:
                shutil.rmtree(full)
            elif os.path.isdir(full):
                os.rmdir(full)
            else:
                os.remove(full)
        except FileNotFoundError:
            raise FileNotFoundErr(f"{volume}/{path}") from None
        self._rm_empty_parents(os.path.dirname(full), self._vol_dir(volume))

    def _rm_empty_parents(self, d: str, stop: str) -> None:
        while d.startswith(stop + os.sep):
            try:
                os.rmdir(d)
            except OSError:
                return
            d = os.path.dirname(d)

    # ------------------------------------------------------------------
    # shard files (streaming writes land in tmp, commit via rename_data)
    # ------------------------------------------------------------------

    def create_file(self, volume: str, path: str, data: bytes | Iterator[bytes]) -> None:
        """Write a shard file with fdatasync (callers pass bitrot-framed
        bytes; reference: cmd/xl-storage.go:2195 Fdatasync).

        Large writes go O_DIRECT when the platform allows (reference:
        writeAllDirect + ioutil.CopyAligned, cmd/xl-storage.go:2147):
        shard data is written once and read rarely, so routing it
        around the page cache keeps streaming PUTs from evicting hot
        pages, and the post-write fdatasync becomes nearly free. The
        aligned bulk writes O_DIRECT; the ragged tail flips the flag
        off on the SAME fd (the CopyAligned trick); any O_DIRECT
        error falls back to the buffered path. MTPU_O_DIRECT=off
        disables it outright.

        The call names its own stages (utils/tracing.stage), one after
        the other inside `disk.stream`: `disk.stream.open`, then
        `disk.stream.write` a write and — entered by the caller's
        iterator where it blocks on its producer — `disk.stream.
        row_wait`, then `disk.stream.sync`. What they leave of
        `disk.stream` is the copy into the bounce buffer and the
        loop's Python. All of them are credited when the stream ends
        (`request_root`'s cell): a part over the whole is a ratio of
        whole streams."""
        dest = self._obj_dir(volume, path)
        STREAM_STATS.stream(+1)
        mode = ""
        try:
            with tracing.request_root(), \
                    tracing.stage("disk.stream", type_="storage"):
                mode = self._create_file(dest, data)
        finally:
            STREAM_STATS.stream(-1, mode)

    def _create_file(self, dest: str, data) -> str:
        """-> the stream's mode, one of STREAM_MODES."""
        streaming = not isinstance(data, (bytes, bytearray, memoryview))
        with tracing.stage("disk.stream.open", type_="storage"):
            os.makedirs(os.path.dirname(dest), exist_ok=True)
            # The iterator form is the streaming shard path — the one
            # worth O_DIRECT. Buffered where the open refuses the flag:
            # nothing is consumed or written by then.
            direct = self._open_direct(dest) \
                if O_DIRECT_ENABLED and streaming else None
            f = open(dest, "wb") if direct is None else None
        if direct is not None:
            return self._write_direct(*direct, data)
        nbytes = 0
        with f:
            # One write stage a chunk; the flush of the file object's
            # own last few KiB is left to the unnamed rest.
            for chunk in (data if streaming else (data,)):
                self._staged_write(f.write, chunk)
                nbytes += memoryview(chunk).nbytes
            f.flush()
            self._fdatasync(f.fileno(), "shard", nbytes)
        return "buffered"

    @staticmethod
    def _staged_write(write, view) -> None:
        # one entry a write, ten to a hundred a stream: wall alone, and
        # no shared counter (the stage's cell is the thread's own)
        with tracing.stage("disk.stream.write", type_="storage", cpu=False):
            write(view)

    # Syncs in flight on this drive; STREAM_STATS's lock guards it.
    _syncs_in_flight = 0

    def _fdatasync(self, fd: int, kind: str, nbytes: int,
                   direct: bool = False) -> None:
        """os.fdatasync(fd) as a stage of its kind (`disk.stream.sync`
        for a shard file, `disk.meta.sync` for a journal or the WAL),
        counted in STREAM_STATS. Armed, the span says what one kept
        trace needs to set a sync's latency against its size and its
        company: the bytes it covers, the syncs already in flight in
        the process and on this drive, and whether the aligned part
        went O_DIRECT."""
        peers, peers_drive = STREAM_STATS.sync_begin(self, kind)
        tags = {"bytes": nbytes, "peers": peers, "peers_drive": peers_drive,
                "direct": direct} if tracing.ACTIVE else None
        t0 = _now()
        try:
            with tracing.stage(_SYNC_STAGE[kind], tags, type_="storage"):
                os.fdatasync(fd)
        finally:
            STREAM_STATS.sync_end(self, kind, _now() - t0)

    _ALIGN = 4096

    @staticmethod
    def _open_direct(dest: str):
        """-> (fd opened O_DIRECT, the leased bounce buffer), or None
        where O_DIRECT cannot be used here.

        The aligned staging buffer is LEASED from the buffer pool
        (io/bufpool) rather than mmap'd fresh per call — at steady
        state the shard-write path allocates nothing. The lease is
        acquired and released on this thread, so a deadline-abandoned
        health-wrapper call can never leave a recycled buffer exposed."""
        from minio_tpu.io.bufpool import global_pool
        try:
            fd = os.open(dest, os.O_CREAT | os.O_WRONLY | os.O_TRUNC
                         | os.O_DIRECT, 0o644)
        except (OSError, AttributeError):
            return None
        try:
            # Page-aligned (O_DIRECT needs aligned memory; pooled
            # buffers are mmap pages, so any lease satisfies it).
            return fd, global_pool().lease(1 << 20)
        except BaseException:
            os.close(fd)
            raise

    def _write_direct(self, fd: int, lease, chunks) -> str:
        """The O_DIRECT streaming write into `fd`; closes it and
        releases the lease. -> "direct", or "direct_dropped" where the
        mount took open(O_DIRECT) and refused the first write."""
        import fcntl
        align = self._ALIGN
        buf = lease.raw
        fill = 0
        nbytes = 0
        wrote_any = False
        refused = False

        def write_full(view):
            # os.pwritev-style full write: os.write may write SHORT
            # (e.g. ENOSPC mid-stream returns a count, not an error):
            # loop the remainder; zero progress raises rather than
            # silently truncating the shard.
            off = 0
            while off < view.nbytes:
                n = os.write(fd, view[off:])
                if n <= 0:
                    raise OSError(errno.EIO, "short write")
                off += n

        try:
            def drop_direct():
                fcntl.fcntl(fd, fcntl.F_SETFL,
                            fcntl.fcntl(fd, fcntl.F_GETFL)
                            & ~os.O_DIRECT)

            def flush_aligned():
                nonlocal fill, wrote_any
                whole = (fill // align) * align
                if whole:
                    self._staged_write(write_full, memoryview(buf)[:whole])
                    wrote_any = True
                    rest = bytes(memoryview(buf)[whole:fill])
                    fill = len(rest)
                    buf.seek(0)
                    buf.write(rest)
                    buf.seek(0)

            def flush_or_drop():
                nonlocal wrote_any, refused
                try:
                    flush_aligned()
                except OSError:
                    if wrote_any:
                        raise
                    # First write rejected (FUSE/overlay mounts accept
                    # open(O_DIRECT) but EINVAL the write): everything
                    # consumed so far still sits in buf — drop the flag
                    # and continue buffered on the same fd.
                    refused = True
                    drop_direct()
                    flush_aligned()
                    wrote_any = True

            for chunk in chunks:
                view = memoryview(chunk)
                nbytes += view.nbytes
                while view.nbytes:
                    take = min(view.nbytes, len(buf) - fill)
                    buf[fill:fill + take] = view[:take]
                    fill += take
                    view = view[take:]
                    if fill == len(buf):
                        flush_or_drop()
            flush_or_drop()
            if fill:
                # Ragged tail: drop O_DIRECT on the same fd and write
                # the remainder buffered (reference CopyAligned's
                # final unaligned write does the same).
                drop_direct()
                self._staged_write(write_full, memoryview(buf)[:fill])
            self._fdatasync(fd, "shard", nbytes, direct=not refused)
            return "direct_dropped" if refused else "direct"
        finally:
            os.close(fd)
            lease.release()

    # Bulk reads at/above this size go O_DIRECT (mirror of the write
    # path): GET/heal shard-window reads are read-once data that would
    # otherwise churn the page cache the hot PUT path needs.
    _DIRECT_READ_MIN = 1 << 20

    def read_file(self, volume: str, path: str, offset: int = 0,
                  length: int = -1) -> bytes:
        full = self._obj_dir(volume, path)
        try:
            if O_DIRECT_ENABLED:
                want = length
                if want < 0:
                    try:
                        want = max(0, os.path.getsize(full) - offset)
                    except OSError:
                        want = -1
                if want >= self._DIRECT_READ_MIN:
                    got = self._read_file_direct(full, offset, want)
                    if got is not None:
                        return got
            with open(full, "rb") as f:
                f.seek(offset)
                return f.read() if length < 0 else f.read(length)
        except FileNotFoundError:
            raise FileNotFoundErr(f"{volume}/{path}") from None

    def _read_file_direct(self, full: str, offset: int,
                          length: int) -> Optional[bytes]:
        """O_DIRECT read of [offset, offset+length) via a page-aligned
        staging buffer (O_DIRECT demands aligned fd offset, memory and
        transfer size; mmap pages satisfy the memory part — the read
        counterpart of _create_file_direct's CopyAligned trick). None
        means "cannot here" (filesystem refused, e.g. tmpfs/overlay) —
        the caller falls back to the buffered path, nothing consumed.
        MTPU_O_DIRECT=off never reaches this."""
        from minio_tpu.io.bufpool import global_pool
        try:
            fd = os.open(full, os.O_RDONLY | os.O_DIRECT)
        except OSError:
            # Includes FileNotFoundError: the buffered path re-opens
            # and raises the proper not-found from its own attempt.
            return None
        align = self._ALIGN
        lo = (offset // align) * align
        head = offset - lo
        # Pooled aligned staging (lease scoped to this thread, so a
        # deadline-abandoned wrapper call cannot expose recycled
        # memory); os.preadv into it keeps the copy loop GIL-free.
        lease = global_pool().lease(1 << 20)
        buf = lease.raw
        out = bytearray()
        try:
            try:
                pos = lo
                need = head + length
                while need > 0:
                    take = min(len(buf),
                               (need + align - 1) // align * align)
                    n = os.preadv(fd, [memoryview(buf)[:take]], pos)
                    if n <= 0:
                        break                    # EOF
                    out += buf[:n]
                    pos += n
                    need -= n
            except OSError:
                # First read EINVAL (mount accepts open(O_DIRECT) but
                # rejects the read) or a mid-stream fault: either way
                # the buffered path re-reads from scratch.
                return None
            return bytes(out[head:head + length])
        finally:
            os.close(fd)
            lease.release()

    def stat_info_file(self, volume: str, path: str) -> os.stat_result:
        try:
            return os.stat(self._obj_dir(volume, path))
        except FileNotFoundError:
            raise FileNotFoundErr(f"{volume}/{path}") from None

    # ------------------------------------------------------------------
    # versioned object metadata
    # ------------------------------------------------------------------

    def _meta_path(self, volume: str, path: str) -> str:
        return os.path.join(self._obj_dir(volume, path), META_FILE)

    def _read_meta(self, volume: str, path: str) -> XLMeta:
        try:
            with open(self._meta_path(volume, path), "rb") as f:
                return XLMeta.load(f.read())
        except FileNotFoundError:
            raise FileNotFoundErr(f"{volume}/{path}") from None

    def _reclaim_data_dir(self, volume: str, path: str, data_dir: str) -> None:
        if data_dir:
            shutil.rmtree(os.path.join(self._obj_dir(volume, path), data_dir),
                          ignore_errors=True)

    def write_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        """Append/replace one version in the journal (creates it fresh)."""
        with self._path_lock(volume, path):
            try:
                xl = self._read_meta(volume, path)
            except FileNotFoundErr:
                xl = XLMeta()
            if xl.version_unchanged(fi):
                # Byte-identical re-add (hot-key overwrite-with-same-
                # content storms: MRF retries, heal rewrites of
                # agreeing copies): the journal would not change, so
                # skip the rewrite + fsync entirely.
                return
            old_ddir = xl.add_version(fi)
            self._atomic_write(self._meta_path(volume, path), xl.dump())
            self._reclaim_data_dir(volume, path, old_ddir)

    def update_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        with self._path_lock(volume, path):
            xl = self._read_meta(volume, path)
            if xl._find(fi.storage_version_id()) is None:
                raise VersionNotFoundErr(fi.version_id)
            if xl.version_unchanged(fi):
                return
            old_ddir = xl.add_version(fi)
            self._atomic_write(self._meta_path(volume, path), xl.dump())
            self._reclaim_data_dir(volume, path, old_ddir)

    def read_version(self, volume: str, path: str, version_id: str = "",
                     read_data: bool = False) -> FileInfo:
        xl = self._read_meta(volume, path)
        return xl.to_fileinfo(volume, path, version_id, read_data=read_data)

    def read_xl(self, volume: str, path: str) -> bytes:
        return self.read_all(volume, os.path.join(path, META_FILE))

    def list_versions(self, volume: str, path: str) -> list[FileInfo]:
        xl = self._read_meta(volume, path)
        return xl.list_versions(volume, path)

    def delete_version(self, volume: str, path: str, version_id: str = "",
                       force_del_marker: bool = False) -> None:
        """Remove one version; drops shard data when unreferenced; removes
        the whole object dir when the journal empties (reference:
        DeleteVersion, cmd/xl-storage.go)."""
        with self._path_lock(volume, path):
            xl = self._read_meta(volume, path)
            vid = version_id or metafmt.NULL_VERSION_ID
            v = xl._find(vid)
            if v is None:
                raise VersionNotFoundErr(version_id)
            data_dir = xl.delete_version(version_id)
            if data_dir and xl.shared_data_dir_count(vid, data_dir) == 0:
                self._reclaim_data_dir(volume, path, data_dir)
            if not xl.versions:
                self.delete(volume, path, recursive=True)
                return
            self._atomic_write(self._meta_path(volume, path), xl.dump())

    # ------------------------------------------------------------------
    # the commit protocol
    # ------------------------------------------------------------------

    def rename_data(self, src_volume: str, src_path: str, fi: FileInfo,
                    dst_volume: str, dst_path: str) -> None:
        """Atomically commit staged shard data + a new version.

        Staged layout (written by the erasure layer):
            <src>/<src_path>/<data_dir>/part.N
        Commit = move data dir into the object dir, then write the merged
        xl.meta (reference: RenameData, cmd/xl-storage.go:2557 — data
        moves first, metadata write is the commit point).
        """
        dst_dir = self._obj_dir(dst_volume, dst_path)
        with self._path_lock(dst_volume, dst_path):
            try:
                xl = self._read_meta(dst_volume, dst_path)
            except FileNotFoundErr:
                xl = XLMeta()
                fi.fresh = True
            if fi.data_dir:
                src_data = os.path.join(self._obj_dir(src_volume, src_path),
                                        fi.data_dir)
                dst_data = os.path.join(dst_dir, fi.data_dir)
                os.makedirs(dst_dir, exist_ok=True)
                if os.path.isdir(dst_data):
                    shutil.rmtree(dst_data)
                os.replace(src_data, dst_data)
            old_ddir = xl.add_version(fi)
            self._atomic_write(os.path.join(dst_dir, META_FILE), xl.dump())
            self._reclaim_data_dir(dst_volume, dst_path, old_ddir)
        # Clean the now-empty staging dir.
        shutil.rmtree(self._obj_dir(src_volume, src_path), ignore_errors=True)

    # ------------------------------------------------------------------
    # the GROUP commit protocol (storage/group_commit.py lanes)
    # ------------------------------------------------------------------

    def commit_group(self, ops: list, _info: Optional[dict] = None,
                     _hook=None) -> list:
        """Batched commit point for a group of write_metadata /
        rename_data ops (storage/group_commit.GroupOp). Returns a
        per-member result list: None = committed, Exception = that
        member failed — batch-mates are unaffected (isolation is per
        member for merge faults, per OBJECT for journal-write faults).

        Protocol (the group twin of _atomic_write — see the module
        docstring of storage/group_commit for the durability story):
          1. per rename_data member: staged data dir moves in;
          2. per DISTINCT object: one journal read-modify-write, every
             member merged in arrival order (same-object overwrite
             storms collapse to one rewrite; byte-identical re-adds
             skip entirely);
          3. ONE write-ahead record (gcommit/<wal>) holding every
             merged journal, fdatasync'd once — the batch's durability
             point, amortized across all members;
          4. per changed object: plain tmp + rename (no per-file
             fdatasync: a destination torn by a power cut is repaired
             from the WAL by replay_wals at mount);
          5. one _fsync_dir pass over distinct parents (MTPU_FS_OSYNC);
          6. old-data-dir reclaim + staging cleanup.
        WAL files retire at the next checkpoint (one os.sync every
        MTPU_GROUP_COMMIT_CKPT batches); replay is idempotent.

        `_info` (optional dict) receives batch accounting: objects,
        merged (same-object extra members), noops, fsyncs_saved.
        `_hook` is the crash-injection seam (storage/crashdisk): called
        at every durable sub-step boundary.
        """
        from minio_tpu.storage import group_commit as gc_mod
        results: list = [None] * len(ops)
        info = _info if _info is not None else {}
        info.setdefault("objects", 0)
        info.setdefault("merged", 0)
        info.setdefault("noops", 0)
        info.setdefault("fsyncs_saved", 0)
        groups: dict[tuple, list[int]] = {}
        order: list[tuple] = []
        for i, op in enumerate(ops):
            key = (op.volume, op.path)
            if key not in groups:
                groups[key] = []
                order.append(key)
            groups[key].append(i)
        # All path locks, sorted: a fixed global order can never
        # deadlock against another multi-lock holder, and solo ops
        # take single locks (trivially compatible).
        lks = [self._path_lock(v, p) for (v, p) in sorted(groups)]
        for lk in lks:
            lk.acquire()
        staging_cleanup: list[tuple[str, str]] = []
        try:
            # (vol, path, meta_path, blob, member_idxs,
            #  replaced_ddirs, was_fresh)
            staged: list = []
            reclaims: list = []    # applied only once the journal LANDS
            for key in order:
                vol, path = key
                idxs = groups[key]
                dst_dir = self._obj_dir(vol, path)
                meta_path = dst_dir + os.sep + META_FILE
                # Raw os.open read: the io.open machinery costs ~4x the
                # syscall on this path, and at KV batch sizes the
                # per-member constant IS the commit cost.
                fresh = False
                try:
                    xl = XLMeta.load(_read_raw(meta_path))
                except (FileNotFoundError, NotADirectoryError):
                    xl = XLMeta()
                    fresh = True
                except (OSError, MetaError, ValueError) as e:
                    for i in idxs:
                        results[i] = e
                    continue
                if fresh:
                    # The object dir is needed for data-dir moves and
                    # the journal rename alike; one mkdir now beats an
                    # ENOENT retry dance per sub-step later.
                    try:
                        os.mkdir(dst_dir)
                    except FileExistsError:
                        pass
                    except FileNotFoundError:
                        os.makedirs(dst_dir, exist_ok=True)
                changed = False
                ok_idxs: list[int] = []
                obj_reclaims: list[str] = []
                for i in idxs:
                    op = ops[i]
                    # Snapshot so one member's fault cannot poison the
                    # merged journal its same-object mates commit.
                    snap = (list(xl.versions), dict(xl.inline))
                    try:
                        if op.kind == "rd":
                            if fresh:
                                op.fi.fresh = True
                            old = xl.add_version(op.fi)
                            if op.fi.data_dir:
                                if _hook is not None:
                                    _hook.step_move(op)
                                src_data = os.path.join(
                                    self._obj_dir(op.src_volume,
                                                  op.src_path),
                                    op.fi.data_dir)
                                dd = os.path.join(dst_dir,
                                                  op.fi.data_dir)
                                if os.path.isdir(dd):
                                    shutil.rmtree(dd)
                                os.replace(src_data, dd)
                            if old:
                                obj_reclaims.append(old)
                            staging_cleanup.append((op.src_volume,
                                                    op.src_path))
                            changed = True
                        else:
                            if xl.version_unchanged(op.fi):
                                info["noops"] += 1
                            else:
                                old = xl.add_version(op.fi)
                                if old:
                                    obj_reclaims.append(old)
                                changed = True
                        ok_idxs.append(i)
                    except PowerFault:
                        raise
                    except Exception as e:  # noqa: BLE001 - per member
                        xl.versions, xl.inline = snap
                        results[i] = e
                if ok_idxs:
                    info["objects"] += 1
                    info["merged"] += len(ok_idxs) - 1
                    if changed:
                        staged.append((vol, path, meta_path, xl.dump(),
                                       ok_idxs, obj_reclaims, fresh))
            if staged:
                recs = [(v, p, b) for v, p, _m, b, _, _, _ in staged]
                try:
                    self._gc_append_wal(recs, _hook)
                except PowerFault:
                    raise
                except Exception as e:  # noqa: BLE001 - batch durability
                    for _v, _p, _m, _b, idxs2, _r, _f in staged:
                        for i in idxs2:
                            if results[i] is None:
                                results[i] = e
                    staged = []
                # One WAL fdatasync covers what would have been one
                # fdatasync per changed journal on the solo path (plus
                # one dir fsync per commit under FS_OSYNC).
                info["fsyncs_saved"] += max(0, len(staged) - 1)
                tmp_dir = os.path.join(self.root, SYS_VOL, TMP_DIR)
                dirs: set[str] = set()
                for vol, path, meta_path, blob, idxs2, obj_reclaims, \
                        was_fresh in staged:
                    try:
                        prior = None
                        if _hook is not None:
                            _hook.step_rename(meta_path, blob)
                            prior = _hook.meta_prior(vol, path)
                        if was_fresh:
                            # FRESH object: no old journal a torn write
                            # could destroy, so the journal lands
                            # DIRECTLY (one filesystem-journal
                            # transaction instead of create+rename —
                            # the KV-ingest case is all fresh keys). A
                            # reader racing the µs-scale write sees an
                            # unparsable journal for a key that is not
                            # yet acked — the same "not there yet" it
                            # would have seen a µs earlier; a power cut
                            # leaves a torn dest replay_wals repairs.
                            _write_raw(meta_path, blob)
                        else:
                            # Overwrite: tmp + rename, so the OLD
                            # journal stays intact (and visible) until
                            # the atomic replace.
                            tmp = os.path.join(
                                tmp_dir, f"gc{os.getpid()}-"
                                f"{next(self._gc_seq)}")
                            _write_raw(tmp, blob)
                            os.replace(tmp, meta_path)
                        dirs.add(meta_path.rsplit(os.sep, 1)[0])
                        if _hook is not None:
                            _hook.note_rename(meta_path, blob, prior)
                        # Old data dirs reclaim only once the NEW
                        # journal actually landed — a failed rename
                        # leaves the old journal, whose versions still
                        # reference them.
                        reclaims.extend((vol, path, dd)
                                        for dd in obj_reclaims)
                    except PowerFault:
                        raise
                    except Exception as e:  # noqa: BLE001 - per object
                        for i in idxs2:
                            if results[i] is None:
                                results[i] = e
                if FS_OSYNC:
                    for d in sorted(dirs):
                        self._fsync_dir(d)
            for vol, path, ddir in reclaims:
                self._reclaim_data_dir(vol, path, ddir)
        finally:
            for lk in lks:
                lk.release()
        for sv, sp in staging_cleanup:
            shutil.rmtree(self._obj_dir(sv, sp), ignore_errors=True)
        return results

    # When False (set by crash doubles that own durability timing) the
    # background checkpoint coordinator never touches this drive's
    # WAL — checkpoints happen only through an explicit, hook-ticked
    # gc_checkpoint().
    _gc_auto = True

    def _gc_append_wal(self, recs: list, _hook=None) -> None:
        """Append one batch frame to this drive's group-commit WAL and
        fdatasync it — the batch's durability point. The file is
        created once and held open; checkpoints truncate it in place
        (no per-batch create/unlink, see storage/group_commit)."""
        from minio_tpu.storage import group_commit as gc_mod
        frame = gc_mod.encode_frame(recs)
        with self._gc_mu:
            created = False
            if self._gc_wal_fd is None:
                path = gc_mod.wal_file_path(self.root)
                flags = os.O_WRONLY | os.O_CREAT | os.O_APPEND
                try:
                    fd = os.open(path, flags, 0o644)
                except FileNotFoundError:
                    os.makedirs(os.path.dirname(path), exist_ok=True)
                    fd = os.open(path, flags, 0o644)
                self._gc_wal_fd = fd
                self._gc_wal_path = path
                created = True
            if _hook is not None:
                _hook.step_wal(self._gc_wal_path, frame)
            fd = self._gc_wal_fd
            off = 0
            view = memoryview(frame)
            while off < len(frame):
                off += os.write(fd, view[off:])
            self._fdatasync(fd, "meta", len(frame))
            if created:
                if FS_OSYNC:
                    self._fsync_dir(os.path.dirname(self._gc_wal_path))
                if _hook is not None:
                    _hook.note_wal(self._gc_wal_path,
                                   synced_dir=FS_OSYNC)
            self._gc_dirty += 1
        if self._gc_auto and _hook is None:
            from minio_tpu.storage.group_commit import \
                schedule_checkpoint
            schedule_checkpoint(self)

    def gc_pending(self) -> int:
        """Frames appended since the last checkpoint."""
        with self._gc_mu:
            return self._gc_dirty

    def gc_truncate_wal(self, expect: Optional[int] = None) -> int:
        """Drop the WAL's frames (caller has ALREADY made the renamed
        destinations durable via sync); returns the frame count.
        `expect` guards the sync-to-truncate window: a frame appended
        AFTER the caller's sync was not covered by it, so a changed
        count skips the truncate (those frames retire next
        checkpoint) instead of erasing a live durability point."""
        with self._gc_mu:
            n = self._gc_dirty
            if n == 0 or (expect is not None and n != expect):
                return 0
            if self._gc_wal_fd is not None:
                try:
                    os.ftruncate(self._gc_wal_fd, 0)
                except OSError:
                    pass
            self._gc_dirty = 0
        return n

    def gc_checkpoint(self, _hook=None) -> int:
        """Forced checkpoint: make every renamed group-commit
        destination durable (one os.sync) and truncate the WAL frames
        it was protecting. Returns the number of frames retired.
        Called at set close (graceful stops leave no frames for the
        next boot to replay) and by the crash harness through its
        injection hook."""
        pre = self.gc_pending()
        if not pre:
            return 0
        if _hook is not None:
            _hook.step_sync()
        os.sync()
        return self.gc_truncate_wal(expect=pre)

    def gc_close(self) -> None:
        """Close the WAL fd (after a final checkpoint; the empty file
        itself may remain — replay of an empty WAL is a no-op)."""
        with self._gc_mu:
            fd, self._gc_wal_fd = self._gc_wal_fd, None
        if fd is not None:
            try:
                os.close(fd)
            except OSError:
                pass

    def rename_file(self, src_volume: str, src_path: str,
                    dst_volume: str, dst_path: str) -> None:
        """Atomic same-drive move (multipart assembly, commit plumbing)."""
        src = self._obj_dir(src_volume, src_path)
        dst = self._obj_dir(dst_volume, dst_path)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        try:
            os.replace(src, dst)
        except FileNotFoundError:
            raise FileNotFoundErr(f"{src_volume}/{src_path}") from None

    # ------------------------------------------------------------------
    # listing / walking
    # ------------------------------------------------------------------

    def list_dir(self, volume: str, dir_path: str, count: int = -1) -> list[str]:
        """Entries of one directory level: files as-is, dirs with '/'."""
        base = self._obj_dir(volume, dir_path) if dir_path else self._vol_dir(volume)
        try:
            names = sorted(os.listdir(base))
        except FileNotFoundError:
            raise FileNotFoundErr(f"{volume}/{dir_path}") from None
        out = []
        for n in names:
            if os.path.isdir(os.path.join(base, n)):
                out.append(n + "/")
            else:
                out.append(n)
            if 0 < count <= len(out):
                break
        return out

    def walk_dir(self, volume: str, base_dir: str = "",
                 recursive: bool = True,
                 forward_from: str = "") -> Iterator[tuple[str, bytes]]:
        """Yield (object_path, raw xl.meta) sorted, streaming.

        The per-drive listing primitive (reference: WalkDir,
        cmd/metacache-walk.go:73): depth-first sorted recursion; a
        directory containing xl.meta IS an object and is yielded instead
        of being descended into (objects can nest under object names).
        """
        vol = self._vol_dir(volume)
        if not os.path.isdir(vol):
            raise VolumeNotFound(volume)

        # emit() keeps only the MOST RECENT journal blob so the descend
        # event for the same directory can derive data dirs without a
        # second read+parse — single slot by construction; an unbounded
        # map would grow O(num_objects x journal_size) over a long walk.
        # A miss in data_dirs_of simply re-reads the file.
        last_blob: list = [None, b""]  # [rel, blob]

        def emit(rel: str) -> Optional[tuple[str, bytes]]:
            try:
                with open(os.path.join(vol, rel, META_FILE), "rb") as f:
                    blob = f.read()
                last_blob[0], last_blob[1] = rel, blob
                return rel, blob
            except (FileNotFoundError, NotADirectoryError):
                return None

        def is_uuid(n: str) -> bool:
            try:
                uuid_mod.UUID(n)
                return True
            except ValueError:
                return False

        def data_dirs_of(rel: str) -> frozenset[str]:
            """Data-dir names referenced by rel's journal — ONLY those are
            version data, any other UUID-named child is a legitimate user
            key prefix and must be walked."""
            try:
                blob = last_blob[1] if last_blob[0] == rel else None
                if blob is None:
                    with open(os.path.join(vol, rel, META_FILE), "rb") as f:
                        blob = f.read()
                xl = XLMeta.load(blob)
                return frozenset(v.get("ddir", "") for v in xl.versions
                                 if v.get("ddir"))
            except (OSError, MetaError):
                # Unreadable journal: no children get classified as data
                # dirs, so every UUID child is walked as a possible key
                # (harmless — dirs without xl.meta yield nothing).
                return frozenset()

        def walk(rel: str, rel_is_obj: bool) -> Iterator[tuple[str, bytes]]:
            """Yields in GLOBAL lexicographic key order. A directory `d`
            produces two ordered events: the object key "d" (sorts before
            siblings like "d-x") and the subtree "d/" (sorts after them) —
            interleaving siblings between an object and its nested keys,
            exactly as S3 key order requires. When rel is itself an
            object, children matching its journal's data dirs are shard
            storage, not keys (any other UUID-named child IS a key)."""
            full = os.path.join(vol, rel) if rel else vol
            try:
                names = os.listdir(full)
            except (FileNotFoundError, NotADirectoryError):
                return
            ddirs: Optional[frozenset] = None  # lazily parsed journal
            events = []  # (sort_key, name, kind)
            for n in names:
                if n == META_FILE:
                    continue
                if rel_is_obj and is_uuid(n):
                    if ddirs is None:
                        ddirs = data_dirs_of(rel)
                    if n in ddirs:
                        continue  # version data dir, not a key prefix
                if os.path.isdir(os.path.join(full, n)):
                    events.append((n, n, "obj"))
                    events.append((n + "/", n, "descend"))
            events.sort()
            for sort_key, n, kind in events:
                child = f"{rel}/{n}" if rel else n
                if kind == "obj":
                    if child >= forward_from or forward_from.startswith(child):
                        got = emit(child)
                        if got is not None:
                            yield got
                else:
                    subtree = child + "/"
                    # Prune subtrees wholly before the resume point.
                    if subtree < forward_from and \
                            not forward_from.startswith(subtree):
                        continue
                    if recursive:
                        is_obj = os.path.exists(
                            os.path.join(vol, child, META_FILE))
                        yield from walk(child, is_obj)
                    else:
                        yield subtree, b""

        base_is_obj = bool(base_dir) and os.path.exists(
            os.path.join(vol, base_dir, META_FILE))
        yield from walk(base_dir, base_is_obj)

    # ------------------------------------------------------------------
    # scanning walk (metadata plane: batched native journal decode)
    # ------------------------------------------------------------------

    def walk_scan(self, volume: str, base_dir: str = "",
                  forward_from: str = "", shallow: bool = False):
        """The listing walk's per-drive primitive: like walk_dir, but
        journals are read in pooled-lease batches and decoded by ONE
        GIL-free native scan per batch (storage/meta_scan) instead of
        one msgpack unpack per object. Yields, in global key order:

            (path, vlist, None)   summarized object (trimmed entry)
            (path, vlist, blob)   summarized, but a version's metadata
                                  exceeds the summary — blob rides
                                  along for full-fidelity resolution
            (path, None, blob)    scanner rejected the journal; the
                                  caller runs the XLMeta.load path
            (path + "/", PREFIX_MARK, None)   shallow mode only: a key
                                  prefix with evidence of keys below it

        `shallow=True` walks ONE directory level under base_dir and
        emits subtree markers instead of descending — the delimiter
        ("/") listing shape: a browse page costs O(page), not
        O(subtree). Marker evidence is one probe scandir per child
        subtree (first grandchild with a journal or a directory), so a
        directory chain holding no keys at all may surface a transient
        empty prefix — dirs are pruned on delete, and the reference's
        non-recursive WalkDir accepts the same ambiguity.

        Unlike walk_dir, this walk never parses journals to classify
        data dirs: it descends everywhere, and a version data dir
        (part files only, never a journal or a subdirectory) simply
        yields nothing. Nested keys shadowed by a same-named data dir
        are therefore listed here — strictly more visible, never less.
        """
        vol = self._vol_dir(volume)
        if not os.path.isdir(vol):
            raise VolumeNotFound(volume)
        from minio_tpu.storage.meta_scan import BlobScanner
        scanner = BlobScanner()
        try:
            if shallow:
                yield from self._walk_shallow(vol, base_dir, forward_from)
                return

            def rec(rel):
                full = os.path.join(vol, rel) if rel else vol
                try:
                    with os.scandir(full) as it:
                        dirs = sorted(
                            e.name for e in it
                            if e.is_dir(follow_symlinks=False))
                except (FileNotFoundError, NotADirectoryError):
                    return
                events = []
                for n in dirs:
                    events.append((n, n, True))
                    events.append((n + "/", n, False))
                events.sort()
                for _, n, obj_slot in events:
                    child = f"{rel}/{n}" if rel else n
                    if obj_slot:
                        if not (child >= forward_from
                                or forward_from.startswith(child)):
                            continue
                        try:
                            fd = os.open(os.path.join(full, n, META_FILE),
                                         os.O_RDONLY)
                        except OSError:
                            continue    # not an object (or vanished)
                        try:
                            if scanner.full():
                                yield from scanner.flush()
                            scanner.add(child, fd)
                        finally:
                            os.close(fd)
                    else:
                        subtree = child + "/"
                        if subtree < forward_from and \
                                not forward_from.startswith(subtree):
                            continue
                        yield from rec(child)

            yield from rec(base_dir)
            yield from scanner.flush()
        finally:
            scanner.close()

    def _walk_shallow(self, vol: str, base_dir: str, forward_from: str):
        """One level under base_dir: objects at this level plus subtree
        markers (see walk_scan). Unbatched — shallow pages are small
        and each child's journal feeds both its entry and its marker
        decision."""
        from minio_tpu.storage.meta_scan import (PREFIX_MARK, scan_blob,
                                                 summary_sufficient)
        full = os.path.join(vol, base_dir) if base_dir else vol
        try:
            with os.scandir(full) as it:
                dirs = sorted(e.name for e in it
                              if e.is_dir(follow_symlinks=False))
        except (FileNotFoundError, NotADirectoryError):
            return
        events = []
        for n in dirs:
            events.append((n, n, True))
            events.append((n + "/", n, False))
        events.sort()
        probes: dict[str, list] = {}    # child -> its subdir names

        def probe(n: str) -> list:
            if n in probes:
                return probes.pop(n)
            try:
                with os.scandir(os.path.join(full, n)) as it:
                    sub = sorted(e.name for e in it
                                 if e.is_dir(follow_symlinks=False))
            except OSError:
                sub = []
            return sub

        def has_keys_below(n: str, subdirs: list) -> bool:
            # Evidence probe: a grandchild holding a journal (a key) or
            # any directory (a deeper tree). Stops at first evidence.
            for s in subdirs:
                try:
                    with os.scandir(os.path.join(full, n, s)) as it:
                        for e in it:
                            if e.name == META_FILE or \
                                    e.is_dir(follow_symlinks=False):
                                return True
                except OSError:
                    continue
            return False

        for _, n, obj_slot in events:
            child = f"{base_dir}/{n}" if base_dir else n
            if obj_slot:
                if not (child >= forward_from
                        or forward_from.startswith(child)):
                    continue
                sub = probe(n)
                if len(probes) < 128:
                    probes[n] = sub
                try:
                    with open(os.path.join(full, n, META_FILE),
                              "rb") as f:
                        blob = f.read()
                except OSError:
                    continue
                vlist = scan_blob(blob)
                need_blob = vlist is None or not summary_sufficient(vlist)
                yield child, vlist, (blob if need_blob else None)
            else:
                subtree = child + "/"
                if subtree < forward_from and \
                        not forward_from.startswith(subtree):
                    continue
                if has_keys_below(n, probe(n)):
                    yield subtree, PREFIX_MARK, None

    # ------------------------------------------------------------------
    # health / usage
    # ------------------------------------------------------------------

    def disk_info(self) -> DiskInfo:
        st = os.statvfs(self.root)
        total = st.f_blocks * st.f_frsize
        free = st.f_bavail * st.f_frsize
        healing = os.path.exists(
            os.path.join(self.root, SYS_VOL, HEALING_FILE))
        return DiskInfo(total=total, free=free, used=total - free,
                        healing=healing, endpoint=self.endpoint,
                        disk_id=self.disk_id())


# -- healing marker (drive replacement lifecycle) -----------------------
# Duck-typed over the StorageAPI (read_all/write_all/delete) so the
# same helpers work on LocalStorage, RemoteStorage and health-wrapped
# drives. The tracker JSON itself is owned by object/drive_heal.


def read_healing(disk) -> Optional[dict]:
    """The drive's healing tracker, or None (absent / unreachable)."""
    try:
        return json.loads(disk.read_all(SYS_VOL, HEALING_FILE))
    except Exception:  # noqa: BLE001 - no marker == not healing
        return None


def write_healing(disk, tracker: dict) -> None:
    disk.write_all(SYS_VOL, HEALING_FILE,
                   json.dumps(tracker, indent=1).encode())


def clear_healing(disk) -> None:
    try:
        disk.delete(SYS_VOL, HEALING_FILE)
    except Exception:  # noqa: BLE001 - already gone / offline
        pass


# Graceful-stop stamp: present only when the previous process exited
# through its shutdown path. Its ABSENCE at boot means a crash/power
# cut, which is what gates the (O(namespace)) deep recovery sweep —
# clean restarts skip straight to the cheap tmp/staging purge. The
# failure direction is safe: a lost stamp only costs an extra sweep.
CLEAN_SHUTDOWN_FILE = "clean.shutdown"


def mark_clean_shutdown(disk) -> None:
    root = getattr(disk, "root", None)
    if root is None:
        return
    try:
        with open(os.path.join(root, SYS_VOL, CLEAN_SHUTDOWN_FILE),
                  "wb") as f:
            f.write(b"1")
    except OSError:
        pass


def consume_clean_shutdown(disk) -> bool:
    """True when the previous stop was graceful. Consumes the stamp so
    the next boot re-evaluates from scratch."""
    root = getattr(disk, "root", None)
    if root is None:
        return False
    try:
        os.remove(os.path.join(root, SYS_VOL, CLEAN_SHUTDOWN_FILE))
        return True
    except OSError:
        return False


def _staging_owner_pid(name: str) -> Optional[int]:
    """Pid embedded in a pid-tagged staging/tmp entry name
    (erasure_object.new_staging writes `p<pid>-<uuid>`)."""
    if not name.startswith("p"):
        return None
    head = name[1:].split("-", 1)[0]
    return int(head) if head.isdigit() else None


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except OSError:
        return True        # EPERM: exists, owned by someone else


def sweep_stale_tmp(disk, min_age: Optional[float] = None) -> int:
    """Boot-time janitor: remove crash leftovers under the system
    volume's tmp/ and staging/ dirs (the reference sweeps .minio.sys/tmp
    at startup; without this, every crashed PUT's staged shards
    accumulate forever). Returns the number of entries removed.

    Safety gates (a worker-0 sweep runs while sibling pre-forked
    workers may already be serving):
      * pid-tagged staging entries (`p<pid>-<uuid>`, see
        erasure_object.new_staging) belonging to a LIVE process other
        than this one are skipped — they are a sibling's in-flight
        PUT; a tag whose owner is dead is a crash leftover at any age;
      * untagged entries are age-gated by `min_age` (default
        MTPU_SWEEP_MIN_AGE, seconds): a freshly-modified legacy entry
        survives the sweep.
    """
    root = getattr(disk, "root", None)
    if root is None:
        return 0
    if min_age is None:
        try:
            min_age = float(os.environ.get("MTPU_SWEEP_MIN_AGE", "0"))
        except ValueError:
            min_age = 0.0
    now = time.time()
    me = os.getpid()
    removed = 0
    for sub in (TMP_DIR, "staging"):
        base = os.path.join(root, SYS_VOL, sub)
        try:
            entries = os.listdir(base)
        except (FileNotFoundError, NotADirectoryError):
            continue
        for name in entries:
            full = os.path.join(base, name)
            pid = _staging_owner_pid(name)
            if pid is not None:
                # Pid tag is authoritative: a live sibling's entry is
                # untouchable at any age; a dead owner's entry is a
                # crash leftover at any age.
                if pid != me and _pid_alive(pid):
                    continue
            elif min_age > 0:
                try:
                    if now - os.lstat(full).st_mtime < min_age:
                        continue
                except OSError:
                    continue
            try:
                if os.path.isdir(full):
                    shutil.rmtree(full)
                else:
                    os.unlink(full)
                removed += 1
            except OSError:
                continue
    return removed


def _is_uuid_name(n: str) -> bool:
    try:
        uuid_mod.UUID(n)
        return True
    except ValueError:
        return False


def _only_part_files(d: str) -> bool:
    """True when `d` holds nothing but shard part files — the shape of
    a version data dir, never of a user key prefix."""
    try:
        names = os.listdir(d)
    except OSError:
        return False
    return bool(names) and all(
        n.startswith("part.") and os.path.isfile(os.path.join(d, n))
        for n in names)


def recovery_sweep(disk, min_age: Optional[float] = None) -> dict:
    """Mount-time crash recovery (extends sweep_stale_tmp): after a
    power cut, bring this drive back to a state where every object is
    either the complete old or the complete new version.

      1. stale tmp/staging purge (torn in-flight writes live there —
         the tmp+fdatasync+rename protocol never exposes a torn file
         at its destination);
      2. dangling data-dir repair: a UUID-named, part-files-only child
         that no xl.meta version references is the first half of an
         interrupted rename_data commit — the journal (= the commit
         point) never flipped, so the orphan is removed and the old
         version stands;
      3. a corrupt (torn) xl.meta is quarantined and the object is
         reported for heal — peers hold the quorum copy;
      4. an xl.meta version whose data dir is MISSING (a lost,
         un-fsynced directory entry) is reported for heal so the MRF
         can rebuild the shards from peers.

    Returns {"removed": int, "dangling": int, "heal": [(bucket, path)]}
    — the caller enqueues the heal list onto the owning set's MRF.
    Only safe before the drive starts serving.

    Group-commit WALs replay FIRST (storage/group_commit.replay_wals):
    a batched commit's journal claims must be reinstated before the
    dangling-data-dir scan looks, or the scan would reap data dirs the
    replayed journals reference.
    """
    from minio_tpu.storage.group_commit import replay_wals
    gc = replay_wals(disk)
    out = {"removed": sweep_stale_tmp(disk, min_age),
           "dangling": 0, "heal": [],
           "wal_replayed": gc["replayed"],
           "wal_repaired": gc["repaired"]}
    root = getattr(disk, "root", None)
    if root is None:
        return out

    def scan(vol: str, rel: str) -> None:
        base = os.path.join(root, vol, rel) if rel else os.path.join(root,
                                                                     vol)
        meta_path = os.path.join(base, META_FILE)
        refs: Optional[frozenset] = None
        if os.path.isfile(meta_path):
            try:
                with open(meta_path, "rb") as f:
                    xl = XLMeta.load(f.read())
                refs = frozenset(v.get("ddir", "") for v in xl.versions
                                 if v.get("ddir"))
                # A version whose shard data should exist locally but
                # does not (lost directory entry): rebuildable from
                # peers. Delete markers carry no ddir; inline versions
                # live in the journal itself; tier-transitioned
                # versions reclaimed their local data on purpose.
                if any(v.get("ddir") and not v.get("inline")
                       and not (v.get("meta") or {}).get(
                           "x-internal-tier-name")  # tier.META_TIER
                       and not os.path.isdir(os.path.join(base, v["ddir"]))
                       for v in xl.versions):
                    out["heal"].append((vol, rel))
            except (OSError, MetaError):
                # Torn journal: quarantine — an unreadable commit point
                # serves nothing; heal rewrites it from the quorum.
                try:
                    os.remove(meta_path)
                except OSError:
                    pass
                out["heal"].append((vol, rel))
                refs = frozenset()
        try:
            names = os.listdir(base)
        except OSError:
            return
        for n in names:
            if n == META_FILE:
                continue
            full = os.path.join(base, n)
            if not os.path.isdir(full):
                continue
            child = f"{rel}/{n}" if rel else n
            if _is_uuid_name(n) and _only_part_files(full) \
                    and (refs is None or n not in refs):
                # Data dir without a journal claim: the un-committed
                # half of an interrupted rename_data. Remove; the old
                # version (or nothing, for a fresh PUT) stands.
                shutil.rmtree(full, ignore_errors=True)
                out["dangling"] += 1
                continue
            scan(vol, child)
        try:
            if not os.listdir(base) and rel:
                os.rmdir(base)
        except OSError:
            pass

    try:
        vols = sorted(os.listdir(root))
    except OSError:
        return out
    for vol in vols:
        if vol == SYS_VOL or not _is_valid_volname(vol):
            continue
        if os.path.isdir(os.path.join(root, vol)):
            scan(vol, "")
    return out

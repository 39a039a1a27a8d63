"""Group-committed small-object write plane: per-drive commit lanes.

The metadata twin of ops/batcher.py (ROADMAP item 4): every inline PUT
commits one version into xl.meta on EVERY drive — a full per-drive
journal read-modify-write plus a tmp-write + fdatasync + rename under a
per-path lock. N concurrent small objects = N durable commits per
drive, so at KV scale the commit machinery, not the codec, is the wall.
This module coalesces them: concurrent `write_metadata`/`rename_data`
calls targeting the same drive accumulate into one deadline-bounded
batch (adaptive window like the stripe batcher — stretches while bursts
keep filling batches, shrinks when traffic is sparse, closes early at
the earliest member deadline minus slack, deadline-exhausted members
culled alone) and commit as ONE journal pass per drive
(storage/local.LocalStorage.commit_group):

  1. staged data dirs move in (rename_data members);
  2. one journal read-modify-write per DISTINCT object — same-object
     members merge in arrival order, so a hot-key overwrite storm is
     one xl.meta rewrite, and byte-identical re-adds (heal/MRF storms)
     short-circuit entirely;
  3. ONE write-ahead frame appended to the drive's WAL
     (`<drive>/.mtpu.sys/gcommit/wal-p<pid>.log`, held open across
     batches) holding every merged journal, made durable with ONE
     fdatasync — the batch's durability point, amortized across all
     members, and the only filesystem-journal transaction the batch
     forces (no per-batch file create/unlink);
  4. each journal lands via plain tmp + rename (no per-file fdatasync:
     the WAL already holds the bytes durably; a destination torn by a
     power cut is repaired from the WAL at mount time — replay_wals);
  5. one `_fsync_dir` pass over the distinct parent dirs under
     MTPU_FS_OSYNC.

Each member's ack is deferred until the batch's commit point lands, so
per-object durability semantics are unchanged: an acknowledged write is
either in its destination journal or in a durable WAL that mount-time
recovery replays (storage/local.recovery_sweep runs replay_wals FIRST,
before the dangling-data-dir scan — the WAL's journal claims must be
reinstated before orphan collection looks). Retired WAL files are
garbage-collected lazily: every MTPU_GROUP_COMMIT_CKPT_S seconds one
os.sync() makes the renamed destinations durable and the retired WALs
unlink; replaying a WAL whose destinations already committed is
idempotent (newer journals win by mtime). The sync runs on ONE
process-wide coordinator thread, never on the commit path.

A member's failure demotes that member — and only it — to the solo
path (plain write_metadata/rename_data); batch-mates are unaffected.
Commit dispatches ride the drive's io/engine submission queue, so the
engine's wait-vs-service split attributes coalesced commits exactly
like solo ops, and ONE `commit` span per batch is fanned into every
member's trace tree (utils/tracing.record_into, like the kernel span).

Environment:
  MTPU_GROUP_COMMIT          on|off (default on): the lane entirely.
  MTPU_GROUP_COMMIT_WAIT_MS  max accumulation window (default 30.0).
  MTPU_GROUP_COMMIT_MAX      max members per drive batch (default 128).
  MTPU_GROUP_COMMIT_CKPT_S   seconds between WAL checkpoints (def 2.0).
"""

from __future__ import annotations

import os
import struct
import threading
import time
import uuid as uuid_mod
import weakref
import zlib
from dataclasses import dataclass, field
from typing import Callable, Optional

import msgpack

from minio_tpu.utils import deadline as deadline_mod
from minio_tpu.utils import tracing
from minio_tpu.utils.deadline import DeadlineExceeded
from minio_tpu.utils.env import env_float, env_int
from minio_tpu.utils.latency import Histogram

GC_DIR = "gcommit"
GC_MAGIC = b"GCW1"

# A member must dispatch at least this long before its deadline: the
# commit (journal merges + WAL fsync + renames) must fit in what
# remains of the request budget.
_DEADLINE_SLACK_S = 0.005
_MIN_WAIT_S = 0.00025


def enabled() -> bool:
    return os.environ.get("MTPU_GROUP_COMMIT", "on").lower() \
        not in ("0", "off", "false")


def base_wait_s() -> float:
    """Max accumulation window. Generous by design: the early-close
    rule (pending >= in-flight requests) dispatches long before this
    whenever the submitters can keep up, so light load never waits it
    out — the cap binds only at saturation, where arrivals are slower
    than the window and queueing latency dwarfs it anyway (fill, and
    with it the per-request share of batch overhead, scales with the
    cap there)."""
    return env_float("MTPU_GROUP_COMMIT_WAIT_MS", 30.0) / 1000.0


def max_members() -> int:
    return env_int("MTPU_GROUP_COMMIT_MAX", 128)


def ckpt_interval_s() -> float:
    return env_float("MTPU_GROUP_COMMIT_CKPT_S", 2.0)


# ---------------------------------------------------------------------------
# WAL retirement: the background checkpoint coordinator
# ---------------------------------------------------------------------------
# A committed batch's WAL may only unlink once its renamed destination
# journals are durable. Syncing on the commit path would put a global
# flush in the hot loop, so retirement is deferred: drives queue their
# retired WALs and ONE process-wide coordinator makes everything
# durable with a single os.sync per interval (the sync is global, so
# one call covers every drive), then unlinks the batch. A WAL that
# outlives its process (SIGKILL before the interval) is replayed
# idempotently at the next boot.

_co_mu = threading.Lock()
_co_disks: "weakref.WeakSet" = weakref.WeakSet()
_co_thread: Optional[threading.Thread] = None
checkpoints_total = 0
wals_retired_total = 0


def schedule_checkpoint(disk) -> None:
    """Register `disk` (a LocalStorage with retired WALs pending) with
    the coordinator; spawns/respawns the daemon on demand."""
    global _co_thread
    with _co_mu:
        _co_disks.add(disk)
        if _co_thread is None or not _co_thread.is_alive():
            _co_thread = threading.Thread(
                target=_co_loop, daemon=True, name="gc-checkpoint")
            _co_thread.start()


def _co_loop() -> None:
    global _co_thread, checkpoints_total, wals_retired_total
    idle = 0
    while True:
        time.sleep(ckpt_interval_s())
        with _co_mu:
            disks = list(_co_disks)
        dirty = [d for d in disks
                 if getattr(d, "gc_pending", lambda: 0)()]
        if not dirty:
            idle += 1
            if idle >= 3:
                with _co_mu:
                    # Exit only when nothing arrived since the last
                    # scan — appends always re-poke via
                    # schedule_checkpoint, which sees the dead handle
                    # and respawns.
                    if not any(getattr(d, "gc_pending", lambda: 0)()
                               for d in _co_disks):
                        _co_thread = None
                        return
                idle = 0
            continue
        idle = 0
        # Capture each drive's frame count BEFORE the sync: frames
        # appended after it were not made durable by it, and truncating
        # them would erase an acked batch's durability point — the
        # guarded truncate skips any drive that moved and retires it
        # next round instead.
        pre = {}
        for d in dirty:
            try:
                pre[id(d)] = d.gc_pending()
            except Exception:  # noqa: BLE001 - drive gone mid-ckpt
                pre[id(d)] = 0
        try:
            # ONE global sync covers every drive's renamed journal
            # destinations; only then may their WAL frames drop.
            os.sync()
        except OSError:
            pass
        frames = 0
        for d in dirty:
            try:
                frames += d.gc_truncate_wal(expect=pre.get(id(d)))
            except Exception:  # noqa: BLE001 - drive gone mid-ckpt
                pass
        with _co_mu:
            checkpoints_total += 1
            wals_retired_total += frames


@dataclass
class GroupOp:
    """One member of a per-drive commit batch."""
    kind: str                  # "wm" (write_metadata) | "rd" (rename_data)
    volume: str
    path: str
    fi: object                 # storage.meta.FileInfo
    src_volume: str = ""       # rename_data staging source
    src_path: str = ""

    @classmethod
    def write_meta(cls, volume, path, fi) -> "GroupOp":
        return cls("wm", volume, path, fi)

    @classmethod
    def rename(cls, src_volume, src_path, fi, volume, path) -> "GroupOp":
        return cls("rd", volume, path, fi,
                   src_volume=src_volume, src_path=src_path)


# ---------------------------------------------------------------------------
# WAL encode / decode / replay
# ---------------------------------------------------------------------------
# One append-mode WAL file per drive per process
# (`gcommit/wal-p<pid>.log`, held open across batches): each batch
# appends ONE framed record and fdatasyncs it — no file create/unlink
# per batch, so the filesystem's metadata journal sees one data flush
# per batch instead of three metadata transactions (on ext4, creates
# and unlinks serialize behind exactly the journal commits the
# fdatasyncs force; the append design is what lets batch commits and
# journal renames flow concurrently). Checkpoints truncate the file in
# place. Frame layout:
#
#     GC_MAGIC | crc32(body) u32 | body = t_ns u64 | len u32 | payload
#
# where payload is msgpack [(volume, path, journal_blob), ...] and
# t_ns is the frame's creation time — every destination journal of the
# batch is renamed in AFTER t_ns, which is what replay's newer-wins
# mtime comparison relies on. The crc makes a torn tail frame (power
# cut mid-append) self-evident: it is discarded, and it protected
# nobody — no member of that batch was ever acked.

_FRAME_HEAD = struct.Struct("<I")       # crc32 over body
_FRAME_BODY_HEAD = struct.Struct("<QI")  # t_ns, payload length


def wal_file_path(root: str) -> str:
    from minio_tpu.storage.local import SYS_VOL
    return os.path.join(root, SYS_VOL, GC_DIR,
                        f"wal-p{os.getpid()}.log")


def encode_frame(recs: list[tuple[str, str, bytes]],
                 t_ns: Optional[int] = None) -> bytes:
    payload = msgpack.packb([(v, p, bytes(b)) for v, p, b in recs],
                            use_bin_type=True)
    body = _FRAME_BODY_HEAD.pack(
        time.time_ns() if t_ns is None else t_ns, len(payload)) + payload
    return GC_MAGIC + _FRAME_HEAD.pack(zlib.crc32(body)) + body


def iter_frames(blob: bytes):
    """Yield (t_ns, recs) for every intact frame; stops at the first
    torn/alien bytes (everything after a torn frame is unreachable —
    appends are strictly ordered). Returns the count of discarded
    tails (0 or 1) via StopIteration value; callers use the generator
    plainly and treat early exhaustion as the torn signal."""
    off = 0
    n = len(blob)
    while off + 20 <= n:   # full header: magic(4)+crc(4)+t_ns(8)+len(4)
        if blob[off:off + 4] != GC_MAGIC:
            return 1
        (crc,) = _FRAME_HEAD.unpack_from(blob, off + 4)
        t_ns, plen = _FRAME_BODY_HEAD.unpack_from(blob, off + 8)
        end = off + 20 + plen
        if end > n:
            return 1
        body = blob[off + 8:end]
        if zlib.crc32(body) != crc:
            return 1
        try:
            recs = msgpack.unpackb(body[12:], raw=False)
        except Exception:  # noqa: BLE001 - decodes like a torn frame
            return 1
        yield t_ns, [(v, p, b) for v, p, b in recs]
        off = end
    return 1 if off < n else 0


def _wal_improves(dest_blob: bytes, jblob: bytes) -> bool:
    """True when the WAL journal holds a version the destination
    journal lacks, or holds at an older mod time — i.e. installing the
    frame adds committed state instead of rolling newer state back.
    Unparsable inputs answer True (the torn-destination repair
    case)."""
    from minio_tpu.storage.meta import XLMeta
    try:
        dest = XLMeta.load(dest_blob)
        wal = XLMeta.load(jblob)
    except Exception:  # noqa: BLE001 - torn either side: repair
        return True
    have = {v.get("vid"): v.get("mt", 0) for v in dest.versions}
    return any(have.get(v.get("vid"), -1) < v.get("mt", 0)
               for v in wal.versions)


def replay_wals(disk) -> dict:
    """Mount-time WAL replay: repair/complete group commits a power
    cut interrupted. Every intact frame across the drive's WAL files
    is collected, sorted by frame time, and each recorded journal is
    installed — with a REAL fdatasync this time — iff its destination
    is missing, unreadable (torn by the cut: the rename landed but the
    un-synced content did not), or strictly older than the frame (the
    rename itself never landed). A destination newer than the frame is
    a later committed write and is left alone; a destination whose
    whole OBJECT DIR is gone is a post-batch delete and is NOT
    resurrected. Torn tail frames are discarded: they were never any
    member's durability point. WAL files are removed afterwards —
    replaying an already-committed batch is idempotent. Returns
    {"replayed", "repaired", "discarded"}."""
    from minio_tpu.storage.local import META_FILE, SYS_VOL
    from minio_tpu.storage.meta import MetaError, XLMeta
    out = {"replayed": 0, "repaired": 0, "discarded": 0}
    root = getattr(disk, "root", None) or \
        (disk if isinstance(disk, str) else None)
    if root is None:
        return out
    gdir = os.path.join(root, SYS_VOL, GC_DIR)
    try:
        names = sorted(os.listdir(gdir))
    except (FileNotFoundError, NotADirectoryError):
        return out
    entries: list[tuple[int, str, str, bytes]] = []
    for name in names:
        full = os.path.join(gdir, name)
        if not name.startswith("wal-"):
            # Stray replay tmp from an interrupted recovery: remove.
            try:
                os.remove(full)
            except OSError:
                pass
            continue
        try:
            with open(full, "rb") as f:
                blob = f.read()
        except OSError:
            continue
        it = iter_frames(blob)
        while True:
            try:
                t_ns, recs = next(it)
            except StopIteration as stop:
                out["discarded"] += stop.value or 0
                break
            out["replayed"] += 1
            for vol, path, jblob in recs:
                entries.append((t_ns, vol, path, jblob))
    # Frame-time order across files: pre-forked sibling workers append
    # to per-pid files, and for one object the NEWEST frame must win.
    entries.sort(key=lambda e: e[0])
    for t_ns, vol, path, jblob in entries:
        obj_dir = os.path.join(root, vol, path)
        dest = os.path.join(obj_dir, META_FILE)
        if not os.path.isdir(obj_dir):
            # Whole object dir gone: a committed post-batch delete
            # pruned it (or, under lose_entry semantics, a fresh
            # object's dir entry was lost — the documented
            # MTPU_FS_OSYNC durability exception). Never resurrect.
            continue
        install = False
        try:
            st = os.stat(dest)
            with open(dest, "rb") as f:
                dest_blob = f.read()
            if st.st_mtime_ns < t_ns:
                # Looks pre-batch (rename lost) — but mtime alone can
                # lie on coarse-granularity filesystems or across a
                # clock step, and blindly installing would roll a
                # NEWER committed overwrite back to the frame's
                # journal. Install only when the frame really carries
                # a version the destination lacks (or holds older).
                install = _wal_improves(dest_blob, jblob)
            else:
                XLMeta.load(dest_blob)
        except FileNotFoundError:
            install = True              # rename never landed
        except Exception:  # noqa: BLE001 - unreadable == torn: repair
            install = True
        if install:
            tmp = os.path.join(gdir, f"replay-{uuid_mod.uuid4().hex}")
            try:
                with open(tmp, "wb") as f:
                    f.write(jblob)
                    f.flush()
                    os.fdatasync(f.fileno())
                os.replace(tmp, dest)
                out["repaired"] += 1
            except OSError:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
    for name in names:
        if name.startswith("wal-"):
            try:
                os.remove(os.path.join(gdir, name))
            except OSError:
                pass
    return out


# ---------------------------------------------------------------------------
# the coalescer
# ---------------------------------------------------------------------------

class _Latch:
    """One countdown shared by a request's members: ONE wait and ONE
    wake per request instead of one per drive (the same trick
    ErasureSet._fanout pulls — future-per-op handoff cost is real at
    12+ drives)."""

    __slots__ = ("event", "mu", "n")

    def __init__(self, n: int):
        self.n = n
        self.mu = threading.Lock()
        self.event = threading.Event()
        if n <= 0:
            # Nothing to wait for (e.g. every drive slot was None
            # because staging failed everywhere): an unset event here
            # would park the caller forever inside the namespace lock.
            self.event.set()

    def dec(self) -> None:
        with self.mu:
            self.n -= 1
            if self.n <= 0:
                self.event.set()


class _Member:
    __slots__ = ("op", "latch", "exc", "done", "expires_at", "tctx",
                 "tparent", "t_enq")

    def __init__(self, op: GroupOp, dl, latch: _Latch):
        self.op = op
        self.latch = latch
        self.done = False
        self.exc: Optional[BaseException] = None
        self.expires_at = dl.expires_at if dl is not None else None
        self.tctx, self.tparent = tracing.capture() if tracing.ACTIVE \
            else (None, 0)
        self.t_enq = time.perf_counter()


@dataclass
class _Lane:
    idx: int
    name: str
    pending: list = field(default_factory=list)
    deadline: float = 0.0          # current window's dispatch-by time
    cur_wait: float = 0.0
    min_expiry: Optional[float] = None   # earliest member deadline

    def bound(self) -> float:
        """When this window must close: the adaptive deadline, pulled
        in to the earliest member deadline minus commit slack."""
        if self.min_expiry is None:
            return self.deadline
        return min(self.deadline, self.min_expiry - _DEADLINE_SLACK_S)


# Live coalescers, for fleet-wide metrics (s3/metrics.py renders
# minio_tpu_group_commit_* from aggregate_stats()).
_REGISTRY: "weakref.WeakSet[GroupCommit]" = weakref.WeakSet()


def _zero_stats() -> dict:
    return {
        "batches": 0, "members": 0, "solo_bypass": 0,
        "objects": 0, "merged_members": 0, "noop_skips": 0,
        "fsyncs_saved": 0, "deadline_culls": 0, "solo_demotions": 0,
        "size_buckets": {}, "wait_hist": None, "fill_mean": 0.0,
    }


def aggregate_stats() -> dict:
    out = _zero_stats()
    hists = []
    for gc in list(_REGISTRY):
        st = gc.stats()
        for key in ("batches", "members", "solo_bypass", "objects",
                    "merged_members", "noop_skips", "fsyncs_saved",
                    "deadline_culls", "solo_demotions"):
            out[key] += st[key]
        for b, v in st["size_buckets"].items():
            out["size_buckets"][b] = out["size_buckets"].get(b, 0) + v
        hists.append(st["wait_hist"])
    out["wait_hist"] = Histogram.merge(hists) if hists \
        else Histogram().state()
    out["fill_mean"] = (out["members"] / out["batches"]) \
        if out["batches"] else 0.0
    out["checkpoints"] = checkpoints_total
    out["wals_retired"] = wals_retired_total
    return out


def merge_stats(states: list) -> dict:
    """Fleet view: sum per-worker aggregate_stats() snapshots (each
    pre-forked worker runs its OWN lanes over the shared drives, and a
    scrape lands on an arbitrary worker — same merge the engine's
    per-drive rows get)."""
    out = _zero_stats()
    out["checkpoints"] = 0
    out["wals_retired"] = 0
    hists = []
    for st in states:
        if not isinstance(st, dict):
            continue
        for key in ("batches", "members", "solo_bypass", "objects",
                    "merged_members", "noop_skips", "fsyncs_saved",
                    "deadline_culls", "solo_demotions",
                    "checkpoints", "wals_retired"):
            out[key] += st.get(key, 0)
        for b, v in (st.get("size_buckets") or {}).items():
            b = int(b)
            out["size_buckets"][b] = out["size_buckets"].get(b, 0) + v
        if st.get("wait_hist"):
            hists.append(st["wait_hist"])
    out["wait_hist"] = Histogram.merge(hists) if hists \
        else Histogram().state()
    out["fill_mean"] = (out["members"] / out["batches"]) \
        if out["batches"] else 0.0
    return out


def _size_bucket(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


class GroupCommit:
    """Per-drive group-commit lanes of one erasure set.

    `disks` are the set's (health-wrapped) drives; `io_engine` its
    per-drive submission queues — batch commits are dispatched through
    them so the engine's queue-wait/service split covers coalesced
    commits. `bump` (set by the erasure layer to metacache.bump) fires
    ONE coalesced invalidation per batch per distinct bucket, BEFORE
    any member is acked — the same before-return semantics per-request
    bumps had, one funnel call per batch instead of per mutation."""

    def __init__(self, disks, io_engine, name: str = ""):
        self._disks = list(disks)
        self._io = io_engine
        self.name = name
        self.bump: Optional[Callable[[str], None]] = None
        base = base_wait_s()
        self._max_wait = base
        self._max_members = max_members()
        self._lanes = [
            _Lane(i, str(getattr(d, "endpoint", "") or i),
                  cur_wait=base / 4)
            for i, d in enumerate(self._disks)]
        self._mu = threading.Condition()
        self._dispatcher: Optional[threading.Thread] = None
        self._inflight = 0
        self._closed = False
        self._stat_mu = threading.Lock()
        self._batches = 0
        self._members = 0
        self._solo_bypass = 0
        self._objects = 0
        self._merged_members = 0
        self._noop_skips = 0
        self._fsyncs_saved = 0
        self._deadline_culls = 0
        self._solo_demotions = 0
        self._size_buckets: dict[int, int] = {}
        self._wait_hist = Histogram()
        _REGISTRY.add(self)

    # -- submission -----------------------------------------------------

    def tracking(self):
        """Context manager marking one group-eligible request in its
        commit section — the concurrency signal worth_batching reads
        (mirror of the stripe batcher's inflight bookkeeping)."""
        gc = self

        class _Track:
            def __enter__(self):
                with gc._mu:
                    gc._inflight += 1
                return gc

            def __exit__(self, *exc):
                with gc._mu:
                    gc._inflight -= 1
                return False

        return _Track()

    def worth_batching(self) -> bool:
        """True when coalescing has company RIGHT NOW: another
        group-eligible request is in its commit section, or members are
        already pending. A lone request (the caller counts as 1) takes
        the solo fan-out and never waits the window."""
        if self._inflight > 1:
            return True
        return any(lane.pending for lane in self._lanes)

    def note_solo(self, n: int = 1) -> None:
        with self._stat_mu:
            self._solo_bypass += n

    def commit_fanout(self, ops: list) -> list:
        """Submit one op per drive (None = skip that slot) and wait for
        every ack; returns a per-drive error list aligned with the
        set's disks (None = committed) — the lane-side mirror of
        ErasureSet._fanout's contract for commit fan-outs."""
        n = len(ops)
        dl = deadline_mod.current()
        if dl is not None and dl.expired():
            err = DeadlineExceeded("request deadline exceeded")
            return [err] * n
        members: list[Optional[_Member]] = [None] * n
        latch = _Latch(sum(1 for op in ops if op is not None))
        with self._mu:
            if self._closed:
                from minio_tpu.storage.local import StorageError
                return [StorageError("group commit closed")] * n
            now = time.monotonic()
            wake = False
            for i, op in enumerate(ops):
                if op is None:
                    continue
                m = _Member(op, dl, latch)
                lane = self._lanes[i]
                if not lane.pending:
                    lane.deadline = now + lane.cur_wait
                    lane.min_expiry = m.expires_at
                    wake = True         # a fresh window: (re)arm sleep
                elif m.expires_at is not None and (
                        lane.min_expiry is None
                        or m.expires_at < lane.min_expiry):
                    lane.min_expiry = m.expires_at
                    wake = True         # bound moved earlier
                lane.pending.append(m)
                if len(lane.pending) >= self._max_members                         or len(lane.pending) >= self._inflight:
                    wake = True         # early-close condition met
                members[i] = m
            if self._dispatcher is None or not self._dispatcher.is_alive():
                self._dispatcher = threading.Thread(
                    target=self._dispatch_loop, daemon=True,
                    name="group-commit")
                self._dispatcher.start()
                wake = True
            if wake:
                # Waking the dispatcher on EVERY append would make it
                # rescan all lanes per member — O(members x lanes) of
                # pure GIL churn. It only needs to hear about window
                # openings, earlier bounds, and early-close triggers;
                # otherwise its timed sleep already ends at the right
                # moment.
                self._mu.notify_all()
        errors: list = [None] * n
        if dl is None:
            latch.event.wait()
            done = True
        else:
            done = latch.event.wait(timeout=max(
                0.0, dl.expires_at + 0.25 - time.monotonic()))
        for i, m in enumerate(members):
            if m is None:
                continue
            if not done and not m.done:
                # Collection deadline blown with this commit still in
                # flight: mark the straggler; late completions write
                # results nobody reads (same contract as _fanout).
                errors[i] = DeadlineExceeded(
                    "request deadline exceeded in group commit")
                continue
            errors[i] = m.exc
        return errors

    # -- dispatch -------------------------------------------------------

    def _dispatch_loop(self) -> None:
        while True:
            with self._mu:
                while not any(ln.pending for ln in self._lanes) \
                        and not self._closed:
                    self._mu.wait(timeout=0.2)
                    if not any(ln.pending for ln in self._lanes) \
                            and self._inflight == 0:
                        # Idle: clear the handle BEFORE dying (under
                        # the lock) so a racing submit starts a fresh
                        # dispatcher instead of trusting a dead one.
                        self._dispatcher = None
                        return
                if self._closed and not any(ln.pending
                                            for ln in self._lanes):
                    self._dispatcher = None
                    return
                now = time.monotonic()
                due = []
                next_bound = None
                for lane in self._lanes:
                    if not lane.pending:
                        continue
                    bound = lane.bound()
                    # Early close: once every group-eligible request in
                    # its commit section has a member on this lane,
                    # nothing more can join before some member leaves —
                    # waiting out the window would buy only latency.
                    if self._closed or now >= bound \
                            or len(lane.pending) >= self._max_members \
                            or len(lane.pending) >= self._inflight:
                        batch, lane.pending = lane.pending, []
                        lane.min_expiry = None
                        due.append((lane, batch))
                    elif next_bound is None or bound < next_bound:
                        next_bound = bound
                if not due:
                    self._mu.wait(timeout=max(0.0, next_bound - now))
                    continue
            for lane, batch in due:
                self._dispatch(lane, batch)

    def _dispatch(self, lane: _Lane, batch: list) -> None:
        """Hand one lane's drained batch to its drive's engine queue
        (wait-vs-service attribution rides the queue's own stats); a
        saturated/closed queue falls back to a fresh thread — a shed
        here would fail every member of the batch, unlike one solo op
        counted against quorum."""
        from minio_tpu.io.engine import EngineSaturated
        fn = lambda: self._run_batch(lane, batch)  # noqa: E731
        try:
            self._io.submit_nowait(lane.idx, fn)
        except EngineSaturated:
            threading.Thread(target=fn, daemon=True,
                             name=f"gc-overflow-{lane.idx}").start()

    def _adapt_window(self, lane: _Lane, size: int) -> None:
        """Coalescing pays per member: batches that actually merge
        stretch the window back toward the base; lone-member windows
        (arrivals slower than the window) shrink it — the early-close
        rule already caps fill at the live concurrency, so the window
        only matters for stragglers mid-submission."""
        if size >= 4:
            lane.cur_wait = min(self._max_wait, lane.cur_wait * 1.5)
        elif size <= 1:
            lane.cur_wait = max(_MIN_WAIT_S, lane.cur_wait * 0.7)

    def _solo(self, disk, op: GroupOp):
        if op.kind == "wm":
            disk.write_metadata(op.volume, op.path, op.fi)
        else:
            disk.rename_data(op.src_volume, op.src_path, op.fi,
                             op.volume, op.path)

    def _run_batch(self, lane: _Lane, batch: list) -> None:
        # Cull members whose budget is already spent: they fail ALONE
        # (DeadlineExceeded, counted) and never poison batch-mates.
        now = time.monotonic()
        live, dead = [], []
        for m in batch:
            if m.expires_at is not None and now >= m.expires_at - 1e-9:
                dead.append(m)
            else:
                live.append(m)
        if dead:
            with self._stat_mu:
                self._deadline_culls += len(dead)
            for m in dead:
                m.exc = DeadlineExceeded(
                    "request deadline exceeded before group commit")
                m.done = True
                m.latch.dec()
        if not live:
            return
        disk = self._disks[lane.idx]
        info: dict = {}
        t_wall = time.time()
        t0 = time.perf_counter()
        results = None
        batch_exc: Optional[BaseException] = None
        try:
            # The batch serves many requests with many budgets; the
            # health wrapper's own op timeout bounds the commit, and
            # the per-member deadlines were enforced at cull time.
            # The members' trees get this span by record_into below
            # (no context is bound on a lane's thread); here it goes
            # onto the profiler's clock.
            with deadline_mod.shield(), \
                    tracing.stage("commit.group", type_="storage",
                                  count=False):
                results = disk.commit_group([m.op for m in live],
                                            _info=info)
        except BaseException as e:  # noqa: BLE001 - delivered per member
            batch_exc = e
        demotions = 0
        for k, m in enumerate(live):
            err = batch_exc if results is None else results[k]
            if err is not None:
                # Member failure (or wholesale batch failure): demote
                # this member — and only it — to the solo path; its
                # own verdict is final.
                demotions += 1
                try:
                    with deadline_mod.shield():
                        self._solo(disk, m.op)
                    err = None
                except BaseException as e2:  # noqa: BLE001 - per member
                    err = e2
            m.exc = err
        dur_ms = (time.perf_counter() - t0) * 1000.0
        size = len(live)
        with self._stat_mu:
            self._batches += 1
            self._members += size
            self._objects += info.get("objects", 0)
            self._merged_members += info.get("merged", 0)
            self._noop_skips += info.get("noops", 0)
            self._fsyncs_saved += info.get("fsyncs_saved", 0)
            self._solo_demotions += demotions
            b = _size_bucket(size)
            self._size_buckets[b] = self._size_buckets.get(b, 0) + 1
        # ONE coalesced invalidation per distinct bucket, BEFORE any
        # member acks: readers that observe the PUT's return must not
        # be able to hit a stale cached fileinfo/listing (the same
        # before-return contract the per-request bump had). Group
        # commit runs on local-only sets, so the bump is an in-process
        # funnel call, never a cross-node push on this thread.
        if self.bump is not None:
            for bucket in sorted({m.op.volume for m in live
                                  if m.exc is None}):
                try:
                    self.bump(bucket)
                except Exception:  # noqa: BLE001 - listeners best-effort
                    pass
        for m in live:
            wait_s = max(0.0, t0 - m.t_enq)
            self._wait_hist.observe(wait_s)
            if m.tctx is not None:
                # ONE commit span fanned into each member's tree.
                tracing.record_into(
                    m.tctx, m.tparent, "storage", "commit.group",
                    t_wall, dur_ms,
                    tags={"drive": lane.name, "members": size,
                          "objects": info.get("objects", 0),
                          "wait_ms": round(wait_s * 1000.0, 3)})
            m.done = True
            m.latch.dec()
        self._adapt_window(lane, size)

    # -- lifecycle / observability --------------------------------------

    def close(self) -> None:
        with self._mu:
            self._closed = True
            self._mu.notify_all()
        # Final WAL checkpoint: a graceful stop leaves no live frames
        # for the next boot to replay; then the WAL fds close.
        for d in self._disks:
            for name in ("gc_checkpoint", "gc_close"):
                fn = getattr(d, name, None)
                if fn is not None:
                    try:
                        fn()
                    except Exception:  # noqa: BLE001 - close best effort
                        pass

    def stats(self) -> dict:
        with self._stat_mu:
            return {
                "name": self.name,
                "batches": self._batches,
                "members": self._members,
                "solo_bypass": self._solo_bypass,
                "objects": self._objects,
                "merged_members": self._merged_members,
                "noop_skips": self._noop_skips,
                "fsyncs_saved": self._fsyncs_saved,
                "deadline_culls": self._deadline_culls,
                "solo_demotions": self._solo_demotions,
                "size_buckets": dict(self._size_buckets),
                "wait_hist": self._wait_hist.state(),
                "fill_mean": (self._members / self._batches)
                if self._batches else 0.0,
            }

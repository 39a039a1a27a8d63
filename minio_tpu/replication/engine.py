"""Replication rules and the durable, ordered replication engine.

v2 of the bucket-replication plane (reference:
cmd/bucket-replication.go + the MRF/resync machinery around it).  The
v1 engine was a bounded in-memory queue.Queue: intents vanished on
SIGKILL, queue.Full counted silently as `failed`, retry backoff slept
ON the worker thread (one dead target wedged the pool), and versions
of one key delivered concurrently so the target's latest could be an
older source version.  This rebuild makes replication meet the same
survivability bar as the rest of the tree:

  * Durable queue — every intent lands in a per-node WAL
    (`<first-local-drive>/.mtpu.sys/repl/wal-p<pid>-<uid>.log`, the
    group-commit frame format: magic + crc32 + t_ns + msgpack) BEFORE
    enqueue returns to the PUT/DELETE handler; completions append a
    `done` marker; boot-time replay re-queues every incomplete intent
    (torn tails discarded — they were never acked).  Overflow past the
    admission cap spills to a persisted pending set (the MRF pattern)
    instead of dropping: `spilled` is lossless, `dropped` stays 0 and
    is the alertable counter.
  * Per-target lanes — each remote endpoint gets its own delivery lane
    with a circuit breaker mirroring grid/client.py (trip after N
    consecutive TRANSPORT faults, one half-open probe per cooldown,
    jittered doubling backoff across failed probes).  Retries and
    breaker re-probes are scheduled on a shared timer heap — no worker
    thread ever sleeps a backoff, so a dead target costs one fast
    failure per probe while healthy targets keep replicating.
  * Ordering — intents for one (bucket, key) serialize per lane in
    source-version order (mod_time, then enqueue seq): the target's
    latest is always the source's latest.  Delete markers replicate as
    versioned marker intents carrying the source marker's version id,
    never as anonymous bare deletes.
  * Resync — a checkpointed, resumable full-bucket sweep
    (`start_resync`) re-queues every version whose status is not
    COMPLETED; the scanner hook walks the FULL version stack (older
    stuck versions and delete markers included, not just versions[0]).

`MTPU_REPLICATION_DURABLE=off` reverts to the v1 in-memory plane:
no WAL, no breakers — only the v1 bug fixes remain (overflow spills
instead of dropping, retries ride the timer heap instead of sleeping
on the worker).
"""

from __future__ import annotations

import dataclasses
import heapq
import json
import os
import queue
import random
import struct
import threading
import time
import uuid as uuid_mod
import xml.etree.ElementTree as ET
import zlib
from typing import Optional

from minio_tpu.utils import tracing

REPL_STATUS_KEY = "x-internal-repl-status"
REMOTE_TARGET_META = "config:remote-target"
REPLICATION_META = "config:replication"

PENDING = "PENDING"
COMPLETED = "COMPLETED"
FAILED = "FAILED"

SYS_VOL = ".mtpu.sys"
WAL_DIR = "repl"
WAL_MAGIC = b"RPW1"
_FRAME_HEAD = struct.Struct("<I")        # crc32(body)
_FRAME_BODY_HEAD = struct.Struct("<QI")  # t_ns, payload length

_PERSIST_EVERY = 2.0      # pending-set persistence throttle (seconds)
_CKPT_EVERY = 64          # resync checkpoint cadence (keys)
_COMPACT_DONE = 256       # WAL compaction threshold (done marks)


def durable_enabled() -> bool:
    return os.environ.get("MTPU_REPLICATION_DURABLE", "on").lower() \
        not in ("0", "off", "false")


def _wal_fsync_enabled() -> bool:
    return os.environ.get("MTPU_REPL_WAL_FSYNC", "on").lower() \
        not in ("0", "off", "false")


def _env_num(name: str, default, cast=float):
    try:
        return cast(os.environ.get(name, ""))
    except (TypeError, ValueError):
        return default


class ReplicationError(Exception):
    pass


class BreakerOpen(ReplicationError):
    """Lane circuit open: fail fast, re-probe later (never a retry
    attempt — breaker waits are scheduling, not delivery failures)."""


@dataclasses.dataclass
class ReplicationRule:
    rule_id: str = ""
    enabled: bool = True
    prefix: str = ""
    delete_markers: bool = False

    def matches(self, key: str) -> bool:
        return self.enabled and key.startswith(self.prefix)


def parse_replication_xml(xml: bytes | str) -> list[ReplicationRule]:
    """ReplicationConfiguration XML -> rules (reference:
    internal/bucket/replication/replication.go)."""
    try:
        root = ET.fromstring(xml)
    except ET.ParseError as e:
        raise ReplicationError(f"malformed replication XML: {e}") from None
    for el in root.iter():
        if isinstance(el.tag, str) and "}" in el.tag:
            el.tag = el.tag.split("}", 1)[1]
    rules = []
    for rel in root.iter("Rule"):
        r = ReplicationRule()
        r.rule_id = rel.findtext("ID") or ""
        r.enabled = (rel.findtext("Status") or "Enabled") != "Disabled"
        filt = rel.find("Filter")
        r.prefix = (filt.findtext("Prefix") if filt is not None else None) \
            or rel.findtext("Prefix") or ""
        dmr = rel.find("DeleteMarkerReplication")
        if dmr is not None and (dmr.findtext("Status") or "") == "Enabled":
            r.delete_markers = True
        if rel.find("Destination") is None:
            raise ReplicationError("Rule missing Destination")
        rules.append(r)
    if not rules:
        raise ReplicationError("replication configuration has no rules")
    return rules


# ---------------------------------------------------------------------------
# Shared retry timer: backoffs and breaker re-probes live on ONE heap
# serviced by one daemon thread — a delivery worker never sleeps.
# ---------------------------------------------------------------------------

class RetryTimer:
    def __init__(self, name: str = "repl-timer"):
        self._cv = threading.Condition(threading.Lock())
        self._heap: list = []      # (due, tiebreak, fn)
        self._n = 0
        self._stopped = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._thread.start()

    def call_later(self, delay: float, fn) -> None:
        with self._cv:
            if self._stopped:
                return
            self._n += 1
            heapq.heappush(self._heap,
                           (time.monotonic() + max(0.0, delay),
                            self._n, fn))
            self._cv.notify()

    def pending(self) -> int:
        with self._cv:
            return len(self._heap)

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._stopped:
                    if self._heap:
                        wait = self._heap[0][0] - time.monotonic()
                        if wait <= 0:
                            break
                        self._cv.wait(wait)
                    else:
                        self._cv.wait()
                if self._stopped:
                    return
                _, _, fn = heapq.heappop(self._heap)
            try:
                fn()
            except Exception:  # noqa: BLE001 - timer must survive callbacks
                pass

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._heap.clear()
            self._cv.notify()
        self._thread.join(timeout=2)


# ---------------------------------------------------------------------------
# Per-lane circuit breaker (mirrors grid/client.py): consecutive
# TRANSPORT faults open it, one half-open probe per cooldown window,
# failed probes double the cooldown (jittered, bounded).
# ---------------------------------------------------------------------------

class LaneBreaker:
    PROBE_TTL = 30.0

    def __init__(self, trip_after: Optional[int] = None,
                 cooldown: Optional[float] = None,
                 cooldown_max: Optional[float] = None):
        self.trip_after = trip_after if trip_after is not None \
            else _env_num("MTPU_REPL_TRIP_AFTER", 3, int)
        self.cooldown = cooldown if cooldown is not None \
            else _env_num("MTPU_REPL_COOLDOWN", 0.5)
        self.cooldown_max = cooldown_max if cooldown_max is not None \
            else _env_num("MTPU_REPL_COOLDOWN_MAX", 15.0)
        self._mu = threading.Lock()
        self._consecutive = 0
        self._open_since = 0.0           # 0 = closed
        self._open_for = 0.0
        self._probe_streak = 0
        self._half_open_probe = False
        self._probe_started = 0.0
        self._probe_owner = 0
        self.opens_total = 0
        self.faults_total = 0

    def admit(self) -> None:
        with self._mu:
            if self._open_since == 0.0:
                return
            now = time.monotonic()
            if now - self._open_since < self._open_for:
                raise BreakerOpen("target circuit open")
            if self._half_open_probe and \
                    now - self._probe_started < self.PROBE_TTL:
                raise BreakerOpen("target circuit half-open, probing")
            self._half_open_probe = True
            self._probe_started = now
            self._probe_owner = threading.get_ident()

    def fault(self) -> None:
        with self._mu:
            self._consecutive += 1
            self.faults_total += 1
            if self._open_since != 0.0:
                # Failed half-open PROBE: restart the cooldown, doubled
                # (jittered, bounded).  Only the probe OWNER's failure
                # counts — stragglers admitted before the trip must not
                # inflate the backoff or release a live probe's slot.
                if not self._half_open_probe or \
                        self._probe_owner != threading.get_ident():
                    return
                self._half_open_probe = False
                self._probe_streak += 1
                self._open_since = time.monotonic()
                self._open_for = min(
                    self.cooldown * (2 ** self._probe_streak),
                    self.cooldown_max) * (0.75 + random.random() / 2)
            elif self._consecutive >= self.trip_after:
                self.opens_total += 1
                self._open_since = time.monotonic()
                self._probe_streak = 0
                self._open_for = self.cooldown * \
                    (0.75 + random.random() / 2)

    def ok(self) -> None:
        with self._mu:
            self._consecutive = 0
            self._open_since = 0.0
            self._open_for = 0.0
            self._probe_streak = 0
            self._half_open_probe = False

    def abstain(self) -> None:
        """The caller's attempt ended without a verdict on the
        transport (it failed before the wire, or the peer answered
        with an S3 error). If it was the half-open probe, the slot
        goes back now — the next chain probes at once — instead of
        parking the whole lane until PROBE_TTL."""
        with self._mu:
            if self._half_open_probe and \
                    self._probe_owner == threading.get_ident():
                self._half_open_probe = False

    def state(self) -> str:
        with self._mu:
            if self._open_since == 0.0:
                return "closed"
            if time.monotonic() - self._open_since >= self._open_for:
                return "half-open"
            return "open"

    def retry_in(self) -> float:
        """Suggested delay until the next admission attempt is worth
        making: the remaining cooldown while open, a short re-check
        while another thread holds the half-open probe."""
        with self._mu:
            if self._open_since == 0.0:
                return 0.0
            remaining = self._open_for - \
                (time.monotonic() - self._open_since)
            if remaining > 0:
                return remaining
            return min(0.25, self.cooldown)


# ---------------------------------------------------------------------------
# Durable intent WAL (the group-commit frame format: PR-14 pattern).
# ---------------------------------------------------------------------------

class ReplWAL:
    """Per-node replication intent log.

    Frames: `RPW1 | crc32(body) u32 | body = t_ns u64 | len u32 |
    msgpack payload`.  Intent payloads carry {seq,b,k,v,op,mt};
    completion payloads carry {done: seq}.  A torn tail (or alien
    bytes) ends replay — a torn frame was never any intent's
    durability point, so discarding it loses nothing acked.  Files are
    per-engine-instance (`wal-p<pid>-<uid>.log`); replay adopts every
    OTHER file in the directory (dead processes / prior boots),
    returns their incomplete intents, and unlinks them once the caller
    has re-logged the survivors into the live file."""

    def __init__(self, root: str, fsync: Optional[bool] = None):
        self.dir = os.path.join(root, SYS_VOL, WAL_DIR)
        os.makedirs(self.dir, exist_ok=True)
        self.path = os.path.join(
            self.dir,
            f"wal-p{os.getpid()}-{uuid_mod.uuid4().hex[:8]}.log")
        self.fsync = _wal_fsync_enabled() if fsync is None else fsync
        self._mu = threading.Lock()
        self._fd = os.open(self.path,
                           os.O_CREAT | os.O_WRONLY | os.O_APPEND, 0o644)
        self._live: dict[int, dict] = {}   # seq -> intent payload
        self._done_since_compact = 0
        self.appended = 0
        self.done_marks = 0
        self.discarded = 0
        self.compactions = 0

    # -- framing --------------------------------------------------------

    @staticmethod
    def _frame(payload: dict) -> bytes:
        import msgpack
        mp = msgpack.packb(payload, use_bin_type=True)
        body = _FRAME_BODY_HEAD.pack(time.time_ns(), len(mp)) + mp
        return WAL_MAGIC + _FRAME_HEAD.pack(zlib.crc32(body)) + body

    @staticmethod
    def iter_frames(blob: bytes):
        """Yield (t_ns, payload) per intact frame; stop at the first
        torn/alien bytes (the discard count is the StopIteration
        value, mirroring group_commit.iter_frames)."""
        import msgpack
        off = 0
        n = len(blob)
        while off < n:
            if blob[off:off + 4] != WAL_MAGIC:
                return 1
            head_end = off + 4 + _FRAME_HEAD.size
            if head_end + _FRAME_BODY_HEAD.size > n:
                return 1
            (crc,) = _FRAME_HEAD.unpack(blob[off + 4:head_end])
            t_ns, plen = _FRAME_BODY_HEAD.unpack(
                blob[head_end:head_end + _FRAME_BODY_HEAD.size])
            body_end = head_end + _FRAME_BODY_HEAD.size + plen
            if body_end > n:
                return 1
            body = blob[head_end:body_end]
            if zlib.crc32(body) != crc:
                return 1
            try:
                payload = msgpack.unpackb(
                    body[_FRAME_BODY_HEAD.size:], raw=False)
            except Exception:  # noqa: BLE001 - corrupt payload = torn
                return 1
            yield t_ns, payload
            off = body_end
        return 0

    # -- appends --------------------------------------------------------

    def _append_locked(self, payload: dict) -> None:
        os.write(self._fd, self._frame(payload))
        if self.fsync:
            try:
                os.fdatasync(self._fd)
            except OSError:
                pass

    def append_intent(self, rec: dict) -> None:
        with self._mu:
            self._append_locked(rec)
            self._live[rec["seq"]] = rec
            self.appended += 1

    def mark_done(self, seq: int) -> None:
        with self._mu:
            if self._live.pop(seq, None) is None:
                return
            self._append_locked({"done": seq})
            self.done_marks += 1
            self._done_since_compact += 1
            if self._done_since_compact >= _COMPACT_DONE:
                self._compact_locked()

    def _compact_locked(self) -> None:
        """Rewrite the WAL with only the live intents: done markers
        and their retired frames drop, so a long-lived process's WAL
        stays proportional to its backlog, not its history."""
        tmp = self.path + ".compact"
        fd = os.open(tmp, os.O_CREAT | os.O_WRONLY | os.O_TRUNC, 0o644)
        try:
            for rec in self._live.values():
                os.write(fd, self._frame(rec))
            if self.fsync:
                try:
                    os.fdatasync(fd)
                except OSError:
                    pass
        finally:
            os.close(fd)
        os.replace(tmp, self.path)
        os.close(self._fd)
        self._fd = os.open(self.path,
                           os.O_CREAT | os.O_WRONLY | os.O_APPEND, 0o644)
        self._done_since_compact = 0
        self.compactions += 1

    # -- replay ---------------------------------------------------------

    def replay_others(self) -> list[dict]:
        """Incomplete intents from every OTHER WAL file in the dir
        (earlier boots / SIGKILLed processes), oldest-first, deduped
        by (bucket, key, version, op).  Caller re-logs them through
        the normal enqueue path, then `retire_replayed` unlinks the
        source files."""
        out: list[tuple[int, dict]] = []
        self._replayed_files: list[str] = []
        try:
            names = sorted(os.listdir(self.dir))
        except OSError:
            return []
        for name in names:
            if not name.startswith("wal-") or not name.endswith(".log"):
                continue
            path = os.path.join(self.dir, name)
            if path == self.path:
                continue
            try:
                with open(path, "rb") as fh:
                    blob = fh.read()
            except OSError:
                continue
            live: dict[int, tuple[int, dict]] = {}
            it = self.iter_frames(blob)
            while True:
                try:
                    t_ns, payload = next(it)
                except StopIteration as stop:
                    self.discarded += stop.value or 0
                    break
                if "done" in payload:
                    live.pop(payload["done"], None)
                elif "seq" in payload:
                    live[payload["seq"]] = (t_ns, payload)
            out.extend(live.values())
            self._replayed_files.append(path)
        out.sort(key=lambda t: (t[0], t[1].get("seq", 0)))
        seen = set()
        recs = []
        for _, rec in out:
            idk = (rec.get("b"), rec.get("k"), rec.get("v"),
                   rec.get("op"))
            if idk in seen:
                continue
            seen.add(idk)
            recs.append(rec)
        return recs

    def retire_replayed(self) -> None:
        for path in getattr(self, "_replayed_files", []):
            try:
                os.unlink(path)
            except OSError:
                pass
        self._replayed_files = []

    def live_count(self) -> int:
        with self._mu:
            return len(self._live)

    def close(self) -> None:
        with self._mu:
            try:
                os.close(self._fd)
            except OSError:
                pass
            if not self._live:
                # Nothing incomplete: the file is pure history — drop
                # it so restarts replay only real backlogs.
                try:
                    os.unlink(self.path)
                except OSError:
                    pass


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

def _layer_sets(layer) -> list:
    pools = getattr(layer, "pools", None)
    if pools is not None:
        return [s for p in pools for s in p.sets]
    sets = getattr(layer, "sets", None)
    if sets is not None:
        return list(sets)
    return [layer] if hasattr(layer, "disks") else []


def _first_local_root(layer) -> Optional[str]:
    for es in _layer_sets(layer):
        for d in getattr(es, "disks", []):
            root = getattr(d, "root", None)
            if root:
                return root
    return None


class _Lane:
    """One remote target's delivery lane: per-key ordered chains plus
    the target's circuit breaker."""

    __slots__ = ("target", "chains", "active", "pending", "breaker",
                 "newest")

    def __init__(self, target: str, use_breaker: bool = True):
        self.target = target
        # (bucket, key) -> intents ordered by (mod_time, seq): the
        # chain head is the only deliverable intent of its key, so
        # versions serialize in source order per target.
        self.chains: dict[tuple, list] = {}
        self.active: set = set()
        self.pending = 0
        self.breaker = LaneBreaker() if use_breaker else None
        # Newest successfully-delivered version per live chain
        # (mod_time, version_id, op): when an out-of-order older
        # delivery ends a chain, the newest re-delivers so the
        # target's latest converges back to the source's latest.
        self.newest: dict[tuple, tuple] = {}


@dataclasses.dataclass
class _Intent:
    seq: int
    bucket: str
    key: str
    version_id: str
    op: str                   # "put" | "delete"
    mod_time: int = 0         # source version mod_time (ns); 0 unknown
    attempt: int = 0
    t_enq: float = 0.0        # monotonic enqueue stamp (lag histogram)

    @property
    def idk(self) -> tuple:
        return (self.bucket, self.key, self.version_id, self.op)

    def rec(self) -> dict:
        return {"seq": self.seq, "b": self.bucket, "k": self.key,
                "v": self.version_id, "op": self.op, "mt": self.mod_time}


class ReplicationEngine:
    """Per-server replication plane (see module docstring).

    object_layer: the local object layer (bucket meta + object reads +
    status updates).  Targets resolve from each bucket's stored remote
    target record ({endpoint, accessKey, secretKey, bucket}); clients
    cache per bucket.  SSE objects are not replicated (their data keys
    are bound to this cluster) — they mark FAILED immediately and
    count in `sse_skipped`."""

    _RETRIES = 5

    def __init__(self, object_layer, workers: int = 2,
                 durable: Optional[bool] = None):
        self.object_layer = object_layer
        self.durable = durable_enabled() if durable is None else durable
        self.queued = 0
        self.completed = 0
        self.failed = 0
        self.spilled = 0
        self.dropped = 0
        self.sse_skipped = 0
        self.replayed = 0
        self._clients: dict[str, tuple] = {}
        self._rules_cache: dict[str, tuple] = {}
        self._q_max = _env_num("MTPU_REPL_QUEUE_MAX", 100_000, int)
        self._mu = threading.Lock()
        self._lanes: dict[str, _Lane] = {}
        self._seen: set = set()
        self._spill: dict[tuple, dict] = {}
        self._spill_saved = 0.0
        self._unfinished = 0
        self._seq = 0
        from minio_tpu.utils.latency import Histogram
        self.lag_hist = Histogram()
        self._work: "queue.Queue[tuple]" = queue.Queue()
        self._stop = threading.Event()
        self.timer = RetryTimer()
        self._resyncs: dict[str, dict] = {}
        self._resync_threads: dict[str, threading.Thread] = {}
        # Durable state rides the first LOCAL drive (the events-store
        # location pattern); a layer with no local drive degrades to
        # the in-memory plane.
        self._root = _first_local_root(object_layer)
        self.wal: Optional[ReplWAL] = None
        if self.durable and self._root is None:
            self.durable = False
        if self.durable:
            self.wal = ReplWAL(self._root)
        self._threads = [threading.Thread(target=self._run, daemon=True,
                                          name=f"repl-{i}")
                         for i in range(workers)]
        for t in self._threads:
            t.start()
        self._load_spill()
        if self.wal is not None:
            self._replay_wal()
            self._resume_resyncs()

    # -- configuration ---------------------------------------------------

    def rules_for(self, bucket: str) -> Optional[list[ReplicationRule]]:
        try:
            doc = self.object_layer.get_bucket_meta(bucket) \
                .get(REPLICATION_META)
        except Exception:  # noqa: BLE001
            return None
        if not doc:
            return None
        # Parse once per distinct document — this runs on every PUT and
        # DELETE of a replicated bucket.
        hit = self._rules_cache.get(bucket)
        if hit is not None and hit[0] == doc:
            return hit[1]
        try:
            rules = parse_replication_xml(doc)
        except ReplicationError:
            rules = None
        self._rules_cache[bucket] = (doc, rules)
        return rules

    def target_for(self, bucket: str):
        """(RemoteS3 client, target bucket) or None."""
        try:
            doc = self.object_layer.get_bucket_meta(bucket) \
                .get(REMOTE_TARGET_META)
        except Exception:  # noqa: BLE001
            return None
        if not doc:
            return None
        hit = self._clients.get(bucket)
        if hit is not None and hit[0] == doc:
            return hit[1]
        try:
            rec = json.loads(doc)
            from minio_tpu.s3.client import RemoteS3
            client = RemoteS3(rec["endpoint"], rec["accessKey"],
                              rec["secretKey"])
            target = (client, rec.get("bucket", bucket))
        except (ValueError, KeyError):
            target = None
        self._clients[bucket] = (doc, target)
        return target

    def should_replicate(self, bucket: str, key: str,
                         delete: bool = False) -> bool:
        rules = self.rules_for(bucket)
        if not rules or self.target_for(bucket) is None:
            return False
        for r in rules:
            if r.matches(key):
                return not delete or r.delete_markers
        return False

    # -- ingestion -------------------------------------------------------

    def _lane_key(self, bucket: str) -> str:
        t = self.target_for(bucket)
        return t[0].address if t is not None else "?"

    def enqueue(self, bucket: str, key: str, version_id: str = "",
                op: str = "put", mod_time: int = 0) -> None:
        """Admit one replication intent.  Durable mode logs it to the
        WAL BEFORE returning — the caller's ack implies the intent
        survives SIGKILL.  Overflow past the admission cap spills to
        the persisted pending set (lossless) instead of dropping."""
        idk = (bucket, key, version_id, op)
        with self._mu:
            if idk in self._seen:
                return
            self._seen.add(idk)
            self._seq += 1
            seq = self._seq
        intent = _Intent(seq=seq, bucket=bucket, key=key,
                         version_id=version_id, op=op, mod_time=mod_time,
                         t_enq=time.monotonic())
        if self.wal is not None:
            # Rides the caller's request span tree when armed: the WAL
            # append (+fsync) sits on the PUT ack path, so a slow PUT
            # trace names the durability tax explicitly.
            with tracing.span("repl", "repl.wal_append",
                              {"bucket": bucket, "op": op}) \
                    if tracing.ACTIVE else tracing.NOOP:
                self.wal.append_intent(intent.rec())
        self._admit(intent)

    def _admit(self, intent: _Intent) -> None:
        lane_key = self._lane_key(intent.bucket)
        with self._mu:
            self.queued += 1
            self._unfinished += 1
            lane = self._lanes.get(lane_key)
            if lane is None:
                lane = self._lanes[lane_key] = _Lane(
                    lane_key, use_breaker=self.durable)
            if lane.pending >= self._q_max:
                # Overflow: spill (lossless, replayed on drain) — the
                # v1 plane counted this as `failed` and LOST the item.
                self._spill[intent.idk] = intent.rec()
                self.spilled += 1
                self._maybe_save_spill_locked()
                return
            self._chain_insert_locked(lane, intent)
        self._maybe_save_spill()

    def _chain_insert_locked(self, lane: _Lane, intent: _Intent) -> None:
        ck = (intent.bucket, intent.key)
        chain = lane.chains.get(ck)
        if chain is None:
            lane.chains[ck] = [intent]
            lane.pending += 1
            self._work.put((lane.target, ck))
            return
        # Source-version order: a resync-discovered OLDER version must
        # deliver before an already-queued newer one, or the target's
        # latest ends up older than the source's.  The head is only
        # pinned while a worker is actually delivering it.
        floor = 1 if ck in lane.active else 0
        pos = len(chain)
        while pos > floor and (intent.mod_time, intent.seq) < \
                (chain[pos - 1].mod_time, chain[pos - 1].seq):
            pos -= 1
        chain.insert(pos, intent)
        lane.pending += 1

    # -- spill persistence (MRF pattern) ---------------------------------

    def _spill_path(self) -> Optional[str]:
        if self._root is None:
            return None
        return os.path.join(self._root, SYS_VOL, WAL_DIR, "pending.json")

    def _maybe_save_spill_locked(self, force: bool = False) -> None:
        path = self._spill_path()
        if path is None:
            return
        now = time.monotonic()
        if not force and now - self._spill_saved < _PERSIST_EVERY:
            return
        if not self._spill:
            # Drained: a stale pending.json would re-enqueue already-
            # delivered intents at the next boot (an old PUT replayed
            # after a completed DELETE regresses the target's latest),
            # so remove the file rather than leave it behind.
            try:
                os.unlink(path)
            except OSError:
                pass
            self._spill_saved = 0.0
            return
        self._spill_saved = now
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump({"items": list(self._spill.values())}, fh)
            os.replace(tmp, path)
        except OSError:
            pass

    def _maybe_save_spill(self) -> None:
        with self._mu:
            if self._spill or self._spill_saved:
                self._maybe_save_spill_locked()

    def _load_spill(self) -> None:
        path = self._spill_path()
        if path is None:
            return
        try:
            with open(path, encoding="utf-8") as fh:
                items = json.load(fh).get("items") or []
        except (OSError, ValueError):
            return
        for rec in items:
            try:
                self.enqueue(rec["b"], rec["k"], rec.get("v", ""),
                             rec.get("op", "put"), rec.get("mt", 0))
            except Exception:  # noqa: BLE001 - malformed entry
                continue
        try:
            os.unlink(path)
        except OSError:
            pass

    def _refill_one(self) -> None:
        """Promote one spilled intent when a delivery frees room — the
        MRF `_refill_one` pattern."""
        with self._mu:
            if not self._spill:
                return
            idk, rec = next(iter(self._spill.items()))
        # Resolve the lane outside the lock (bucket-meta read).
        lane_key = self._lane_key(idk[0])
        with self._mu:
            lane = self._lanes.get(lane_key)
            if lane is not None and lane.pending >= self._q_max:
                return
            rec = self._spill.pop(idk, None)
            if rec is None:
                return
            if lane is None:
                lane = self._lanes[lane_key] = _Lane(
                    lane_key, use_breaker=self.durable)
            self._chain_insert_locked(lane, _Intent(
                seq=rec.get("seq", 0), bucket=rec["b"], key=rec["k"],
                version_id=rec.get("v", ""), op=rec.get("op", "put"),
                mod_time=rec.get("mt", 0), t_enq=time.monotonic()))
            # Keep the on-disk pending set in step with the pops
            # (forced on the drain-to-empty transition so the file is
            # removed, not left listing delivered intents).
            self._maybe_save_spill_locked(force=not self._spill)

    # -- WAL replay ------------------------------------------------------

    def _replay_wal(self) -> None:
        recs = self.wal.replay_others()
        for rec in recs:
            try:
                self.enqueue(rec["b"], rec["k"], rec.get("v", ""),
                             rec.get("op", "put"), rec.get("mt", 0))
                self.replayed += 1
            except Exception:  # noqa: BLE001 - malformed frame payload
                continue
        self.wal.retire_replayed()

    # -- delivery --------------------------------------------------------

    def _set_status(self, bucket, key, version_id, status,
                    allow_delete_marker: bool = False) -> bool:
        try:
            self.object_layer.update_version_metadata(
                bucket, key, version_id,
                lambda meta: meta.__setitem__(REPL_STATUS_KEY, status),
                allow_delete_marker=allow_delete_marker)
            return True
        except TypeError:
            # Layer without the allow_delete_marker parameter (older
            # wrapper): plain call, markers stay unstamped.
            try:
                self.object_layer.update_version_metadata(
                    bucket, key, version_id,
                    lambda meta: meta.__setitem__(REPL_STATUS_KEY, status))
                return True
            except Exception:  # noqa: BLE001 - status is advisory
                return False
        except Exception:  # noqa: BLE001 - status is advisory
            return False

    def _replicate_put(self, bucket, key, version_id) -> None:
        target = self.target_for(bucket)
        if target is None:
            raise ReplicationError("no remote target")
        client, tbucket = target
        from minio_tpu.replication.common import push_object
        push_object(self.object_layer, client, bucket, key,
                    version_id, tbucket)

    def _replicate_delete(self, bucket, key, version_id) -> None:
        target = self.target_for(bucket)
        if target is None:
            raise ReplicationError("no remote target")
        client, tbucket = target
        from minio_tpu.replication.common import push_delete_marker
        push_delete_marker(client, tbucket, key, version_id)

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                lane_key, ck = self._work.get(timeout=0.2)
            except queue.Empty:
                continue
            try:
                self._service(lane_key, ck)
            except Exception:  # noqa: BLE001 - worker must survive
                pass

    def _requeue_token(self, lane_key, ck) -> None:
        if not self._stop.is_set():
            self._work.put((lane_key, ck))

    def _service(self, lane_key: str, ck: tuple) -> None:
        if not tracing.ACTIVE:
            self._service_inner(lane_key, ck, tracing.NOOP)
            return
        # Armed: each delivery attempt is one standalone published span
        # chain (repl.deliver with lane-wait/breaker tags, repl.wire
        # for the target apply) so the lag histogram's p99 decomposes
        # into dequeue wait vs breaker park vs wire time.
        with tracing.op_span("repl", "repl.deliver",
                             {"target": lane_key}) as sp:
            self._service_inner(lane_key, ck, sp)

    def _service_inner(self, lane_key: str, ck: tuple, sp) -> None:
        with self._mu:
            lane = self._lanes.get(lane_key)
            if lane is None:
                return
            chain = lane.chains.get(ck)
            if not chain or ck in lane.active:
                return
            if lane.breaker is not None:
                try:
                    lane.breaker.admit()
                except BreakerOpen:
                    # Parked, not failed: the chain waits out the
                    # cooldown on the timer heap — no attempt burned,
                    # no worker blocked.
                    delay = lane.breaker.retry_in() or 0.05
                    sp.tag(breaker="open",
                           retry_in_ms=round(delay * 1000.0, 1))
                    self.timer.call_later(
                        delay, lambda: self._requeue_token(lane_key, ck))
                    return
            intent = chain[0]
            lane.active.add(ck)
        sp.tag(bucket=intent.bucket, key=intent.key, op=intent.op,
               attempt=intent.attempt + 1,
               lane_wait_ms=round(
                   (time.monotonic() - intent.t_enq) * 1000.0, 1)
               if intent.t_enq else 0.0)
        err: Optional[Exception] = None
        try:
            with tracing.span("repl", "repl.wire",
                              {"target": lane_key}) \
                    if tracing.ACTIVE else tracing.NOOP:
                if intent.op == "put":
                    self._replicate_put(intent.bucket, intent.key,
                                        intent.version_id)
                else:
                    self._replicate_delete(intent.bucket, intent.key,
                                           intent.version_id)
        except Exception as e:  # noqa: BLE001 - classified below
            err = e
            sp.tag(error=type(e).__name__)
        if err is None:
            self._finish(lane, ck, intent, ok=True)
            return
        from minio_tpu.replication.common import (DeliveryError,
                                                  is_transport_error)
        if lane.breaker is not None:
            if is_transport_error(err):
                lane.breaker.fault()
            else:
                lane.breaker.abstain()
        if isinstance(err, DeliveryError):
            # SSE (or otherwise non-replicable) version: terminal on
            # the first attempt, accounted separately from real
            # delivery failures.
            self.sse_skipped += 1
            self._finish(lane, ck, intent, ok=False)
            return
        intent.attempt += 1
        if intent.attempt < self._RETRIES and not self._stop.is_set():
            with self._mu:
                lane.active.discard(ck)
            # Off-thread backoff: the v1 plane slept this on the
            # worker (head-of-line blocking during target outages).
            delay = min(0.2 * 2 ** (intent.attempt - 1), 5.0)
            self.timer.call_later(
                delay, lambda: self._requeue_token(lane_key, ck))
            return
        self.failed += 1
        self._finish(lane, ck, intent, ok=False)

    def _finish(self, lane: _Lane, ck: tuple, intent: _Intent,
                ok: bool) -> None:
        """Terminal outcome for the chain-head intent: pop it, release
        the chain, stamp status, retire the WAL entry."""
        if ok and lane.breaker is not None:
            lane.breaker.ok()
        stamped = True
        if intent.op == "put":
            stamped = self._set_status(intent.bucket, intent.key,
                                       intent.version_id,
                                       COMPLETED if ok else FAILED)
        elif intent.version_id or not ok:
            # Versioned delete markers carry their own status so the
            # scanner can resync them like any stuck version.
            stamped = self._set_status(intent.bucket, intent.key,
                                       intent.version_id,
                                       COMPLETED if ok else FAILED,
                                       allow_delete_marker=True)
        if ok:
            self.completed += 1
            if intent.t_enq:
                self.lag_hist.observe(time.monotonic() - intent.t_enq)
        if self.wal is not None:
            if ok or intent.op == "put" or stamped:
                self.wal.mark_done(intent.seq)
            # A failed DELETE whose marker could not be stamped keeps
            # its WAL entry: with no durable status to drive the
            # scanner resync, replay is its only road back.
        refresh = None
        with self._mu:
            chain = lane.chains.get(ck)
            if chain and chain[0] is intent:
                chain.pop(0)
                lane.pending -= 1
            if ok:
                nm = lane.newest.get(ck)
                if nm is None or intent.mod_time > nm[0]:
                    lane.newest[ck] = (intent.mod_time,
                                       intent.version_id, intent.op)
            if not chain:
                lane.chains.pop(ck, None)
                # Chain drained on an out-of-order OLDER delivery (an
                # in-flight head pinned ahead of a late resync insert):
                # re-deliver the newest so the target's latest
                # converges back to the source's.
                nm = lane.newest.pop(ck, None)
                if ok and nm is not None and nm[0] > intent.mod_time:
                    refresh = nm
            lane.active.discard(ck)
            self._seen.discard(intent.idk)
            self._unfinished -= 1
            if lane.chains.get(ck):
                self._work.put((lane.target, ck))
        if refresh is not None:
            self.enqueue(intent.bucket, intent.key, refresh[1],
                         refresh[2], mod_time=refresh[0])
        self._refill_one()

    # -- resync (scanner hook) -------------------------------------------

    def scanner_hook(self, es, bucket: str, key: str, versions) -> None:
        """Re-queue versions stuck PENDING/FAILED (crash recovery /
        target-outage resync).  Walks the FULL version stack: older
        stuck versions and delete markers resync too, not just
        versions[0]."""
        del es
        if not versions:
            return
        rules = self.rules_for(bucket)
        if not rules or self.target_for(bucket) is None:
            return
        rule = next((r for r in rules if r.matches(key)), None)
        if rule is None:
            return
        for v in versions:
            meta = getattr(v, "metadata", None) or {}
            status = meta.get(REPL_STATUS_KEY, "")
            if status not in (PENDING, FAILED):
                continue
            if getattr(v, "deleted", False):
                if rule.delete_markers:
                    self.enqueue(bucket, key, v.version_id, "delete",
                                 mod_time=getattr(v, "mod_time", 0))
            elif not meta.get("x-internal-sse-alg"):
                # SSE objects never replicate: their FAILED state is
                # terminal, not resync fuel.
                self.enqueue(bucket, key, v.version_id, "put",
                             mod_time=getattr(v, "mod_time", 0))

    def ilm_deleted(self, bucket: str, key: str, deleted) -> None:
        """Lifecycle-created delete markers replicate like API deletes
        when the bucket's rules replicate markers (ILM expiry on the
        source must not strand a live latest on the target)."""
        if deleted is None or not getattr(deleted, "delete_marker", False):
            return
        if not self.should_replicate(bucket, key, delete=True):
            return
        vid = getattr(deleted, "delete_marker_version_id", "") or ""
        self._set_status(bucket, key, vid, PENDING,
                         allow_delete_marker=True)
        self.enqueue(bucket, key, vid, "delete", mod_time=time.time_ns())

    # -- full-bucket resync (checkpointed, resumable) --------------------

    def _resync_path(self, bucket: str) -> Optional[str]:
        if self._root is None:
            return None
        return os.path.join(self._root, SYS_VOL, WAL_DIR,
                            f"resync-{bucket}.json")

    def _save_resync(self, doc: dict) -> None:
        path = self._resync_path(doc["bucket"])
        if path is None:
            return
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(doc, fh)
            os.replace(tmp, path)
        except OSError:
            pass

    def start_resync(self, bucket: str) -> dict:
        """Kick (or resume) a full-bucket resync sweep: every version
        whose status is not COMPLETED re-queues, drive_heal-style
        checkpoint every 64 keys so a crashed sweep resumes where it
        stopped instead of at 'a'."""
        with self._mu:
            t = self._resync_threads.get(bucket)
            if t is not None and t.is_alive():
                return dict(self._resyncs[bucket])
            doc = self._resyncs.get(bucket)
            if doc is None or doc.get("state") != "running":
                # A FAILED sweep resumes at its last checkpoint (the
                # walk up to there already queued); done/fresh sweeps
                # start over.  `running` docs fall through above and
                # keep their own set/checkpoint.
                prior = doc if doc and doc.get("state") == "failed" \
                    else None
                doc = {"bucket": bucket, "state": "running",
                       "set": (prior or {}).get("set", 0),
                       "checkpoint": (prior or {}).get("checkpoint", ""),
                       "scanned": 0, "queued": 0,
                       "started": time.time(), "finished": 0.0}
            self._resyncs[bucket] = doc
            t = threading.Thread(target=self._resync_run,
                                 args=(bucket, doc), daemon=True,
                                 name=f"repl-resync-{bucket}")
            self._resync_threads[bucket] = t
        self._save_resync(doc)
        t.start()
        return dict(doc)

    def _resume_resyncs(self) -> None:
        """Boot-time pickup of sweeps that were mid-flight when the
        process died (state still `running` in the checkpoint doc)."""
        if self._root is None:
            return
        d = os.path.join(self._root, SYS_VOL, WAL_DIR)
        try:
            names = os.listdir(d)
        except OSError:
            return
        for name in names:
            if not name.startswith("resync-") or \
                    not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(d, name), encoding="utf-8") as fh:
                    doc = json.load(fh)
            except (OSError, ValueError):
                continue
            if isinstance(doc, dict) and doc.get("state") == "running" \
                    and doc.get("bucket"):
                with self._mu:
                    self._resyncs[doc["bucket"]] = doc
                self.start_resync(doc["bucket"])

    def _resync_run(self, bucket: str, doc: dict) -> None:
        from minio_tpu.object.scanner import walk_bucket_versions
        rules = self.rules_for(bucket) or []
        start_set = int(doc.get("set", 0))
        try:
            for i, es in enumerate(_layer_sets(self.object_layer)):
                if i < start_set:
                    # Finished before the crash/restart.
                    continue
                if i != start_set:
                    # Keys are hash-distributed across sets: each set's
                    # walk restarts at '' — carrying one set's (lexically
                    # late) checkpoint into the next would skip most of
                    # its keys.
                    doc["set"] = i
                    doc["checkpoint"] = ""
                    self._save_resync(doc)
                for path, versions in walk_bucket_versions(
                        es, bucket, forward_from=doc.get("checkpoint",
                                                         "")):
                    if self._stop.is_set():
                        return
                    doc["scanned"] += 1
                    # Delete-marker policy is per matching rule, same as
                    # scanner_hook — the first rule's prefix says nothing
                    # about keys under a later rule's.
                    rule = next((r for r in rules if r.matches(path)),
                                None)
                    for v in versions:
                        meta = getattr(v, "metadata", None) or {}
                        if meta.get(REPL_STATUS_KEY) == COMPLETED:
                            continue
                        if getattr(v, "deleted", False):
                            if rule is not None and rule.delete_markers:
                                self.enqueue(bucket, path, v.version_id,
                                             "delete",
                                             mod_time=v.mod_time)
                                doc["queued"] += 1
                        elif not meta.get("x-internal-sse-alg") and \
                                self.should_replicate(bucket, path):
                            if not meta.get(REPL_STATUS_KEY):
                                # Pre-config data has no stamp: mark it
                                # so the delivery's COMPLETED/FAILED
                                # transition has a base state.
                                self._set_status(bucket, path,
                                                 v.version_id, PENDING)
                            self.enqueue(bucket, path, v.version_id,
                                         "put", mod_time=v.mod_time)
                            doc["queued"] += 1
                    doc["checkpoint"] = path
                    if doc["scanned"] % _CKPT_EVERY == 0:
                        self._save_resync(doc)
            doc["state"] = "done"
        except Exception as e:  # noqa: BLE001 - surfaced in status
            doc["state"] = "failed"
            doc["error"] = str(e)[:300]
        doc["finished"] = time.time()
        self._save_resync(doc)

    def resync_status(self, bucket: Optional[str] = None):
        with self._mu:
            if bucket:
                doc = self._resyncs.get(bucket)
                return dict(doc) if doc else None
            return {b: dict(d) for b, d in self._resyncs.items()}

    # -- introspection ---------------------------------------------------

    def stats(self) -> dict:
        with self._mu:
            lanes = [{"target": ln.target,
                      "state": ln.breaker.state()
                      if ln.breaker is not None else "closed",
                      "pending": ln.pending,
                      "chains": len(ln.chains),
                      "breaker_opens": ln.breaker.opens_total
                      if ln.breaker is not None else 0}
                     for ln in self._lanes.values()]
            out = {"durable": self.durable,
                   "queued": self.queued,
                   "completed": self.completed,
                   "failed": self.failed,
                   "spilled": self.spilled,
                   "dropped": self.dropped,
                   "sse_skipped": self.sse_skipped,
                   "replayed": self.replayed,
                   "pending": self._unfinished,
                   "spill_backlog": len(self._spill),
                   "lanes": lanes,
                   "lag_hist": self.lag_hist.state()}
            if self._resyncs:
                out["resync"] = {b: dict(d)
                                 for b, d in self._resyncs.items()}
        if self.wal is not None:
            out["wal"] = {"path": self.wal.path,
                          "live": self.wal.live_count(),
                          "appended": self.wal.appended,
                          "done": self.wal.done_marks,
                          "discarded": self.wal.discarded,
                          "compactions": self.wal.compactions}
        return out

    def drain(self, timeout: float = 15.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._mu:
                if self._unfinished == 0:
                    return True
            time.sleep(0.05)
        return False

    def stop(self) -> None:
        self._stop.set()
        self.timer.stop()
        for t in self._threads:
            t.join(timeout=2)
        with self._mu:
            # Unconditional: an empty backlog must unlink any stale
            # pending.json, or the next boot replays delivered intents.
            self._maybe_save_spill_locked(force=True)
        if self.wal is not None:
            self.wal.close()

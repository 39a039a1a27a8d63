"""Erasure object store: one erasure set of n disks.

The analogue of the reference's erasureObjects (cmd/erasure-object.go):
object CRUD with quorum semantics over a set of StorageAPI drives.

Data path (PutObject, reference hot loop cmd/erasure-object.go:1249 +
cmd/erasure-encode.go:69): the whole object is batched into stripe
tensors and encoded in ONE device pass per object (full 1 MiB blocks in
one [B, k, L] batch, ragged tail in a second) instead of the
reference's block-at-a-time SIMD loop — the TPU-first reshape of the
same math. Shards are bitrot-framed (vectorized HighwayHash across all
shards x blocks), staged to tmp on every drive in parallel threads, and
committed with quorum-counted atomic rename (write quorum = k, +1 when
k == m, reference: cmd/erasure-object.go:1326-1330).

Read path (GetObject, reference: cmd/erasure-object.go:309 +
cmd/erasure-decode.go): quorum-pick the version from all drives'
journals, read the k preferred shards (data shards first), verify
bitrot per block, and only run the GF reconstruct when shards are
missing — batched across all blocks in one device call.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import threading
import time as _time_mod
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np

from minio_tpu.erasure.codec import CodecError, Erasure, ceil_frac
from minio_tpu.io.bufpool import global_pool
from minio_tpu.io.engine import EngineSaturated, IOEngine
from minio_tpu.ops import device
from minio_tpu.ops.batcher import batch_force_mode
from minio_tpu.utils import deadline as deadline_mod
from minio_tpu.utils import tracing
from minio_tpu.utils.deadline import DeadlineExceeded
from minio_tpu.object.types import (BucketExists, BucketInfo, BucketNotEmpty,
                                    BucketNotFound, DeleteOptions,
                                    DeletedObject, GetOptions, InvalidRange,
                                    MethodNotAllowed, ObjectInfo,
                                    ObjectNotFound, PutOptions,
                                    ReadQuorumError, VersionNotFound,
                                    WriteQuorumError)
from minio_tpu.storage import bitrot
from minio_tpu.storage.local import (SYS_VOL, StorageError, VolumeExists,
                                     VolumeNotEmpty, VolumeNotFound)
from minio_tpu.storage import meta as metafmt
from minio_tpu.storage.meta import (ErasureInfo, FileInfo, FileNotFoundErr,
                                    MetaError, ObjectPartInfo,
                                    VersionNotFoundErr, new_uuid, now_ns)
from minio_tpu.utils.streams import Payload

BLOCK_SIZE = 1 << 20          # reference blockSizeV2 (cmd/object-api-common.go:37)
SMALL_FILE_THRESHOLD = 128 << 10  # inline threshold (storage-class.go:278)
STAGING_PREFIX = "staging"
# O(block) streaming: objects larger than one window stream through the
# encoder in fixed 32-block (32 MiB) windows with double-buffered shard
# writers — the analogue of the reference's 1 MiB-block readahead
# pipeline (cmd/erasure-object.go:1415-1428), widened so each window is
# one batched device encode. Peak memory is O(window), never O(object).
STREAM_WINDOW_BLOCKS = 32
STREAM_THRESHOLD = STREAM_WINDOW_BLOCKS * BLOCK_SIZE
# Streamed GETs decode and yield this many plaintext bytes per step.
# 32 MiB = 32 erasure blocks: at EC:4 that is k*32 = 256 shard blocks
# per window, enough streams for device-batched bitrot verification
# (ops/hh_device.framed_digests_eligible).
GET_WINDOW_BYTES = 32 << 20

# PUTs below this many full erasure blocks encode on the host codec
# even when the set runs the TPU backend (see _encode_and_frame).
MIN_DEVICE_BLOCKS = 8

_RESERVED_BUCKETS = {SYS_VOL}


def new_staging() -> str:
    """A fresh staging dir path, pid-tagged (`staging/p<pid>-<uuid>`)
    so the boot janitor (storage/local.sweep_stale_tmp) can tell a LIVE
    sibling worker's in-flight PUT from a crash leftover and never
    sweep the former."""
    import os as _os
    return f"{STAGING_PREFIX}/p{_os.getpid()}-{new_uuid()}"


class _Md5Stream:
    """Streaming etag md5 for the windowed PUT loop: a native digest
    context updated GIL-free — and folded INTO the pooled frame call
    (mtpu_put_frame_md5) when the window takes that path — with
    hashlib as the fallback."""

    __slots__ = ("_lib", "_ctx", "_h", "_folded")

    def __init__(self):
        self._h = None
        self._ctx = None
        self._folded = False
        try:
            from minio_tpu import native
            lib = native.load()
            if lib is not None and hasattr(lib, "mtpu_digest_init"):
                import ctypes
                self._lib = lib
                self._ctx = (ctypes.c_uint8 * 128)()
                lib.mtpu_digest_init(0, self._ctx)
                return
        except Exception:  # noqa: BLE001 - loader failure -> hashlib
            pass
        self._lib = None
        self._h = hashlib.md5()

    @property
    def native_ctx(self):
        return self._ctx

    def mark_folded(self) -> None:
        self._folded = True

    def take_folded(self) -> bool:
        folded, self._folded = self._folded, False
        return folded

    def update(self, data) -> None:
        if self._ctx is not None:
            from minio_tpu import native
            self._lib.mtpu_digest_update(0, self._ctx, native._u8(data),
                                         len(data))
        else:
            self._h.update(data)

    def hexdigest(self) -> str:
        if self._ctx is not None:
            import ctypes
            out = (ctypes.c_uint8 * 16)()
            self._lib.mtpu_digest_final(0, self._ctx, out)
            return bytes(out).hex()
        return self._h.hexdigest()


@functools.lru_cache(maxsize=64)
def _framer_for(k: int, m: int):
    """Fused device encode+bitrot framer for one EC config (the PUT hot
    loop on TPU: RS parity, HighwayHash framing, and the on-disk byte
    layout in one device pipeline — ops/hh_device.make_encode_framer)."""
    from minio_tpu.ops.hh_device import make_encode_framer
    return make_encode_framer(_parity_matrix(k, m))


def _host_rows(k: int, m: int, stacked: np.ndarray) -> list[list]:
    """Host-codec equivalent of the fused framer's rows: per-drive
    lists over erasure blocks of (digest, block) piece tuples. Used as
    the stripe batcher's fallback (and its calibration rival)."""
    from minio_tpu.erasure.codec import _HOST
    b, _, shard = stacked.shape
    n = k + m
    if m:
        flat = np.ascontiguousarray(stacked.transpose(1, 0, 2)) \
            .reshape(k, b * shard)
        parity = np.asarray(_HOST.apply_matrix(_parity_matrix(k, m),
                                               flat)) \
            .reshape(m, b, shard).transpose(1, 0, 2)
    else:
        parity = np.zeros((b, 0, shard), dtype=np.uint8)
    blocks = np.concatenate([stacked, parity], axis=1)   # [B, n, S]
    digs = bitrot.hash_blocks_many(
        bitrot.DEFAULT_ALGORITHM, blocks.reshape(b * n, shard)) \
        .reshape(b, n, 32)
    return [[(digs[bi, i], blocks[bi, i]) for bi in range(b)]
            for i in range(n)]


@functools.lru_cache(maxsize=64)
def _mesh_framer_for(k: int, m: int):
    """Mesh-sharded cross-request framer for one EC config: the batch
    dim ("stripes from many requests") is pjit-sharded over every
    available chip with donated inputs (ops/hh_device.make_mesh_framer);
    degrades to the single-chip fused framer on one device."""
    from minio_tpu.ops.hh_device import make_mesh_framer
    return make_mesh_framer(_parity_matrix(k, m))


@functools.lru_cache(maxsize=64)
def _batcher_for(k: int, m: int):
    """Cross-request stripe batcher for one EC config: coalesces
    concurrent PUT windows into one mesh-wide device step when the
    measured device round trip beats the host codec (ops/batcher.py).
    Staging rides the global buffer pool so the coalesced window is
    one pooled host buffer donated into HBM."""
    from minio_tpu.ops.batcher import StripeBatcher
    return StripeBatcher(_mesh_framer_for(k, m),
                         functools.partial(_host_rows, k, m),
                         min_device_blocks=MIN_DEVICE_BLOCKS,
                         pool=global_pool(), name=f"{k}+{m}")


@functools.lru_cache(maxsize=64)
def _transform_batcher_for(k: int, m: int):
    """The fused transform plane's frame-stage batcher: same mesh
    framer / host-row rivalry as the PUT batcher, but a SEPARATE
    route ("transform") with its own calibration entry and
    MTPU_BATCH_FORCE pin — the transform pipeline's stored windows
    (post-compress/encrypt) coalesce and route on their own
    measurement, since their arrival pattern and sizes differ from raw
    PUT windows."""
    from minio_tpu.ops.batcher import StripeBatcher
    return StripeBatcher(_mesh_framer_for(k, m),
                         functools.partial(_host_rows, k, m),
                         min_device_blocks=MIN_DEVICE_BLOCKS,
                         pool=global_pool(), name=f"tf:{k}+{m}",
                         route="transform")


# -- the decode mirror: GET verify + reconstruct batchers -------------------

def _get_batch_min_blocks() -> int:
    try:
        v = int(os.environ.get("MTPU_GET_BATCH_MIN_BLOCKS", "")
                or MIN_DEVICE_BLOCKS)
        return v if v > 0 else MIN_DEVICE_BLOCKS
    except ValueError:
        return MIN_DEVICE_BLOCKS


def _host_deframe(stacked: np.ndarray):
    """Host twin of the device de-framer (the get batcher's fallback
    and calibration rival): vectorized HighwayHash of every framed
    block in `stacked` [B, k, 32+S], verdicts [B, k] plus the data
    payload as zero-copy views — field-identical to
    hh_device.make_mesh_deframer's run() + the get split_fn."""
    b, k, f = stacked.shape
    s = f - 32
    digs = bitrot.hash_blocks_many(
        bitrot.DEFAULT_ALGORITHM, stacked[:, :, 32:].reshape(b * k, s))
    want = stacked[:, :, :32].reshape(b * k, 32)
    ok = (digs == want).all(axis=1).reshape(b, k)
    return ok, stacked[:, :, 32:]


def _get_split(ok, off, c, member):
    """Demux one coalesced GET verify dispatch: the member's verdict
    rows plus its payload as views of its OWN framed window (the
    device returns only the B*k verdicts — blocks never ride the
    device->host link back)."""
    return ok[off:off + c], member[:, :, 32:]


def _interleave_window(out, data, out_len: int,
                       block_size: int) -> tuple[int, int]:
    """Write a verified window's payload `data` ([full, k, shard], any
    strides: `_get_split`'s view past each frame's digest) block-major
    into `out`: the first `block_size` bytes of each block's k x shard,
    up to `out_len`. The blocks `out` holds whole go in one strided
    assignment (two where k x shard overshoots the block: the last
    shard column is cut), so the copy hands the GIL back once or twice a
    window, not twice a block with a temporary between; a block that
    `out_len` cuts is copied alone. Returns (blocks placed in bulk,
    blocks copied alone)."""
    full, k, shard = data.shape
    n = min(full, out_len // block_size)
    if n:
        rows = out[:n * block_size].reshape(n, block_size)
        if k * shard == block_size:
            rows.reshape(n, k, shard)[...] = data[:n]
        else:
            head = (k - 1) * shard
            rows[:, :head].reshape(n, k - 1, shard)[...] = data[:n, :k - 1]
            rows[:, head:] = data[:n, k - 1, :block_size - head]
    pos = n * block_size
    if n == full or pos >= out_len:
        return n, 0
    _copy_block(out[pos:out_len], data[n])
    return n, 1


def _copy_block(dst, cols) -> None:
    """Copy one block's shard columns in order into `dst`, stopping at
    its end: column by column, no temporary."""
    pos = 0
    for col in cols:
        take = min(len(col), len(dst) - pos)
        if take <= 0:
            return
        dst[pos:pos + take] = col[:take]
        pos += take


def _get_concat(a, b):
    return (np.concatenate([a[0], b[0]]),
            np.concatenate([a[1], b[1]]))


@functools.lru_cache(maxsize=64)
def _get_batcher_for(k: int, m: int):
    """Cross-request GET verify batcher for one EC config: stacked
    framed windows [B, k, 32+shard] from concurrent GETs coalesce into
    one device de-framer dispatch (ops/hh_device.make_mesh_deframer)
    when the decode-route calibration says the device wins; the
    vectorized host hash is the byte-identical fallback. k == 1 is the
    shard-file verifier heal rides (one member per drive blob)."""
    from minio_tpu.ops.batcher import StripeBatcher
    from minio_tpu.ops.hh_device import make_mesh_deframer
    return StripeBatcher(make_mesh_deframer(k), _host_deframe,
                         min_device_blocks=_get_batch_min_blocks(),
                         pool=global_pool(), name=f"get:{k}+{m}",
                         route="get", split_fn=_get_split,
                         concat_fn=_get_concat)


def _host_apply_rows(rows: np.ndarray, stacked: np.ndarray) -> np.ndarray:
    """Host GF application of `rows` [r, k] to a stripe batch
    [B, k, S] -> [B, r, S] (the reconstruct batcher's fallback): the
    transform is per byte column, so the batch flattens into one wide
    host-codec call."""
    from minio_tpu.erasure.codec import _HOST
    b, k, s = stacked.shape
    flat = np.ascontiguousarray(stacked.transpose(1, 0, 2)) \
        .reshape(k, b * s)
    out = np.asarray(_HOST.apply_matrix(rows, flat))
    return np.ascontiguousarray(
        out.reshape(rows.shape[0], b, s).transpose(1, 0, 2))


@functools.lru_cache(maxsize=256)
def _reconstruct_batcher_for(k: int, m: int, use: tuple,
                             missing_data: tuple):
    """Batched device reconstruct for one (EC config, surviving-shard
    set): degraded-read windows stack their survivors [B, k, S] and the
    decode-matrix rows for the missing data shards apply across the
    mesh in one dispatch (ops/rs_device.make_mesh_matrix). One batcher
    per survivor set — the common case is exactly one set per dead
    drive, so concurrent degraded GETs of that drive's objects coalesce
    cross-request just like healthy-path PUT/GET windows."""
    from minio_tpu.ops import gf256
    from minio_tpu.ops.batcher import StripeBatcher
    from minio_tpu.ops.rs_device import make_mesh_matrix
    dec = gf256.decode_matrix(k, m, use)
    rows = np.ascontiguousarray(dec[list(missing_data), :])
    return StripeBatcher(
        make_mesh_matrix(rows), functools.partial(_host_apply_rows, rows),
        min_device_blocks=_get_batch_min_blocks(),
        pool=global_pool(),
        name=f"rec:{k}+{m}:" + ",".join(map(str, use)),
        route="reconstruct",
        split_fn=lambda out, off, c, _member: out[off:off + c],
        concat_fn=lambda a, b: np.concatenate([a, b]))


def default_parity(set_size: int) -> int:
    """Default EC parity by set size (reference storage-class defaults:
    internal/config/storageclass/storage-class.go:355-367):
    1 drive -> 0, 2-3 -> 1, 4-5 -> 2, 6-7 -> 3, 8+ -> 4."""
    if set_size == 1:
        return 0
    if set_size <= 3:
        return 1
    if set_size <= 5:
        return 2
    if set_size <= 7:
        return 3
    return 4


def hash_order(key: str, cardinality: int) -> list[int]:
    """Deterministic shard distribution for a key: a rotation of
    [1..cardinality] starting at crc32(key) % cardinality (behavioural
    equivalent of the reference's hashOrder spread,
    cmd/erasure-metadata-utils.go:178)."""
    if cardinality <= 0:
        return []
    start = zlib.crc32(key.encode()) % cardinality
    return [1 + (start + i) % cardinality for i in range(cardinality)]


class ErasureSet:
    """One erasure set over n drives (LocalStorage or remote clients)."""

    def __init__(self, disks: Sequence, parity: Optional[int] = None,
                 backend=None, pool: Optional[ThreadPoolExecutor] = None):
        self.disks = list(disks)
        n = len(self.disks)
        if parity is not None and not 0 <= parity <= n // 2:
            # Parity above n/2 makes write quorum (k) smaller than read
            # quorum (n/2): acknowledged writes could be unreadable and
            # then purged as dangling. The reference rejects it in
            # storage-class config validation
            # (internal/config/storageclass/storage-class.go).
            raise ValueError(
                f"parity {parity} out of range for {n} drives "
                f"(need 0 <= parity <= {n // 2})")
        self.default_parity = default_parity(n) if parity is None else parity
        self.backend = backend
        self.pool = pool or ThreadPoolExecutor(max_workers=max(8, 2 * n))
        # Per-drive submission queues (io/engine.py): aligned fan-outs
        # ride these fixed crews instead of the shared pool, so one
        # drive's backlog convoys only itself and depth stays bounded.
        self.io = IOEngine([getattr(d, "endpoint", "") or str(i)
                            for i, d in enumerate(self.disks)])
        from minio_tpu.object.nslock import NSLockMap
        self.ns = NSLockMap()
        self._mrf = None
        self._mrf_lock = __import__("threading").Lock()
        # Warm-tier registry (object/tier.TierRegistry); None = no
        # tiering configured. Set at boot, shared across sets.
        self.tiers = None
        # Listing page cache with write invalidation (metacache).
        from minio_tpu.object.metacache import MetaCache
        self.metacache = MetaCache()
        # Quorum-fileinfo cache: repeat GET/HEAD of a key serves the
        # quorum-agreed (fi, fis) from memory instead of a k-drive
        # read_version fan-out. Invalidation rides the metacache bump
        # funnel (every namespace mutation already goes through it);
        # pre-forked workers additionally attach a shared-generation
        # observer (io/workers._wire_set).
        from minio_tpu.object.fi_cache import FileInfoCache
        self.fi_cache = FileInfoCache()
        self.metacache.listeners.append(self.fi_cache.invalidate_bucket)
        self._remote_set = any(
            _unwrap_disk(d).__class__.__module__
            == "minio_tpu.storage.remote"
            for d in self.disks if d is not None)
        if self._remote_set:
            # Distributed set: the cache stays ENABLED, gated on the
            # cross-node generation protocol (grid/coherence). The
            # distributed boot replaces this deny-all sentinel with
            # the live PeerCoherence.coherent gate; until then (and on
            # bare remote sets built without the protocol) lookups
            # answer misses — correct, just uncached — instead of
            # hits no invalidation contract covers.
            self.fi_cache.remote_gate = lambda: False
            self.metacache.remote_gate = lambda: False
        # Group-commit lanes (storage/group_commit): concurrent
        # small-object journal commits coalesce per drive into one
        # WAL-backed batch — the metadata twin of the stripe batcher.
        # Local sets only: every drive must implement the batched
        # commit protocol (remote drives and fault doubles that would
        # lose their injection seam fall back to the solo fan-out).
        from minio_tpu.storage import group_commit as gc_mod
        self.group_commit = None
        if gc_mod.enabled() and not self._remote_set and \
                all(_group_commit_capable(d) for d in self.disks):
            self.group_commit = gc_mod.GroupCommit(
                self.disks, self.io,
                name=f"set:{id(self) & 0xffff:x}")
            # One coalesced invalidation per batch per bucket, through
            # the same metacache-bump funnel per-request mutations use
            # (fi_cache listeners + worker shared-gen observers ride
            # along), fired BEFORE any member acks.
            self.group_commit.bump = self.metacache.bump
        # Read-kernel counters (admin info): windows served by the
        # fused native GET kernel, by the numpy path, and native
        # verifies that demoted to reconstruction. Incremented from
        # concurrent request/prefetch threads — dict += is a
        # read-modify-write, so a lock keeps the counts honest.
        self.get_kernel = {"native": 0, "numpy": 0, "demoted": 0,
                           "device": 0}
        # Beside them, under the same lock: what a multi-window GET's
        # windows waited in `self.pool`'s queue (submit to the read's
        # first instruction: [seconds, windows]), the fetched shards
        # the rebuild path's verify refused for bitrot, and the device
        # windows' blocks interleaved into answers: by the strided
        # bulk copy, or alone (a cut last block, a ragged tail).
        self.get_pool_wait = [0.0, 0]
        self.get_survivors_refused = 0
        self.get_interleave_blocks = {"bulk": 0, "block": 0}
        self._gk_mu = threading.Lock()

    def close(self) -> None:
        """Release the set's background resources (fan-out executor,
        MRF worker). Repeated boot/stop cycles — sidecars, tests —
        would otherwise leak 8+ threads per lifecycle (caught by the
        leak harness, tests/test_leak_race.py)."""
        self.stop_mrf()
        if self.group_commit is not None:
            # Final WAL checkpoint rides along: graceful stops leave no
            # group-commit WALs for the next boot to replay.
            self.group_commit.close()
        self.pool.shutdown(wait=False)
        self.io.close()

    def stop_mrf(self, timeout: float = 2.0) -> bool:
        """Stop the MRF heal worker while the set still works under it;
        True when no heal is in flight any more (MRFQueue.stop). Under
        _mrf_lock with a closed sentinel: a racing lazy `mrf` access
        must not start a fresh worker after this looked."""
        with self._mrf_lock:
            self._mrf_closed = True
            return self._mrf is None or self._mrf.stop(timeout)

    @property
    def mrf(self):
        """Lazy MRF heal queue (background worker starts on first use).
        After close(), enqueues go to a stopped queue (accepted but not
        worked — the set is going away) instead of starting a worker."""
        if self._mrf is None:
            with self._mrf_lock:
                if self._mrf is None:
                    from minio_tpu.object.healing import MRFQueue
                    q = MRFQueue(self)
                    if getattr(self, "_mrf_closed", False):
                        q.stop()
                    self._mrf = q
        return self._mrf

    # -- healing entry points ------------------------------------------

    def heal_object(self, bucket: str, object_: str, version_id: str = "",
                    deep: bool = False):
        from minio_tpu.object import healing
        return healing.heal_object(self, bucket, object_, version_id,
                                   deep=deep)

    def heal_bucket(self, bucket: str):
        from minio_tpu.object import healing
        return healing.heal_bucket(self, bucket)

    # -- multipart (object/multipart.py) -------------------------------

    def new_multipart_upload(self, bucket, object_, opts=None):
        from minio_tpu.object import multipart
        return multipart.new_multipart_upload(self, bucket, object_, opts)

    def put_object_part(self, bucket, object_, upload_id, part_number, data,
                        actual_size=None, nonce=""):
        from minio_tpu.object import multipart
        return multipart.put_object_part(self, bucket, object_, upload_id,
                                         part_number, data,
                                         actual_size=actual_size,
                                         nonce=nonce)

    def get_multipart_upload(self, bucket, object_, upload_id):
        from minio_tpu.object import multipart
        return multipart.get_multipart_upload(self, bucket, object_,
                                              upload_id)

    def complete_multipart_upload(self, bucket, object_, upload_id, parts):
        from minio_tpu.object import multipart
        return multipart.complete_multipart_upload(self, bucket, object_,
                                                   upload_id, parts)

    def abort_multipart_upload(self, bucket, object_, upload_id):
        from minio_tpu.object import multipart
        return multipart.abort_multipart_upload(self, bucket, object_,
                                                upload_id)

    def list_parts(self, bucket, object_, upload_id, part_marker=0,
                   max_parts=1000):
        from minio_tpu.object import multipart
        return multipart.list_parts(self, bucket, object_, upload_id,
                                    part_marker, max_parts)

    def list_multipart_uploads(self, bucket, prefix=""):
        from minio_tpu.object import multipart
        return multipart.list_multipart_uploads(self, bucket, prefix)

    # ------------------------------------------------------------------
    # fan-out helper
    # ------------------------------------------------------------------

    # Grace added to the request deadline when collecting fan-out
    # futures: the per-op deadline inside the worker (health wrapper,
    # grid call) is the precise one and should fire first; this bound
    # only catches workers on raw, unwrapped drives that can hang.
    _FANOUT_DEADLINE_SLOP = 0.25

    def _fanout(self, fns):
        """Run one callable per disk in parallel; returns (results, errors).

        A fns list aligned with self.disks (the common case: one op per
        drive) routes each entry through that drive's engine queue
        (io/engine.py) — bounded depth, fixed crew; anything else
        (subset cleanups, ad-hoc shapes) uses the shared pool. Jobs are
        fire-and-forget into shared result slots with ONE countdown
        latch for collection (one caller wait per fan-out, not one per
        drive — future-per-op handoff cost is real at 12+ drives). The
        caller's request deadline (utils/deadline.py) is re-bound
        inside each worker thread — thread locals do not cross the pool
        boundary on their own — and bounds the collection wait, so one
        hung drive can never hold the whole request past its budget."""
        dl = deadline_mod.current()
        n = len(fns)
        if dl is not None and dl.expired():
            # Budget already spent: answer without touching any drive.
            err = DeadlineExceeded("request deadline exceeded")
            return [None] * n, [err] * n

        results: list = [None] * n
        errors: list = [None] * n
        done: list = [False] * n
        pending = sum(1 for fn in fns if fn)
        if pending == 0:
            return results, [StorageError("disk offline")] * n
        all_done = threading.Event()
        latch_mu = threading.Lock()
        latch = [pending]

        def finish_one():
            with latch_mu:
                latch[0] -= 1
                if latch[0] == 0:
                    all_done.set()

        # Trace scope crosses the pool boundary the same way the
        # deadline does: captured here, re-bound in the worker. The
        # per-drive span wraps the whole queued op and carries the
        # queue-wait vs in-span (service) split — the child storage
        # span (health wrapper) then names the concrete disk op.
        tctx, tparent = tracing.capture() if tracing.ACTIVE else (None, 0)

        def make_job(i, fn):
            t_sub = _time_mod.perf_counter() if tctx is not None else 0.0

            def run():
                try:
                    with deadline_mod.bind(dl), \
                            tracing.bind(tctx, tparent):
                        tags = None
                        if tctx is not None:
                            wait_ms = (_time_mod.perf_counter() - t_sub) \
                                * 1000.0
                            tags = {"drive": i,
                                    "queue_wait_ms": round(wait_ms, 3)}
                        # No stage counter: drive_op_duration_seconds
                        # already counts the op.
                        with tracing.stage("engine.op", tags,
                                           type_="storage", count=False):
                            results[i] = fn()
                except BaseException as e:  # noqa: BLE001 - per-disk isolation
                    errors[i] = e
                finally:
                    done[i] = True
                    finish_one()
            return run

        per_drive = n == len(self.disks)
        for i, fn in enumerate(fns):
            if not fn:
                errors[i] = StorageError("disk offline")
                continue
            job = make_job(i, fn)
            if per_drive:
                try:
                    self.io.submit_nowait(i, job)
                except EngineSaturated as e:
                    # A saturated drive queue is a drive fault for THIS
                    # op: surfaced per disk, counted against quorum.
                    errors[i] = StorageError(str(e))
                    done[i] = True
                    finish_one()
            else:
                self.pool.submit(job)
        # One ABSOLUTE collection deadline for the whole fan-out: the
        # slop must not stack per hung worker, or n stuck drives
        # overshoot the budget n times over.
        if dl is None:
            all_done.wait()
        else:
            collect_by = dl.expires_at + self._FANOUT_DEADLINE_SLOP
            if not all_done.wait(timeout=max(
                    0.0, collect_by - _time_mod.monotonic())):
                # Workers stuck on something that ignores deadlines:
                # mark their slots and leave them to finish unobserved
                # (late completions write results nobody reads — the
                # snapshot below is what callers see).
                for i in range(n):
                    if fns[i] and not done[i]:
                        errors[i] = DeadlineExceeded(
                            "request deadline exceeded in drive fan-out")
        return list(results), list(errors)

    def _cleanup_fanout(self, fns):
        """Best-effort rollback/cleanup fan-out, SHIELDED from the
        request deadline (utils/deadline.shield): a request whose
        budget just expired still must not leave partially committed
        versions or staged shard files behind — skipping the rollback
        because the request timed out would create exactly the partial
        state the rollback exists to remove."""
        with deadline_mod.shield():
            return self._fanout(fns)

    # ------------------------------------------------------------------
    # buckets
    # ------------------------------------------------------------------

    def make_bucket(self, bucket: str) -> None:
        if bucket in _RESERVED_BUCKETS:
            raise BucketExists(bucket)
        results, errors = self._fanout(
            [lambda d=d: d.make_vol(bucket) for d in self.disks])
        quorum = len(self.disks) // 2 + 1
        if sum(e is None for e in errors) < quorum:
            if any(isinstance(e, VolumeExists) for e in errors):
                raise BucketExists(bucket)
            raise WriteQuorumError(bucket)
        # Heal disks that failed transiently so the set stays consistent.
        self._cleanup_fanout([lambda d=d: _swallow(
            lambda: d.make_vol_if_missing(bucket))
            for d, e in zip(self.disks, errors) if e is not None])

    def get_bucket_info(self, bucket: str) -> BucketInfo:
        results, errors = self._fanout(
            [lambda d=d: d.stat_vol(bucket) for d in self.disks])
        ok = [r for r in results if r is not None]
        if not ok:
            raise BucketNotFound(bucket)
        return BucketInfo(name=bucket, created=min(v.created for v in ok))

    def list_buckets(self) -> list[BucketInfo]:
        results, _ = self._fanout([lambda d=d: d.list_vols() for d in self.disks])
        seen: dict[str, int] = {}
        for vols in results:
            for v in vols or ():
                if v.name not in seen or v.created < seen[v.name]:
                    seen[v.name] = v.created
        return [BucketInfo(name=n, created=c)
                for n, c in sorted(seen.items())]

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        results, errors = self._fanout(
            [lambda d=d: d.delete_vol(bucket, force=force) for d in self.disks])
        if any(isinstance(e, VolumeNotEmpty) for e in errors):
            raise BucketNotEmpty(bucket)
        if all(isinstance(e, VolumeNotFound) for e in errors):
            raise BucketNotFound(bucket)
        ok = sum(e is None or isinstance(e, VolumeNotFound) for e in errors)
        if ok < len(self.disks) // 2 + 1:
            raise WriteQuorumError(bucket)
        # Drop bucket metadata so a recreated bucket starts fresh
        # (versioning state must not survive deletion).
        self.invalidate_bucket_meta(bucket)
        self.metacache.drop_bucket(bucket)
        self._cleanup_fanout([lambda d=d: _swallow(
            lambda: d.delete(SYS_VOL, f"buckets/{bucket}", recursive=True))
            for d in self.disks])

    # -- bucket metadata (versioning etc.; full subsystem arrives with
    #    IAM/policies — stored as quorum-replicated JSON under SYS_VOL,
    #    the shape of the reference's .minio.sys bucket metadata) --------

    def _bucket_meta_path(self, bucket: str) -> str:
        return f"buckets/{bucket}/bucket-meta.json"

    _BUCKET_META_TTL = 2.0

    def get_bucket_meta(self, bucket: str) -> dict:
        """Quorum-voted bucket metadata with an in-memory TTL cache
        (the reference caches bucket metadata cluster-wide; without a
        cache every object write pays an n-drive metadata fan-out).

        Local-only sets get a long TTL: in-process mutations call
        invalidate_bucket_meta directly and pre-forked siblings are
        covered by the meta generation file (io/workers._wire_set), so
        the TTL is not a coherence mechanism there — the short 2 s
        window is kept only for distributed sets, where a PEER node's
        bucket-meta write reaches us through best-effort invalidation
        and the TTL is the backstop."""
        import time as _time
        cache = getattr(self, "_bmeta_cache", None)
        if cache is None:
            cache = self._bmeta_cache = {}
        ttl = self._BUCKET_META_TTL if getattr(self, "_remote_set", True) \
            else 60.0
        hit = cache.get(bucket)
        if hit is not None and _time.monotonic() - hit[0] < ttl:
            return hit[1]
        meta = self._get_bucket_meta_uncached(bucket)
        cache[bucket] = (_time.monotonic(), meta)
        return meta

    def _get_bucket_meta_uncached(self, bucket: str) -> dict:
        import json
        results, _ = self._fanout(
            [lambda d=d: d.read_all(SYS_VOL, self._bucket_meta_path(bucket))
             for d in self.disks])
        votes: dict[bytes, int] = {}
        for r in results:
            if r is not None:
                votes[r] = votes.get(r, 0) + 1
        if not votes:
            return {}
        blob = max(votes, key=lambda b: votes[b])
        try:
            return json.loads(blob)
        except ValueError:
            return {}

    def set_bucket_meta(self, bucket: str, meta: dict) -> None:
        import json
        blob = json.dumps(meta, sort_keys=True).encode()
        _, errors = self._fanout(
            [lambda d=d: d.write_all(SYS_VOL, self._bucket_meta_path(bucket),
                                     blob) for d in self.disks])
        self.invalidate_bucket_meta(bucket)
        if sum(e is None for e in errors) < len(self.disks) // 2 + 1:
            raise WriteQuorumError(bucket)

    def invalidate_bucket_meta(self, bucket: str = "") -> None:
        """Drop the TTL cache for one bucket ("" = all): the peer
        control plane calls this when another node rewrites bucket
        metadata, so policy/versioning changes take effect here
        immediately instead of after the TTL."""
        for cache in (getattr(self, "_bmeta_cache", None),
                      getattr(self, "_bexists_cache", None)):
            if cache is None:
                continue
            if bucket:
                cache.pop(bucket, None)
            else:
                cache.clear()

    def bucket_versioning(self, bucket: str) -> bool:
        return bool(self.get_bucket_meta(bucket).get("versioning"))

    def set_bucket_versioning(self, bucket: str, status) -> None:
        """status: True/"Enabled", "Suspended", or False (off).
        Suspension is a distinct state (null-versionId writes replace
        the null version; Enabled-era versions survive) — both keys
        are managed here so every caller keeps them consistent."""
        meta = self.get_bucket_meta(bucket)
        meta["versioning"] = status is True or status == "Enabled"
        meta["versioning-suspended"] = status == "Suspended"
        self.set_bucket_meta(bucket, meta)

    def _check_bucket(self, bucket: str) -> None:
        """Bucket existence, positive-cached for the metadata TTL: the
        reference answers this from its in-memory bucket metadata system
        rather than statting every drive per request — a per-PUT
        n-drive stat fan-out costs more than the GF encode. Deletions
        invalidate via invalidate_bucket_meta (local and peer paths)."""
        import time as _time
        if bucket in _RESERVED_BUCKETS:
            raise BucketNotFound(bucket)
        cache = getattr(self, "_bexists_cache", None)
        if cache is None:
            cache = self._bexists_cache = {}
        deadline = cache.get(bucket)
        if deadline is not None and _time.monotonic() < deadline:
            return
        results, _ = self._fanout(
            [lambda d=d: d.stat_vol(bucket) for d in self.disks])
        if not any(r is not None for r in results):
            raise BucketNotFound(bucket)
        cache[bucket] = _time.monotonic() + self._BUCKET_META_TTL

    # ------------------------------------------------------------------
    # quorum metadata
    # ------------------------------------------------------------------

    def _read_version_all(self, bucket: str, object_: str, version_id: str,
                          read_data: bool = False):
        return self._fanout(
            [lambda d=d: d.read_version(bucket, object_, version_id,
                                        read_data=read_data)
             for d in self.disks])

    @staticmethod
    def _quorum_fileinfo(fis: list, quorum: int):
        """Pick the version agreed by >= quorum disks (reference:
        findFileInfoInQuorum keyed on mod-time + data layout)."""
        groups: dict[tuple, list[int]] = {}
        for i, fi in enumerate(fis):
            if fi is None:
                continue
            key = (fi.mod_time, fi.storage_version_id(), fi.data_dir,
                   fi.deleted, fi.size)
            groups.setdefault(key, []).append(i)
        best = None
        for key, idxs in groups.items():
            if len(idxs) >= quorum:
                if best is None or key[0] > best[0][0]:
                    best = (key, idxs)
        if best is None:
            return None, []
        return fis[best[1][0]], best[1]

    def _get_object_fileinfo(self, bucket: str, object_: str,
                             version_id: str = "", read_data: bool = False,
                             stat_only: bool = False):
        """(fi, per-disk fis, errors) with read-quorum enforcement.

        Repeat lookups of an unchanged key are memory hits in the
        fileinfo cache — zero drive calls; the token protocol makes
        the insert race-free against concurrent mutations (see
        object/fi_cache.py). Only fully-healthy reads (every drive
        answered, quorum found) are cached: a degraded read must keep
        re-reading so heal progress is observed and the MRF hook in
        callers keeps firing.

        `stat_only` is the HEAD path: lookups and inserts ride the
        cache's large stat class (quorum fi only — fis comes back
        None), so metadata storms at high key cardinality neither
        evict the GET fast path's data-class entries nor pay repeat
        fan-outs."""
        if stat_only:
            fi = self.fi_cache.get_stat(bucket, object_, version_id)
            if fi is not None:
                return fi, None, [None] * len(self.disks)
        else:
            cached = self.fi_cache.get(bucket, object_, version_id,
                                       need_data=read_data)
            if cached is not None:
                fi, fis = cached
                return fi, fis, [None] * len(self.disks)
        token = self.fi_cache.token(bucket)
        fis, errors = self._read_version_all(bucket, object_, version_id,
                                             read_data=read_data)
        not_found = sum(isinstance(e, FileNotFoundErr) for e in errors)
        version_gone = sum(isinstance(e, VersionNotFoundErr) for e in errors)
        n = len(self.disks)
        if not_found > n // 2:
            self._check_bucket(bucket)
            # Dangling-object GC (reference: cmd/erasure-object.go:484
            # deleteIfDangling): a MINORITY of drives still carries
            # metadata for a key the majority definitively lacks —
            # the leftover of a failed write. Reap it so it can neither
            # resurrect via heal nor haunt listings. Only when every
            # non-holding drive answered a clean not-found: a transient
            # IO error could mean the metadata majority is merely
            # unreachable. The reap itself runs ASYNC under the key's
            # write lock with a re-read (this read path may hold the
            # read lock, and an unlocked delete would race an in-flight
            # PUT commit fan-out into destroying fresh shards).
            holders = [i for i, fi in enumerate(fis) if fi is not None]
            definitive = not_found + len(holders) == n
            if holders and definitive and not version_id:
                threading.Thread(
                    target=self._reap_dangling, args=(bucket, object_),
                    daemon=True, name="dangling-gc").start()
            raise ObjectNotFound(bucket, object_)
        if version_gone > n // 2:
            raise VersionNotFound(bucket, object_)
        # Read quorum = data shards of the stored object (reference:
        # getReadQuorum == dataBlocks).
        any_fi = next((f for f in fis if f is not None), None)
        if any_fi is None:
            _raise_for_quorum(errors, ReadQuorumError(bucket, object_),
                              quorum=n // 2 + 1)
        quorum = max(any_fi.erasure.data_blocks, n // 2) if any_fi.erasure.data_blocks \
            else n // 2 + 1
        fi, idxs = self._quorum_fileinfo(fis, quorum)
        if fi is None:
            _raise_for_quorum(errors, ReadQuorumError(bucket, object_),
                              quorum=quorum)
        if all(e is None for e in errors):
            if stat_only:
                self.fi_cache.put_stat(bucket, object_, version_id, fi,
                                       token)
            else:
                self.fi_cache.put(bucket, object_, version_id, fi, fis,
                                  read_data, token)
        return fi, fis, errors

    def _reap_dangling(self, bucket: str, object_: str) -> None:
        """Destroy a dangling minority version stack — re-verified
        under the key's WRITE lock so a concurrent PUT commit (which
        also holds it) can never lose freshly-written shards to the
        reaper."""
        try:
            with self.ns.write(bucket, object_):
                fis, errors = self._read_version_all(bucket, object_, "")
                n = len(self.disks)
                not_found = sum(isinstance(e, FileNotFoundErr)
                                for e in errors)
                holders = [i for i, fi in enumerate(fis)
                           if fi is not None]
                if holders and not_found + len(holders) == n \
                        and not_found > n // 2:
                    self._fanout([
                        lambda d=self.disks[i]: _swallow(
                            lambda: d.delete(bucket, object_,
                                             recursive=True))
                        for i in holders])
        except Exception:  # noqa: BLE001 - GC is best-effort
            pass

    # ------------------------------------------------------------------
    # encode helpers (the TPU-batched data path)
    # ------------------------------------------------------------------

    def _erasure(self, k: int, m: int) -> Erasure:
        return Erasure(k, m, BLOCK_SIZE, backend=self.backend)

    def _encode_object(self, data: bytes, k: int, m: int) -> np.ndarray:
        """Encode a whole object -> shards uint8 [k+m, shard_file_len].

        All full blocks go through the backend in one batched call;
        the ragged tail block goes in a second. This is where PutObject's
        per-block loop becomes one device step.
        """
        e = self._erasure(k, m)
        n = k + m
        total = len(data)
        if total == 0:
            return np.zeros((n, 0), dtype=np.uint8)
        full = total // BLOCK_SIZE
        tail = total - full * BLOCK_SIZE
        shard_size = e.shard_size()
        pieces: list[np.ndarray] = []
        if full:
            buf = np.frombuffer(data, dtype=np.uint8, count=full * BLOCK_SIZE)
            if k * shard_size == BLOCK_SIZE:
                stacked = buf.reshape(full, k, shard_size)
            else:
                # Split pads each block to k*ceil(block/k) with zeros
                # (reference Split semantics) — e.g. k=3 on 1 MiB blocks.
                stacked = np.zeros((full, k * shard_size), dtype=np.uint8)
                stacked[:, :BLOCK_SIZE] = buf.reshape(full, BLOCK_SIZE)
                stacked = stacked.reshape(full, k, shard_size)
            parity = self._apply_batch(e, stacked)           # [full, m, L]
            blocks = np.concatenate([stacked, parity], axis=1)  # [full, n, L]
            pieces.append(blocks.transpose(1, 0, 2).reshape(n, -1))
        if tail:
            tail_shards = e.split(data[full * BLOCK_SIZE:])
            parity = np.asarray(e.backend.apply_matrix(
                _parity_matrix(k, m), tail_shards)) if m else \
                np.zeros((0, tail_shards.shape[1]), dtype=np.uint8)
            pieces.append(np.concatenate([tail_shards, parity], axis=0))
        return np.concatenate(pieces, axis=1) if len(pieces) > 1 else pieces[0]

    def _apply_batch(self, e: Erasure, stacked: np.ndarray) -> np.ndarray:
        """[B, k, L] -> [B, m, L] parity via the device backend when it
        supports batching, else per-block."""
        if e.parity_blocks == 0:
            return np.zeros((stacked.shape[0], 0, stacked.shape[2]), np.uint8)
        pm = _parity_matrix(e.data_blocks, e.parity_blocks)
        be = e.backend
        cutover = getattr(be, "HOST_CUTOVER_BYTES", 0)
        if hasattr(be, "apply_matrix_device") and stacked.nbytes >= cutover:
            import jax.numpy as jnp
            out = be.apply_matrix_device(pm, jnp.asarray(stacked))
            return np.asarray(out)
        return np.stack([be.apply_matrix(pm, stacked[b])
                         for b in range(stacked.shape[0])])

    def _frame_pooled(self, data: bytes, k: int, m: int, full: int,
                      shard_size: int, md5=None):
        """Fused HOST encode+frame into a pooled aligned buffer: GF
        parity + HighwayHash + `digest || block` interleave in ONE
        GIL-free native call (native/native.cc mtpu_put_frame), output
        leased from the buffer pool instead of fresh per-put arrays.
        Returns (chunks, lease) covering the FULL blocks — chunks[i] a
        single memoryview into the lease — or None when the native
        library, the shape, or the algorithm rules it out.

        md5: optional _Md5Stream — when it carries a native context the
        WHOLE window (ragged tail included) md5-extends inside the same
        native call (mtpu_put_frame_md5) and the stream is marked
        folded, so the streaming PUT hot loop never touches the GIL for
        its per-window etag update."""
        if bitrot.DEFAULT_ALGORITHM != bitrot.HIGHWAYHASH256S \
                or k * shard_size != BLOCK_SIZE:
            return None
        from minio_tpu import native
        lib = native.load()
        if lib is None:
            return None
        n = k + m
        hsize = bitrot.digest_size(bitrot.DEFAULT_ALGORITHM)
        frame = hsize + shard_size
        span = full * frame
        lease = global_pool().lease(n * span)
        import ctypes

        from minio_tpu.utils.highwayhash import MAGIC_KEY
        src = np.frombuffer(data, dtype=np.uint8, count=full * BLOCK_SIZE)
        pm = np.ascontiguousarray(_parity_matrix(k, m)) if m \
            else np.zeros((0, k), dtype=np.uint8)
        out = (ctypes.c_uint8 * (n * span)).from_buffer(lease.raw)
        md5_ctx = md5.native_ctx if md5 is not None else None
        try:
            with tracing.stage("mtpu_put_frame",
                               {"blocks": full, "k": k, "m": m},
                               type_="kernel", count=False):
                if md5_ctx is not None:
                    lib.mtpu_put_frame_md5(
                        md5_ctx, native._u8(MAGIC_KEY), native._u8(pm),
                        src.ctypes.data_as(
                            ctypes.POINTER(ctypes.c_uint8)),
                        full, k, m, shard_size, len(data), out)
                    md5.mark_folded()
                else:
                    lib.mtpu_put_frame(
                        native._u8(MAGIC_KEY), native._u8(pm),
                        src.ctypes.data_as(
                            ctypes.POINTER(ctypes.c_uint8)),
                        full, k, m, shard_size, out)
        except BaseException:
            lease.release()
            raise
        mv = lease.view(n * span)
        return [[mv[i * span:(i + 1) * span]] for i in range(n)], lease

    def _frame_windows(self, data: bytes, k: int, m: int,
                       route: str = "put", md5=None):
        """Encode + bitrot-frame the object: (chunks, lease) where
        chunks is per-drive lists of framed byte chunks (shard index
        order) ready to write as shard files, and lease is a bufpool
        Lease the chunks view into (None when they own their bytes).
        The caller must release the lease — exactly once — after the
        chunks have been consumed; retain() it per concurrent consumer.

        On TPU with an eligible shape the full 1 MiB blocks run through
        the fused device pipeline (RS parity + HighwayHash + on-disk
        framing in one pass, ops/hh_device); on the host they run
        through the fused native kernel into a pooled buffer. Fallback
        is the batched numpy path (byte-identical output everywhere).
        """
        e = self._erasure(k, m)
        n = k + m
        total = len(data)
        shard_size = e.shard_size()
        if total == 0:
            return [[b""] for _ in range(n)], None
        full = total // BLOCK_SIZE
        # Honor the set's injected backend seam: the fused framer runs
        # only when this set was explicitly configured with a device
        # backend (server --ec-backend tpu/auto), so host/mock backends
        # see every encode, same as the tail path below. Eligible full
        # blocks route through the cross-request stripe batcher: windows
        # from concurrent PUTs coalesce into ONE device step (the batch
        # dim = stripes from many requests) when the batcher's measured
        # calibration says the device link wins; otherwise — including
        # a lone PUT with nobody to batch with — the host codec runs
        # with zero added latency (ops/batcher.py).
        # MTPU_BATCH_FORCE=device overrides the platform check: the
        # pin must reach the REAL batched device route on any host
        # (the tests' way to the mesh on virtual CPU devices) — without
        # it a non-TPU backend would serve from the host path whatever
        # the batcher was pinned to.
        batcher_for = _batcher_for if route == "put" \
            else _transform_batcher_for
        use_device = (full >= 1 and m > 0
                      and self._wants_device_route(route)
                      and BLOCK_SIZE % k == 0 and shard_size % 1024 == 0
                      # Once the batcher's calibration resolves to
                      # host, skip its queue entirely: the pooled
                      # native path below IS the fast host path.
                      and batcher_for(k, m).wants_device())
        chunks: list[list] = [[] for _ in range(n)]
        lease = None
        if use_device:
            buf = np.frombuffer(data, dtype=np.uint8,
                                count=full * BLOCK_SIZE)
            stacked = buf.reshape(full, k, shard_size)
            rows = batcher_for(k, m).frame(stacked)
            # rows[i] = per-block (digest, block) piece tuples. The
            # `hash || block` on-disk frame is assembled by the writer
            # from the pieces (reference cmd/bitrot-streaming.go:44-75
            # likewise writes hash then block; no interleaved buffer
            # ever exists).
            for i in range(n):
                for pieces in rows[i][:full]:
                    chunks[i].extend(pieces)
        elif full:
            pooled = self._frame_pooled(data, k, m, full, shard_size,
                                        md5=md5)
            if pooled is not None:
                chunks, lease = pooled
            else:
                shards = self._encode_object(
                    data[:full * BLOCK_SIZE] if total % BLOCK_SIZE
                    else data, k, m)
                chunks = [[f] for f in
                          bitrot.frame_shards_batch(shards, shard_size)]
        tail = total - full * BLOCK_SIZE
        if tail:
            tail_shards = e.split(data[full * BLOCK_SIZE:])
            parity = np.asarray(e.backend.apply_matrix(
                _parity_matrix(k, m), tail_shards)) if m else \
                np.zeros((0, tail_shards.shape[1]), dtype=np.uint8)
            framed_tail = bitrot.frame_shards_batch(
                np.concatenate([tail_shards, parity], axis=0)
                if m else tail_shards, shard_size)
            for i in range(n):
                chunks[i].append(framed_tail[i])
        return chunks, lease

    def _encode_and_frame(self, data: bytes, k: int, m: int) -> list[list]:
        """_frame_windows for callers that want self-owned bytes
        (decom/restore paths, tests): any pooled views are copied out
        and the lease returns immediately."""
        chunks, lease = self._frame_windows(data, k, m)
        if lease is None:
            return chunks
        try:
            return [[bytes(c) for c in row] for row in chunks]
        finally:
            lease.release()

    # ------------------------------------------------------------------
    # Fused single-pass transform plane (object/transform.TransformSpec)
    # ------------------------------------------------------------------

    def _transform_frame_windows(self, data, k: int, m: int, spec):
        """Execute a TransformSpec over `data` (the LOGICAL body) next
        to the framer: ONE GIL-free native call computes the etag md5 +
        declared checksums, deflates into the block scheme, seals into
        DARE packages, and frames the stored stream's full erasure
        blocks (native/native.cc mtpu_transform_frame) — the
        composition of the layered pipeline's separate walks. Returns
        (framed_chunks, lease, stored_len, etag_hex); spec is filled
        with digests/metadata and its pre-commit verify hook has run.

        Where the transform-route batcher calibrates to the device,
        the native call skips its frame stage and the stored windows
        ride the mesh framer through _frame_windows(route="transform").
        Ineligible shapes (no native library, non-HighwayHash bitrot,
        k not dividing the block) fall back to the staged Python
        pipeline — byte-identical stored stream, counted as
        path=legacy."""
        import ctypes

        from minio_tpu import native
        from minio_tpu.crypto import compress as comp_mod
        from minio_tpu.crypto import sse as sse_mod
        from minio_tpu.object import transform as transform_mod

        plen = len(data)
        spec.plain_size = plen
        # native.feature honors the MTPU_TRANSFORM_FUSED kill-switch:
        # direct object-layer callers (tests) must take the staged
        # pipeline under "off" exactly like the S3 handler path does.
        lib = native.feature("mtpu_transform_frame")
        e = self._erasure(k, m)
        n = k + m
        shard_size = e.shard_size()
        if lib is None \
                or bitrot.DEFAULT_ALGORITHM != bitrot.HIGHWAYHASH256S \
                or plen == 0:
            return self._transform_staged(data, k, m, spec)
        use_device = (m > 0
                      and self._wants_device_route("transform")
                      and BLOCK_SIZE % k == 0 and shard_size % 1024 == 0
                      and _transform_batcher_for(k, m).wants_device())
        frame_native = not use_device and k * shard_size == BLOCK_SIZE
        PKG, TAG = 64 * 1024, 16
        npkg = (plen + PKG - 1) // PKG if spec.encrypt else 0
        ncomp = (plen + comp_mod.BLOCK - 1) // comp_mod.BLOCK \
            if spec.compress else 0
        stored_cap = plen + npkg * TAG + ncomp * 1104 + 64
        scratch_cap = plen + ncomp * 1104 + 64 \
            if (spec.compress and spec.encrypt) else 0
        max_full = stored_cap // BLOCK_SIZE + 1
        frames_cap = n * max_full * (32 + shard_size) if frame_native \
            else 0
        lease = global_pool().lease(stored_cap + scratch_cap + frames_cap)
        from minio_tpu.utils.highwayhash import MAGIC_KEY
        flags = 1
        for algo, bit in (("sha256", 2), ("sha1", 4), ("crc32", 8)):
            if algo in spec.algos:
                flags |= bit
        if spec.compress:
            flags |= 16
        if spec.encrypt:
            flags |= 32
        if frame_native:
            flags |= 64
        digests = (ctypes.c_uint8 * 72)()
        comp_ends = (ctypes.c_int64 * max(1, ncomp))()
        info = (ctypes.c_int64 * 8)()
        src = np.frombuffer(data, dtype=np.uint8, count=plen)
        pm = np.ascontiguousarray(_parity_matrix(k, m)) if m \
            else np.zeros((0, k), dtype=np.uint8)
        stored_arr = (ctypes.c_uint8 * stored_cap).from_buffer(lease.raw)
        scratch_arr = (ctypes.c_uint8 * max(1, scratch_cap)).from_buffer(
            lease.raw, stored_cap) if scratch_cap else None
        framed_arr = (ctypes.c_uint8 * frames_cap).from_buffer(
            lease.raw, stored_cap + scratch_cap) if frames_cap else None
        try:
            with tracing.span("kernel", "mtpu_transform_frame",
                              {"bytes": plen, "k": k, "m": m,
                               "flags": flags}) \
                    if tracing.ACTIVE else tracing.NOOP:
                ret = lib.mtpu_transform_frame(
                    src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                    plen, flags, native._u8(spec.enc_key or b"\0" * 32),
                    native._u8(spec.enc_nonce or b"\0" * 12), digests,
                    stored_arr, stored_cap, scratch_arr or stored_arr,
                    scratch_cap, comp_ends, max(1, ncomp),
                    comp_mod.BLOCK, native._u8(MAGIC_KEY),
                    pm.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                    k, m, shard_size, BLOCK_SIZE,
                    framed_arr or stored_arr, frames_cap, info)
            if ret == -2:
                # Built without zlib (-DMTPU_NO_ZLIB): the compress
                # stage cannot run natively — the staged pipeline's
                # Python zlib path owns this shape.
                lease.release()
                lease = None
                return self._transform_staged(data, k, m, spec)
            if ret < 0:
                raise CodecError(f"mtpu_transform_frame failed: {ret}")
            stored_len, full = int(info[0]), int(info[1])
            spec.stored_size = stored_len
            spec.comp_used = bool(info[2])
            spec.digests = {"md5": bytes(digests[0:16])}
            if "sha256" in spec.algos:
                spec.digests["sha256"] = bytes(digests[16:48])
            if "sha1" in spec.algos:
                spec.digests["sha1"] = bytes(digests[48:68])
            if "crc32" in spec.algos:
                spec.digests["crc32"] = bytes(digests[68:72])
            spec.etag = spec.digests["md5"].hex()
            if spec.comp_used:
                spec.comp_ends = list(comp_ends[: int(info[7])])
                spec.meta.update(comp_mod.index_meta(plen, spec.comp_ends))
                if spec.encrypt:
                    # The DARE stream's plaintext is the COMPRESSED
                    # stream: patch the sse size the handler stamped
                    # with the pre-compression value.
                    spec.meta[sse_mod.META_SIZE] = str(spec.comp_ends[-1])
            spec.run_verify()
            # Frame stage: views of the native output + the ragged
            # stored tail through the split path, or the whole stored
            # stream through the transform-route batcher.
            stored_mv = lease.view(stored_len)
            if frame_native:
                hsize = 32
                frame = hsize + shard_size
                span = full * frame
                base = stored_cap + scratch_cap
                mv = lease.view(base + n * span)
                chunks = [[mv[base + i * span: base + (i + 1) * span]]
                          for i in range(n)]
                tail = stored_len - full * BLOCK_SIZE
                if tail:
                    framed_tail = self._frame_tail(
                        e, bytes(stored_mv[full * BLOCK_SIZE:stored_len]),
                        k, m, shard_size)
                    for i in range(n):
                        chunks[i].append(framed_tail[i])
                if stored_len == 0:
                    chunks = [[b""] for _ in range(n)]
                transform_mod.note_put("fused", plen, list(info[3:7]))
                return chunks, lease, stored_len, spec.etag
            # Device (or non-dividing-k) frame route: the stored bytes
            # re-enter the shared windowed framer under the transform
            # route label.
            chunks, flease = self._frame_windows(
                bytes(stored_mv[:stored_len]) if stored_len else b"",
                k, m, route="transform")
            transform_mod.note_put("fused", plen, list(info[3:7]))
            lease.release()
            lease = None
            return chunks, flease, stored_len, spec.etag
        except BaseException:
            if lease is not None:
                lease.release()
            raise

    def _frame_tail(self, e, tail: bytes, k: int, m: int,
                    shard_size: int):
        """Frame the sub-block ragged tail exactly like _frame_windows'
        tail path (split + parity + bitrot frame)."""
        tail_shards = e.split(tail)
        parity = np.asarray(e.backend.apply_matrix(
            _parity_matrix(k, m), tail_shards)) if m else \
            np.zeros((0, tail_shards.shape[1]), dtype=np.uint8)
        return bitrot.frame_shards_batch(
            np.concatenate([tail_shards, parity], axis=0)
            if m else tail_shards, shard_size)

    def _transform_staged(self, data, k: int, m: int, spec):
        """Staged (layered) execution of a TransformSpec for shapes the
        single native call cannot take: same stored bytes, same
        metadata, counted as path=legacy in the transform plane's
        split counters."""
        import hashlib as _hl
        import zlib as _zl

        from minio_tpu.crypto import compress as comp_mod
        from minio_tpu.crypto import dare as dare_mod
        from minio_tpu.crypto import sse as sse_mod
        from minio_tpu.object import transform as transform_mod

        data = bytes(data)
        plen = len(data)
        spec.plain_size = plen
        spec.digests = {}
        if "sha256" in spec.algos:
            spec.digests["sha256"] = _hl.sha256(data).digest()
        if "sha1" in spec.algos:
            spec.digests["sha1"] = _hl.sha1(data).digest()
        if "crc32" in spec.algos:
            import struct as _st
            spec.digests["crc32"] = _st.pack(
                ">I", _zl.crc32(data) & 0xFFFFFFFF)
        body = data
        if spec.compress and plen:
            result = comp_mod.compress(data)
            if result is not None:
                body, meta = result
                spec.comp_used = True
                spec.meta.update(meta)
        if spec.encrypt:
            sealed = dare_mod.seal_bulk(spec.enc_key, spec.enc_nonce, 0,
                                        body)
            if sealed is None:
                from minio_tpu.utils.streams import Payload as _P
                enc = dare_mod.EncryptingPayload(
                    _P.wrap(body), spec.enc_key, spec.enc_nonce)
                parts = []
                while True:
                    c = enc.read(1 << 20)
                    if not c:
                        break
                    parts.append(c)
                sealed = b"".join(parts)
            stored = sealed
            if spec.comp_used:
                spec.meta[sse_mod.META_SIZE] = str(len(body))
        else:
            stored = body
        spec.stored_size = len(stored)
        spec.digests["md5"] = _hl.md5(
            data if (spec.comp_used or not spec.encrypt)
            else stored).digest()
        spec.etag = spec.digests["md5"].hex()
        spec.run_verify()
        chunks, lease = self._frame_windows(stored, k, m,
                                            route="transform")
        transform_mod.note_put("legacy", plen)
        return chunks, lease, len(stored), spec.etag

    # ------------------------------------------------------------------
    # PutObject
    # ------------------------------------------------------------------

    def put_object(self, bucket: str, object_: str, data,
                   opts: Optional[PutOptions] = None) -> ObjectInfo:
        """data: bytes, or a utils.streams.Payload for O(window)-memory
        streaming of large bodies (reference: PutObject streams 1 MiB
        blocks, cmd/erasure-object.go:1415)."""
        opts = opts or PutOptions()
        payload = Payload.wrap(data)
        if payload.size > STREAM_THRESHOLD:
            if opts.transform is not None:
                # The fused spec is a buffered-plane contract; silently
                # ignoring it here would commit plaintext under
                # encrypted metadata.
                raise ValueError(
                    "TransformSpec requires a buffered-size body "
                    f"(<= {STREAM_THRESHOLD} bytes)")
            return self._put_object_streaming(bucket, object_, payload, opts)
        return self._put_object_buffered(bucket, object_,
                                         payload.read_all(), opts)

    def _put_object_buffered(self, bucket: str, object_: str, data: bytes,
                             opts: PutOptions) -> ObjectInfo:
        if self.group_commit is not None and len(data) <= BLOCK_SIZE:
            # Track the WHOLE buffered-put body, not just the commit:
            # the lanes' early-close rule compares pending members to
            # in-flight requests, so a request still encoding must
            # already count — its members are coming, and closing a
            # batch without them costs a whole extra commit round.
            # Bodies over one erasure block stay untracked: their
            # encode can run tens of ms, and a lane waiting on one as
            # an expected member would stall every small PUT behind
            # the window cap (they still join batches opportunistically
            # when small traffic is in flight).
            with self.group_commit.tracking():
                return self._put_object_buffered_inner(bucket, object_,
                                                       data, opts)
        return self._put_object_buffered_inner(bucket, object_, data, opts)

    def _put_object_buffered_inner(self, bucket: str, object_: str,
                                   data: bytes,
                                   opts: PutOptions) -> ObjectInfo:
        self._check_bucket(bucket)
        n = len(self.disks)
        m = self.default_parity
        if opts.storage_class == "REDUCED_REDUNDANCY" and n > 1:
            m = max(1, min(m, 2))
        k = n - m
        write_quorum = k + (1 if k == m else 0)

        distribution = hash_order(f"{bucket}/{object_}", n)
        # Encode outside the namespace lock (pure compute); only the
        # commit fan-out below serializes against other ops on this key.
        e = self._erasure(k, m)
        shard_size = e.shard_size()
        if opts.transform is not None:
            # Fused single-pass plane: digest + compress + DARE + frame
            # in one native call (spec verify hook runs pre-commit
            # inside); `size` below is the STORED length — exactly what
            # a pre-transformed payload's len() was on the layered
            # path. The spec's metadata (compression index, corrected
            # sse size) lands in internal metadata with the rest.
            framed, frames_lease, size, etag = \
                self._transform_frame_windows(data, k, m, opts.transform)
            opts.internal_metadata.update(opts.transform.meta)
            etag = opts.etag or etag
        else:
            framed, frames_lease = self._frame_windows(data, k, m)
            size = len(data)
            etag = opts.etag or hashlib.md5(data).hexdigest()
        version_id = opts.version_id or (new_uuid() if opts.versioned else "")
        mod_time = opts.mod_time or now_ns()
        shard_file_len = e.shard_file_size(size)
        inline = shard_file_len <= SMALL_FILE_THRESHOLD and not opts.versioned \
            or shard_file_len <= SMALL_FILE_THRESHOLD // 8
        if inline and frames_lease is not None:
            # Inline data commits straight into xl.meta (no staging +
            # rename gate), so the journal must never reference pooled
            # memory a recycled buffer could tear under a late writer:
            # copy out now and return the lease immediately.
            framed = [[bytes(c) for c in row] for row in framed]
            frames_lease.release()
            frames_lease = None

        data_dir = "" if inline else new_uuid()
        metadata = _clean_user_meta(opts.user_metadata)
        metadata["etag"] = etag
        if opts.content_type:
            metadata["content-type"] = opts.content_type
        if opts.tags:
            metadata["x-amz-tagging"] = opts.tags
        metadata.update(opts.internal_metadata)

        def make_fi(shard_idx: int) -> FileInfo:
            return FileInfo(
                volume=bucket, name=object_, version_id=version_id,
                deleted=False, data_dir=data_dir, mod_time=mod_time,
                size=size, metadata=metadata,
                parts=[ObjectPartInfo(number=1, size=size,
                                      actual_size=size, etag=etag)],
                erasure=ErasureInfo(
                    data_blocks=k, parity_blocks=m, block_size=BLOCK_SIZE,
                    index=shard_idx + 1, distribution=tuple(distribution)),
                inline_data=_join_chunks(framed[shard_idx]) if inline else None,
            )

        staging = new_staging()

        def write_one(disk_idx: int):
            d = self.disks[disk_idx]
            shard_idx = distribution[disk_idx] - 1
            fi = make_fi(shard_idx)
            if inline:
                d.write_metadata(bucket, object_, fi)
            else:
                d.create_file(SYS_VOL, f"{staging}/{data_dir}/part.1",
                              list(framed[shard_idx]))
                d.rename_data(SYS_VOL, staging, fi, bucket, object_)

        def stage_one(disk_idx: int):
            d = self.disks[disk_idx]
            shard_idx = distribution[disk_idx] - 1
            d.create_file(SYS_VOL, f"{staging}/{data_dir}/part.1",
                          list(framed[shard_idx]))

        gc = self.group_commit
        used_group = False
        try:
            with self.ns.write(bucket, object_):
                if gc is not None and gc.worth_batching():
                    # Coalesced commit: journal writes ride the
                    # per-drive group lanes — one WAL-backed batch per
                    # drive per window instead of one durable commit
                    # per drive per request. Non-inline shards stage
                    # first (the solo engine fan-out), then the
                    # rename_data commits coalesce the same way.
                    used_group = True
                    from minio_tpu.storage.group_commit import GroupOp
                    if inline:
                        errors = gc.commit_fanout(
                            [GroupOp.write_meta(
                                bucket, object_,
                                make_fi(distribution[i] - 1))
                             for i in range(n)])
                    else:
                        _, serrors = self._fanout(
                            _leased_fns([lambda i=i: stage_one(i)
                                         for i in range(n)],
                                        frames_lease))
                        gerrors = gc.commit_fanout(
                            [GroupOp.rename(
                                SYS_VOL, staging,
                                make_fi(distribution[i] - 1),
                                bucket, object_)
                             if serrors[i] is None else None
                             for i in range(n)])
                        errors = [se if se is not None else ge
                                  for se, ge in zip(serrors, gerrors)]
                else:
                    if gc is not None:
                        gc.note_solo()
                    _, errors = self._fanout(
                        _leased_fns([lambda i=i: write_one(i)
                                     for i in range(n)], frames_lease))
        finally:
            # The producer's reference, released even when the lock
            # times out; per-drive references (_leased_fns) are
            # returned by the workers themselves.
            if frames_lease is not None:
                frames_lease.release()
                frames_lease = None
        ok = sum(e is None for e in errors)
        if ok < write_quorum:
            # Best-effort cleanup: committed versions on the disks that
            # succeeded, and staged shard files everywhere (a failed
            # rename_data leaves its staging dir behind).
            self._cleanup_fanout([lambda d=d: _swallow(
                lambda: d.delete_version(bucket, object_, version_id))
                for d, err in zip(self.disks, errors) if err is None])
            if not inline:
                self._cleanup_fanout([lambda d=d: _swallow(
                    lambda: d.delete(SYS_VOL, staging, recursive=True))
                    for d in self.disks])
            _raise_for_quorum(errors, WriteQuorumError(
                bucket, object_, f"wrote {ok}/{n}, need {write_quorum}"),
                quorum=write_quorum)
        if ok < n:
            # Partial success: queue immediate background repair of the
            # drives that missed the write (reference MRF hook,
            # cmd/erasure-object.go:1556-1594).
            self.mrf.enqueue(bucket, object_, version_id)
        if not used_group:
            # Group commits already fired ONE coalesced bump per batch
            # (before any member ack); a second per-request bump here
            # would undo the coalescing the lane exists for.
            self.metacache.bump(bucket)
        return ObjectInfo(bucket=bucket, name=object_, mod_time=mod_time,
                          size=size, etag=etag,
                          content_type=opts.content_type,
                          version_id=version_id,
                          user_metadata=dict(opts.user_metadata),
                          actual_size=size)

    def restore_version(self, bucket: str, object_: str, src_fi,
                        data: Optional[bytes],
                        skip_if_newer_null: bool = False) -> None:
        """Write one version copied from ANOTHER erasure set into this
        set's geometry — the decommission/rebalance transfer primitive
        (reference: cmd/erasure-server-pool-decom.go decommissionObject
        re-putting through the destination pool).

        `src_fi`: the source FileInfo (version id, mod time, metadata
        map, parts, deleted flag) — preserved verbatim so the version
        is indistinguishable from the original (same etag, same SSE
        params, same part boundaries for part-aware decryption).
        `data`: the full STORED byte stream (None for delete markers);
        re-encoded here because the destination's (k, m) geometry can
        differ from the source's."""
        self._check_bucket(bucket)
        n = len(self.disks)

        def newer_null_exists() -> bool:
            """Under the key lock: is there already a null version at
            least as new as the one being restored? There is only ONE
            null slot per key — restoring an old null (data OR marker)
            over a newer concurrently-written one would lose an
            acknowledged write."""
            if not skip_if_newer_null or src_fi.version_id:
                return False
            try:
                return any(v.version_id == "" and
                           v.mod_time >= src_fi.mod_time
                           for v in self.list_versions_all(bucket, object_))
            except ObjectNotFound:
                return False

        if src_fi.deleted:
            fi = FileInfo(volume=bucket, name=object_,
                          version_id=src_fi.version_id, deleted=True,
                          mod_time=src_fi.mod_time)
            with self.ns.write(bucket, object_):
                if newer_null_exists():
                    return
                _, errors = self._fanout(
                    [lambda d=d: d.write_metadata(bucket, object_, fi)
                     for d in self.disks])
            if sum(e is None for e in errors) < n // 2 + 1:
                raise WriteQuorumError(bucket, object_)
            self.metacache.bump(bucket)
            return
        from minio_tpu.object.tier import META_TIER
        if (src_fi.metadata or {}).get(META_TIER):
            # Transitioned version: the DATA lives in its warm tier;
            # only the metadata pointer migrates (re-encoding would
            # duplicate the tier copy locally and shadow nothing).
            fi = FileInfo(
                volume=bucket, name=object_,
                version_id=src_fi.version_id, deleted=False,
                mod_time=src_fi.mod_time, size=src_fi.size,
                metadata=dict(src_fi.metadata),
                parts=[dataclasses.replace(p)
                       for p in (src_fi.parts or [])])
            with self.ns.write(bucket, object_):
                if newer_null_exists():
                    return
                _, errors = self._fanout(
                    [lambda d=d: d.write_metadata(bucket, object_, fi)
                     for d in self.disks])
            if sum(e is None for e in errors) < n // 2 + 1:
                raise WriteQuorumError(bucket, object_)
            self.metacache.bump(bucket)
            return
        m = self.default_parity
        k = n - m
        write_quorum = k + (1 if k == m else 0)
        distribution = hash_order(f"{bucket}/{object_}", n)
        parts = list(src_fi.parts or [])
        if not parts:
            parts = [ObjectPartInfo(number=1, size=len(data or b""),
                                    actual_size=len(data or b""))]
        data_dir = new_uuid()
        staging = new_staging()
        # Frame each part independently: the read path opens part files
        # one by one and sizes shards per part.
        framed_parts = []
        off = 0
        for p in parts:
            framed_parts.append(
                (p.number, self._encode_and_frame(data[off:off + p.size],
                                                  k, m)))
            off += p.size

        def write_one(disk_idx: int):
            d = self.disks[disk_idx]
            shard_idx = distribution[disk_idx] - 1
            for num, framed in framed_parts:
                d.create_file(SYS_VOL, f"{staging}/{data_dir}/part.{num}",
                              list(framed[shard_idx]))
            fi = FileInfo(
                volume=bucket, name=object_,
                version_id=src_fi.version_id, deleted=False,
                data_dir=data_dir, mod_time=src_fi.mod_time,
                size=src_fi.size, metadata=dict(src_fi.metadata),
                parts=[dataclasses.replace(p) for p in parts],
                erasure=ErasureInfo(
                    data_blocks=k, parity_blocks=m, block_size=BLOCK_SIZE,
                    index=shard_idx + 1,
                    distribution=tuple(distribution)))
            d.rename_data(SYS_VOL, staging, fi, bucket, object_)

        with self.ns.write(bucket, object_):
            if newer_null_exists():
                self._cleanup_fanout([lambda d=d: _swallow(
                    lambda: d.delete(SYS_VOL, staging, recursive=True))
                    for d in self.disks])
                return
            _, errors = self._fanout(
                [lambda i=i: write_one(i) for i in range(n)])
        ok = sum(e is None for e in errors)
        if ok < write_quorum:
            self._cleanup_fanout([lambda d=d: _swallow(
                lambda: d.delete(SYS_VOL, staging, recursive=True))
                for d in self.disks])
            raise WriteQuorumError(bucket, object_)
        if ok < n:
            self.mrf.enqueue(bucket, object_, src_fi.version_id)
        self.metacache.bump(bucket)

    # ------------------------------------------------------------------
    # Streaming PutObject (O(window) memory)
    # ------------------------------------------------------------------

    def _stream_framed_writes(self, payload: Payload, k: int, m: int,
                              distribution: Sequence[int],
                              path_for) -> tuple[str, list]:
        """Windowed encode+frame with parallel streamed shard writers.

        Reads `payload` in STREAM_WINDOW_BLOCKS windows, frames each
        (device or host), and feeds per-drive bounded queues consumed by
        one writer thread per drive (`path_for(i) -> (disk, vol, path)`,
        written via create_file's iterator form). Memory is bounded by
        the window size times the queue depth; a dead writer drains its
        queue so the producer never blocks on it. Returns (md5 etag,
        per-drive error list). The reference's shape: parallelWriter
        goroutines fed block-by-block (cmd/erasure-encode.go:69).
        """
        import queue as queue_mod

        n = len(self.disks)
        window_bytes = STREAM_WINDOW_BLOCKS * BLOCK_SIZE
        qs = [queue_mod.Queue(maxsize=2) for _ in range(n)]
        errors: list = [None] * n
        dead = [False] * n
        sentinel_seen = [False] * n
        _SENTINEL = object()

        dl = deadline_mod.current()
        tctx, tparent = tracing.capture() if tracing.ACTIVE else (None, 0)

        def got_sentinel(i: int, c) -> bool:
            """Sentinel handling shared by every consumer of qs[i]. The
            sentinel is STICKY (re-queued on receipt): when a health-
            wrapped create_file times out, its abandoned pool worker is
            still blocked in gen()'s get() while the writer's drain
            loop also consumes — one sentinel with two consumers would
            park the loser forever (leaking a pool worker per timed-out
            stream, or hanging the producer's join). Re-queueing wakes
            every consumer; the producer has stopped feeding this
            queue, so the re-put can never block."""
            if c is _SENTINEL:
                sentinel_seen[i] = True
                qs[i].put(c)
                return True
            return False

        def writer(i: int):
            # Release hook for the window row currently being consumed:
            # rows framed into pooled buffers carry a per-consumer
            # reference (bufpool.Lease.retain) that must return exactly
            # once — at the next queue pull (row fully written), in the
            # drain loop (row skipped), or when the writer dies
            # mid-row. TWO threads can reach the in-flight hook (this
            # writer thread's finally, and a deadline-abandoned
            # health-pool worker still driving gen()), so the handoff
            # swaps the callback out under a lock: whoever swaps it
            # runs it, nobody runs it twice.
            in_mu = threading.Lock()
            inflight: list = []

            def finish_inflight():
                with in_mu:
                    cbs, inflight[:] = list(inflight), []
                for cb in cbs:
                    if cb is not None:
                        cb()

            try:
                with deadline_mod.bind(dl), tracing.bind(tctx, tparent):
                    disk, vol, path = path_for(i)

                    def gen():
                        while True:
                            # The stream is starved: its thread (the
                            # drive's, inside `disk.stream`) waits for
                            # the next window's row, i.e. the batcher.
                            with tracing.stage("disk.stream.row_wait",
                                               type_="storage", cpu=False):
                                c = qs[i].get()
                            finish_inflight()
                            if got_sentinel(i, c):
                                return
                            row, cb = c
                            with in_mu:
                                inflight.append(cb)
                            yield from row
                    disk.create_file(vol, path, gen())
            except Exception as exc:  # noqa: BLE001 - collected for quorum
                errors[i] = exc
                dead[i] = True
                while not sentinel_seen[i]:
                    c = qs[i].get()
                    if not got_sentinel(i, c):
                        # Drain-owned rows never enter inflight: this
                        # thread is their only holder.
                        _, cb = c
                        if cb is not None:
                            cb()
            finally:
                finish_inflight()

        import threading
        threads = [threading.Thread(target=writer, args=(i,), daemon=True)
                   for i in range(n)]
        with tracing.stage("put.writers_start", type_="storage"):
            # start() returns once the new thread has run: one wait
            # for the GIL per drive
            for t in threads:
                t.start()
        # Streaming etag: a native md5 context that the pooled frame
        # call extends INSIDE the same GIL-free native pass as the
        # encode+frame (mtpu_put_frame_md5); windows that take the
        # device or fallback route update it explicitly (still native,
        # still no GIL held over the buffer walk).
        md5 = _Md5Stream()
        write_quorum = k + (1 if k == m else 0)
        stream_error: Optional[Exception] = None
        try:
            # The request thread's stages (utils/tracing.stage), one
            # after the other and never nested: their seconds add up
            # to this call's.
            while payload.remaining > 0:
                if dl is not None:
                    dl.check()
                with tracing.stage("put.body_read"):
                    # socket recv + SHA-256 / chunk-signature check
                    window = payload.read_exact(window_bytes)
                window_lease = None
                try:
                    with tracing.stage("put.frame", type_="kernel"):
                        framed, window_lease = self._frame_windows(
                            window, k, m, md5=md5)
                    if not md5.take_folded():
                        with tracing.stage("put.md5"):
                            md5.update(window)
                    if n - sum(dead) < write_quorum:
                        raise WriteQuorumError(
                            "", "",
                            f"{sum(dead)}/{n} writers failed mid-stream")
                    with tracing.stage("put.shard_enqueue",
                                       type_="storage"):
                        # blocks while a drive's writer is two windows
                        # behind (queue depth 2)
                        for i in range(n):
                            if dead[i]:
                                continue
                            cb = None
                            if window_lease is not None:
                                window_lease.retain()
                                cb = window_lease.release
                            qs[i].put((framed[distribution[i] - 1], cb))
                finally:
                    # The producer's own reference; per-writer refs are
                    # returned by each consumer.
                    if window_lease is not None:
                        window_lease.release()
        except Exception as exc:  # noqa: BLE001 - unwind writers first
            stream_error = exc
        finally:
            with tracing.stage("put.shard_drain", type_="storage"):
                for i in range(n):
                    qs[i].put(_SENTINEL)
                for t in threads:
                    t.join()
        if stream_error is not None:
            raise stream_error
        return md5.hexdigest(), errors

    def _put_object_streaming(self, bucket: str, object_: str,
                              payload: Payload,
                              opts: PutOptions) -> ObjectInfo:
        """Large-object PUT: stream windows to staged shard files, then
        quorum-commit with atomic renames under the namespace lock —
        encode and IO run unlocked, only the commit serializes (the
        reference's tmp-write + renameData commit discipline)."""
        with tracing.stage("put.prepare", type_="storage"):
            # a stat_vol fan-out when the bucket's TTL entry has run out
            self._check_bucket(bucket)
        n = len(self.disks)
        m = self.default_parity
        if opts.storage_class == "REDUCED_REDUNDANCY" and n > 1:
            m = max(1, min(m, 2))
        k = n - m
        write_quorum = k + (1 if k == m else 0)
        size = payload.size
        distribution = hash_order(f"{bucket}/{object_}", n)
        version_id = opts.version_id or (new_uuid() if opts.versioned else "")
        data_dir = new_uuid()
        staging = new_staging()

        def path_for(i: int):
            return self.disks[i], SYS_VOL, f"{staging}/{data_dir}/part.1"

        def cleanup_staging(disks=None):
            self._cleanup_fanout([lambda d=d: _swallow(
                lambda: d.delete(SYS_VOL, staging, recursive=True))
                for d in (disks if disks is not None else self.disks)])

        try:
            etag, errors = self._stream_framed_writes(
                payload, k, m, distribution, path_for)
            etag = opts.etag or etag
        except Exception:
            cleanup_staging()
            raise
        ok = sum(err is None for err in errors)
        if ok < write_quorum:
            cleanup_staging()
            _raise_for_quorum(errors, WriteQuorumError(
                bucket, object_, f"staged {ok}/{n}, need {write_quorum}"),
                quorum=write_quorum)

        mod_time = opts.mod_time or now_ns()
        metadata = _clean_user_meta(opts.user_metadata)
        metadata["etag"] = etag
        if opts.content_type:
            metadata["content-type"] = opts.content_type
        if opts.tags:
            metadata["x-amz-tagging"] = opts.tags
        metadata.update(opts.internal_metadata)

        def make_fi(shard_idx: int) -> FileInfo:
            return FileInfo(
                volume=bucket, name=object_, version_id=version_id,
                deleted=False, data_dir=data_dir, mod_time=mod_time,
                size=size, metadata=metadata,
                parts=[ObjectPartInfo(number=1, size=size,
                                      actual_size=size, etag=etag)],
                erasure=ErasureInfo(
                    data_blocks=k, parity_blocks=m, block_size=BLOCK_SIZE,
                    index=shard_idx + 1, distribution=tuple(distribution)))

        def commit_one(i: int):
            if errors[i] is not None:
                raise errors[i]
            self.disks[i].rename_data(SYS_VOL, staging,
                                      make_fi(distribution[i] - 1),
                                      bucket, object_)

        with tracing.stage("put.commit", type_="storage"), \
                self.ns.write(bucket, object_):
            _, cerrors = self._fanout(
                [lambda i=i: commit_one(i) for i in range(n)])
        ok = sum(e2 is None for e2 in cerrors)
        if ok < write_quorum:
            self._cleanup_fanout([lambda d=d: _swallow(
                lambda: d.delete_version(bucket, object_, version_id))
                for d, err in zip(self.disks, cerrors) if err is None])
            cleanup_staging()
            _raise_for_quorum(cerrors, WriteQuorumError(
                bucket, object_,
                f"committed {ok}/{n}, need {write_quorum}"),
                quorum=write_quorum)
        laggards = [d for d, err in zip(self.disks, cerrors)
                    if err is not None]
        if laggards:
            cleanup_staging(laggards)
            self.mrf.enqueue(bucket, object_, version_id)
        self.metacache.bump(bucket)
        return ObjectInfo(bucket=bucket, name=object_, mod_time=mod_time,
                          size=size, etag=etag,
                          content_type=opts.content_type,
                          version_id=version_id,
                          user_metadata=dict(opts.user_metadata),
                          actual_size=size)

    # ------------------------------------------------------------------
    # GetObject
    # ------------------------------------------------------------------

    def get_object(self, bucket: str, object_: str,
                   opts: Optional[GetOptions] = None) -> tuple[ObjectInfo, bytes]:
        opts = opts or GetOptions()
        cm, info, fi, fis, offset, length = self._open_get(bucket, object_,
                                                           opts)
        try:
            if fi.size == 0 or length == 0:
                return info, b""
            return info, self._read_payload(bucket, object_, fi, fis,
                                            offset, length)
        finally:
            cm.__exit__(None, None, None)

    def _open_get(self, bucket: str, object_: str, opts: GetOptions):
        """The stage `get.prepare`: the namespace read lock taken
        (shared with other readers, excludes put/delete/heal on this
        key — reference: GetObjectNInfo's NSLock) and the GET prepared.
        Returns (lock, info, fi, fis, offset, length); the caller
        releases the lock (`lock.__exit__`) when the read is over."""
        with tracing.stage("get.prepare", type_="storage", cpu=False):
            cm = self.ns.read(bucket, object_)
            cm.__enter__()
            try:
                return (cm,) + self._prepare_get(bucket, object_, opts)
            except BaseException:
                cm.__exit__(None, None, None)
                raise

    def _prepare_get(self, bucket: str, object_: str, opts: GetOptions):
        """Shared GET preamble: quorum fileinfo, delete-marker mapping,
        range resolution. Returns (info, fi, fis, offset, length)."""
        fi, fis, errors = self._get_object_fileinfo(
            bucket, object_, opts.version_id, read_data=True)
        if any(e is not None for e in errors):
            # Some drive is missing this version's metadata: schedule a
            # background heal even if the read itself succeeds from the
            # healthy k (reference: heal-on-missing-metadata in
            # getObjectFileInfo's MRF hook).
            self.mrf.enqueue(bucket, object_, fi.version_id)
        if fi.deleted:
            # Latest-is-delete-marker reads 404 (NoSuchKey); naming the
            # marker's version explicitly is 405 (MethodNotAllowed) —
            # AWS semantics, as in the reference's toAPIError mapping.
            if opts.version_id:
                raise MethodNotAllowed(bucket, object_)
            raise ObjectNotFound(bucket, object_)
        info = self._to_object_info(bucket, object_, fi)

        total = fi.size
        if opts.range_spec is not None:
            offset, length = _resolve_range(opts.range_spec, total,
                                            bucket, object_)
        else:
            offset = opts.offset
            length = total - offset if opts.length < 0 else opts.length
            if offset < 0 or length < 0 or offset + length > total:
                raise InvalidRange(bucket, object_)
        info.range_start, info.range_length = offset, length
        return info, fi, fis, offset, length

    def get_object_stream(self, bucket: str, object_: str,
                          opts: Optional[GetOptions] = None):
        """Streaming GET: (ObjectInfo, iterator of plaintext chunks).

        Decodes GET_WINDOW_BYTES block windows at a time, so memory is
        O(window) regardless of range size. The namespace read lock is
        held until the iterator is exhausted or closed (the reference's
        GetObjectNInfo reader-with-unlock-on-close)."""
        opts = opts or GetOptions()
        cm, info, fi, fis, offset, length = self._open_get(bucket, object_,
                                                           opts)

        def gen():
            try:
                # Primer yield: the caller advances past it immediately
                # (below), so the generator is always STARTED — close()
                # on a never-started generator would skip this finally
                # and leak the namespace lock.
                yield b""
                if fi.size and length:
                    yield from self._iter_payload(bucket, object_, fi, fis,
                                                  offset, length)
            finally:
                cm.__exit__(None, None, None)
        g = gen()
        next(g)
        return info, g

    def get_object_file(self, bucket: str, object_: str,
                        opts: Optional[GetOptions] = None,
                        info: Optional[ObjectInfo] = None):
        """Sendfile source probe for the serve plane (s3/eventloop
        connection plane): (info, fd, offset, length) when this
        object's STORED bytes equal its plaintext and live contiguously
        in one local file — today the FS-warm-tier copy of a
        transitioned version. Erasure-resident objects are never
        eligible: every shard file interleaves bitrot digests with the
        blocks (`digest || block` framing), so no raw-byte file exists
        for them. Whole-object, unencrypted, uncompressed reads only;
        None when ineligible. The caller owns the returned fd.

        Pass `info` (an ObjectInfo already resolved for this exact
        version, e.g. from an open get_object_stream whose read lock
        is still held) to skip the quorum fileinfo fan-out — the probe
        then needs only the tier file open+fstat."""
        from minio_tpu.object import tier as tier_mod
        opts = opts or GetOptions()
        if opts.range_spec is not None or opts.offset:
            return None
        if info is None:
            with self.ns.read(bucket, object_):
                info, _fi, _fis, _offset, _length = self._prepare_get(
                    bucket, object_, opts)
        imeta = info.internal_metadata or {}
        if imeta.get("x-internal-sse-alg") \
                or imeta.get("x-internal-comp"):
            return None
        length = info.size
        name = imeta.get(tier_mod.META_TIER)
        if not name or self.tiers is None or length == 0:
            return None
        try:
            backend = self.tiers.get(name)
        except Exception:  # noqa: BLE001 - tier config drift
            return None
        local_path = getattr(backend, "local_path", None)
        if local_path is None:
            return None
        path = local_path(imeta.get(tier_mod.META_TIER_KEY, ""))
        try:
            fd = os.open(path, os.O_RDONLY)
        except OSError:
            return None
        if os.fstat(fd).st_size != length:
            # Stored size must equal the plaintext length for a raw
            # file copy (no transform); anything else is not ours
            # to stream.
            os.close(fd)
            return None
        return info, fd, 0, length

    def _window_descs(self, fi: FileInfo, offset: int,
                      length: int) -> list[tuple]:
        """(part_number, part_size, rel, step) windows covering
        [offset, offset+length), snapped to erasure-block boundaries
        within each part so consecutive windows never re-read a block."""
        parts = fi.parts or [ObjectPartInfo(number=1, size=fi.size,
                                            actual_size=fi.size)]
        descs: list[tuple] = []
        cum = 0
        for p in parts:
            p_lo = max(offset, cum)
            p_hi = min(offset + length, cum + p.size)
            pos = p_lo
            while pos < p_hi:
                rel = pos - cum
                end_rel = min(p.size,
                              (rel // BLOCK_SIZE) * BLOCK_SIZE
                              + GET_WINDOW_BYTES)
                step = min(p_hi - pos, end_rel - rel)
                descs.append((p.number, p.size, rel, step))
                pos += step
            cum += p.size
            if cum >= offset + length:
                break
        return descs

    def _iter_payload(self, bucket: str, object_: str, fi: FileInfo,
                      fis: list, offset: int, length: int):
        """Yield [offset, offset+length) as block-aligned windows.

        Readahead: while window i is on the wire to the client, window
        i+1 is already fetching/verifying/decoding through the
        per-drive engine queues — bounded to ONE window in flight, so
        memory stays O(window). Chunks decoded by the native kernel
        are POOLED-buffer views (pool -> decode -> socket, the read
        mirror of the PUT path's leased buffers): each is valid until
        the consumer pulls the next chunk (or closes the generator),
        when its lease returns to the pool. The caller's request
        deadline is re-bound inside the prefetch thread, and the
        namespace read lock (held by get_object_stream around this
        iterator) outlives every prefetch it issues."""
        from minio_tpu.object import tier as tier_mod
        if (fi.metadata or {}).get(tier_mod.META_TIER):
            # Transitioned version: stream the warm-tier range in
            # GET_WINDOW_BYTES windows instead of one O(range) blob.
            pos, end = offset, offset + length
            while pos < end:
                step = min(GET_WINDOW_BYTES, end - pos)
                yield self._tier_read(fi, pos, step)
                pos += step
            return
        descs = self._window_descs(fi, offset, length)
        if not descs:
            return
        inline_cache: dict = {}
        if len(descs) == 1:
            # Sub-window response (inline objects, small ranges, any
            # GET that fits one window): read on the calling thread —
            # there is nothing to prefetch, so the pool submit/join
            # round-trip is pure overhead — and hand the pooled view
            # straight to the socket, where the serve path gathers it
            # with the response head into ONE sendmsg.
            num, psize, rel, step = descs[0]
            chunk, lease = self._read_part_window_pooled(
                bucket, object_, fi, fis, num, psize, rel, step,
                inline_cache=inline_cache)
            try:
                yield chunk
            finally:
                if lease is not None:
                    lease.release()
            return
        dl = deadline_mod.current()
        tctx, tparent = tracing.capture() if tracing.ACTIVE else (None, 0)

        def read_desc(desc, t_submit):
            waited = _time_mod.perf_counter() - t_submit
            with self._gk_mu:
                self.get_pool_wait[0] += waited
                self.get_pool_wait[1] += 1
            num, psize, rel, step = desc
            with deadline_mod.bind(dl), tracing.bind(tctx, tparent):
                return self._read_part_window_pooled(
                    bucket, object_, fi, fis, num, psize, rel, step,
                    inline_cache=inline_cache)

        def submit(desc):
            return self.pool.submit(read_desc, desc,
                                    _time_mod.perf_counter())

        fut = submit(descs[0])
        lease = None
        try:
            for i in range(len(descs)):
                # The request waits for its window: the pool's queue,
                # then the window's own read (stage get.window).
                with tracing.stage("get.window_wait", type_="storage",
                                   cpu=False):
                    chunk, lease = fut.result()
                # Prefetch the NEXT window before handing this one to
                # the consumer: its drive reads overlap the socket
                # sends (and the native decode releases the GIL).
                fut = submit(descs[i + 1]) if i + 1 < len(descs) else None
                yield chunk
                if lease is not None:
                    lease.release()
                    lease = None
        finally:
            if lease is not None:
                lease.release()
            if fut is not None:
                # A prefetch is still in flight (consumer closed early
                # or a window failed): collect it so its lease returns
                # — abandoning the future would park a pooled buffer
                # until GC (the pool's leak net would count it).
                try:
                    _, l2 = fut.result()
                    if l2 is not None:
                        l2.release()
                except BaseException:  # noqa: BLE001 - already unwinding
                    pass

    def _tier_read(self, fi: FileInfo, offset: int,
                   length: int) -> Optional[bytes]:
        """Transitioned version? Fetch the stored byte range from its
        warm tier (reference: getTransitionedObjectReader,
        cmd/bucket-lifecycle.go); None for local versions."""
        from minio_tpu.object import tier as tier_mod
        name = (fi.metadata or {}).get(tier_mod.META_TIER)
        if not name:
            return None
        if self.tiers is None:
            raise StorageError(
                f"version is tiered to {name!r} but no tier registry "
                "is configured")
        backend = self.tiers.get(name)
        return backend.get(fi.metadata[tier_mod.META_TIER_KEY],
                           offset, length)

    def _read_payload(self, bucket: str, object_: str, fi: FileInfo,
                      fis: list, offset: int, length: int) -> bytes:
        """Read [offset, offset+length) across the object's parts.

        Each part is an independent erasure encode stored as part.N shard
        files (reference: multipart parts keep their own erasure framing,
        cmd/erasure-object.go per-part loop at :368-387); single-put
        objects are the one-part special case."""
        tb = self._tier_read(fi, offset, length)
        if tb is not None:
            return tb
        parts = fi.parts or [ObjectPartInfo(number=1, size=fi.size,
                                            actual_size=fi.size)]
        out = bytearray()
        cum = 0
        inline_cache: dict = {}
        for p in parts:
            p_lo = max(offset, cum)
            p_hi = min(offset + length, cum + p.size)
            if p_hi > p_lo:
                out += self._read_part_window(
                    bucket, object_, fi, fis, p.number, p.size,
                    p_lo - cum, p_hi - p_lo, inline_cache=inline_cache)
            cum += p.size
            if cum >= offset + length:
                break
        return bytes(out)

    def _read_part_window(self, bucket: str, object_: str, fi: FileInfo,
                          fis: list, part_number: int, part_size: int,
                          offset: int, length: int,
                          inline_cache: Optional[dict] = None) -> bytes:
        """Self-owned-bytes wrapper over _read_part_window_pooled for
        callers that hold the result past the read (buffered GET,
        tiering upload)."""
        chunk, lease = self._read_part_window_pooled(
            bucket, object_, fi, fis, part_number, part_size, offset,
            length, inline_cache=inline_cache)
        if lease is None:
            return chunk
        try:
            return bytes(chunk)
        finally:
            lease.release()

    def _read_part_window_pooled(self, bucket: str, object_: str,
                                 fi: FileInfo, fis: list, part_number: int,
                                 part_size: int, offset: int, length: int,
                                 inline_cache: Optional[dict] = None):
        """_read_window as the stage `get.window`, inside a nameless
        `request_root()`: the window's parts (get.fetch, get.stack,
        get.deframe, get.interleave; on the rebuild path
        get.survivor_verify, get.rebuild_stack, get.rebuild, get.join)
        reach the totals with it, so a part over the whole is a ratio
        of whole windows, whichever thread read them."""
        with tracing.request_root(), \
                tracing.stage("get.window", type_="storage", cpu=False):
            return self._read_window(bucket, object_, fi, fis, part_number,
                                     part_size, offset, length,
                                     inline_cache)

    def _read_window(self, bucket: str, object_: str, fi: FileInfo,
                     fis: list, part_number: int, part_size: int,
                     offset: int, length: int,
                     inline_cache: Optional[dict] = None):
        """Gather only the erasure blocks covering the window inside one
        part: verified shard-block slices (k preferred, hedge to all),
        batched reconstruct of missing shards, block-major reassembly.
        I/O, hashing and memory are O(range), not O(object) — the
        reference's ShardFileOffset range math (cmd/erasure-coding.go:135).

        Returns (chunk, lease). The fast path is the fused native GET
        kernel (native/native.cc mtpu_get_frame): ONE GIL-free ctypes
        call verifies every shard block's HighwayHash digest and
        interleaves the data block-major straight into a pooled buffer;
        chunk is then a memoryview into `lease` and the caller owns one
        reference. The numpy path (native lib absent, non-default
        algorithm, missing/corrupt shards needing reconstruction)
        returns (bytes, None) — byte-identical output either way.

        `inline_cache`: per-REQUEST dict sharing resolved inline blobs
        across this request's windows and shard fetches — an inline
        journal read with the empty not-loaded sentinel re-fetches each
        holder's xl.meta at most once per request, not once per shard
        fetch per window."""
        k, m = fi.erasure.data_blocks, fi.erasure.parity_blocks
        n = k + m
        e = self._erasure(k, m)
        shard_size = e.shard_size()
        shard_file_len = e.shard_file_size(part_size)
        hsize = bitrot.digest_size(bitrot.DEFAULT_ALGORITHM)
        frame = hsize + shard_size
        part_file = f"part.{part_number}"

        start_b = offset // BLOCK_SIZE
        end_b = (offset + length - 1) // BLOCK_SIZE
        # Per-shard data/framed byte windows covering those blocks.
        data_lo = start_b * shard_size
        data_hi = min(shard_file_len, (end_b + 1) * shard_size)
        framed_lo = start_b * frame
        framed_hi = min(bitrot.shard_file_size(shard_file_len, shard_size),
                        (end_b + 1) * frame)
        win_len = data_hi - data_lo

        # Which disk holds which shard index for THIS version.
        holders: dict[int, int] = {}  # shard_idx -> disk idx
        for disk_idx, dfi in enumerate(fis):
            if dfi is None or dfi.deleted:
                continue
            if (dfi.mod_time, dfi.data_dir) != (fi.mod_time, fi.data_dir):
                continue
            holders[dfi.erasure.index - 1] = disk_idx

        def resolve_inline(disk_idx: int) -> bytes:
            """This holder's full inline shard blob, re-read from its
            journal at most once per request when fis carries the
            empty not-loaded sentinel."""
            blob = fis[disk_idx].inline_data
            if blob:
                return blob
            if inline_cache is not None and disk_idx in inline_cache:
                return inline_cache[disk_idx]
            blob = self.disks[disk_idx].read_version(
                bucket, object_, fi.version_id,
                read_data=True).inline_data or b""
            if inline_cache is not None:
                inline_cache[disk_idx] = blob
            return blob

        def fetch_raw(shard_idx: int):
            """Raw framed bytes of this shard's block window (no verify)."""
            disk_idx = holders.get(shard_idx)
            if disk_idx is None:
                return None
            d = self.disks[disk_idx]
            dfi = fis[disk_idx]
            try:
                if dfi.inline_data is not None:
                    return resolve_inline(disk_idx)[framed_lo:framed_hi]
                return d.read_file(
                    bucket, f"{object_}/{fi.data_dir}/{part_file}",
                    offset=framed_lo, length=framed_hi - framed_lo)
            except DeadlineExceeded:
                # The REQUEST ran out of budget, not the shard out of
                # luck — must reach the quorum triage, not become a
                # silent missing shard.
                raise
            except Exception:  # noqa: BLE001 - bad shard == missing shard
                return None

        # Bitrot verification batches across shards AND blocks — on the
        # device when this set runs the TPU backend and the window is
        # big enough to fill vector tiles, vectorized-host otherwise
        # (read-side counterpart of the fused PUT pipeline; the
        # reference hashes per block in ReadAt,
        # cmd/bitrot-streaming.go:161-200).
        use_device = self._device_capable() and device.on_tpu()

        def verify(blobs):
            """The rebuild path's verify of fetched shards (the host's
            HighwayHash, or the device's for a large enough batch),
            counting each fetched one it refuses for bitrot."""
            with tracing.stage("get.survivor_verify", type_="kernel",
                               cpu=False):
                out = bitrot.read_framed_blocks_many(
                    blobs, shard_size, win_len, device=use_device)
            refused = sum(1 for b, r in zip(blobs, out)
                          if b is not None and r is None)
            if refused:
                with self._gk_mu:
                    self.get_survivors_refused += refused
            return out

        def fetch_many(shard_idxs):
            """Fetch a set of shards through their holders' per-drive
            engine queues: the fns list is aligned with self.disks (so
            _fanout routes it per drive), results return in shard
            order. Shards with no holder stay None."""
            n_disks = len(self.disks)
            fns: list = [None] * n_disks
            pos: dict[int, int] = {}
            for s in shard_idxs:
                di = holders.get(s)
                if di is None:
                    continue
                pos[s] = di
                fns[di] = (lambda s=s: fetch_raw(s))
            with tracing.stage("get.fetch", type_="storage", cpu=False):
                results, errs = self._fanout(fns)
            return ([results[pos[s]] if s in pos else None
                     for s in shard_idxs],
                    [errs[pos[s]] if s in pos else None
                     for s in shard_idxs])

        # Read data shards first; hedge with parity shards for failures.
        shards: list[Optional[np.ndarray]] = [None] * n
        results, ferrs = fetch_many(range(k))
        skip = offset - start_b * BLOCK_SIZE

        # Fast path: all k data shards present and whole -> ONE
        # verify+interleave pass over the window. Per-host calibration
        # picks between the batched DEVICE route (cross-request
        # coalesced de-framer dispatch, ops/batcher get route) and the
        # fused native host kernel — byte-identical outputs. A nonzero
        # bad-mask either way means bitrot: demote those shards to
        # missing and take the reconstruct path below (which
        # re-verifies, rebuilds, and enqueues the MRF heal).
        dev_got = self._device_get_window(results, k, m, shard_size,
                                          win_len, start_b, end_b,
                                          part_size)
        got = None
        if dev_got is not None:
            view, lease, bad, route = dev_got
            if not bad:
                # A coalesced batch below min_device_blocks resolves to
                # the batcher's vectorized host fallback even under a
                # device calibration — count it as the numpy path, not
                # a device window.
                self._count_get("device" if route == "device"
                                else "numpy")
                return view[skip:skip + length], lease
            got = (view, lease, bad)
        else:
            got = self._native_get_window(results, k, shard_size,
                                          win_len, start_b, end_b,
                                          part_size)
        if got is not None:
            view, lease, bad = got
            if not bad:
                self._count_get("native")
                return view[skip:skip + length], lease
            self._count_get("demoted")
            for s in range(k):
                if bad >> s & 1:
                    results[s] = None

        self._count_get("numpy")
        for s, r in enumerate(verify(results)):
            shards[s] = r
        missing = [s for s in range(k) if shards[s] is None]
        if missing:
            extra, ferrs2 = fetch_many(range(k, n))
            for j, r in enumerate(verify(extra)):
                shards[k + j] = r
            available = sum(1 for s in shards if s is not None)
            if available < k:
                _raise_for_quorum(
                    ferrs + ferrs2,
                    ReadQuorumError(bucket, object_,
                                    f"{available}/{n} shards readable"),
                    quorum=k, ok=available)
            self._decode_missing(e, k, m, shards, shard_size)
            # Bytes were served from reconstruction: heal in background
            # (reference: MRF enqueue on degraded reads,
            # cmd/erasure-object.go:399-417).
            self.mrf.enqueue(bucket, object_, fi.version_id)

        # Blocks interleave across shards: reassemble block-major, trimming
        # each block's zero padding (k*shard_size may exceed BLOCK_SIZE).
        with tracing.stage("get.join", type_="kernel", cpu=False):
            out = bytearray()
            for b in range(start_b, end_b + 1):
                lo = (b - start_b) * shard_size
                hi = min((b - start_b + 1) * shard_size, win_len)
                chunk = b"".join(shards[s][lo:hi].tobytes()
                                 for s in range(k))
                take = min(BLOCK_SIZE, part_size - b * BLOCK_SIZE)
                out += chunk[:take]
            # `out` holds object bytes [start_b*BLOCK_SIZE, ...); cut
            # the range.
            return bytes(out[skip:skip + length]), None

    def _count_get(self, path: str) -> None:
        with self._gk_mu:
            self.get_kernel[path] += 1

    def _device_capable(self) -> bool:
        """The set was configured with a device backend. Checked FIRST
        at every device gate: a host-codec process (every pre-forked
        worker is one) must never import JAX just to learn the
        platform — on a TPU host that alone would fight the owner
        process for the chip."""
        return hasattr(self.backend, "apply_matrix_device")

    def _wants_device_route(self, route: str) -> bool:
        """Platform gate for a batched device dispatch on `route`: the
        set must run a device-capable backend, and either this host is
        a TPU host or MTPU_BATCH_FORCE pins the route (the
        reproducibility knob must reach the REAL batched device route
        on any host: CI plumbing proofs and the scaling sweeps run it
        on virtual CPU devices)."""
        return (self._device_capable()
                and (batch_force_mode(route) == "device"
                     or device.on_tpu()))

    def _device_get_window(self, results, k: int, m: int,
                           shard_size: int, win_len: int, start_b: int,
                           end_b: int, part_size: int):
        """Batched device verify of k fetched shard windows — the
        device twin of _native_get_window, riding the cross-request
        get batcher. The window's FULL frames stack into one member
        [full, k, 32+shard_size]; concurrent GETs' members coalesce
        into one mesh de-framer dispatch that recomputes every digest
        on device. The ragged tail frame (a part's short last block)
        verifies on host. Verified payload interleaves block-major
        into a pooled lease from the member's own bytes (views — the
        payload never rides the device link back).

        None when the route does not apply (calibration resolved to
        host, non-default algorithm, missing/short shards, no full
        frames); otherwise (view, lease, 0, route) on success or
        (None, None, bad_mask, route) — route is the dispatch path the
        batcher actually took ("device", or "host"/"bypass" when a
        coalesced batch fell below the device threshold), so the
        caller's path metrics stay honest."""
        if bitrot.DEFAULT_ALGORITHM != bitrot.HIGHWAYHASH256S \
                or win_len <= 0 or not self._wants_device_route("get"):
            return None
        sb = _get_batcher_for(k, m)
        nb = end_b - start_b + 1
        slast = win_len - (nb - 1) * shard_size
        hsize = bitrot.digest_size(bitrot.DEFAULT_ALGORITHM)
        frame = hsize + shard_size
        expect = nb * hsize + win_len
        blobs = []
        for r in results:
            if r is None or len(r) != expect:
                return None
            blobs.append(np.frombuffer(
                r if isinstance(r, (bytes, bytearray)) else bytes(r),
                dtype=np.uint8))
        full = nb if slast == shard_size else nb - 1
        if full < 1 or not sb.worth_batching(full):
            # Solo sub-threshold windows (the hot 1 MiB repeat GET with
            # no concurrency) keep the fused native kernel — the
            # batcher only wins when there is a device-sized window or
            # company to coalesce with.
            return None
        # The member is stacked into a pooled lease, not a fresh array:
        # at EC 8+4 it is 33.5 MB, over glibc's mmap threshold, so a
        # fresh one is mapped anew each window and faults in a page per
        # 4 KiB written (~10 us each behind a gVisor sandbox). The
        # de-frame's payload views it (_get_split), and a lone member
        # that fills its bucket is handed to the device as it is, so
        # the lease is held until the answer is interleaved.
        stack = None
        try:
            with tracing.stage("get.stack", type_="kernel"):
                stack = global_pool().lease(full * k * frame)
                stacked = stack.ndarray((full, k, frame))
                for i, arr in enumerate(blobs):
                    stacked[:, i, :] = arr[:full * frame].reshape(full,
                                                                  frame)
            try:
                # the batcher's queue, its stage, the lane and its finish
                with tracing.stage("get.deframe", type_="kernel",
                                   cpu=False):
                    ok, data = sb.frame(stacked)
            except DeadlineExceeded:
                raise
            except Exception:  # noqa: BLE001 - device trouble != corruption
                # Counted and logged by the batcher (device.record_fault);
                # absorbed into the native path only where nobody asked
                # for the device.
                if device.required():
                    raise
                return None
            route = sb.last_route()
            bad = 0
            for i in range(k):
                if not ok[:, i].all():
                    bad |= 1 << i
            if full < nb:
                off = full * frame
                for i, arr in enumerate(blobs):
                    want = arr[off:off + hsize].tobytes()
                    tail = arr[off + hsize:off + hsize + slast]
                    if bitrot.hash_block(bitrot.DEFAULT_ALGORITHM,
                                         tail) != want:
                        bad |= 1 << i
            if bad:
                return None, None, bad, route
            take_last = min(BLOCK_SIZE, part_size - end_b * BLOCK_SIZE)
            out_len = (nb - 1) * BLOCK_SIZE + min(take_last, k * slast)
            with tracing.stage("get.interleave", type_="kernel"):
                lease = global_pool().lease(out_len)
                try:
                    out = lease.ndarray((out_len,))
                    bulk, alone = _interleave_window(out, data, out_len,
                                                     BLOCK_SIZE)
                    if full < nb:
                        off = full * frame + hsize
                        _copy_block(out[full * BLOCK_SIZE:],
                                    [arr[off:off + slast] for arr in blobs])
                        alone += 1
                except BaseException:
                    lease.release()
                    raise
            with self._gk_mu:
                self.get_interleave_blocks["bulk"] += bulk
                self.get_interleave_blocks["block"] += alone
            return lease.view(out_len), lease, 0, route
        finally:
            if stack is not None:
                stack.release()

    def _decode_missing(self, e, k: int, m: int, shards, shard_size: int):
        """Fill missing DATA shards from k survivors, routing the GF
        rebuild through the batched device reconstruct
        (ops/rs_device.make_mesh_matrix via the reconstruct batcher)
        when this host's decode calibration says the device wins; the
        host codec path (e.decode_data_blocks) is the byte-identical
        fallback and still owns every edge shape (short survivor sets,
        zero-length shards, ragged-only windows)."""
        missing_data = [i for i in range(k)
                        if shards[i] is None or shards[i].size == 0]
        if not missing_data:
            return
        plan = self._device_rebuild_plan(k, m, shards, shard_size,
                                         missing_data)
        if plan is None:
            with tracing.stage("get.rebuild", type_="kernel", cpu=False):
                e.decode_data_blocks(shards)
            return
        sb, use, shard_len, full = plan
        with tracing.stage("get.rebuild_stack", type_="kernel", cpu=False):
            stacked = np.empty((full, k, shard_size), dtype=np.uint8)
            for j, i in enumerate(use):
                stacked[:, j, :] = \
                    shards[i][:full * shard_size].reshape(full, shard_size)
        with tracing.stage("get.rebuild", type_="kernel", cpu=False):
            try:
                out = sb.frame(stacked)      # [full, r, shard_size]
            except DeadlineExceeded:
                raise
            except Exception:  # noqa: BLE001 - device trouble -> host codec
                if device.required():
                    raise
                e.decode_data_blocks(shards)
                return
            tail = shard_len - full * shard_size
            rebuilt = [np.empty(shard_len, dtype=np.uint8)
                       for _ in missing_data]
            for r_i in range(len(missing_data)):
                rebuilt[r_i][:full * shard_size] = \
                    out[:, r_i, :].reshape(-1)
            if tail:
                from minio_tpu.ops import gf256
                dec = gf256.decode_matrix(k, m, use)
                tail_in = np.stack([shards[i][full * shard_size:]
                                    for i in use])
                tout = np.asarray(e.backend.apply_matrix(
                    dec[list(missing_data), :], tail_in))
                for r_i in range(len(missing_data)):
                    rebuilt[r_i][full * shard_size:] = tout[r_i]
            for r_i, i in enumerate(missing_data):
                shards[i] = rebuilt[r_i]

    def _device_rebuild_plan(self, k: int, m: int, shards, shard_size: int,
                             missing_data: list):
        """(batcher, survivors used, shard length, full blocks) when the
        batched device reconstruct takes this window; None when the
        host codec does — which also owns every edge shape: too few
        survivors (surfaces ReconstructError), survivors of unequal
        length (ShardSizeError), no full block or a lone window under
        the batcher's threshold."""
        if not (m > 0 and self._wants_device_route("reconstruct")):
            return None
        present = [i for i, s in enumerate(shards)
                   if s is not None and s.size > 0]
        if len(present) < k:
            return None
        use = tuple(present[:k])             # same pick as the codec
        shard_len = shards[use[0]].shape[0]
        if any(shards[i].shape[0] != shard_len for i in use):
            return None
        full = shard_len // shard_size
        sb = _reconstruct_batcher_for(k, m, use, tuple(missing_data))
        if full < 1 or not sb.worth_batching(full):
            return None
        return sb, use, shard_len, full

    def _verify_shard_blob(self, blob, shard_size: int, data_size: int):
        """Verified un-framed data of ONE framed shard blob, or None on
        bitrot/short read — bitrot.read_framed_blocks_many's per-blob
        contract, with the full frames routed through the batched
        device verify (k=1 members of the get batcher) when calibration
        says the device wins. Heal's deep verification — including the
        drive-replacement bulk heal — fans one call per drive through
        the engine crews, so concurrent shard files coalesce into
        shared de-framer dispatches."""
        hsize = bitrot.digest_size(bitrot.DEFAULT_ALGORITHM)
        frame = hsize + shard_size
        nb = (data_size + shard_size - 1) // shard_size if shard_size \
            else 0
        full = nb if data_size == nb * shard_size else nb - 1
        use_device = self._device_capable()
        if bitrot.DEFAULT_ALGORITHM != bitrot.HIGHWAYHASH256S \
                or full < 1 or not self._wants_device_route("get") \
                or len(blob) != bitrot.shard_file_size(data_size,
                                                       shard_size):
            arr, = bitrot.read_framed_blocks_many(
                [blob], shard_size, data_size, device=use_device)
            return arr
        sb = _get_batcher_for(1, 0)
        if not sb.worth_batching(full):
            arr, = bitrot.read_framed_blocks_many(
                [blob], shard_size, data_size, device=use_device)
            return arr
        arr8 = np.frombuffer(blob, dtype=np.uint8)
        member = arr8[:full * frame].reshape(full, 1, frame)
        try:
            ok, data = sb.frame(member)
        except DeadlineExceeded:
            raise
        except Exception:  # noqa: BLE001 - device trouble -> host path
            if device.required():
                raise
            arr, = bitrot.read_framed_blocks_many(
                [blob], shard_size, data_size, device=use_device)
            return arr
        if not ok.all():
            return None
        tail = data_size - full * shard_size
        if tail:
            off = full * frame
            want = arr8[off:off + hsize].tobytes()
            tdat = arr8[off + hsize:off + hsize + tail]
            if bitrot.hash_block(bitrot.DEFAULT_ALGORITHM, tdat) != want:
                return None
        out = np.empty(data_size, dtype=np.uint8)
        out[:full * shard_size] = data.reshape(full, shard_size) \
            .reshape(-1)
        if tail:
            off = full * frame + hsize
            out[full * shard_size:] = arr8[off:off + tail]
        return out

    def _native_get_window(self, results, k: int, shard_size: int,
                           win_len: int, start_b: int, end_b: int,
                           part_size: int):
        """Run the fused native GET kernel over k fetched shard windows.

        None when the fast path does not apply (native lib absent,
        non-default bitrot algorithm, a shard missing or short — those
        need the reconstruct path). Otherwise (view, lease, 0) with the
        window's plaintext in a pooled lease the caller now owns, or
        (None, None, bad_mask) when verification failed bit-mask shards
        (the lease is already returned)."""
        if bitrot.DEFAULT_ALGORITHM != bitrot.HIGHWAYHASH256S \
                or win_len <= 0:
            return None
        from minio_tpu import native
        lib = native.load()
        if lib is None:
            return None
        nb = end_b - start_b + 1
        slast = win_len - (nb - 1) * shard_size
        hsize = bitrot.digest_size(bitrot.DEFAULT_ALGORITHM)
        expect = nb * hsize + win_len
        blobs = []
        for r in results:
            if r is None or len(r) != expect:
                return None
            blobs.append(r if isinstance(r, bytes) else bytes(r))
        take_last = min(BLOCK_SIZE, part_size - end_b * BLOCK_SIZE)
        out_len = (nb - 1) * BLOCK_SIZE + min(take_last, k * slast)

        import ctypes

        from minio_tpu.utils.highwayhash import MAGIC_KEY
        u8p = ctypes.POINTER(ctypes.c_uint8)
        # c_char_p views the bytes objects' buffers without copying;
        # `keep` pins them for the duration of the call.
        keep = [ctypes.c_char_p(b) for b in blobs]
        ptrs = (u8p * k)(*[ctypes.cast(c, u8p) for c in keep])
        lease = global_pool().lease(out_len)
        out = (ctypes.c_uint8 * out_len).from_buffer(lease.raw)
        try:
            with tracing.span("kernel", "mtpu_get_frame",
                              {"blocks": nb, "k": k}) \
                    if tracing.ACTIVE else tracing.NOOP:
                bad = lib.mtpu_get_frame(
                    native._u8(MAGIC_KEY), ptrs, k, shard_size, nb, slast,
                    BLOCK_SIZE, take_last, out)
        except BaseException:
            lease.release()
            raise
        finally:
            del out     # drop the ctypes export so the mmap can recycle
        if bad:
            lease.release()
            return None, None, int(bad)
        return lease.view(out_len), lease, 0

    # ------------------------------------------------------------------
    # info / delete / list
    # ------------------------------------------------------------------

    def get_object_info(self, bucket: str, object_: str,
                        opts: Optional[GetOptions] = None) -> ObjectInfo:
        opts = opts or GetOptions()
        fi, _, _ = self._get_object_fileinfo(bucket, object_,
                                             opts.version_id,
                                             stat_only=True)
        if fi.deleted:
            # Same AWS mapping as get_object: 404 for latest-is-marker,
            # 405 when the marker's version is named explicitly.
            if opts.version_id:
                raise MethodNotAllowed(bucket, object_)
            raise ObjectNotFound(bucket, object_)
        return self._to_object_info(bucket, object_, fi)

    @staticmethod
    def _to_object_info(bucket: str, object_: str, fi: FileInfo) -> ObjectInfo:
        meta = dict(fi.metadata)
        etag = meta.pop("etag", "")
        ctype = meta.pop("content-type", "")
        tags = meta.pop("x-amz-tagging", "")
        internal = {k: meta.pop(k) for k in list(meta)
                    if k.startswith("x-internal-")}
        size = fi.size
        # Content transforms (SSE, compression) store the logical size
        # internally; the API surface reports it, the storage size
        # stays in fi. Compression's size wins when BOTH transforms are
        # present (compress-then-encrypt): the sse size is then the
        # DARE stream's plaintext = the COMPRESSED length, not the
        # object's logical bytes.
        logical = internal.get("x-internal-comp-size") \
            or internal.get("x-internal-sse-size")
        if logical is not None:
            try:
                size = int(logical)
            except (TypeError, ValueError):
                pass
        return ObjectInfo(bucket=bucket, name=object_, mod_time=fi.mod_time,
                          size=size, etag=etag, content_type=ctype,
                          version_id=fi.version_id, is_latest=fi.is_latest,
                          delete_marker=fi.deleted, user_metadata=meta,
                          actual_size=size, user_tags=tags,
                          internal_metadata=internal,
                          parts=list(fi.parts or []))

    def update_version_metadata(self, bucket: str, object_: str,
                                version_id: str,
                                mutate,
                                allow_delete_marker: bool = False) -> ObjectInfo:
        """Apply `mutate(meta_dict)` to one version's metadata in
        place: each quorum-agreeing drive's own journal copy is
        rewritten, preserving its shard index and inline data
        (reference: PutObjectTags-style updateObjectMeta,
        cmd/erasure-object.go:1925).  Delete markers refuse the update
        unless allow_delete_marker is set — replication stamps its
        COMPLETED/FAILED status onto markers, while user-facing tag
        paths must keep rejecting them."""
        self._check_bucket(bucket)
        with self.ns.write(bucket, object_):
            fis, errors = self._read_version_all(bucket, object_, version_id,
                                                 read_data=True)
            n = len(self.disks)
            quorum = n // 2 + 1
            fi, idxs = self._quorum_fileinfo(fis, quorum)
            if fi is None:
                raise ObjectNotFound(bucket, object_)
            if fi.deleted and not allow_delete_marker:
                raise MethodNotAllowed(bucket, object_)
            # Only drives holding the quorum-agreeing copy are written
            # and counted: a success on a stale-version drive must not
            # let the update claim quorum (reference bounds writes to
            # onlineDisks of the read quorum).
            agree = set(idxs)

            def write_one(i: int):
                dfi = fis[i]
                meta = dict(dfi.metadata)
                mutate(meta)
                self.disks[i].write_metadata(
                    bucket, object_,
                    dataclasses.replace(dfi, metadata=meta))

            _, werrs = self._fanout(
                [(lambda i=i: write_one(i)) if i in agree else None
                 for i in range(n)])
            ok = sum(1 for i in agree if werrs[i] is None)
            if ok < quorum:
                raise WriteQuorumError(bucket, object_)
            if len(agree) < n:
                # Drives outside the agreeing set are stale/missing:
                # background heal brings them (and the update) over.
                self.mrf.enqueue(bucket, object_, fi.version_id)
        self.metacache.bump(bucket)
        meta = dict(fi.metadata)
        mutate(meta)
        return self._to_object_info(bucket, object_,
                                    dataclasses.replace(fi, metadata=meta))

    def update_object_tags(self, bucket: str, object_: str,
                           version_id: str = "",
                           tags: Optional[str] = None) -> ObjectInfo:
        """Set (tags=str) or remove (tags=None) a version's object tags
        in place (reference: PutObjectTags, cmd/erasure-object.go:1925)."""
        def mutate(meta):
            if tags is None:
                meta.pop("x-amz-tagging", None)
            else:
                meta["x-amz-tagging"] = tags
        return self.update_version_metadata(bucket, object_, version_id,
                                            mutate)

    def transition_version(self, bucket: str, object_: str,
                           version_id: str, tier_name: str) -> None:
        """Move one version's DATA to a warm tier, leaving its metadata
        local with a pointer (reference: transitionObject,
        cmd/bucket-lifecycle.go). The stored byte stream ships verbatim
        (SSE/compression transforms stay intact), so reads through
        _tier_read are byte-identical to local reads."""
        from minio_tpu.object import tier as tier_mod
        if self.tiers is None:
            raise StorageError("no tier registry configured")
        backend = self.tiers.get(tier_name)    # resolve before touching
        self._check_bucket(bucket)
        # Phase 1 — read + upload WITHOUT the key lock: shipping a
        # large object to a remote tier can take minutes, and holding
        # ns.write through it would LockTimeout every client operation
        # on the key. (Memory is O(object) for the upload buffer — a
        # v1 bound; the reference streams.)
        with self.ns.read(bucket, object_):
            fis, errors = self._read_version_all(bucket, object_,
                                                 version_id,
                                                 read_data=True)
            n = len(self.disks)
            quorum = n // 2 + 1
            fi, idxs = self._quorum_fileinfo(fis, quorum)
            if fi is None:
                raise ObjectNotFound(bucket, object_)
            if fi.deleted or fi.metadata.get(tier_mod.META_TIER):
                return                    # marker / already transitioned
            data = self._read_payload(bucket, object_, fi,
                                      fis, 0, fi.size)
        remote_key = tier_mod.tier_object_key(
            "", bucket, object_, fi.version_id).lstrip("/")
        backend.put(remote_key, data)
        # Phase 2 — commit the pointer under the lock, re-validating
        # that the version is still the one we uploaded (an overwrite
        # or delete during the upload orphans our tier copy: remove it
        # and bail; the next scanner cycle re-evaluates).
        with self.ns.write(bucket, object_):
            # read_data=False: only metadata decides the commit; the
            # data was uploaded in phase 1 and must not be re-read
            # under the exclusive lock.
            fis2, _ = self._read_version_all(bucket, object_, version_id,
                                             read_data=False)
            fi2, idxs2 = self._quorum_fileinfo(fis2, quorum)
            if fi2 is None or fi2.deleted or fi2.mod_time != fi.mod_time \
                    or fi2.metadata.get(tier_mod.META_TIER):
                # A concurrent transition may have committed a pointer
                # to the SAME deterministic remote key — removing it
                # would destroy the winner's blob. Reclaim only when a
                # READABLE version provably does not reference our
                # upload; fi2 None (transient quorum loss) proves
                # nothing, and an orphaned blob is the tolerable
                # failure mode.
                if fi2 is not None and fi2.metadata.get(
                        tier_mod.META_TIER_KEY) != remote_key:
                    backend.remove(remote_key)
                return
            new_meta = dict(fi2.metadata)
            new_meta[tier_mod.META_TIER] = tier_name
            new_meta[tier_mod.META_TIER_KEY] = remote_key
            new_meta[tier_mod.META_TIER_SIZE] = str(len(data))
            agree = set(idxs2)

            def rewrite_one(i: int):
                dfi = fis2[i]
                self.disks[i].write_metadata(
                    bucket, object_,
                    dataclasses.replace(dfi, metadata=dict(new_meta),
                                        inline_data=None))
                # The local shard files are now garbage: reclaim.
                if dfi.data_dir:
                    _swallow(lambda: self.disks[i].delete(
                        bucket, f"{object_}/{dfi.data_dir}",
                        recursive=True))

            _, werrs = self._fanout(
                [(lambda i=i: rewrite_one(i)) if i in agree else None
                 for i in range(n)])
            ok = sum(1 for i in agree if werrs[i] is None)
            if ok < quorum:
                # The tier copy exists but the pointer didn't commit:
                # remove the orphan and fail (next cycle retries).
                backend.remove(remote_key)
                raise WriteQuorumError(bucket, object_)
            if len(agree) < n:
                self.mrf.enqueue(bucket, object_, fi.version_id)
        # The version's data just moved off-drive and its local shard
        # dirs are gone: cached fileinfo (ours and sibling workers')
        # must re-resolve or reads would chase deleted shard files
        # instead of the tier pointer.
        self.metacache.bump(bucket)

    def _tier_pointer(self, bucket: str, object_: str,
                      version_id: str) -> Optional[tuple[str, str]]:
        """(tier name, remote key) when the version was transitioned,
        else None — read BEFORE deletion (the pointer dies with the
        metadata) but acted on only AFTER the delete succeeds."""
        if self.tiers is None:
            return None
        from minio_tpu.object import tier as tier_mod
        for d in self.disks:
            try:
                fi = d.read_version(bucket, object_, version_id)
            except Exception:  # noqa: BLE001 - try another drive
                continue
            name = (fi.metadata or {}).get(tier_mod.META_TIER)
            if name:
                return name, fi.metadata.get(tier_mod.META_TIER_KEY, "")
            return None
        return None

    def delete_object(self, bucket: str, object_: str,
                      opts: Optional[DeleteOptions] = None) -> DeletedObject:
        opts = opts or DeleteOptions()
        self._check_bucket(bucket)
        with self.ns.write(bucket, object_):
            ptr = None
            if (opts.version_id or not opts.versioned) \
                    and not opts.null_marker:
                # (null_marker stacks a marker — the latest version
                # SURVIVES, so its warm-tier blob must too.)
                # Version destruction (not marker stacking): note a
                # transitioned version's tier pointer now; the blob is
                # reclaimed only AFTER the delete commits (removing it
                # first would lose the data if the delete then fails
                # quorum). Lives HERE, not in _delete_object_locked —
                # decommission's internal deletes migrate the pointer
                # and must keep the blob.
                ptr = self._tier_pointer(bucket, object_, opts.version_id)
            result = self._delete_object_locked(bucket, object_, opts)
            if ptr is not None:
                name, remote_key = ptr
                try:
                    self.tiers.get(name).remove(remote_key)
                except Exception:  # noqa: BLE001 - orphan tolerated
                    pass
            return result

    def _delete_object_locked(self, bucket: str, object_: str,
                              opts: DeleteOptions) -> DeletedObject:
        n = len(self.disks)
        write_quorum = n // 2 + 1

        if (opts.versioned or opts.null_marker) and not opts.version_id:
            # Versioned delete without a version: write a delete marker.
            # Suspended buckets stamp the NULL versionId instead of a
            # fresh one — write_metadata's add_version then REPLACES
            # the previous null version, exactly AWS's suspended-state
            # semantics (any Enabled-era versions stay untouched).
            marker_vid = "" if opts.null_marker \
                else (opts.marker_version_id or new_uuid())
            fi = FileInfo(volume=bucket, name=object_, version_id=marker_vid,
                          deleted=True, mod_time=now_ns(),
                          metadata=dict(opts.marker_metadata or {}))
            gc = self.group_commit
            used_group = False
            if gc is not None:
                # Delete markers are journal-only commits — the same
                # shape as inline PUTs, so a concurrent delete storm
                # coalesces through the same per-drive lanes.
                with gc.tracking():
                    if gc.worth_batching():
                        used_group = True
                        from minio_tpu.storage.group_commit import GroupOp
                        errors = gc.commit_fanout(
                            [GroupOp.write_meta(bucket, object_, fi)
                             for _ in self.disks])
                    else:
                        gc.note_solo()
                        _, errors = self._fanout(
                            [lambda d=d: d.write_metadata(
                                bucket, object_, fi)
                             for d in self.disks])
            else:
                _, errors = self._fanout(
                    [lambda d=d: d.write_metadata(bucket, object_, fi)
                     for d in self.disks])
            if sum(e is None for e in errors) < write_quorum:
                raise WriteQuorumError(bucket, object_)
            if not used_group:
                self.metacache.bump(bucket)
            return DeletedObject(object_name=object_, delete_marker=True,
                                 delete_marker_version_id=marker_vid or "null")

        _, errors = self._fanout(
            [lambda d=d: d.delete_version(bucket, object_, opts.version_id)
             for d in self.disks])
        ok = sum(e is None for e in errors)
        missing = sum(isinstance(e, (FileNotFoundErr, VersionNotFoundErr))
                      for e in errors)
        if ok + missing < write_quorum:
            raise WriteQuorumError(bucket, object_)
        if ok + missing < n and ok > 0:
            # A drive missed the delete: repair so listings/reads cannot
            # resurrect the version from the stale copy.
            self.mrf.enqueue(bucket, object_, opts.version_id)
        self.metacache.bump(bucket)
        return DeletedObject(object_name=object_, version_id=opts.version_id)

    def _walk_resolved(self, bucket: str, prefix: str,
                       start: str = "", shallow: bool = False):
        """Sorted (path, entry) stream — the metacache's production
        side. Per-drive sorted SCAN walks (storage/local.walk_scan:
        batched native journal decode; plain walk_dir for drives
        without it) over a MAJORITY of drives (any write quorum
        intersects the walked set, so committed objects are never
        invisible even when some drives missed the write), k-way
        merged, each key resolved from its journal copies into a
        trimmed stream entry. The walked set rotates per walk
        (reference askDisks rotation) so a drive failing mid-walk only
        shadows objects for some walks. `shallow` walks one level and
        passes subtree markers through (delimiter pages)."""
        import heapq
        from itertools import groupby

        from minio_tpu.storage.meta_scan import PREFIX_MARK

        base_dir = ""
        if "/" in prefix:
            base_dir = prefix.rsplit("/", 1)[0]

        def disk_iter(d):
            try:
                ws = getattr(d, "walk_scan", None)
                if ws is not None:
                    yield from ws(bucket, base_dir=base_dir,
                                  forward_from=max(start, prefix),
                                  shallow=shallow)
                else:
                    # Remote / legacy drives: stream raw journals; the
                    # resolver summarizes per blob (shallow callers
                    # gate on every drive supporting walk_scan).
                    for path, blob in d.walk_dir(
                            bucket, base_dir=base_dir,
                            forward_from=max(start, prefix)):
                        yield path, None, blob
            except Exception:  # noqa: BLE001 - drive loss tolerated
                return

        n_disks = len(self.disks)
        rotor = getattr(self, "_walk_rotor", 0)
        self._walk_rotor = (rotor + 1) % n_disks
        rotated = [self.disks[(rotor + i) % n_disks]
                   for i in range(n_disks)]
        walk_disks = rotated[:n_disks // 2 + 1]
        iters = [disk_iter(d) for d in walk_disks if d is not None]
        merged = heapq.merge(*iters, key=lambda kv: kv[0])
        for path, grp in groupby(merged, key=lambda kv: kv[0]):
            items = [(v, b) for _, v, b in grp]
            if any(v is PREFIX_MARK for v, _ in items):
                # Shallow subtree marker: present on ANY walked drive
                # => the prefix exists (same union the merged deep walk
                # would produce).
                yield path, PREFIX_MARK
                continue
            entry = self._resolve_walked(bucket, path, items, len(iters))
            if entry is not None:
                yield path, entry

    def _resolve_walked(self, bucket, path, items, total_walked):
        """Resolve one walked key's per-drive (summary, blob) copies to
        a stream entry.

        When every walked drive has the key and the copies agree on
        the latest version, the journal is authoritative (no extra I/O
        — the hot path): a summary covering listing needs becomes a
        trimmed ("s", vlist) entry with no Python journal parse at
        all; otherwise ONE copy's blob is parsed into a full ("m",
        maps) entry. Disagreement (a drive missed a delete/overwrite,
        or the object never reached all walked drives) falls back to a
        full quorum metadata read, exactly how the reference's
        metacache resolver escalates — a lone stale copy must not
        resurrect deleted objects, and a quorum-thin write must still
        be listed."""
        from minio_tpu.storage.meta import XLMeta
        from minio_tpu.storage.meta_scan import (FLAG_DELETED,
                                                 summary_sufficient)
        parsed = []      # (latest-key, vlist|None, blob|None, xl|None)
        for vlist, blob in items:
            if vlist is not None:
                if not vlist:
                    continue             # empty journal: nothing listed
                lv = vlist[0]
                latest = (lv[1], lv[3], bool(lv[0] & FLAG_DELETED),
                          lv[4])
                parsed.append((latest, vlist, blob, None))
            else:
                try:
                    xl = XLMeta.load(blob)
                    v0 = xl.versions[0]
                except Exception:  # noqa: BLE001 - unreadable copy
                    continue
                latest = (v0["mt"], v0["vid"],
                          v0.get("kind") == metafmt.KIND_DELETE_MARKER,
                          v0.get("ddir", "") or "")
                parsed.append((latest, None, blob, xl))
        agree = (len(parsed) == total_walked
                 and len({p[0] for p in parsed}) == 1)
        if agree:
            for _, vlist, _, _ in parsed:
                if vlist is not None and summary_sufficient(vlist):
                    return ("s", vlist)
            for _, _, blob, xl in parsed:
                if xl is None and blob is not None:
                    try:
                        xl = XLMeta.load(blob)
                    except Exception:  # noqa: BLE001
                        continue
                if xl is not None:
                    return ("m", list(xl.versions))
        try:
            fi, _, _ = self._get_object_fileinfo(bucket, path)
        except Exception:  # noqa: BLE001 - dangling / below quorum
            return None
        # Walked copies disagreed — only the quorum fi is trustworthy.
        return ("m", [fi.to_version_map()])

    def _shallow_ok(self, delimiter: str) -> bool:
        """Delimiter pages ride a one-level shallow walk when the
        delimiter is the path separator (collapse boundaries ==
        directory boundaries) and every drive can shallow-walk
        (storage/local.walk_scan; remote drives stream deep walks)."""
        if delimiter != "/" or os.environ.get(
                "MTPU_LIST_SHALLOW", "on").lower() in ("0", "off",
                                                       "false"):
            return False
        return all(d is not None
                   and getattr(d, "walk_scan", None) is not None
                   for d in self.disks)

    def _entry_fileinfos(self, bucket: str, path: str,
                         entry) -> list[FileInfo]:
        """Stream entry -> per-version FileInfos, latest first.

        Trimmed ("s") entries rebuild exactly the fields listings
        consume (identity with the full-journal path is golden-tested
        with the scanner on and off; `parts` is deliberately absent —
        no listing surface reads it)."""
        from minio_tpu.storage.meta_scan import (FLAG_DELETED,
                                                 FLAG_INLINE)
        kind, payload = entry
        if kind == "m":
            xl = metafmt.XLMeta()
            xl.versions = list(payload)
            try:
                return xl.list_versions(bucket, path)
            except Exception:  # noqa: BLE001 - empty maps
                return []
        out = []
        for i, (flags, mt, size, vid, ddir, etag, ctype, tags) in \
                enumerate(payload):
            fi = FileInfo(
                volume=bucket, name=path,
                version_id="" if vid == metafmt.NULL_VERSION_ID else vid,
                is_latest=(i == 0),
                deleted=bool(flags & FLAG_DELETED), mod_time=mt)
            meta = {}
            if etag:
                meta["etag"] = etag
            if ctype:
                meta["content-type"] = ctype
            if tags:
                meta["x-amz-tagging"] = tags
            fi.metadata = meta
            if not fi.deleted:
                fi.data_dir = ddir
                fi.size = size
                if flags & FLAG_INLINE:
                    fi.inline_data = b""     # marker: inline, not loaded
            out.append(fi)
        return out

    def list_objects(self, bucket: str, prefix: str = "", marker: str = "",
                     delimiter: str = "", max_keys: int = 1000,
                     include_versions: bool = False):
        """Sorted listing with prefix/marker/delimiter semantics, served
        from the shared metacache walk stream (reference:
        cmd/metacache-set.go:700): every page, every concurrent listing
        of the same prefix, and every follow-up within the reuse window
        consumes ONE background walk — a large bucket walks once, not
        once per page. Writes bump the bucket generation, orphaning the
        stream (object/metacache.py). "/"-delimiter pages use a SHALLOW
        stream (one directory level + subtree markers) so a browse page
        costs O(page) instead of O(subtree)."""
        import bisect

        from minio_tpu.object.types import ListObjectsInfo
        from minio_tpu.storage.meta_scan import PREFIX_MARK

        self._check_bucket(bucket)
        max_keys = max(1, min(max_keys, 1000))
        shallow = self._shallow_ok(delimiter)
        floor = marker if marker > prefix else prefix
        # A marker strictly INSIDE a collapsed subtree must re-surface
        # that subtree's common prefix (S3 semantics). The deep stream
        # does this naturally (later keys re-collapse); the shallow
        # stream holds ONE entry per subtree, sorted before such a
        # marker — widen the page scan floor back to it.
        page_floor, floor_left = marker, False
        if shallow and marker and marker.startswith(prefix):
            di = marker[len(prefix):].find("/")
            if di >= 0:
                cp = marker[:len(prefix) + di + 1]
                if cp != marker:
                    page_floor, floor_left = cp, True
        walk = self.metacache.walk_for(
            self, bucket, prefix, shallow=shallow,
            seek=page_floor if floor_left else marker)
        if walk.truncated and walk.done and walk.keys and \
                marker >= walk.keys[-1]:
            # Continuing past a capped stream: a start-floored walk
            # (shared by further continuations) keeps pagination
            # moving instead of re-walking into the same cap.
            walk = self.metacache.walk_for(self, bucket, prefix,
                                           start=marker, shallow=shallow)
        need = max_keys + 1
        while True:
            count, done = walk.wait_past(floor, need)
            keys, entries = walk.keys, walk.entries  # append-only; read
            # only indices < count (stable)
            info = ListObjectsInfo()
            seen_prefixes: set[str] = set()
            last_added = ""
            complete = False     # page filled or range exhausted
            if not marker:
                idx = 0
            elif floor_left:
                idx = bisect.bisect_left(keys, page_floor, 0, count)
            else:
                idx = bisect.bisect_right(keys, marker, 0, count)
            for i in range(idx, count):
                path = keys[i]
                if not path.startswith(prefix):
                    if path > prefix and not prefix.startswith(path):
                        complete = True
                        break    # sorted stream passed the prefix range
                    continue
                if delimiter:
                    rest = path[len(prefix):]
                    di = rest.find(delimiter)
                    if di >= 0:
                        cp = prefix + rest[:di + len(delimiter)]
                        # Skip a prefix only when the whole page before
                        # it was already returned; a marker INSIDE the
                        # prefix (start-after=a/1, cp=a/) must still
                        # surface it.
                        if cp in seen_prefixes or (
                                marker and cp <= marker
                                and not (marker.startswith(cp)
                                         and marker != cp)):
                            continue
                        if len(info.objects) + len(seen_prefixes) \
                                >= max_keys:
                            info.is_truncated = True
                            info.next_marker = last_added
                            complete = True
                            break
                        seen_prefixes.add(cp)
                        last_added = cp
                        continue
                entry = entries[i]
                if entry is PREFIX_MARK:
                    continue     # only reachable with a delimiter set
                fis = self._entry_fileinfos(bucket, path, entry)
                if not fis:
                    continue
                fi = fis[0]
                if fi.deleted and not include_versions:
                    continue
                if len(info.objects) + len(seen_prefixes) >= max_keys:
                    info.is_truncated = True
                    info.next_marker = last_added
                    complete = True
                    break
                if include_versions:
                    for v in fis:
                        info.objects.append(
                            self._to_object_info(bucket, path, v))
                else:
                    info.objects.append(
                        self._to_object_info(bucket, path, fi))
                last_added = path
            if complete or done:
                if walk.error is not None and not complete and not keys:
                    raise walk.error
                if done and not complete and walk.truncated:
                    # The stream hit its memory cap before the range
                    # was exhausted: page out what we have; the next
                    # page starts a fresh walk (expensive but correct —
                    # names past the cap must not silently vanish).
                    info.is_truncated = True
                    info.next_marker = last_added or (
                        keys[count - 1] if count else "")
                info.prefixes = sorted(seen_prefixes)
                return info
            # Stream not deep enough to fill the page yet: wait for
            # more entries (delimiter collapse can consume many raw
            # entries per returned prefix).
            need *= 2

    def list_versions_all(self, bucket: str, object_: str) -> list[FileInfo]:
        results, _ = self._fanout(
            [lambda d=d: d.list_versions(bucket, object_) for d in self.disks])
        for r in results:
            if r:
                return r
        raise ObjectNotFound(bucket, object_)


def _resolve_range(spec: tuple, size: int, bucket: str, object_: str):
    """(start|None, end|None) -> (offset, length), HTTP Range semantics."""
    lo, hi = spec
    if lo is None:                       # suffix: last `hi` bytes
        if hi is None or hi <= 0:
            raise InvalidRange(bucket, object_)
        start = max(0, size - hi)
        return start, size - start
    if lo >= size:
        raise InvalidRange(bucket, object_)
    if hi is None:
        return lo, size - lo
    if lo > hi:
        raise InvalidRange(bucket, object_)
    return lo, min(hi, size - 1) - lo + 1


def _join_chunks(chunks) -> bytes:
    """Flatten a per-drive framed chunk list to one bytes object."""
    if len(chunks) == 1:
        return bytes(chunks[0])
    return b"".join(bytes(c) for c in chunks)


def _clean_user_meta(meta: dict) -> dict:
    """Strip keys that would collide with the internal metadata
    namespace — a client must not be able to inject or clobber SSE
    parameters via x-amz-meta-x-internal-* headers."""
    return {k: v for k, v in meta.items()
            if not k.startswith("x-internal-")}


def _parity_matrix(k: int, m: int) -> np.ndarray:
    from minio_tpu.ops import gf256
    return gf256.parity_matrix(k, m)


def _swallow(fn):
    try:
        fn()
    except Exception:  # noqa: BLE001
        pass


def _unwrap_disk(d):
    """Innermost drive behind health/test wrappers (each exposes
    `wrapped`), bounded against pathological self-wrapping."""
    for _ in range(8):
        inner = getattr(d, "wrapped", None)
        if inner is None:
            return d
        d = inner
    return d


def _group_commit_capable(d) -> bool:
    """True when `d` implements the batched commit protocol in a way
    the group lanes may use. The health wrapper forwards; LocalStorage
    and CrashDisk define commit_group on their type; anything else
    (remote drives, NaughtyDisk — whose targeted fault injection a
    forwarded commit_group would silently bypass) keeps the solo
    fan-out. OfflineDisk slots pass: every op on them fails the same
    way solo ops do."""
    for _ in range(8):
        if d is None:
            return False
        cls = type(d)
        if cls.__name__ == "OfflineDisk":
            return True
        if "commit_group" in cls.__dict__:
            return True
        if cls.__name__ == "DiskHealthWrapper":
            d = d.wrapped
            continue
        return False
    return False


def _leased_fns(fns, lease):
    """Wrap per-drive fan-out callables so each holds its own reference
    on `lease` until its op truly completes: fan-out collection may
    abandon a future on deadline while the drive worker is still
    reading the pooled memory, and an unreferenced buffer recycled
    under a live reader is silent shard corruption. Each wrapper
    releases exactly once, in the worker's own thread. (A wrapper that
    never runs — engine shed, pre-expired deadline — parks its
    reference until GC, where the pool's leak net returns and counts
    it.) No-op when lease is None."""
    if lease is None:
        return fns
    out = []
    for fn in fns:
        if fn is None:
            out.append(None)
            continue
        lease.retain()

        def run(fn=fn):
            try:
                return fn()
            finally:
                lease.release()
        out.append(run)
    return out


def _raise_for_quorum(errors, exc, quorum=None, ok=None):
    """Quorum-failure triage: surface DeadlineExceeded (-> 408
    RequestTimeout) only when the REQUEST's budget was DECISIVE — had
    the deadline-cut drives been given time and succeeded, `quorum`
    could have been met. When genuine drive faults alone preclude
    quorum, the honest verdict stays the 503 quorum error: masking
    real cluster unhealth as a client timeout would hide it from
    operators and send clients into retry loops."""
    deadline_cut = sum(isinstance(e, DeadlineExceeded) for e in errors)
    if deadline_cut:
        if ok is None:
            ok = sum(e is None for e in errors)
        if quorum is None or ok + deadline_cut >= quorum:
            raise DeadlineExceeded(
                "request deadline exceeded before quorum")
    raise exc

"""Tiered, reference-counted pool of O_DIRECT-aligned buffers.

The analogue of the reference's internal/bpool byte pools: the PUT
encode+frame output, the GET/heal read staging, and the O_DIRECT
write staging all lease buffers here instead of allocating fresh
numpy/mmap memory per window. At steady state the hot paths allocate
ZERO fresh window buffers (pool hit rate ~100% after warmup — asserted
in tests/test_io_engine.py).

Design:
  * size classes — powers of two from 64 KiB to 512 MiB, each class a
    bounded free list (a request larger than the largest class is
    served unpooled and counted, never refused). The classes up to
    64 MiB keep `max_per_class` idle buffers each. The three above
    (128, 256, 512 MiB) exist for the stripe batcher's staging buffer
    (ops/batcher._stage: a 256-block dispatch is 256 MiB on routes put
    and transform, 256 x k x (shard + 32) = 268.5 MB on route get) and
    keep TWO each: the dispatcher's depth — one batch in the lane, one
    being staged — is all one loaded batcher ever holds, and sixteen
    idle mappings of 512 MiB would not do. A mapping is address space;
    only the pages a holder wrote are resident (a fresh mapping costs
    a page fault per 4 KiB on first touch — ~0.7 s for 256 MiB on a
    gVisor host — which is why the staging buffer is kept and not
    mapped anew), so two kept buffers of route get hold ~0.5 GiB
    resident whatever `idle_bytes`, which counts capacity, says;
  * leases — a Lease wraps one buffer with a reference count. Writers
    that may outlive the request (a health-wrapped create_file whose
    deadline expired but whose abandoned worker is still writing)
    retain() the lease, so the buffer is never recycled under a live
    reader — the data-corruption mode a plain free list invites;
  * leak accounting — a Lease dropped without release() is returned by
    its finalizer and COUNTED (`leaks`); release() after the refcount
    already hit zero is also counted (`double_releases`) and ignored.
    A dropped lease is returned, never lost.

Alignment: every pooled buffer is backed by mmap pages, so the memory
side of O_DIRECT's alignment contract holds for any pooled view.

Environment:
  MTPU_BUFPOOL_MAX_PER_CLASS  buffers kept per size class (default 16;
                              the classes above 64 MiB keep 2 at most)
  MTPU_BUFPOOL_OFF            "1"/"on" disables pooling (every lease
                              is a fresh buffer; leases still work)
"""

from __future__ import annotations

import mmap
import os
import threading
import weakref

# Size classes: 64 KiB .. 512 MiB, powers of two. Matched to the data
# path's working sizes: shard windows (~128 KiB at EC 8+4), framed
# whole-object outputs (~1.5 MiB per 1 MiB object), streaming encode
# windows (32 MiB), their framed outputs (48 MiB at EC 8+4), and the
# batcher's coalesced staging buffer (up to 268.5 MB: the 512 MiB class).
_MIN_CLASS = 16          # 2**16 = 64 KiB
_MAX_CLASS = 29          # 2**29 = 512 MiB
CLASS_SIZES = tuple(1 << p for p in range(_MIN_CLASS, _MAX_CLASS + 1))
# Classes above this size keep _LARGE_IDLE idle buffers, not
# max_per_class (module docstring: the batcher's depth is two).
_LARGE_FROM = 1 << 26    # 64 MiB
_LARGE_IDLE = 2


def _class_for(size: int) -> int:
    """Index of the smallest class holding `size`, or -1 if oversized."""
    for i, c in enumerate(CLASS_SIZES):
        if size <= c:
            return i
    return -1


class _LeaseState:
    """Refcount shared by the Lease and its leak finalizer. Lives in a
    separate object because weakref.finalize callbacks run AFTER the
    lease itself is unreachable — the count must survive it."""

    __slots__ = ("mu", "refs")

    def __init__(self) -> None:
        self.mu = threading.Lock()
        self.refs = 1


class Lease:
    """One leased buffer. `view(n)` gives a writable memoryview of the
    first n bytes. Reference-counted: retain() before handing the
    memory to a worker that may outlive you, release() exactly once
    per holder; the buffer returns to the pool when the count hits 0."""

    __slots__ = ("_pool", "_buf", "_cls", "_state", "size", "__weakref__")

    def __init__(self, pool: "BufferPool", buf, cls: int, size: int):
        self._pool = pool
        self._buf = buf
        self._cls = cls
        self._state = _LeaseState()
        self.size = size

    def view(self, n: int | None = None) -> memoryview:
        n = self.size if n is None else n
        if n > len(self._buf):
            raise ValueError(f"lease of {len(self._buf)} cannot view {n}")
        return memoryview(self._buf)[:n]

    @property
    def raw(self):
        """The backing mmap (capacity >= size) for consumers that need
        its file-like API (seek/write) or ctypes.from_buffer. Only
        valid while this holder's reference is live."""
        return self._buf

    def ndarray(self, shape, dtype="uint8"):
        """A numpy view of the leased bytes shaped `shape` (must fit in
        `size`). Only valid while this holder's reference is live — the
        device-batching staging path retains the lease across the whole
        host->HBM dispatch so a recycled buffer can never be rewritten
        under an in-flight transfer."""
        import numpy as _np
        items = int(_np.prod(shape))
        if items * _np.dtype(dtype).itemsize > self.size:
            raise ValueError(f"lease of {self.size} cannot shape {shape}")
        return _np.frombuffer(self._buf, dtype=dtype,
                              count=items).reshape(shape)

    def retain(self) -> "Lease":
        with self._state.mu:
            if self._state.refs <= 0:
                raise ValueError("retain() after final release")
            self._state.refs += 1
        return self

    def release(self) -> None:
        st = self._state
        with st.mu:
            if st.refs <= 0:
                # Double release: counted, never corrupts the free list
                # (returning the same buffer twice would alias two
                # future leases onto one allocation).
                self._pool._count_double_release()
                return
            st.refs -= 1
            done = st.refs == 0
        if done:
            self._pool._return_buf(self._buf, self._cls)

    @property
    def refs(self) -> int:
        with self._state.mu:
            return self._state.refs


class BufferPool:
    """Tiered free lists + lease accounting. Thread-safe."""

    def __init__(self, max_per_class: int | None = None,
                 enabled: bool | None = None):
        if max_per_class is None:
            try:
                max_per_class = int(
                    os.environ.get("MTPU_BUFPOOL_MAX_PER_CLASS", "16"))
            except ValueError:
                max_per_class = 16
        if enabled is None:
            enabled = os.environ.get("MTPU_BUFPOOL_OFF", "").lower() \
                not in ("1", "on", "true")
        self.max_per_class = max(1, max_per_class)
        # Idle buffers kept per class: the large classes never more
        # than _LARGE_IDLE, whatever the small ones are allowed.
        self._keep = tuple(
            min(self.max_per_class, _LARGE_IDLE) if c > _LARGE_FROM
            else self.max_per_class for c in CLASS_SIZES)
        self.enabled = enabled
        self._mu = threading.Lock()
        self._free: list[list] = [[] for _ in CLASS_SIZES]
        # Stats (all monotonic counters except outstanding/idle_bytes).
        self.hits = 0
        self.misses = 0
        self.oversized = 0
        self.leaks = 0
        self.double_releases = 0
        self.outstanding = 0
        self.idle_bytes = 0

    # -- leasing ---------------------------------------------------------

    def lease(self, size: int) -> Lease:
        """Lease a buffer of at least `size` bytes (pooled when a class
        fits, fresh-and-unpooled otherwise)."""
        cls = _class_for(size) if self.enabled else -1
        buf = None
        if cls >= 0:
            with self._mu:
                if self._free[cls]:
                    buf = self._free[cls].pop()
                    self.hits += 1
                    self.idle_bytes -= len(buf)
                else:
                    self.misses += 1
                self.outstanding += 1
            if buf is None:
                buf = mmap.mmap(-1, CLASS_SIZES[cls])
        else:
            with self._mu:
                self.oversized += 1
                self.outstanding += 1
            buf = mmap.mmap(-1, max(size, mmap.PAGESIZE))
        lease = Lease(self, buf, cls, size)
        # Leak net: a lease dropped with refs still held is returned to
        # the pool by the finalizer and counted. The finalizer holds
        # the shared state + buffer, never the lease itself.
        weakref.finalize(lease, self._finalize_dropped,
                         buf, cls, lease._state)
        return lease

    # -- internals -------------------------------------------------------

    def _count_double_release(self) -> None:
        with self._mu:
            self.double_releases += 1

    def _return_buf(self, buf, cls: int) -> None:
        with self._mu:
            self.outstanding -= 1
            if cls >= 0 and self.enabled \
                    and len(self._free[cls]) < self._keep[cls]:
                self._free[cls].append(buf)
                self.idle_bytes += len(buf)
                return
        # Oversized / over-capacity: the mapping dies here.
        try:
            buf.close()
        except (BufferError, ValueError):
            pass          # an exported view still holds it; GC reclaims

    def _finalize_dropped(self, buf, cls: int, state: _LeaseState) -> None:
        """GC found a dropped lease: if refs were still held (the
        leak), zero them, count it, and return the buffer."""
        with state.mu:
            leaked = state.refs > 0
            state.refs = 0
        if leaked:
            with self._mu:
                self.leaks += 1
            self._return_buf(buf, cls)

    # -- observability ---------------------------------------------------

    def stats(self) -> dict:
        with self._mu:
            total = self.hits + self.misses
            return {
                "enabled": self.enabled,
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": (self.hits / total) if total else 0.0,
                "oversized": self.oversized,
                "outstanding": self.outstanding,
                "leaks": self.leaks,
                "double_releases": self.double_releases,
                "idle_bytes": self.idle_bytes,
            }

    def drain(self) -> None:
        """Drop every idle buffer (tests / memory pressure)."""
        with self._mu:
            free, self._free = self._free, [[] for _ in CLASS_SIZES]
            self.idle_bytes = 0
        for lst in free:
            for buf in lst:
                try:
                    buf.close()
                except (BufferError, ValueError):
                    pass


_GLOBAL: BufferPool | None = None
_GLOBAL_MU = threading.Lock()


def global_pool() -> BufferPool:
    """Process-wide pool shared by every set/drive in this process
    (workers are separate processes, so each gets its own)."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_MU:
            if _GLOBAL is None:
                _GLOBAL = BufferPool()
    return _GLOBAL

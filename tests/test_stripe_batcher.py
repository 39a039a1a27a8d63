"""Cross-request stripe batching (ops/batcher.py): coalescing,
demultiplexing, calibration routing, and the solo-bypass guarantee —
the submission-queue half of the blueprint's "a full erasure set's
stripes encode in one pmap" (BASELINE.json north star)."""

import threading
import time

import numpy as np
import pytest

from minio_tpu.object.erasure_object import _host_rows
from minio_tpu.ops.batcher import StripeBatcher

K, M, SHARD = 8, 4, 4096


def _mk_window(b, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=(b, K, SHARD), dtype=np.uint8)


def _rows_equal(a, b):
    assert len(a) == len(b)
    for da, db in zip(a, b):
        assert len(da) == len(db)
        for (ha, blka), (hb, blkb) in zip(da, db):
            assert np.array_equal(np.asarray(ha), np.asarray(hb))
            assert np.array_equal(np.asarray(blka), np.asarray(blkb))


class _RecordingDevice:
    """Fake device framer: host math, records every dispatched batch."""

    def __init__(self):
        self.batches = []

    def __call__(self, stacked):
        self.batches.append(stacked.shape[0])
        return _host_rows(K, M, stacked)


def test_concurrent_windows_coalesce_into_one_device_batch():
    dev = _RecordingDevice()
    sb = StripeBatcher(dev, lambda s: _host_rows(K, M, s),
                       probe_fn=lambda: True, min_device_blocks=8)
    sb._device_ok = True               # skip async probe latency
    sb._probe_started = True
    n_req = 6
    windows = [_mk_window(3, i) for i in range(n_req)]
    results = [None] * n_req
    barrier = threading.Barrier(n_req)

    def worker(i):
        barrier.wait()
        results[i] = sb.frame(windows[i])

    # Pre-register inflight so no thread sees itself solo: the barrier
    # releases all at once, but the first to grab the lock would
    # otherwise bypass. Simulate a busy system with a dummy inflight.
    with sb._mu:
        sb._inflight += 1
    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_req)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20)
    with sb._mu:
        sb._inflight -= 1
    # Every request got exactly its own blocks back, byte-identical to
    # the host codec.
    for i in range(n_req):
        assert results[i] is not None
        _rows_equal(results[i], _host_rows(K, M, windows[i]))
    # Coalescing happened: fewer device dispatches than requests, and
    # at least one batch bigger than any single request.
    assert dev.batches, "device never dispatched"
    assert len(dev.batches) < n_req
    assert max(dev.batches) > 3
    # Batch dims are padded to fixed buckets (bounded compile cache).
    assert all(b in (8, 16, 32, 64, 128, 256) for b in dev.batches)


def test_solo_request_bypasses_queue_with_no_wait():
    dev = _RecordingDevice()
    sb = StripeBatcher(dev, lambda s: _host_rows(K, M, s),
                       probe_fn=lambda: True)
    sb._device_ok = True
    sb._probe_started = True
    w = _mk_window(2, 99)
    t0 = time.perf_counter()
    rows = sb.frame(w)
    elapsed = time.perf_counter() - t0
    _rows_equal(rows, _host_rows(K, M, w))
    assert dev.batches == []           # host path, no device dispatch
    assert elapsed < 0.2               # and no batching wait


def test_negative_calibration_routes_everything_host():
    dev = _RecordingDevice()
    sb = StripeBatcher(dev, lambda s: _host_rows(K, M, s),
                       probe_fn=lambda: False)
    sb._device_ok = False              # probe said: device link loses
    sb._probe_started = True
    with sb._mu:
        sb._inflight += 1              # simulate concurrency
    try:
        rows = sb.frame(_mk_window(4, 5))
    finally:
        with sb._mu:
            sb._inflight -= 1
    _rows_equal(rows, _host_rows(K, M, _mk_window(4, 5)))
    assert dev.batches == []


def test_device_failure_delivered_to_all_waiters():
    def boom(stacked):
        raise RuntimeError("device fell over")

    sb = StripeBatcher(boom, lambda s: _host_rows(K, M, s),
                       probe_fn=lambda: True, min_device_blocks=2)
    sb._device_ok = True
    sb._probe_started = True
    with sb._mu:
        sb._inflight += 1
    errs = []

    def worker(i):
        try:
            sb.frame(_mk_window(2, i))
        except RuntimeError as e:
            errs.append(str(e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20)
    with sb._mu:
        sb._inflight -= 1
    assert len(errs) == 3


def test_oversized_burst_splits_into_bucketed_batches():
    """Pending blocks beyond the largest pad bucket (256) must split
    across dispatches, not blow up the pad math (review r5 finding)."""
    dev = _RecordingDevice()
    sb = StripeBatcher(dev, lambda s: _host_rows(K, M, s),
                       probe_fn=lambda: True, min_device_blocks=8)
    sb._device_ok = True
    sb._probe_started = True
    n_req = 10                      # 10 x 32 blocks = 320 > 256
    windows = [_mk_window(32, i) for i in range(n_req)]
    results = [None] * n_req
    with sb._mu:
        sb._inflight += 1
    threads = [threading.Thread(
        target=lambda i=i: results.__setitem__(i, sb.frame(windows[i])))
        for i in range(n_req)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    with sb._mu:
        sb._inflight -= 1
    for i in range(n_req):
        assert results[i] is not None, f"request {i} hung"
        _rows_equal(results[i], _host_rows(K, M, windows[i]))
    assert all(b <= 256 for b in dev.batches)


def test_solo_device_sized_window_dispatches_directly():
    """A lone streaming window at or above min_device_blocks skips the
    queue but still rides the device when calibration approves — a
    single-stream large PUT must not regress to the host codec
    (review r5 finding)."""
    dev = _RecordingDevice()
    sb = StripeBatcher(dev, lambda s: _host_rows(K, M, s),
                       probe_fn=lambda: True, min_device_blocks=8)
    sb._device_ok = True
    sb._probe_started = True
    w = _mk_window(32, 42)
    rows = sb.frame(w)              # solo, but device-sized
    _rows_equal(rows, _host_rows(K, M, w))
    assert dev.batches == [32]


def test_host_rows_matches_framer_format():
    """_host_rows output is byte-identical to the fused framer's run()
    (the portable path) for the same window."""
    from minio_tpu.object.erasure_object import _framer_for
    w = _mk_window(3, 7)
    _rows_equal(_host_rows(K, M, w), _framer_for(K, M)(w))


# -- the dispatcher stages the next batch while the last one is in the
# -- lane (tests/batcher_rig.py: events only, no clock) -----------------

from minio_tpu.utils.deadline import DeadlineExceeded  # noqa: E402
from tests import batcher_rig as rig  # noqa: E402

routes = pytest.mark.parametrize("route", rig.ROUTES)


@routes
def test_next_batch_is_staged_while_the_last_is_in_the_lane(route):
    """(a) Batch N+1's copy runs while N's device call has not
    returned, and N's members are home before N+1's staging ends."""
    with rig.Rig(route, hold_dev=[0], hold_stage=[1]) as r:
        first = r.send(seed=10)
        rig.wait(r.dev.entered[0], "N in the lane")
        second = r.send(seed=20)
        rig.wait(r.stage.entered[1], "N+1's copy started")
        assert not r.dev.left[0].is_set()      # N is still in the lane
        r.dev.go[0].set()
        for m in first:
            assert m.returned().exc is None
        # N+1's staging has not ended, and its members wait for it.
        assert not r.stage.left[1].is_set()
        assert all(m.inside() for m in second)
        r.stage.go[1].set()
        for m in second:
            assert m.returned().exc is None
        st = r.sb.stats()
        assert st["dispatches"]["device"] == 2 and st["overlapped"] == 1
        assert st["requests"]["device"] == 4


@routes
def test_two_staging_leases_at_most_and_each_held_through_its_call(route):
    """(b) One batch in the lane, one being staged: never a third
    staging lease, and a batch's lease is held until ITS device call
    has returned (donation safety)."""
    with rig.Rig(route, hold_dev=[0, 1]) as r:
        refs_in_call = {}
        r.dev.on_call = lambda i: refs_in_call.setdefault(
            i, r.leases[i].refs)
        n0 = r.send(seed=1)
        rig.wait(r.dev.entered[0], "N in the lane")
        n1 = r.send(seed=3)
        rig.wait(r.stage.left[1], "N+1 staged")
        n2 = r.send(seed=5)
        # N+1 is staged and waits for N's call; N+2 waits for the
        # dispatcher: it cannot be staged before N's lease is back.
        rig.until(lambda: len(r.sb._pending) == 2, "N+2 queued")
        assert r.pool.stats()["outstanding"] == 2
        assert r.leases[0].refs == 1 and r.leases[1].refs == 1
        assert not r.stage.entered[2].is_set()
        r.dev.go[0].set()
        for m in n0:
            m.returned()
        assert r.leases[0].refs == 0           # back with N's rows
        rig.wait(r.dev.entered[1], "N+1 in the lane")
        rig.wait(r.stage.left[2], "N+2 staged")
        assert r.leases[1].refs == 1 and r.leases[2].refs == 1
        r.dev.go[1].set()
        for m in n1 + n2:
            assert m.returned().exc is None
        assert refs_in_call == {0: 1, 1: 1, 2: 1}
        assert max(r.outstanding) == 2 and len(r.leases) == 3
    st = r.pool.stats()
    assert st["outstanding"] == 0 and st["leaks"] == 0


@routes
def test_a_failed_batch_fails_its_own_members_only(route):
    """(c) What N's device call raised reaches N's members and nobody
    else: N+1, staged meanwhile, is served."""
    with rig.Rig(route, hold_dev=[0], fail=[0]) as r:
        first = r.send(seed=7)
        rig.wait(r.dev.entered[0], "N in the lane")
        second = r.send(seed=9)
        rig.wait(r.stage.left[1], "N+1 staged")
        r.dev.go[0].set()
        for m in first:
            assert isinstance(m.returned().exc, RuntimeError)
            assert m.rows is None
        for m, want in zip(second, r.synchronous(second)):
            assert m.returned().exc is None
            rig.same(route, m.rows, want)
        assert r.sb.stats()["dispatches"]["device"] == 1
    assert r.pool.stats()["outstanding"] == 0


@routes
def test_a_member_past_its_deadline_fails_alone_under_overlap(route):
    """(d) A member whose budget ran out while it queued is culled when
    the dispatcher takes its batch — while the batch before is still in
    the lane — and its batch-mates are served."""
    with rig.Rig(route, hold_dev=[0], hold_stage=[0]) as r:
        first = r.send(seed=30)
        rig.wait(r.stage.entered[0], "N being staged")
        mates = r.send(seed=40, n=3)
        rig.until(lambda: len(r.sb._pending) == 3, "N+1 queued")
        with r.sb._mu:
            doomed = r.sb._pending[1]
            doomed.expires_at = 0.0          # spent while it queued
        late = next(m for m in mates if m.stacked is doomed.stacked)
        r.stage.go[0].set()
        rig.wait(r.dev.entered[0], "N in the lane")
        assert isinstance(late.returned().exc, DeadlineExceeded)
        assert not r.dev.left[0].is_set()      # N is still in the lane
        r.dev.go[0].set()
        kept = [m for m in mates if m is not late]
        for m, want in zip(first + kept, r.synchronous(first + kept)):
            assert m.returned().exc is None
            rig.same(route, m.rows, want)
        assert r.sb.stats()["deadline_failures"] == 1
        assert r.sb.stats()["requests"]["device"] == 4


@routes
def test_close_returns_after_the_batch_in_flight_has_delivered(route):
    """(e) close() waits for what the dispatcher has taken: the batch
    in the lane and the one staged behind it."""
    with rig.Rig(route, hold_dev=[0]) as r:
        first = r.send(seed=50)
        rig.wait(r.dev.entered[0], "N in the lane")
        second = r.send(seed=60)
        rig.wait(r.stage.left[1], "N+1 staged")
        seen = {}

        def closer():
            r.sb.close()
            seen["delivered"] = r.sb.stats()["requests"]["device"]

        t = threading.Thread(target=closer)
        t.start()
        rig.until(lambda: r.sb._closed, "close() called")
        assert t.is_alive() and not seen       # N is still in the lane
        r.dev.go[0].set()
        t.join(rig.WAIT_S)
        assert not t.is_alive()
        assert seen["delivered"] == 4    # counted before close() returned
        for m in first + second:
            assert m.returned().exc is None


@routes
def test_pipelined_rows_are_the_synchronous_paths_rows(route):
    """(f) Rows of N and N+1, staged and finished on three threads,
    are byte for byte what one thread gives for the same windows."""
    with rig.Rig(route, hold_dev=[0]) as r:
        first = r.send(seed=70)
        rig.wait(r.dev.entered[0], "N in the lane")
        second = r.send(seed=80)
        rig.wait(r.stage.left[1], "N+1 staged")
        r.dev.go[0].set()
        for batch in (first, second):
            for m, want in zip(batch, r.synchronous(batch)):
                assert m.returned().exc is None
                rig.same(route, m.rows, want)
                if route == "put":
                    # Data rows point into the member's own window, not
                    # into a staging buffer that went back to the pool.
                    for drive in range(rig.K):
                        assert all(np.shares_memory(np.asarray(blk),
                                                    m.stacked)
                                   for _dig, blk in m.rows[drive])
        assert r.sb.stats()["overlapped"] == 1


@pytest.mark.parametrize("route", rig.SHIPPED)
def test_staged_dispatches_over_64_mib_reuse_two_kept_buffers(route):
    """(g) The staging buffer is kept, not mapped anew, whatever its
    size: three dispatches of 64 blocks at a trailing shape that puts
    the staged copy just over 64 MiB (unpooled until PR 35) take two
    mappings between them — N+2 is staged into N's, handed back when
    N's rows were read and not before — with two alive at most; the
    rows the kept buffer carried are the host route's, and what N left
    behind in it is zero where N+2 pads."""
    with rig.Rig(route, hold_dev=[0], dev_after=True,
                 shard=rig.BIG_SHARD) as r:
        staged = 64 * rig.K * rig.BIG_SHARD
        assert staged > 1 << 26
        n0 = r.send_in_order(seed=100, counts=(16, 40))
        rig.wait(r.dev.entered[0], "N in the lane")
        n1 = r.send_in_order(seed=110, counts=(16, 17))
        rig.wait(r.stage.left[1], "N+1 staged")
        n2 = r.send_in_order(seed=120, counts=(16, 17))
        # N's rows exist (its device function has run) and its call has
        # not returned: its buffer is still its own, N+1 took a second
        # mapping, and N+2 cannot be staged.
        assert [ls.size for ls in r.leases] == [staged, staged]
        assert r.leases[0].refs == 1 and r.leases[1].refs == 1
        assert r.leases[1].raw is not r.leases[0].raw
        assert r.pool.stats()["idle_bytes"] == 0
        assert not r.stage.entered[2].is_set()
        r.dev.go[0].set()
        rig.wait(r.stage.left[2], "N+2 staged")
        assert r.leases[0].refs == 0
        assert r.leases[2].raw is r.leases[0].raw
        for m in n0 + n1 + n2:
            assert m.returned().exc is None
            rig.same(route, m.rows, r.fn(m.stacked))
            m.rows = m.stacked = None
        # 56 real rows, then 33 twice: rows 33..55 of N+2's batch held
        # N's members until the copy zeroed them.
        assert r.seen == [(64, 56, False), (64, 33, False),
                          (64, 33, False)]
        st = r.pool.stats()
        assert (st["misses"], st["hits"], st["oversized"]) == (2, 1, 0)
        assert max(r.outstanding) == 2
        assert r.sb.stats()["buckets"] == {64: 3}
    st = r.pool.stats()
    assert st["outstanding"] == 0 and st["leaks"] == 0
    assert st["idle_bytes"] == 2 * (1 << 27)
    r.pool.drain()

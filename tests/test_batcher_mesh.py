"""Device-resident cross-request stripe batching (ops/batcher.py +
ops/hh_device.make_mesh_framer): byte-identity of batched vs
solo-framed output across ragged tails and every padding bucket,
donation safety of the pooled staging lease, deadline-exhausted members
failing without poisoning batch-mates, the kernel span fanned into each
member's trace, the MTPU_BATCH_FORCE knob, and the mesh framer on a
virtual 8-device mesh."""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from minio_tpu.io.bufpool import BufferPool
from minio_tpu.object.erasure_object import _host_rows
from minio_tpu.ops.batcher import _BUCKETS, StripeBatcher
from minio_tpu.utils import deadline as deadline_mod
from minio_tpu.utils import tracing
from minio_tpu.utils.deadline import Deadline, DeadlineExceeded

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

    def __init__(self, mesh_devices=1, delay=0.0):
        self.batches = []
        self.mesh_devices = mesh_devices
        self.delay = delay
        self.in_flight_hook = None

    def __call__(self, stacked):
        self.batches.append(stacked.shape[0])
        if self.in_flight_hook is not None:
            self.in_flight_hook(stacked)
        if self.delay:
            time.sleep(self.delay)
        return _host_rows(K, M, stacked)


def _pinned(device_fn, pool=None, **kw):
    sb = StripeBatcher(device_fn, lambda s: _host_rows(K, M, s),
                       probe_fn=lambda: True, pool=pool, **kw)
    sb.force(True)
    return sb


def _coalesce(sb, windows, timeout=30):
    """Run the windows through sb.frame concurrently (with a dummy
    inflight so nobody sees itself solo); returns the results list."""
    results = [None] * len(windows)
    errors = [None] * len(windows)

    def worker(i):
        try:
            results[i] = sb.frame(windows[i])
        except BaseException as e:  # noqa: BLE001 - asserted by tests
            errors[i] = e

    with sb._mu:
        sb._inflight += 1
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(windows))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=timeout)
    finally:
        with sb._mu:
            sb._inflight -= 1
    return results, errors


def test_batched_output_byte_identical_across_ragged_members():
    """Coalesced windows of UNEVEN sizes (ragged tails riding full
    windows) demultiplex to exactly the bytes each member would get
    solo-framed — incl. the data-drive views re-pointed at each
    member's own window after the staging lease returns."""
    dev = _RecordingDevice()
    pool = BufferPool(max_per_class=4)
    # Wide window: all five threads must enqueue into ONE batch even on
    # a loaded CI box (a split batch would route a sub-minimum tail to
    # the host codec, which is not what this test asserts).
    sb = _pinned(dev, pool=pool, min_device_blocks=8, max_wait_s=0.1)
    sizes = [1, 2, 3, 5, 7]              # 18 blocks, ragged mix
    windows = [_mk_window(b, i) for i, b in enumerate(sizes)]
    results, errors = _coalesce(sb, windows)
    assert all(e is None for e in errors)
    for i, w in enumerate(windows):
        assert results[i] is not None
        _rows_equal(results[i], _host_rows(K, M, w))
        # Data-drive blocks are views of the MEMBER's own window, not
        # of the (already recycled) staging buffer.
        for drive in range(K):
            for bi, (_dig, blk) in enumerate(results[i][drive]):
                assert np.shares_memory(np.asarray(blk), w)
    assert dev.batches and all(b in _BUCKETS for b in dev.batches)
    st = sb.stats()
    assert st["dispatches"]["device"] >= 1
    assert st["batched_blocks"] <= st["capacity_blocks"]
    assert pool.stats()["outstanding"] == 0      # staging lease returned


@pytest.mark.parametrize("bucket", _BUCKETS)
def test_every_padding_bucket_byte_identity(bucket):
    """Solo device-sized windows at every bucket size (full and
    one-under, exercising the zero-pad tail) frame byte-identically
    to the host codec."""
    dev = _RecordingDevice()
    pool = BufferPool(max_per_class=2)
    sb = _pinned(dev, pool=pool, min_device_blocks=4)
    for b in (bucket, bucket - 1):
        w = _mk_window(b, b)
        rows = sb.frame(w)
        _rows_equal(rows, _host_rows(K, M, w))
    assert dev.batches == [bucket, bucket]
    assert pool.stats()["outstanding"] == 0


def test_oversized_window_chunks_through_device_route():
    """A window larger than the biggest padding bucket (whole-part
    framing of a huge multipart part) must dispatch in bucket-sized
    chunks — never reach the staging buffer as one >256-row copy —
    and splice back byte-identical to the solo host framing."""
    dev = _RecordingDevice(mesh_devices=4)
    sb = _pinned(dev)
    w = _mk_window(300, seed=77)
    rows = sb.frame(w)
    _rows_equal(rows, _host_rows(K, M, w))
    # Both chunks rode the device route, each within the bucket cap.
    assert len(dev.batches) == 2
    assert all(b <= 256 for b in dev.batches)
    assert sum(dev.batches) >= 300


def test_donation_safety_staging_lease_held_across_dispatch():
    """While a dispatch is in flight, the pooled staging buffer backing
    the device input is NOT recyclable: a concurrent lease of the same
    size class must get different memory, and the lease returns to the
    pool only after the dispatch completes."""
    dev = _RecordingDevice()
    pool = BufferPool(max_per_class=4)
    sb = _pinned(dev, pool=pool, min_device_blocks=8, max_wait_s=0.1)
    seen = {}

    def hook(stacked):
        addr = stacked.__array_interface__["data"][0]
        size = stacked.nbytes
        assert pool.stats()["outstanding"] >= 1
        rival = pool.lease(size)
        try:
            raddr = rival.ndarray((size,)).__array_interface__["data"][0]
            # The staging mapping must never be handed out again while
            # the device is still reading it.
            assert raddr != addr
        finally:
            rival.release()
        seen["addr"] = addr

    dev.in_flight_hook = hook
    windows = [_mk_window(5, i) for i in range(3)]   # forces staging
    results, errors = _coalesce(sb, windows)
    assert all(e is None for e in errors)
    assert seen, "staged dispatch never ran"
    for i, w in enumerate(windows):
        _rows_equal(results[i], _host_rows(K, M, w))
    st = pool.stats()
    assert st["outstanding"] == 0 and st["leaks"] == 0


def test_deadline_exhausted_member_fails_without_poisoning_mates():
    """A member whose budget is spent by dispatch time is culled with
    DeadlineExceeded; batch-mates still get byte-correct rows. Driven
    through _run_batch directly (the dispatcher's entry point for every
    accumulated batch): the wall-clock race of arranging a mid-window
    expiry with live threads made the end-to-end variant flaky under
    parallel-suite load, while the cull contract itself is exactly
    what this exercises."""
    from minio_tpu.ops.batcher import _Pending
    dev = _RecordingDevice()
    sb = _pinned(dev, min_device_blocks=8)
    good = [_mk_window(4, 1), _mk_window(4, 2)]
    doomed = _mk_window(4, 3)
    pgood = [_Pending(w, None) for w in good]
    pdead = _Pending(doomed, Deadline(-1.0))    # spent before dispatch
    sb._run_batch([pgood[0], pdead, pgood[1]])
    assert isinstance(pdead.exc, DeadlineExceeded)
    assert pdead.event.is_set() and pdead.rows is None
    for i, p in enumerate(pgood):
        assert p.exc is None and p.event.is_set()
        _rows_equal(p.rows, _host_rows(K, M, good[i]))
    # The surviving pair still dispatched on the device route.
    assert dev.batches == [8]
    assert sb.stats()["deadline_failures"] == 1


def test_already_expired_deadline_fails_fast_without_device():
    dev = _RecordingDevice()
    sb = _pinned(dev, min_device_blocks=2)
    with deadline_mod.bind(Deadline(-1.0)):
        with pytest.raises(DeadlineExceeded):
            sb.frame(_mk_window(4, 9))
    assert dev.batches == []


def test_kernel_span_fans_into_each_member_trace():
    """One coalesced dispatch records ONE kernel span into EVERY
    member request's span tree, tagged with the shared batch shape and
    the member's own block count."""
    dev = _RecordingDevice()
    sb = _pinned(dev, min_device_blocks=8, max_wait_s=0.1)
    tracing.arm("test-batcher")
    try:
        ctxs = [tracing.TraceContext() for _ in range(3)]
        windows = [_mk_window(4, i) for i in range(3)]
        results = [None] * 3

        def worker(i):
            with tracing.bind(ctxs[i]):
                results[i] = sb.frame(windows[i])

        with sb._mu:
            sb._inflight += 1
        try:
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=20)
        finally:
            with sb._mu:
                sb._inflight -= 1
        for i, ctx in enumerate(ctxs):
            assert results[i] is not None
            spans = [s for s in ctx.spans
                     if s["type"] == "kernel"
                     and s["name"] == "batcher.dispatch"]
            assert len(spans) == 1, f"member {i} got {len(spans)} spans"
            tags = spans[0]["tags"]
            assert tags["blocks"] == 4
            assert tags["route"] == "device"
            assert tags["bucket"] in _BUCKETS
    finally:
        tracing.disarm("test-batcher")


def test_batch_force_env_knob(monkeypatch):
    host_calls = []

    def host(s):
        host_calls.append(s.shape[0])
        return _host_rows(K, M, s)

    monkeypatch.setenv("MTPU_BATCH_FORCE", "host")
    dev = _RecordingDevice()
    sb = StripeBatcher(dev, host, probe_fn=lambda: True)
    assert sb._device_ok is False and not sb.wants_device()
    sb.frame(_mk_window(16, 1))
    assert dev.batches == [] and host_calls == [16]
    sb.reset_calibration()                     # re-pins under the env
    assert sb._device_ok is False

    monkeypatch.setenv("MTPU_BATCH_FORCE", "device")
    sb2 = StripeBatcher(dev, host, probe_fn=lambda: False)
    assert sb2._device_ok is True
    rows = sb2.frame(_mk_window(16, 2))        # solo big -> device
    _rows_equal(rows, _host_rows(K, M, _mk_window(16, 2)))
    assert dev.batches == [16]

    monkeypatch.setenv("MTPU_BATCH_FORCE", "auto")
    sb3 = StripeBatcher(dev, host, probe_fn=lambda: True)
    assert sb3._device_ok is None and not sb3._probe_started


def test_adaptive_window_tracks_fill():
    dev = _RecordingDevice()
    sb = _pinned(dev, min_device_blocks=8, max_wait_s=0.002)
    w0 = sb._cur_wait
    sb._adapt_window(1.0)                      # full buckets: stretch
    assert sb._cur_wait >= w0
    for _ in range(8):
        sb._adapt_window(0.1)                  # sparse: shrink
    assert sb._cur_wait < w0


def test_fill_target_scales_with_mesh():
    dev1 = _RecordingDevice(mesh_devices=1)
    dev8 = _RecordingDevice(mesh_devices=8)
    sb1 = _pinned(dev1, min_device_blocks=8)
    sb8 = _pinned(dev8, min_device_blocks=8)
    assert sb1._fill_target() < sb8._fill_target()
    assert sb8._fill_target() <= 256
    assert sb8.mesh_devices == 8


def test_batcher_metrics_render():
    """The occupancy satellites surface in Prometheus text."""
    dev = _RecordingDevice()
    sb = _pinned(dev, min_device_blocks=4)
    sb.frame(_mk_window(8, 0))
    from minio_tpu.s3.metrics import Metrics
    text = Metrics().render()
    for name in ("minio_tpu_batcher_dispatches_total",
                 "minio_tpu_batcher_requests_total",
                 "minio_tpu_batcher_fill_ratio",
                 "minio_tpu_batcher_wait_seconds_bucket",
                 "minio_tpu_batcher_deadline_failures_total",
                 "minio_tpu_kernel_lane_dispatches_total"):
        assert name in text, name


def test_force_device_engages_batcher_off_tpu(monkeypatch, tmp_path):
    """MTPU_BATCH_FORCE=device reaches the REAL batched device route
    even off-TPU: the erasure layer's platform gate yields to the knob,
    so a device-window-sized PUT through a device-capable backend
    records a batcher device dispatch and still round-trips
    byte-identically. (Without the gate honoring the knob, a non-TPU
    host silently measured the host codec no matter what the batcher
    was forced to — the exact invisible degradation the knob exists to
    rule out in CI/bench runs.)"""
    monkeypatch.setenv("MTPU_BATCH_FORCE", "device")
    from minio_tpu.object.erasure_object import ErasureSet, _batcher_for
    from minio_tpu.ops.rs_device import DeviceBackend
    from minio_tpu.storage.local import LocalStorage
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    for d in disks:
        d.make_vol("bkt")
    es = ErasureSet(disks, parity=2, backend=DeviceBackend("auto"))
    sb = _batcher_for(2, 2)
    sb.reset_calibration()              # re-pin the cached batcher
    try:
        before = sb.stats()["dispatches"]["device"]
        # 8 full blocks = one device-sized window (>= min_device_blocks):
        # a solo PUT this big dispatches straight through the batch path.
        body = np.random.default_rng(11).integers(
            0, 256, size=8 << 20, dtype=np.uint8).tobytes()
        es.put_object("bkt", "o", body)
        assert sb.stats()["dispatches"]["device"] == before + 1
        _, got = es.get_object("bkt", "o")
        assert got == body
    finally:
        es.close()
        monkeypatch.delenv("MTPU_BATCH_FORCE", raising=False)
        sb.reset_calibration()          # un-pin for suite-mates
    assert sb._device_ok is None


def test_mesh_batches_overlap_under_the_mesh_bucket_rule():
    """The pipelined dispatcher on a mesh (the mesh framer over four of
    the suite's virtual devices): batch N+1 is staged while N is in
    the lane, both are whole multiples of the mesh at the mesh's fill
    target, and their rows are the host codec's byte for byte."""
    import jax
    from minio_tpu.ops import gf256
    from minio_tpu.ops.hh_device import make_mesh_framer
    from tests import batcher_rig as rig
    framer = make_mesh_framer(gf256.parity_matrix(rig.K, rig.M),
                              devices=jax.devices()[:4])
    seen = []

    def mesh(stacked):
        seen.append(stacked.shape[0])
        return framer(stacked)
    mesh.mesh_devices = framer.mesh_devices
    with rig.Rig("put", hold_dev=[0], device_fn=mesh) as r:
        assert r.sb.mesh_devices == 4 and r.sb._fill_target() == 128
        first = r.send(seed=100)
        rig.wait(r.dev.entered[0], "N in the lane")
        second = r.send(seed=200)
        rig.wait(r.stage.left[1], "N+1 staged")
        assert not r.dev.left[0].is_set()
        r.dev.go[0].set()
        for batch in (first, second):
            for m, want in zip(batch, r.synchronous(batch)):
                assert m.returned().exc is None
                rig.same("put", m.rows, want)
        st = r.sb.stats()
        assert seen == [128, 128]
        assert st["dispatches"]["device"] == 2 and st["overlapped"] == 1
        assert max(r.outstanding) == 2


_MESH_BODY = r"""
import numpy as np
from minio_tpu.object.erasure_object import _host_rows
from minio_tpu.ops import gf256
from minio_tpu.ops.device import mesh_batch_devices
from minio_tpu.ops.hh_device import make_mesh_framer
import jax

K, M, SHARD = 8, 4, 256
assert len(jax.devices()) == 8, jax.devices()
framer = make_mesh_framer(gf256.parity_matrix(K, M))
assert framer.mesh_devices == 8, framer.mesh_devices
rng = np.random.default_rng(0)
for b in (8, 16, 32):
    w = rng.integers(0, 256, size=(b, K, SHARD), dtype=np.uint8)
    rows = framer(w)
    want = _host_rows(K, M, w)
    assert len(rows) == K + M
    for d in range(K + M):
        for (hg, bg), (hw, bw) in zip(rows[d], want[d]):
            assert np.array_equal(np.asarray(hg), np.asarray(hw)), d
            assert np.array_equal(np.asarray(bg), np.asarray(bw)), d
# The batcher over the real mesh framer coalesces into mesh-divisible
# buckets and stays byte-identical.
from minio_tpu.ops.batcher import StripeBatcher
import threading
sb = StripeBatcher(framer, lambda s: _host_rows(K, M, s),
                   probe_fn=lambda: True, min_device_blocks=8)
sb.force(True)
windows = [rng.integers(0, 256, size=(3, K, SHARD), dtype=np.uint8)
           for _ in range(4)]
results = [None] * 4
with sb._mu:
    sb._inflight += 1
ts = [threading.Thread(target=lambda i=i: results.__setitem__(
    i, sb.frame(windows[i]))) for i in range(4)]
[t.start() for t in ts]
[t.join(timeout=60) for t in ts]
with sb._mu:
    sb._inflight -= 1
for i in range(4):
    want = _host_rows(K, M, windows[i])
    for d in range(K + M):
        for (hg, bg), (hw, bw) in zip(results[i][d], want[d]):
            assert np.array_equal(np.asarray(hg), np.asarray(hw))
            assert np.array_equal(np.asarray(bg), np.asarray(bw))
print("MESH_OK")
"""


def test_mesh_framer_byte_identity_on_virtual_8_device_mesh():
    """The sharded dispatch on a real 8-device mesh (virtual CPU
    devices — the platform must be chosen before JAX initializes, so a
    fresh subprocess) produces bytes identical to the host codec, solo
    and through the batcher."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("MTPU_MESH_DEVICES", None)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=8", "")
        + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_BODY], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, timeout=420)
    assert proc.returncode == 0, proc.stderr.decode()[-4000:]
    assert b"MESH_OK" in proc.stdout

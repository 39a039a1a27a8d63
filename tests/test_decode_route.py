"""Device-resident read path (the decode mirror of the cross-request
stripe batching): the mesh de-framer's batched-vs-solo verdict/byte
identity across ragged member mixes and every padding bucket, degraded
reads with 1..m missing shards riding the batched device reconstruct,
bitrot-demote-then-device-reconstruct, deadline-cull isolation on the
get route, per-route MTPU_BATCH_FORCE parsing, mixed-geometry batch
isolation (heal verifies of different EC configs through one
verifier), and real shard_map byte-identity on a virtual 8-device mesh
in a subprocess."""

import os
import shutil
import subprocess
import sys
import threading

import numpy as np
import pytest

from minio_tpu.io.bufpool import BufferPool
from minio_tpu.object.erasure_object import (_get_concat, _get_split,
                                             _host_deframe)
from minio_tpu.ops.batcher import (_BUCKETS, StripeBatcher,
                                   batch_force_mode)
from minio_tpu.ops.hh_device import make_deframer
from minio_tpu.storage import bitrot
from minio_tpu.utils.deadline import Deadline, DeadlineExceeded

K, M, SHARD = 8, 4, 4096
FRAME = 32 + SHARD


def _mk_framed(b, seed, k=K, shard=SHARD, corrupt=()):
    """[b, k, 32+shard] of valid on-disk frames; (bi, si) entries in
    `corrupt` get a flipped payload byte after hashing."""
    rng = np.random.default_rng(seed)
    blocks = rng.integers(0, 256, size=(b, k, shard), dtype=np.uint8)
    digs = bitrot.hash_blocks_many(
        bitrot.DEFAULT_ALGORITHM, blocks.reshape(b * k, shard)) \
        .reshape(b, k, 32)
    framed = np.concatenate([digs, blocks], axis=2)
    for bi, si in corrupt:
        framed[bi, si, 32 + (seed % shard)] ^= 0xFF
    return np.ascontiguousarray(framed)


class _RecordingDeframer:
    """Wraps the real single-chip de-framer, recording batch shapes."""

    def __init__(self, k=K):
        self.inner = make_deframer(k)
        self.batches = []
        self.mesh_devices = 1

    def __call__(self, stacked):
        self.batches.append(stacked.shape)
        return self.inner(stacked)


def _get_batcher(dev, pool=None, **kw):
    kw.setdefault("min_device_blocks", 8)
    sb = StripeBatcher(dev, _host_deframe, probe_fn=lambda: True,
                       pool=pool, route="get", split_fn=_get_split,
                       concat_fn=_get_concat, **kw)
    sb.force(True)
    return sb


def _coalesce(sb, windows, timeout=60):
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


def test_get_route_batched_vs_solo_identity_ragged_members():
    """Coalesced framed windows of UNEVEN sizes demultiplex to exactly
    the per-member verdicts and payload the host de-framer computes
    solo — including corrupt blocks flagged in the right member, and
    payload served as views of the member's OWN window."""
    dev = _RecordingDeframer()
    pool = BufferPool(max_per_class=4)
    sb = _get_batcher(dev, pool=pool, max_wait_s=0.1)
    sizes = [1, 2, 3, 5, 7]
    corrupt = {2: ((1, 4),), 4: ((0, 0), (6, 7))}
    windows = [_mk_framed(b, i, corrupt=corrupt.get(i, ()))
               for i, b in enumerate(sizes)]
    results, errors = _coalesce(sb, windows)
    assert all(e is None for e in errors)
    for i, w in enumerate(windows):
        ok, data = results[i]
        want_ok, want_data = _host_deframe(w)
        assert np.array_equal(ok, want_ok), i
        assert np.array_equal(data, want_data), i
        assert np.shares_memory(data, w)
        for bi, si in corrupt.get(i, ()):
            assert not ok[bi, si]
    assert dev.batches and all(s[0] in _BUCKETS for s in dev.batches)
    st = sb.stats()
    assert st["route"] == "get"
    assert st["dispatches"]["device"] >= 1
    assert pool.stats()["outstanding"] == 0


@pytest.mark.parametrize("bucket", _BUCKETS[:4])
def test_get_route_padding_buckets(bucket):
    """Solo device-sized framed windows at full and one-under bucket
    sizes verify identically to the host de-framer (zero-pad rows of a
    recycled staging buffer must never leak into verdicts)."""
    dev = _RecordingDeframer()
    pool = BufferPool(max_per_class=2)
    sb = _get_batcher(dev, pool=pool, min_device_blocks=4)
    for b in (bucket, bucket - 1):
        w = _mk_framed(b, b, corrupt=((b - 1, 3),))
        ok, data = sb.frame(w)
        want_ok, want_data = _host_deframe(w)
        assert np.array_equal(ok, want_ok)
        assert np.array_equal(data, want_data)
    assert [s[0] for s in dev.batches] == [bucket, bucket]
    assert pool.stats()["outstanding"] == 0


def test_get_route_deadline_cull_isolation():
    """A get-route member whose budget is spent by dispatch time fails
    alone with DeadlineExceeded; batch-mates still get correct
    verdicts."""
    from minio_tpu.ops.batcher import _Pending
    dev = _RecordingDeframer()
    sb = _get_batcher(dev)
    good = [_mk_framed(4, 1), _mk_framed(4, 2)]
    pgood = [_Pending(w, None) for w in good]
    pdead = _Pending(_mk_framed(4, 3), Deadline(-1.0))
    sb._run_batch([pgood[0], pdead, pgood[1]])
    assert isinstance(pdead.exc, DeadlineExceeded)
    assert pdead.event.is_set() and pdead.rows is None
    for i, p in enumerate(pgood):
        assert p.exc is None and p.event.is_set()
        ok, data = p.rows
        want_ok, want_data = _host_deframe(good[i])
        assert np.array_equal(ok, want_ok)
        assert np.array_equal(data, want_data)
    assert sb.stats()["deadline_failures"] == 1


def test_mixed_member_geometries_never_share_a_batch():
    """One verify batcher carries members of DIFFERENT trailing shapes
    (heal verifies of objects with different EC configs): the
    dispatcher drains same-shape runs per batch, so verdicts stay
    correct and no staging buffer mixes geometries."""
    dev = _RecordingDeframer(k=1)
    sb = _get_batcher(dev, max_wait_s=0.1)
    small = [_mk_framed(3, i, k=1, shard=1024) for i in range(3)]
    big = [_mk_framed(3, 10 + i, k=1, shard=4096) for i in range(3)]
    windows = [w for pair in zip(small, big) for w in pair]
    results, errors = _coalesce(sb, windows)
    assert all(e is None for e in errors)
    for i, w in enumerate(windows):
        ok, data = results[i]
        want_ok, want_data = _host_deframe(w)
        assert np.array_equal(ok, want_ok)
        assert np.array_equal(data, want_data)
    for shape in dev.batches:
        assert shape[2] in (32 + 1024, 32 + 4096)


def test_batch_force_mode_per_route(monkeypatch):
    monkeypatch.setenv("MTPU_BATCH_FORCE", "device")
    assert batch_force_mode("put") == "device"
    assert batch_force_mode("get") == "device"
    monkeypatch.setenv("MTPU_BATCH_FORCE", "put=device,get=host")
    assert batch_force_mode("put") == "device"
    assert batch_force_mode("get") == "host"
    assert batch_force_mode("reconstruct") == "auto"
    monkeypatch.setenv("MTPU_BATCH_FORCE", "reconstruct=device")
    assert batch_force_mode("put") == "auto"
    assert batch_force_mode("reconstruct") == "device"
    monkeypatch.setenv("MTPU_BATCH_FORCE", "get=bogus")
    assert batch_force_mode("get") == "auto"


def test_route_split_metrics_render():
    """Batcher occupancy splits by route in Prometheus text, and the
    decode-route kernel-lane service histogram is exported."""
    dev = _RecordingDeframer()
    sb = _get_batcher(dev, min_device_blocks=4)
    sb.frame(_mk_framed(8, 0))
    from minio_tpu.s3.metrics import Metrics
    text = Metrics().render()
    assert 'minio_tpu_batcher_dispatches_total{route="get",' in text
    assert 'minio_tpu_batcher_dispatches_total{route="put",' in text
    assert 'minio_tpu_batcher_fill_ratio{route="reconstruct"}' in text
    assert "minio_tpu_kernel_lane_decode_service_seconds_bucket" in text


# ---------------------------------------------------------------------------
# End-to-end through the object layer (device routes forced off-TPU)
# ---------------------------------------------------------------------------

@pytest.fixture
def forced_decode(monkeypatch, tmp_path):
    """12-drive EC 8+4 set with the decode routes pinned to the device
    (XLA-CPU here — the reproducibility knob reaches the real batched
    route on any host; calibration pins reset on teardown)."""
    monkeypatch.setenv("MTPU_BATCH_FORCE", "get=device,reconstruct=device")
    from minio_tpu.object.erasure_object import (ErasureSet,
                                                 _get_batcher_for)
    from minio_tpu.ops.rs_device import DeviceBackend
    from minio_tpu.storage.local import LocalStorage
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(12)]
    for d in disks:
        d.make_vol("b")
    es = ErasureSet(disks, parity=M, backend=DeviceBackend("auto"))
    for sb in (_get_batcher_for(8, 4), _get_batcher_for(1, 0)):
        sb.reset_calibration()          # re-pin cached instances
    yield es, tmp_path
    es.close()
    monkeypatch.delenv("MTPU_BATCH_FORCE", raising=False)
    for sb in (_get_batcher_for(8, 4), _get_batcher_for(1, 0)):
        sb.reset_calibration()


def test_device_get_window_and_degraded_reads_1_to_m(forced_decode):
    """A device-window-sized GET rides the batched de-framer
    (get_kernel["device"]), and degraded reads with 1..m shards
    missing reconstruct through the device route byte-identically."""
    es, root = forced_decode
    from minio_tpu.object.erasure_object import _get_batcher_for
    from minio_tpu.ops import batcher as batcher_mod
    rng = np.random.default_rng(21)
    body = rng.integers(0, 256, size=9 << 20, dtype=np.uint8).tobytes()
    es.put_object("b", "o", body)
    before = _get_batcher_for(8, 4).stats()["dispatches"]["device"]
    _, got = es.get_object("b", "o")
    assert got == body
    assert es.get_kernel["device"] >= 1
    assert _get_batcher_for(8, 4).stats()["dispatches"]["device"] \
        == before + 1
    # Degraded: knock out 1..m drives' copies; every read must
    # reconstruct byte-identically via the device reconstruct route.
    es.fi_cache.enabled = False
    for n_missing in range(1, M + 1):
        for i in range(n_missing):
            shutil.rmtree(str(root / f"d{i}" / "b" / "o"),
                          ignore_errors=True)
        es.metacache.bump("b")
        _, got = es.get_object("b", "o")
        assert got == body, f"{n_missing} missing"
    recs = [s for s in batcher_mod._REGISTRY
            if s.route == "reconstruct"]
    assert sum(s.stats()["dispatches"]["device"] for s in recs) >= 1


def test_bitrot_demote_then_device_reconstruct(forced_decode):
    """A corrupt shard flagged by the DEVICE verify demotes to the
    reconstruct path, which rebuilds on the device route and serves
    the original bytes."""
    es, root = forced_decode
    import glob
    from minio_tpu.object.erasure_object import hash_order
    rng = np.random.default_rng(22)
    body = rng.integers(0, 256, size=9 << 20, dtype=np.uint8).tobytes()
    es.put_object("b", "rot", body)
    es.fi_cache.enabled = False
    # Corrupt a DATA shard's holder (shard index 0): parity holders are
    # only read after a demotion, so the device verify must see this.
    dist = hash_order("b/rot", 12)
    disk = dist.index(1)
    files = glob.glob(str(root / f"d{disk}" / "b" / "rot" / "*"
                          / "part.1"))
    assert files
    with open(files[0], "r+b") as f:
        f.seek(2000)
        f.write(b"\x5a\xa5\x5a\xa5")
    _, got = es.get_object("b", "rot")
    assert got == body
    assert es.get_kernel["demoted"] >= 1


@pytest.mark.parametrize("path, stacks_a_get, counted", [
    ("verified", 2, "device"),
    ("bitrot", 2, "demoted"),
    ("device_raises", 2, "native"),
    ("device_raises_required", 1, None),
])
def test_get_window_stacks_are_pool_leases(forced_decode, monkeypatch,
                                           path, stacks_a_get, counted):
    """A multi-window streamed GET on the device route stacks each
    window's de-framer member in a lease of the process-wide pool,
    inside the counted stage `get.stack` (once a window), and gives
    every lease back: on a verified window, on a bitrot demote, and
    when the device function raises (absorbed into the native kernel,
    or raised where the device is required). Once warm, every stack is
    a pool hit and the answer is byte-identical."""
    import gc
    import glob
    from minio_tpu.io.bufpool import global_pool
    from minio_tpu.object import erasure_object as eo
    from minio_tpu.ops import device as device_mod
    from minio_tpu.utils import tracing
    es, root = forced_decode
    # Two 8-block windows a GET (the served path reads 32 MiB windows;
    # the stack is the same code at any size).
    monkeypatch.setattr(eo, "GET_WINDOW_BYTES", 8 << 20)
    # The rot stays planted: no background heal leases beside the GETs.
    monkeypatch.setattr(es.mrf, "enqueue", lambda *a, **kw: None)
    rng = np.random.default_rng(24)
    body = rng.integers(0, 256, size=16 << 20, dtype=np.uint8).tobytes()
    es.put_object("b", "mw", body)
    es.fi_cache.enabled = False
    if path == "bitrot":
        dist = eo.hash_order("b/mw", 12)
        files = glob.glob(str(root / f"d{dist.index(1)}" / "b" / "mw"
                              / "*" / "part.1"))
        assert files
        with open(files[0], "r+b") as f:
            f.seek(2000)
            f.write(b"\x5a\xa5\x5a\xa5")
    if path.startswith("device_raises"):
        def boom(stacked):
            raise RuntimeError("device fault (planted)")
        monkeypatch.setattr(eo._get_batcher_for(8, 4), "_device_fn", boom)
        monkeypatch.setattr(device_mod, "required",
                            lambda: path == "device_raises_required")
    pool = global_pool()
    stack_bytes = 8 * K * (32 + (1 << 20) // K)
    leased = []
    lease = pool.lease

    def spy(size):
        leased.append(size)
        return lease(size)
    monkeypatch.setattr(pool, "lease", spy)

    def get():
        _, chunks = es.get_object_stream("b", "mw")
        return b"".join(bytes(c) for c in chunks)

    def read():
        if counted is None:
            with pytest.raises(RuntimeError, match="planted"):
                get()
        else:
            assert get() == body

    def entries(stage):
        return tracing.stage_totals().get(stage, [0.0, 0.0, 0])[2]

    def stacks():
        return entries("get.stack")

    # The windows the device verified are interleaved, 8 whole blocks
    # each in one strided copy; a demoted or failed one never is.
    interleaves_a_get = {"verified": 2, "bitrot": 1}.get(path, 0)
    before = pool.stats()
    read()                       # warm: the pool's first leases miss
    gc.collect()
    warm = pool.stats()
    assert warm["outstanding"] == before["outstanding"]
    n0, kernel0 = stacks(), dict(es.get_kernel)
    i0, blocks0 = entries("get.interleave"), dict(es.get_interleave_blocks)
    del leased[:]
    for _ in range(3):
        read()
    gc.collect()
    after = pool.stats()
    assert stacks() - n0 == 3 * stacks_a_get
    assert entries("get.interleave") - i0 == 3 * interleaves_a_get
    assert es.get_interleave_blocks == {
        "bulk": blocks0["bulk"] + 3 * 8 * interleaves_a_get,
        "block": blocks0["block"]}
    assert leased.count(stack_bytes) == 3 * stacks_a_get
    assert after["hits"] - warm["hits"] >= 3 * stacks_a_get
    assert after["misses"] == warm["misses"]
    assert after["outstanding"] == before["outstanding"]
    assert after["leaks"] == before["leaks"]
    if counted is not None:
        assert es.get_kernel[counted] - kernel0[counted] >= 3


@pytest.mark.parametrize("window_mib, body_len, bulk, alone", [
    (8, 16 << 20, 16, 0),                # two windows of whole blocks
    (8, (16 << 20) - 3, 15, 1),          # the last block cut at out_len
    (9, (18 << 20) - (200 << 10), 17, 1),  # a ragged tail frame
], ids=["whole_blocks", "cut_last_block", "ragged_tail"])
def test_a_multi_window_get_is_interleaved_in_bulk(forced_decode,
                                                   monkeypatch, window_mib,
                                                   body_len, bulk, alone):
    """Each verified window of a two-window device GET is interleaved
    once (get.interleave), its whole blocks by the strided bulk copy and
    a cut last block or a ragged tail frame alone, as
    minio_tpu_get_interleave_blocks_total{mode} counts; the answer is
    byte-identical and every lease goes back to the pool."""
    import gc
    from minio_tpu.io.bufpool import global_pool
    from minio_tpu.object import erasure_object as eo
    from minio_tpu.s3.metrics import Metrics
    from minio_tpu.utils import tracing
    es, _ = forced_decode
    monkeypatch.setattr(eo, "GET_WINDOW_BYTES", window_mib << 20)
    rng = np.random.default_rng(28)
    body = rng.integers(0, 256, size=body_len, dtype=np.uint8).tobytes()
    es.put_object("b", "iw", body)

    def get():
        _, chunks = es.get_object_stream("b", "iw")
        return b"".join(bytes(c) for c in chunks)

    assert get() == body                  # warm: the programs compile
    gc.collect()
    outstanding = global_pool().stats()["outstanding"]
    before, blocks0 = tracing.stage_totals(), dict(es.get_interleave_blocks)
    device0 = es.get_kernel["device"]
    assert get() == body
    gc.collect()
    assert _entries(_stages_since(before), ("get.interleave",)) == {
        "get.interleave": 2}
    assert es.get_kernel["device"] - device0 == 2
    assert es.get_interleave_blocks == {"bulk": blocks0["bulk"] + bulk,
                                        "block": blocks0["block"] + alone}
    assert global_pool().stats()["outstanding"] == outstanding
    text = Metrics().render(object_layer=es)
    for mode, n in es.get_interleave_blocks.items():
        assert (f'\nminio_tpu_get_interleave_blocks_total{{mode="{mode}"}} '
                f"{n}\n") in text


def test_concurrent_gets_lose_no_interleave_count(forced_decode,
                                                  monkeypatch):
    """Twelve threads each GET a two-window object at once, with the
    interpreter switching threads every 10 us: every answer is
    byte-identical and minio_tpu_get_interleave_blocks_total{mode="bulk"}
    rises by exactly 8 blocks a window — no count is lost to a race
    between the windows' threads."""
    import sys
    from minio_tpu.object import erasure_object as eo
    es, _ = forced_decode
    monkeypatch.setattr(eo, "GET_WINDOW_BYTES", 8 << 20)
    rng = np.random.default_rng(29)
    body = rng.integers(0, 256, size=16 << 20, dtype=np.uint8).tobytes()
    es.put_object("b", "cc", body)
    es.fi_cache.enabled = False

    def get():
        _, chunks = es.get_object_stream("b", "cc")
        return b"".join(bytes(c) for c in chunks)

    assert get() == body                  # warm: the programs compile
    blocks0 = dict(es.get_interleave_blocks)
    answers = [None] * 12
    threads = [threading.Thread(target=lambda i=i: answers.__setitem__(
        i, get())) for i in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert all(a == body for a in answers)
    assert es.get_interleave_blocks == {
        "bulk": blocks0["bulk"] + 12 * 2 * 8, "block": blocks0["block"]}


def _interleave_by_block(data, out_len, block_size):
    """The per-block reassembly the window used before the strided copy:
    a temporary of each block's k x shard, cut to the block."""
    out = np.zeros(out_len, dtype=np.uint8)
    pos = 0
    for b in range(data.shape[0]):
        take = min(block_size, out_len - pos)
        out[pos:pos + take] = data[b].reshape(-1)[:take]
        pos += take
    return out


@pytest.mark.parametrize("k, shard", [(8, 131072), (4, 262144),
                                      (12, 87382), (6, 174763)])
@pytest.mark.parametrize("end", ["all_blocks", "inside_last",
                                 "one_byte_into_last"])
def test_interleave_window_is_the_per_block_copy(k, shard, end):
    """_interleave_window over a de-framer member's payload view (the
    32-byte digest before each shard) writes exactly what the per-block
    loop wrote, for the geometries whose k x shard is the block (one
    strided copy) and those whose k x shard overshoots it (two: the
    last column cut), with out_len ending at the last block's end,
    inside it, and one byte into it; nothing past out_len is touched,
    and it says how many blocks went in bulk and how many alone."""
    from minio_tpu.object.erasure_object import (BLOCK_SIZE,
                                                 _interleave_window)
    full = 3
    member = np.random.default_rng(k).integers(
        0, 256, size=(full, k, 32 + shard), dtype=np.uint8)
    data = _get_split(np.ones((full, k), bool), 0, full, member)[1]
    out_len = {"all_blocks": full * BLOCK_SIZE,
               "inside_last": full * BLOCK_SIZE - 3,
               "one_byte_into_last": (full - 1) * BLOCK_SIZE + 1}[end]
    out = np.full(out_len + 64, 0xA5, dtype=np.uint8)
    counts = _interleave_window(out, data, out_len, BLOCK_SIZE)
    assert np.array_equal(out[:out_len],
                          _interleave_by_block(data, out_len, BLOCK_SIZE))
    assert (out[out_len:] == 0xA5).all()
    assert counts == ((full, 0) if end == "all_blocks" else (full - 1, 1))


def test_heal_deep_verify_rides_verify_batcher(forced_decode):
    """Deep heal's bitrot verification routes through the k=1 verify
    batcher (one member per drive shard file) and still detects and
    repairs corruption."""
    es, root = forced_decode
    import glob
    from minio_tpu.object.erasure_object import _get_batcher_for
    rng = np.random.default_rng(23)
    body = rng.integers(0, 256, size=9 << 20, dtype=np.uint8).tobytes()
    es.put_object("b", "hv", body)
    sb = _get_batcher_for(1, 0)
    before = sb.stats()["dispatches"]["device"]
    r = es.heal_object("b", "hv", deep=True)
    assert r.healed == 0
    assert sb.stats()["dispatches"]["device"] > before
    files = glob.glob(str(root / "d5" / "b" / "hv" / "*" / "part.1"))
    with open(files[0], "r+b") as f:
        f.seek(500)
        f.write(b"\xde\xad\xbe\xef")
    r = es.heal_object("b", "hv", deep=True)
    assert r.healed == 1
    es.fi_cache.enabled = False
    es.metacache.bump("b")
    _, got = es.get_object("b", "hv")
    assert got == body


# -- the read path's stages and counters (utils/tracing.stage) -----------

WINDOW_PARTS = ("get.fetch", "get.stack", "get.deframe", "get.interleave",
                "get.survivor_verify", "get.rebuild_stack", "get.rebuild",
                "get.join")


def _stages_since(before: dict) -> dict:
    """{stage: (wall s, entries)} the process-wide accumulator gained
    since `before`."""
    from minio_tpu.utils import tracing
    out = {}
    for name, (wall, _cpu, n) in tracing.stage_totals().items():
        w0, _c0, n0 = before.get(name, (0.0, 0.0, 0))
        if n > n0:
            out[name] = (wall - w0, n - n0)
    return out


def _entries(got: dict, names) -> dict:
    return {n: got.get(n, (0.0, 0))[1] for n in names}


def test_a_multi_window_get_names_each_stage_of_its_read(forced_decode,
                                                          monkeypatch):
    """A warm two-window GET through the S3 server on the device route
    enters get.prepare once, and get.window_wait and get.send (request
    thread) and get.window, get.fetch, get.stack, get.deframe and
    get.interleave (the window's thread) once a window. The window's
    parts hold no more than the window, the request thread's stages no
    more than the GET's own seconds, and each window's wait for the
    set's read pool is counted."""
    from minio_tpu.object import erasure_object as eo
    from minio_tpu.s3.server import S3Server
    from minio_tpu.utils import tracing
    from tests.batcher_rig import until
    from tests.s3client import S3Client
    es, _ = forced_decode
    monkeypatch.setattr(eo, "GET_WINDOW_BYTES", 8 << 20)
    monkeypatch.setattr(es.mrf, "enqueue", lambda *a, **kw: None)
    rng = np.random.default_rng(25)
    body = rng.integers(0, 256, size=16 << 20, dtype=np.uint8).tobytes()
    es.put_object("b", "mw", body)
    srv = S3Server(es, address="127.0.0.1:0")
    srv.start()
    try:
        cli = S3Client(srv.address)

        def gets():
            return srv.metrics.state()["latency_hist"].get(
                "GET:object", {"count": 0, "sum": 0.0})
        for i in (1, 2):                  # the first one warms the route
            if i == 2:
                before, get0 = tracing.stage_totals(), gets()
                waited0 = list(es.get_pool_wait)
            st, _, got = cli.request("GET", "/b/mw")
            assert st == 200 and got == body
            # credited when the server has left `_route`
            until(lambda: gets()["count"] == i, f"GET {i} is recorded")
        get_s = gets()["sum"] - get0["sum"]
    finally:
        srv.stop()
    got = _stages_since(before)
    assert _entries(got, ("get.prepare", "get.window_wait", "get.send",
                          "get.window") + WINDOW_PARTS) == {
        "get.prepare": 1, "get.window_wait": 2, "get.send": 2,
        "get.window": 2, "get.fetch": 2, "get.stack": 2, "get.deframe": 2,
        "get.interleave": 2, "get.survivor_verify": 0,
        "get.rebuild_stack": 0, "get.rebuild": 0, "get.join": 0}
    parts = sum(got[n][0] for n in WINDOW_PARTS if n in got)
    assert 0 < parts <= got["get.window"][0]
    request = sum(got[n][0] for n in ("s3.auth", "get.prepare",
                                      "get.window_wait", "get.send"))
    assert 0 < request <= get_s + 1e-5   # the sum is rounded to 1 us
    assert es.get_pool_wait[1] - waited0[1] == 2
    assert es.get_pool_wait[0] >= waited0[0]


def test_a_degraded_get_names_the_rebuild_path(forced_decode, monkeypatch):
    """A GET with one data shard's drive gone verifies the fetched
    shards on the host (get.survivor_verify: the data shards, then
    parity), fetches twice (get.fetch), stacks the survivors and
    rebuilds on the device route (get.rebuild_stack, get.rebuild) and
    joins the answer (get.join) — and never enters the healthy
    window's stack, de-framer or interleave."""
    from minio_tpu.object.erasure_object import hash_order
    from minio_tpu.utils import tracing
    es, root = forced_decode
    monkeypatch.setattr(es.mrf, "enqueue", lambda *a, **kw: None)
    rng = np.random.default_rng(26)
    body = rng.integers(0, 256, size=9 << 20, dtype=np.uint8).tobytes()
    es.put_object("b", "dg", body)
    es.fi_cache.enabled = False
    dist = hash_order("b/dg", 12)
    shutil.rmtree(str(root / f"d{dist.index(1)}" / "b" / "dg"))
    es.metacache.bump("b")
    assert es.get_object("b", "dg")[1] == body   # warm: the pattern's
    before = tracing.stage_totals()              # program compiles
    assert es.get_object("b", "dg")[1] == body
    got = _stages_since(before)
    assert _entries(got, ("get.prepare", "get.window") + WINDOW_PARTS) \
        == {"get.prepare": 1, "get.window": 1, "get.fetch": 2,
            "get.stack": 0, "get.deframe": 0, "get.interleave": 0,
            "get.survivor_verify": 2, "get.rebuild_stack": 1,
            "get.rebuild": 1, "get.join": 1}
    parts = sum(got[n][0] for n in WINDOW_PARTS if n in got)
    assert 0 < parts <= got["get.window"][0]


def test_a_refused_survivor_is_counted_and_a_dead_drives_shard_is_not(
        forced_decode, monkeypatch):
    """On the rebuild path, a fetched shard that the verify refuses for
    bitrot counts once in minio_tpu_get_survivors_refused_total; the
    shard of a drive that is gone was never fetched and counts 0. Both
    new series render in /metrics."""
    from minio_tpu.object.erasure_object import hash_order
    from minio_tpu.s3.metrics import Metrics
    es, root = forced_decode
    monkeypatch.setattr(es.mrf, "enqueue", lambda *a, **kw: None)
    rng = np.random.default_rng(27)
    body = rng.integers(0, 256, size=9 << 20, dtype=np.uint8).tobytes()
    es.put_object("b", "rs", body)
    es.fi_cache.enabled = False
    dist = hash_order("b/rs", 12)
    shutil.rmtree(str(root / f"d{dist.index(1)}" / "b" / "rs"))
    es.metacache.bump("b")
    assert es.get_object("b", "rs")[1] == body
    assert es.get_survivors_refused == 0          # gone, not refused
    import glob
    files = glob.glob(str(root / f"d{dist.index(2)}" / "b" / "rs" / "*"
                          / "part.1"))
    assert files
    with open(files[0], "r+b") as f:              # data shard 1 rots
        f.seek(2000)
        f.write(b"\x5a\xa5\x5a\xa5")
    assert es.get_object("b", "rs")[1] == body
    assert es.get_survivors_refused == 1
    text = Metrics().render(object_layer=es)
    assert "\nminio_tpu_get_survivors_refused_total 1\n" in text
    for name in ("minio_tpu_get_window_pool_wait_seconds_sum",
                 "minio_tpu_get_window_pool_wait_seconds_count"):
        assert f"\n{name} " in text, name


_MESH_BODY = r"""
import numpy as np
import jax
from minio_tpu.object.erasure_object import _host_deframe, _host_apply_rows
from minio_tpu.ops import gf256
from minio_tpu.ops.hh_device import make_mesh_deframer
from minio_tpu.ops.rs_device import make_mesh_matrix
from minio_tpu.storage import bitrot

K, M, SHARD = 8, 4, 256
assert len(jax.devices()) == 8, jax.devices()

def mk(b, seed, corrupt=()):
    rng = np.random.default_rng(seed)
    blocks = rng.integers(0, 256, size=(b, K, SHARD), dtype=np.uint8)
    digs = bitrot.hash_blocks_many(
        bitrot.DEFAULT_ALGORITHM, blocks.reshape(b * K, SHARD)) \
        .reshape(b, K, 32)
    framed = np.concatenate([digs, blocks], axis=2)
    for bi, si in corrupt:
        framed[bi, si, 40] ^= 0xFF
    return np.ascontiguousarray(framed)

deframer = make_mesh_deframer(K)
assert deframer.mesh_devices == 8, deframer.mesh_devices
for b in (8, 16):
    w = mk(b, b, corrupt=((b - 1, 2), (0, 7)))
    ok = deframer(w)
    want_ok, _ = _host_deframe(w)
    assert np.array_equal(ok, want_ok), b

# Batched reconstruct on the mesh: decode rows for 3 missing data
# shards applied across the stripe axis, byte-identical to the host
# codec.
missing = (1, 3, 5)
avail = tuple(i for i in range(K + M) if i not in missing)[:K]
dec = gf256.decode_matrix(K, M, avail)
rows = np.ascontiguousarray(dec[list(missing), :])
mm = make_mesh_matrix(rows)
assert mm.mesh_devices == 8, mm.mesh_devices
rng = np.random.default_rng(9)
surv = rng.integers(0, 256, size=(16, K, SHARD), dtype=np.uint8)
out = mm(surv)
want = _host_apply_rows(rows, surv)
assert np.array_equal(out, want)

# Through the batcher: concurrent get-route members coalesce into
# mesh-divisible buckets and stay verdict-identical.
import threading
from minio_tpu.object.erasure_object import _get_concat, _get_split
from minio_tpu.ops.batcher import StripeBatcher
sb = StripeBatcher(deframer, _host_deframe, probe_fn=lambda: True,
                   min_device_blocks=8, route="get",
                   split_fn=_get_split, concat_fn=_get_concat)
sb.force(True)
windows = [mk(3, 50 + i, corrupt=(((i, i % K),) if i < 3 else ()))
           for i in range(4)]
results = [None] * 4
with sb._mu:
    sb._inflight += 1
ts = [threading.Thread(target=lambda i=i: results.__setitem__(
    i, sb.frame(windows[i]))) for i in range(4)]
[t.start() for t in ts]
[t.join(timeout=120) for t in ts]
with sb._mu:
    sb._inflight -= 1
for i in range(4):
    ok, data = results[i]
    want_ok, want_data = _host_deframe(windows[i])
    assert np.array_equal(ok, want_ok), i
    assert np.array_equal(data, want_data), i
assert sb.stats()["dispatches"]["device"] >= 1
print("MESH_DECODE_OK")
"""


def test_decode_byte_identity_on_virtual_8_device_mesh():
    """The sharded de-framer and reconstruct dispatches on a real
    8-device mesh (virtual CPU devices in a fresh subprocess) produce
    verdicts/bytes identical to the host path, solo and through the
    get-route batcher."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("MTPU_MESH_DEVICES", None)
    env.pop("MTPU_BATCH_FORCE", None)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=8", "")
        + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_BODY], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, timeout=420)
    assert proc.returncode == 0, proc.stderr.decode()[-4000:]
    assert b"MESH_DECODE_OK" in proc.stdout

"""The mesh framer on four chips (four of the suite's eight virtual
devices), held to the benchmark's plain reference (`benchmark/
reference/`: GF(2^8) Reed-Solomon with upstream's matrix,
HighwayHash-256 — code that shares nothing with `minio_tpu/`): every
padding bucket of both benchmarked geometries, the chips' shares adding
up to the whole, a served PUT whose shard files the benchmark's own
comparison accepts, and the per-chip block counters
(`minio_tpu_mesh_blocks_total`)."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare  # noqa: E402
from benchmark.reference import gf_rs, highway  # noqa: E402
from minio_tpu.object import erasure_object as eo  # noqa: E402
from minio_tpu.ops import device, gf256  # noqa: E402
from minio_tpu.ops.batcher import _BUCKETS, StripeBatcher  # noqa: E402
from minio_tpu.ops.hh_device import (make_encode_framer,  # noqa: E402
                                     make_mesh_framer)

CHIPS = 4
BLOCK = 64 << 10                   # a small erasure block: 64 KiB
GEOMETRIES = [(8, 4), (4, 2)]      # the benchmark's two


@pytest.fixture(scope="module")
def framers():
    """One mesh framer a geometry over four devices (a framer compiles
    one program per batch size: shared by the tests below)."""
    devs = jax.devices()[:CHIPS]
    assert len(devs) == CHIPS
    out = {}
    for k, m in GEOMETRIES:
        out[k, m] = make_mesh_framer(gf256.parity_matrix(k, m), devices=devs)
        assert out[k, m].mesh_devices == CHIPS
    return out


def window(k: int, blocks: int, seed: int) -> np.ndarray:
    return np.random.default_rng([seed, k, blocks]).integers(
        0, 256, size=(blocks, k, BLOCK // k), dtype=np.uint8)


def reference_rows(data: np.ndarray, k: int, m: int):
    """(digests [n, B, 32], shards [n, B, L]) by the plain reference."""
    b, _, piece = data.shape
    flat = np.ascontiguousarray(data.transpose(1, 0, 2)).reshape(k, -1)
    parity = gf_rs.encode(flat, k, m).reshape(m, b, piece)
    shards = np.concatenate([flat.reshape(k, b, piece), parity])
    digests = highway.hash256_many(
        shards.reshape((k + m) * b, piece)).reshape(k + m, b, 32)
    return digests, shards


def as_arrays(rows):
    """Per-drive (digest, block) rows -> (digests [n, B, 32], blocks
    [n, B, L])."""
    return (np.stack([np.stack([np.asarray(d) for d, _ in drive])
                      for drive in rows]),
            np.stack([np.stack([np.asarray(blk) for _, blk in drive])
                      for drive in rows]))


@pytest.mark.parametrize("bucket", _BUCKETS)
@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_every_bucket_equals_the_plain_reference(framers, k, m, bucket):
    data = window(k, bucket, seed=28)
    digests, blocks = as_arrays(framers[k, m](data))
    want_digests, want_shards = reference_rows(data, k, m)
    assert blocks.shape == (k + m, bucket, BLOCK // k)
    assert np.array_equal(blocks, want_shards)
    assert np.array_equal(digests, want_digests)


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_the_chips_shares_add_up_to_the_whole(framers, k, m):
    """`P("stripe")` cuts the batch in order: chip i's slice, framed
    alone on one device, is rows i*B/4 .. (i+1)*B/4 of the mesh's
    answer, and the four together are all of it."""
    bucket = 32
    data = window(k, bucket, seed=29)
    digests, blocks = as_arrays(framers[k, m](data))
    alone = make_encode_framer(gf256.parity_matrix(k, m))
    per = bucket // CHIPS
    covered = 0
    for chip in range(CHIPS):
        lo, hi = chip * per, (chip + 1) * per
        d1, b1 = as_arrays(alone(data[lo:hi]))
        assert np.array_equal(d1, digests[:, lo:hi]), chip
        assert np.array_equal(b1, blocks[:, lo:hi]), chip
        covered += hi - lo
    assert covered == bucket


def mesh_blocks() -> dict:
    return dict(device.stats()["mesh_blocks"])


def gained(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in mesh_blocks().items()
            if v != before.get(k, 0)}


def test_mesh_blocks_count_real_and_padding_by_chip(framers):
    """40 real blocks in a 64 bucket on four chips: 16 a chip, the
    real ones first, so the padding lands on the last chips."""
    k, m = 8, 4
    sb = StripeBatcher(framers[k, m], lambda s: eo._host_rows(k, m, s),
                       probe_fn=lambda: True, min_device_blocks=8)
    sb.force(True)
    before = mesh_blocks()
    try:
        rows = sb.frame(window(k, 40, seed=30))
    finally:
        sb.close()
    assert len(rows[0]) == 40
    got = gained(before)
    assert [got.get(f"{c}/real", 0) for c in range(CHIPS)] == [16, 16, 8, 0]
    assert [got.get(f"{c}/pad", 0) for c in range(CHIPS)] == [0, 0, 8, 16]
    from minio_tpu.s3.metrics import Metrics
    text = Metrics().render()
    assert 'minio_tpu_mesh_blocks_total{chip="3",kind="pad"}' in text
    assert 'minio_tpu_mesh_blocks_total{chip="0",kind="real"}' in text


def test_a_call_without_the_batchers_word_counts_every_row_as_real(framers):
    before = mesh_blocks()
    framers[4, 2](window(4, 8, seed=31))
    got = gained(before)
    assert got == {f"{c}/real": 2 for c in range(CHIPS)}


def test_one_device_has_no_mesh_series(monkeypatch):
    """On one device the mesh framer is the single-chip framer: it
    writes no per-chip counter, and with none written the series is
    absent from /metrics, not zero."""
    one = make_mesh_framer(gf256.parity_matrix(4, 2),
                           devices=jax.devices()[:1])
    assert one.mesh_devices == 1
    before = mesh_blocks()
    with device.batch_of(5):
        one(window(4, 8, seed=32))
    assert gained(before) == {}
    monkeypatch.setattr(device, "_mesh_blocks", {})
    from minio_tpu.s3.metrics import Metrics
    assert "minio_tpu_mesh_blocks_total" not in Metrics().render()


def test_a_served_put_on_a_four_device_mesh_is_upstreams_on_disk(
        tmp_path, monkeypatch):
    """A PUT through the S3 front end, the object layer, the batcher
    and the mesh framer on four devices (the portable XLA path) leaves
    shard files that the benchmark's own comparison with the plain
    reference accepts, on every drive."""
    from minio_tpu.ops.rs_device import DeviceBackend
    from minio_tpu.s3.server import S3Server
    from minio_tpu.storage.local import LocalStorage
    from s3client import S3Client

    k, m, drives = 4, 2, 6
    monkeypatch.setenv("MTPU_BATCH_FORCE", "put=device,transform=device")
    monkeypatch.setenv("MTPU_MESH_DEVICES", str(CHIPS))
    # the framer and the batchers are cached per geometry, with the
    # mesh width they were built on; a buffered PUT's window rides
    # route `transform`, a streamed one's route `put`
    caches = (eo._mesh_framer_for, eo._batcher_for,
              eo._transform_batcher_for)
    for cache in caches:
        cache.cache_clear()
    disks = [LocalStorage(str(tmp_path / f"d{i}"))
             for i in range(1, drives + 1)]
    for d in disks:
        d.make_vol("bkt")
    es = eo.ErasureSet(disks, parity=m, backend=DeviceBackend("auto"))
    srv = S3Server(es, address="127.0.0.1:0")
    srv.start()
    before = mesh_blocks()
    body = np.random.default_rng(33).integers(
        0, 256, size=10 << 20, dtype=np.uint8).tobytes()
    try:
        batchers = (eo._batcher_for(k, m), eo._transform_batcher_for(k, m))
        assert [sb.mesh_devices for sb in batchers] == [CHIPS, CHIPS]
        cli = S3Client(srv.address)
        assert cli.request("PUT", "/bkt/served", body=body)[0] == 200
        st, _, got = cli.request("GET", "/bkt/served")
        assert st == 200 and got == body
        dispatched = sum(sb.stats()["dispatches"]["device"]
                         for sb in batchers)
    finally:
        srv.stop()
        for cache in caches:
            cache.cache_clear()
    assert dispatched == 1
    # 10 blocks in a 16 bucket: 4 a chip
    got = gained(before)
    assert [got.get(f"{c}/real", 0) for c in range(CHIPS)] == [4, 4, 2, 0]
    assert [got.get(f"{c}/pad", 0) for c in range(CHIPS)] == [0, 0, 2, 4]
    cfg = {"data_shards": k, "parity_shards": m, "drives": drives,
           "erasure_block_bytes": eo.BLOCK_SIZE, "write_quorum": k}
    seen = compare.check_object_on_disk(str(tmp_path), cfg, "bkt", "served",
                                        body)
    assert seen["wrong"] == 0
    assert seen["right"] == drives >= cfg["write_quorum"]


# -- one body and one run() per operation, one placement rule (PR 31) ----------

def _framed(k: int, blocks: int, seed: int) -> np.ndarray:
    """On-disk frames [B, k, 32 + L], the last one with a flipped byte."""
    data = window(k, blocks, seed)
    digests = highway.hash256_many(
        data.reshape(blocks * k, -1)).reshape(blocks, k, 32)
    framed = np.concatenate([digests, data], axis=2)
    framed[-1, -1, -1] ^= 1
    return framed


def _operation(name: str):
    """(the one-chip entry, the mesh entry given devices, an input, the
    result as an array) of a batched device operation."""
    from minio_tpu.ops.hh_device import make_deframer, make_mesh_deframer
    from minio_tpu.ops.rs_device import make_mesh_matrix
    k, m = 4, 2
    rows = gf256.parity_matrix(k, m)
    if name == "frame":
        return (lambda: make_encode_framer(rows),
                lambda devs: make_mesh_framer(rows, devices=devs),
                window(k, 8, seed=34), lambda out: np.concatenate(
                    as_arrays(out), axis=2))
    if name == "deframe":
        return (lambda: make_deframer(k),
                lambda devs: make_mesh_deframer(k, devices=devs),
                _framed(k, 8, seed=35), np.asarray)
    # the matrix route has the one entry: one chip is one device named
    return (lambda: make_mesh_matrix(rows, devices=jax.devices()[:1]),
            lambda devs: make_mesh_matrix(rows, devices=devs),
            window(k, 8, seed=36), np.asarray)


@pytest.mark.parametrize("name", ["frame", "deframe", "matrix"])
def test_the_one_chip_entry_is_the_mesh_entry_at_one_device(name):
    """Each operation is written once: the one-chip factory is the mesh
    factory at one device, and on four devices the same run() drives
    the same body under another placement. Same answers on 1 and 4."""
    one_chip, mesh, x, as_array = _operation(name)
    devs = jax.devices()
    solo, at_one, at_four = one_chip(), mesh(devs[:1]), mesh(devs[:CHIPS])
    assert solo.mesh_devices == at_one.mesh_devices == 1
    assert at_four.mesh_devices == CHIPS
    assert solo.__code__ is at_one.__code__ is at_four.__code__
    want = as_array(solo(x))
    assert np.array_equal(as_array(at_one(x)), want)
    assert np.array_equal(as_array(at_four(x)), want)
    if name == "deframe":
        assert not want[-1, -1] and want.sum() == want.size - 1
    if name == "matrix":
        b, k, piece = x.shape
        flat = np.ascontiguousarray(x.transpose(1, 0, 2)).reshape(k, -1)
        assert np.array_equal(
            want.transpose(1, 0, 2).reshape(-1, b * piece),
            gf_rs.encode(flat, k, want.shape[1]))


def test_placement_is_one_rule_for_every_operation():
    """`device.batch_placement` alone builds a mesh, a sharding or a
    donation: one device gets a plain jit and no mesh object, several
    the body under shard_map with the batch cut in order."""
    jit_body, upload, ndev = device.batch_placement(jax.devices()[:1])
    assert ndev == 1
    x = np.arange(8 * 3, dtype=np.uint8).reshape(8, 3)
    step = jit_body(lambda batch, bias: batch + bias)
    assert np.array_equal(np.asarray(step(upload(x), np.uint8(1))), x + 1)
    jit_body, upload, ndev = device.batch_placement(jax.devices()[:CHIPS])
    assert ndev == CHIPS
    on_mesh = upload(x)
    assert [s.data.shape for s in on_mesh.addressable_shards] == \
        [(8 // CHIPS, 3)] * CHIPS
    step = jit_body(lambda batch, bias: batch + bias)
    assert np.array_equal(np.asarray(step(on_mesh, np.uint8(1))), x + 1)
    with pytest.raises(AssertionError, match="not divisible"):
        upload(x[:6])

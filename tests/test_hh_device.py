"""Device HighwayHash + fused encode/bitrot framing must be byte-identical
to the host bitrot layer (and therefore to the reference's golden digests,
cmd/bitrot.go:225-230).

The Pallas kernels run in interpret mode off-TPU, so shapes here stay
small; the TPU-gated tests (run this file on the chip) and the benchmark's
cells exercise the compiled kernels on real hardware.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from minio_tpu.erasure.codec import Erasure
from minio_tpu.ops import device, gf256
from minio_tpu.ops.hh_device import (_hash_words_pallas, _init_smem_np,
                                     _init_state_np, _pick_pchunk,
                                     hash_blocks_device, hash_blocks_pallas,
                                     make_encode_framer)
from minio_tpu.storage import bitrot
from minio_tpu.utils.highwayhash import MAGIC_KEY, highwayhash256_many

_ON_TPU = device.on_tpu()


# ---------------------------------------------------------------------------
# XLA (portable) path
# ---------------------------------------------------------------------------

_XLA_LENGTHS = [0, 1, 3, 17, 31, 32, 33, 63, 64, 100, 1024, 4096] if _ON_TPU \
    else [0, 17, 31, 32, 100, 1024]   # each length = one ~3s CPU compile


@pytest.mark.parametrize("length", _XLA_LENGTHS)
def test_xla_hash_matches_host(length):
    rng = np.random.default_rng(length)
    blocks = rng.integers(0, 256, size=(5, length), dtype=np.uint8)
    want = highwayhash256_many(MAGIC_KEY, blocks)
    got = hash_blocks_device(MAGIC_KEY, blocks, mode="xla")
    assert np.array_equal(want, got)


def test_xla_hash_arbitrary_key():
    key = bytes(range(32))
    rng = np.random.default_rng(7)
    blocks = rng.integers(0, 256, size=(3, 333), dtype=np.uint8)
    want = highwayhash256_many(key, blocks)
    got = hash_blocks_device(key, blocks, mode="xla")
    assert np.array_equal(want, got)


# ---------------------------------------------------------------------------
# Pallas kernel (interpret off-TPU, compiled on TPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,length", [(7, 256), (130, 512), (1024, 2048)])
def test_pallas_hash_matches_host(s, length):
    if not _ON_TPU and (s, length) != (7, 256):
        pytest.skip("interpret mode: reduced sweep off-TPU")
    rng = np.random.default_rng(s)
    blocks = rng.integers(0, 256, size=(s, length), dtype=np.uint8)
    want = highwayhash256_many(MAGIC_KEY, blocks)
    got = np.asarray(hash_blocks_pallas(
        blocks, jnp.asarray(_init_smem_np(MAGIC_KEY)), interpret=not _ON_TPU))
    assert np.array_equal(want, got)


def _hash_words(words, pchunk):
    """Run the natural-layout kernel (interpret off-TPU) -> [S, 32] u8."""
    out = _hash_words_pallas(jnp.asarray(words),
                             jnp.asarray(_init_smem_np(MAGIC_KEY)),
                             pchunk=pchunk, interpret=not _ON_TPU)
    return np.asarray(jax.lax.bitcast_convert_type(out, jnp.uint8)) \
        .reshape(out.shape[0], 32)


@pytest.mark.parametrize("shape,pchunk", [
    ((130, 512 // 4), 16),        # 2-D fast path, stream padding
    ((10, 8, 4096 // 4), 16),     # 3-D fast path (no reshape), padding
    ((5, 4, 1024 // 4), 16),      # 3-D, X=4 (parity-shaped), padding
])
def test_hh_kernel_nt_matches_host(shape, pchunk):
    """The transpose-fused natural-layout kernel (_hh_kernel_nt), both
    2-D and 3-D block-spec variants, byte-identical to the host hash in
    interpret mode — a TPU-only regression here must fail off-TPU too."""
    rng = np.random.default_rng(sum(shape))
    words = rng.integers(0, 2 ** 32, size=shape, dtype=np.uint32)
    blocks = words.reshape(-1, shape[-1]).view(np.uint8)
    want = highwayhash256_many(MAGIC_KEY, blocks)
    got = _hash_words(words, pchunk)
    assert np.array_equal(want, got)


# ---------------------------------------------------------------------------
# Fused framer vs the host bitrot layer
# ---------------------------------------------------------------------------

def _host_framed(data, k, m):
    """Reference framing: host encode + frame_shards_batch per block."""
    n = k + m
    b, _, l = data.shape
    e = Erasure(k, m, k * l)
    files = [bytearray() for _ in range(n)]
    for bi in range(b):
        shards = e.encode_data(data[bi].reshape(-1).tobytes())
        for i in range(n):
            blk = np.asarray(shards[i])
            files[i] += bitrot.hash_block(bitrot.DEFAULT_ALGORITHM, blk)
            files[i] += blk.tobytes()
    return [bytes(f) for f in files]


_FRAMER_CONFIGS = [(4, 2, 3, 512), (8, 4, 2, 1024)] if _ON_TPU \
    else [(4, 2, 3, 512)]


def _join_pieces(row) -> bytes:
    """row = per-block (digest, block) piece tuples -> the framed file."""
    return b"".join(bytes(p) for pieces in row for p in pieces)


@pytest.mark.parametrize("k,m,b,l", _FRAMER_CONFIGS)
def test_framer_matches_host_bitrot(k, m, b, l):
    rng = np.random.default_rng(k * m)
    data = rng.integers(0, 256, size=(b, k, l), dtype=np.uint8)
    framer = make_encode_framer(gf256.parity_matrix(k, m))
    rows = framer(data)
    want = _host_framed(data, k, m)
    assert len(rows) == k + m
    for i in range(k + m):
        assert len(rows[i]) == b
        assert _join_pieces(rows[i]) == want[i], f"drive {i} differs"


@pytest.mark.skipif(not _ON_TPU, reason="compiled u32 pipeline needs TPU")
def test_framer_u32_pipeline_on_tpu():
    """The full u32 Pallas pipeline (encode32 + hash) on real hardware,
    eligible shape, including stream padding."""
    k, m = 8, 4
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(10, k, 4096), dtype=np.uint8)
    framer = make_encode_framer(gf256.parity_matrix(k, m))
    rows = framer(data)
    want = _host_framed(data, k, m)
    for i in range(k + m):
        assert _join_pieces(rows[i]) == want[i], f"drive {i} differs"


def test_framed_digests_device_matches_host():
    """Read-path device digests of framed shard windows == host hashes
    (interpret off-TPU). Frame layout: `digest || block` per row."""
    from minio_tpu.ops.hh_device import framed_digests_device
    shard_size = 1024
    rng = np.random.default_rng(21)
    blobs, want = [], []
    for nb in (3, 5):
        blocks = rng.integers(0, 256, size=(nb, shard_size), dtype=np.uint8)
        digs = highwayhash256_many(MAGIC_KEY, blocks)
        framed = np.concatenate([digs, blocks], axis=1)   # [nb, 32+ss]
        blobs.append(np.ascontiguousarray(framed).view(np.uint32))
        want.append(digs)
    got = framed_digests_device(blobs, interpret=not _ON_TPU)
    assert np.array_equal(got, np.concatenate(want, axis=0))


def test_framed_digests_device_chunked(monkeypatch):
    """The whole-chunk dispatch path and its output-offset bookkeeping
    (framed_digests_device splits blobs into _FRAMED_CHUNK-row device
    calls + one padded remainder): shrink the chunk constants so tiny
    interpret-mode shapes exercise chunk slicing, multi-chunk blobs, and
    chunk/remainder mixing."""
    from minio_tpu.ops import hh_device
    monkeypatch.setattr(hh_device, "_FRAMED_CHUNK", 4)
    monkeypatch.setattr(hh_device, "_FRAMED_PAD", 2)
    shard_size = 1024
    rng = np.random.default_rng(33)
    blobs, want = [], []
    for nb in (9, 4, 3):    # 2 chunks + rem 1; 1 chunk exactly; rem only
        blocks = rng.integers(0, 256, size=(nb, shard_size), dtype=np.uint8)
        digs = highwayhash256_many(MAGIC_KEY, blocks)
        framed = np.ascontiguousarray(
            np.concatenate([digs, blocks], axis=1))
        blobs.append(framed.view(np.uint32))
        want.append(digs)
    got = hh_device.framed_digests_device(blobs, interpret=not _ON_TPU)
    assert np.array_equal(got, np.concatenate(want, axis=0))

"""Bitrot protection: algorithms, golden self-test, streaming shard format.

Mirrors the reference's bitrot layer (cmd/bitrot.go): four algorithms
(SHA256, BLAKE2b-512, HighwayHash-256 whole-file, HighwayHash-256S
streamed), the keyed-HighwayHash default, and the streaming shard-file
framing `hash || shard_block` repeated per erasure block
(cmd/bitrot-streaming.go:44-75). The self-test reproduces the
reference's boot gate byte for byte (cmd/bitrot.go:224-255) — a mismatch
means we would silently corrupt data, so callers treat it as fatal.

The HighwayHash core is ours (minio_tpu/utils/highwayhash.py, vectorized
across shard streams); SHA-256 / BLAKE2b come from hashlib (OpenSSL),
exactly as the reference takes them from crypto libraries.
"""

from __future__ import annotations

import hashlib
from typing import Callable

import numpy as np

from minio_tpu.utils.highwayhash import (MAGIC_KEY, highwayhash256,
                                         highwayhash256_many)

# Algorithm names follow the reference's wire/disk identifiers
# (cmd/bitrot.go:39-44) so xl.meta stays interoperable in spirit.
SHA256 = "sha256"
BLAKE2B512 = "blake2b"
HIGHWAYHASH256 = "highwayhash256"
HIGHWAYHASH256S = "highwayhash256S"

DEFAULT_ALGORITHM = HIGHWAYHASH256S  # reference: cmd/bitrot.go:105-110

_ALGORITHMS: dict[str, tuple[int, Callable[[bytes], bytes]]] = {
    SHA256: (32, lambda data: hashlib.sha256(data).digest()),
    BLAKE2B512: (64, lambda data: hashlib.blake2b(data, digest_size=64).digest()),
    HIGHWAYHASH256: (32, lambda data: highwayhash256(MAGIC_KEY, data)),
    HIGHWAYHASH256S: (32, lambda data: highwayhash256(MAGIC_KEY, data)),
}

# hash.Hash.BlockSize() of each algorithm in the reference's Go stdlib
# sense — only used to reproduce the self-test message schedule.
_SELFTEST_BLOCKSIZE = {SHA256: 64, BLAKE2B512: 128,
                       HIGHWAYHASH256: 32, HIGHWAYHASH256S: 32}

# Golden digests from the reference's bitrotSelfTest (cmd/bitrot.go:225-230).
_GOLDEN = {
    SHA256: "a7677ff19e0182e4d52e3a3db727804abc82a5818749336369552e54b838b004",
    BLAKE2B512: ("e519b7d84b1c3c917985f544773a35cf265dcab10948be3550320d156bab6121"
                 "24a5ae2ae5a8c73c0eea360f68b0e28136f26e858756dbfe7375a7389f26c669"),
    HIGHWAYHASH256: "39c0407ed3f01b18d22c85db4aeff11e060ca5f43131b0126731ca197cd42313",
    HIGHWAYHASH256S: "39c0407ed3f01b18d22c85db4aeff11e060ca5f43131b0126731ca197cd42313",
}


def available(algorithm: str) -> bool:
    return algorithm in _ALGORITHMS


def digest_size(algorithm: str) -> int:
    return _ALGORITHMS[algorithm][0]


def hash_block(algorithm: str, data: bytes | np.ndarray) -> bytes:
    """One-shot digest of a shard block."""
    if isinstance(data, np.ndarray):
        data = data.tobytes()
    return _ALGORITHMS[algorithm][1](data)


def hash_blocks_many(algorithm: str, blocks: np.ndarray) -> np.ndarray:
    """Digest S equal-length shard blocks: uint8 [S, L] -> uint8 [S, size].

    HighwayHash uses the vectorized lockstep core (the bitrot hot path);
    the rare non-default algorithms loop per stream.
    """
    if algorithm in (HIGHWAYHASH256, HIGHWAYHASH256S):
        return highwayhash256_many(MAGIC_KEY, blocks)
    size = digest_size(algorithm)
    out = np.empty((blocks.shape[0], size), dtype=np.uint8)
    for i in range(blocks.shape[0]):
        out[i] = np.frombuffer(hash_block(algorithm, blocks[i]), dtype=np.uint8)
    return out


def shard_file_size(size: int, shard_size: int, algorithm: str = DEFAULT_ALGORITHM) -> int:
    """On-disk size of a bitrot-framed shard file (reference:
    bitrotShardFileSize, cmd/bitrot.go:156-161): one digest per shard
    block plus the data itself; whole-file algorithms store bare data."""
    if algorithm != HIGHWAYHASH256S:
        return size
    if size < 0:
        return -1
    from minio_tpu.erasure.codec import ceil_frac
    return ceil_frac(size, shard_size) * digest_size(algorithm) + size


def frame_shard(shard: np.ndarray, shard_size: int,
                algorithm: str = DEFAULT_ALGORITHM) -> bytes:
    """Frame one shard file: `digest || block` per shard_size block
    (reference: streamingBitrotWriter.Write, cmd/bitrot-streaming.go:44-75)."""
    shard = np.ascontiguousarray(shard, dtype=np.uint8)
    n = shard.shape[0]
    hsize = digest_size(algorithm)
    out = bytearray()
    for off in range(0, n, shard_size):
        block = shard[off:off + shard_size]
        out += hash_block(algorithm, block)
        out += block.tobytes()
    return bytes(out)


def frame_shards_batch(shards: np.ndarray, shard_size: int,
                       algorithm: str = DEFAULT_ALGORITHM) -> list[bytes]:
    """Frame all n shards of one object at once: uint8 [n, L] -> n files.

    All full blocks across all shards hash in ONE vectorized lockstep pass
    (n * n_blocks streams), the ragged tail in a second — the host-side
    shape of the reference's per-shard-block hashing, batched.
    """
    shards = np.ascontiguousarray(shards, dtype=np.uint8)
    n, length = shards.shape
    if length == 0:
        return [b""] * n
    full = length // shard_size
    tail = length - full * shard_size
    digests = np.zeros((n, full + (1 if tail else 0), digest_size(algorithm)),
                       dtype=np.uint8)
    if full:
        blocks = shards[:, :full * shard_size].reshape(n, full, shard_size)
        digests[:, :full] = hash_blocks_many(
            algorithm, blocks.reshape(n * full, shard_size)
        ).reshape(n, full, -1)
    if tail:
        digests[:, full] = hash_blocks_many(algorithm, shards[:, full * shard_size:])
    out = []
    for i in range(n):
        buf = bytearray()
        for b in range(full):
            buf += digests[i, b].tobytes()
            buf += shards[i, b * shard_size:(b + 1) * shard_size].tobytes()
        if tail:
            buf += digests[i, full].tobytes()
            buf += shards[i, full * shard_size:].tobytes()
        out.append(bytes(buf))
    return out


class BitrotError(Exception):
    """Stored digest does not match data (errFileCorrupt analogue)."""


class FramedShardReader:
    """Random-access verified reads from a bitrot-framed shard blob.

    The erasure decode path asks for whole shard blocks by index; every
    read re-hashes the block and compares against the stored digest
    (reference: streamingBitrotReader.ReadAt, cmd/bitrot-streaming.go:161-200).
    """

    def __init__(self, blob: bytes, shard_size: int, data_size: int,
                 algorithm: str = DEFAULT_ALGORITHM):
        self.blob = blob
        self.shard_size = shard_size
        self.data_size = data_size  # un-framed shard length
        self.algorithm = algorithm
        self.hsize = digest_size(algorithm)
        if algorithm == HIGHWAYHASH256S and \
                len(blob) != shard_file_size(data_size, shard_size, algorithm):
            raise BitrotError("framed shard file has wrong size")

    def block(self, index: int) -> np.ndarray:
        """Verified shard block `index` (uint8 array)."""
        start = index * self.shard_size
        if start >= self.data_size:
            raise BitrotError("block index out of range")
        blen = min(self.shard_size, self.data_size - start)
        off = index * (self.hsize + self.shard_size)
        want = self.blob[off:off + self.hsize]
        data = self.blob[off + self.hsize:off + self.hsize + blen]
        if len(want) < self.hsize or len(data) < blen:
            raise BitrotError("short framed shard read")
        if hash_block(self.algorithm, data) != bytes(want):
            raise BitrotError("bitrot detected")
        return np.frombuffer(data, dtype=np.uint8)


def verify_framed_shard(blob: bytes, shard_size: int, data_size: int,
                        algorithm: str = DEFAULT_ALGORITHM) -> None:
    """Full-file verification (reference: bitrotVerify, cmd/bitrot.go:164-215)."""
    r = FramedShardReader(blob, shard_size, data_size, algorithm)
    n_blocks = (data_size + shard_size - 1) // shard_size
    for i in range(n_blocks):
        r.block(i)


def read_framed_blocks_many(blobs, shard_size: int, data_size: int,
                            algorithm: str = DEFAULT_ALGORITHM,
                            device: bool = False):
    """Batched verified reads of same-shape framed shard blobs.

    blobs: sequence of bytes-like or None (a missing shard). Returns a
    list with, per blob, the verified un-framed data (uint8 [data_size])
    or None if the entry was None, malformed, or failed digest
    verification. This is the GET/heal hot path: instead of the
    reference's per-block ReadAt hashing (cmd/bitrot-streaming.go:
    161-200), ALL full blocks across all shards hash in one batch — on
    the TPU (ops/hh_device.framed_digests_device) when `device` is set
    and the batch is big enough, else in the vectorized lockstep host
    core. Ragged tail blocks hash per blob.
    """
    n_items = len(blobs)
    hsize = digest_size(algorithm)
    frame = hsize + shard_size
    nb = (data_size + shard_size - 1) // shard_size
    if nb == 0:
        return [np.zeros(0, dtype=np.uint8) if b is not None else None
                for b in blobs]
    tail = data_size - (nb - 1) * shard_size
    full = nb if tail == shard_size else nb - 1
    if tail == shard_size:
        tail = 0
    # Exact framed geometry for ANY algorithm (one digest per block) —
    # a truncated or padded blob must demote to a missing shard here,
    # never raise out of the batch.
    expect = full * frame + ((hsize + tail) if tail else 0)

    arrs: list = [None] * n_items
    for i, blob in enumerate(blobs):
        if blob is None or len(blob) != expect:
            continue
        arrs[i] = np.frombuffer(blob, dtype=np.uint8)
    oks = [i for i in range(n_items) if arrs[i] is not None]
    if not oks:
        return [None] * n_items

    bad = set()
    if full:
        wants = {i: arrs[i][:full * frame].reshape(full, frame)[:, :hsize]
                 for i in oks}
        blockv = {i: arrs[i][:full * frame].reshape(full, frame)[:, hsize:]
                  for i in oks}
        use_dev = (device and algorithm == HIGHWAYHASH256S
                   and frame % 4 == 0)
        got_dev = None
        if use_dev:
            from minio_tpu.ops import hh_device
            if hh_device.framed_digests_eligible(full * len(oks),
                                                 shard_size):
                u32 = [arrs[i][:full * frame].view(np.uint32)
                       .reshape(full, frame // 4) for i in oks]
                try:
                    got_dev = hh_device.framed_digests_device(u32) \
                        .reshape(len(oks), full, hsize)
                except Exception as e:  # noqa: BLE001 - device trouble
                    # is not corruption: counted and logged, then host
                    # hashing — unless the device was asked for.
                    from minio_tpu.ops import device as device_mod
                    device_mod.record_fault("framed_digests", e)
                    if device_mod.required():
                        raise
                    got_dev = None
        if got_dev is not None:
            for j, i in enumerate(oks):
                if not np.array_equal(got_dev[j], wants[i]):
                    bad.add(i)
        else:
            # One vectorized lockstep pass over ALL shards' full blocks.
            stacked = np.concatenate([blockv[i] for i in oks]) \
                if len(oks) > 1 else np.ascontiguousarray(blockv[oks[0]])
            got = hash_blocks_many(algorithm, stacked) \
                .reshape(len(oks), full, hsize)
            for j, i in enumerate(oks):
                if not np.array_equal(got[j], wants[i]):
                    bad.add(i)
    if tail:
        off = full * frame
        for i in oks:
            if i in bad:
                continue
            # Exact blob length was already enforced above, so the tail
            # frame is complete — only the digest can disagree.
            want = arrs[i][off:off + hsize].tobytes()
            data = arrs[i][off + hsize:off + hsize + tail]
            if hash_block(algorithm, data) != want:
                bad.add(i)

    out: list = [None] * n_items
    for i in oks:
        if i in bad:
            continue
        data = np.empty(data_size, dtype=np.uint8)
        if full:
            data[:full * shard_size].reshape(full, shard_size)[:] = \
                arrs[i][:full * frame].reshape(full, frame)[:, hsize:]
        if tail:
            off = full * frame
            data[full * shard_size:] = arrs[i][off + hsize:off + hsize + tail]
        out[i] = data
    return out


class SelfTestError(Exception):
    """A bitrot digest differs from the reference. Fatal at boot."""


def bitrot_self_test() -> None:
    """Reproduces the reference's boot-time golden check (cmd/bitrot.go:232-254).

    Schedule: starting from an empty message, repeat size*blocksize/size
    times: digest the message, append the digest to the message. The final
    digest must equal the golden value.
    """
    for algorithm, want_hex in _GOLDEN.items():
        size = digest_size(algorithm)
        rounds = _SELFTEST_BLOCKSIZE[algorithm]
        msg = b""
        sum_ = b""
        for _ in range(0, size * rounds, size):
            sum_ = hash_block(algorithm, msg)
            msg += sum_
        if sum_.hex() != want_hex:
            raise SelfTestError(
                f"bitrot self-test {algorithm}: got {sum_.hex()}, want {want_hex}")

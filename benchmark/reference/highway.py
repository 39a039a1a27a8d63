"""Plain HighwayHash-256 (Google's public algorithm), keyed with the
constant upstream MinIO uses for bitrot protection.

Written from the public portable C description: four 4-lane uint64
state vectors, one 32-byte packet per update, ten permute rounds and a
modular reduction to finish. Many independent messages of ONE length
hash side by side (numpy over a leading axis) because every shard
block of an object is hashed on its own; the recurrence inside one
message stays sequential. Lengths must be multiples of 32 (every shard
block of the benchmark's objects is); anything else raises. No code
shared with `minio_tpu/`.
"""

from __future__ import annotations

import numpy as np

U = np.uint64
# upstream cmd/bitrot.go magicHighwayHash256Key: HighwayHash-256 of the
# first 100 decimals of pi under a zero key.
BITROT_KEY = bytes.fromhex(
    "4be734fa8e238acd263e83e6bb968552040f935da39f441497e09d1322de36a0")
INIT0 = np.array([0xDBE6D5D5FE4CCE2F, 0xA4093822299F31D0,
                  0x13198A2E03707344, 0x243F6A8885A308D3], dtype=U)
INIT1 = np.array([0x3BD39E10CB0EF593, 0xC0ACF169B5F18A8C,
                  0xBE5466CF34E90C6C, 0x452821E638D01377], dtype=U)
LOW32 = U(0xFFFFFFFF)


def _rot32(x):
    return (x >> U(32)) | (x << U(32))


def _zipper(v1, v0):
    """(add0, add1) of ZipperMergeAndAdd for lane pair (v1, v0)."""
    m = U
    add0 = ((((v0 & m(0xFF000000)) | (v1 & m(0xFF00000000))) >> m(24))
            | (((v0 & m(0xFF0000000000)) | (v1 & m(0xFF000000000000)))
               >> m(16))
            | (v0 & m(0xFF0000)) | ((v0 & m(0xFF00)) << m(32))
            | ((v1 & m(0xFF00000000000000)) >> m(8)) | (v0 << m(56)))
    add1 = ((((v1 & m(0xFF000000)) | (v0 & m(0xFF00000000))) >> m(24))
            | (v1 & m(0xFF0000)) | ((v1 & m(0xFF0000000000)) >> m(16))
            | ((v1 & m(0xFF00)) << m(24))
            | ((v0 & m(0xFF000000000000)) >> m(8))
            | ((v1 & m(0xFF)) << m(48)) | (v0 & m(0xFF00000000000000)))
    return add0, add1


class _State:
    def __init__(self, key: bytes, n: int):
        lanes = np.frombuffer(key, dtype="<u8").astype(U)
        self.mul0 = np.tile(INIT0, (n, 1))
        self.mul1 = np.tile(INIT1, (n, 1))
        self.v0 = self.mul0 ^ lanes
        self.v1 = self.mul1 ^ _rot32(lanes)

    def update(self, p):
        """p: uint64 [n, 4], one packet per message."""
        v0, v1, mul0, mul1 = self.v0, self.v1, self.mul0, self.mul1
        v1 += mul0 + p
        mul0 ^= (v1 & LOW32) * (v0 >> U(32))
        v0 += mul1
        mul1 ^= (v0 & LOW32) * (v1 >> U(32))
        for hi, lo in ((1, 0), (3, 2)):
            a0, a1 = _zipper(v1[:, hi], v1[:, lo])
            v0[:, lo] += a0
            v0[:, hi] += a1
        for hi, lo in ((1, 0), (3, 2)):
            a0, a1 = _zipper(v0[:, hi], v0[:, lo])
            v1[:, lo] += a0
            v1[:, hi] += a1

    def finish(self) -> np.ndarray:
        for _ in range(10):
            self.update(_rot32(self.v0[:, [2, 3, 0, 1]]))
        out = np.empty((self.v0.shape[0], 4), dtype=U)
        for base in (0, 2):
            a0 = self.v0[:, base] + self.mul0[:, base]
            a1 = self.v0[:, base + 1] + self.mul0[:, base + 1]
            a2 = self.v1[:, base] + self.mul1[:, base]
            a3 = (self.v1[:, base + 1] + self.mul1[:, base + 1]) \
                & U(0x3FFFFFFFFFFFFFFF)
            out[:, base + 1] = a1 ^ ((a3 << U(1)) | (a2 >> U(63))) \
                ^ ((a3 << U(2)) | (a2 >> U(62)))
            out[:, base] = a0 ^ (a2 << U(1)) ^ (a2 << U(2))
        return out


def hash256_many(messages: np.ndarray, key: bytes = BITROT_KEY) -> np.ndarray:
    """messages: uint8 [n, length], length % 32 == 0 -> uint8 [n, 32]."""
    n, length = messages.shape
    if length % 32:
        raise ValueError(f"message length {length} is not a multiple of 32")
    packets = np.ascontiguousarray(messages).view("<u8").reshape(
        n, length // 32, 4)
    st = _State(key, n)
    with np.errstate(over="ignore"):
        for i in range(length // 32):
            st.update(packets[:, i, :].astype(U))
        digest = st.finish()
    return digest.astype("<u8").view(np.uint8).reshape(n, 32)


def hash256(message: bytes, key: bytes = BITROT_KEY) -> bytes:
    return hash256_many(np.frombuffer(message, dtype=np.uint8)
                        .reshape(1, -1), key)[0].tobytes()

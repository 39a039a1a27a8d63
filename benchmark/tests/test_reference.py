"""The plain reference against upstream's boot goldens (copied here as
data: cmd/erasure-coding.go erasureSelfTest, cmd/bitrot.go
bitrotSelfTest), so the yardstick stands without the program."""

import numpy as np
import pytest

from benchmark.reference import gf_rs, highway

M64 = (1 << 64) - 1
P1, P2, P3, P4, P5 = (11400714785074694791, 14029467366897019727,
                      1609587929392839161, 9650029242287828579,
                      2870177450012600261)


def _rotl(x, r):
    return ((x << r) | (x >> (64 - r))) & M64


def _round(acc, inp):
    return (_rotl((acc + inp * P2) & M64, 31) * P1) & M64


def xxh64(data: bytes, seed: int = 0) -> int:
    n, i = len(data), 0
    u64 = lambda o: int.from_bytes(data[o:o + 8], "little")  # noqa: E731
    if n >= 32:
        v = [(seed + P1 + P2) & M64, (seed + P2) & M64, seed,
             (seed - P1) & M64]
        while i <= n - 32:
            for j in range(4):
                v[j] = _round(v[j], u64(i + 8 * j))
            i += 32
        h = (_rotl(v[0], 1) + _rotl(v[1], 7) + _rotl(v[2], 12)
             + _rotl(v[3], 18)) & M64
        for x in v:
            h = ((h ^ _round(0, x)) * P1 + P4) & M64
    else:
        h = (seed + P5) & M64
    h = (h + n) & M64
    while i + 8 <= n:
        h = (_rotl(h ^ _round(0, u64(i)), 27) * P1 + P4) & M64
        i += 8
    if i + 4 <= n:
        h = (_rotl(h ^ (int.from_bytes(data[i:i + 4], "little") * P1) & M64,
                   23) * P2 + P3) & M64
        i += 4
    while i < n:
        h = (_rotl(h ^ (data[i] * P5) & M64, 11) * P1) & M64
        i += 1
    h ^= h >> 33
    h = (h * P2) & M64
    h ^= h >> 29
    h = (h * P3) & M64
    return h ^ (h >> 32)


# cmd/erasure-coding.go:163 — xxhash64 of (index byte || shard) over
# all k+m shards of the 256-byte staircase vector
GOLDEN_RS = {
    (2, 2): 0x23FB21BE2496F5D3, (3, 3): 0x672F6F242B227B21,
    (4, 2): 0x62B9552945504FEF, (4, 4): 0x09A07581DCD03DA8,
    (5, 3): 0x7AD9161ACBB4C325, (6, 6): 0x4B79056484883E4C,
    (8, 4): 0x03BA5E9B41BF07F0, (8, 7): 0x50748E0099D657E8,
    (10, 5): 0x4383E58A086CC1AC, (12, 3): 0xD5CE58368AE90B13,
    (14, 1): 0x78A28BBAEC57996E,
}
# cmd/bitrot.go:225 — HighwayHash256 and HighwayHash256S
GOLDEN_HH = "39c0407ed3f01b18d22c85db4aeff11e060ca5f43131b0126731ca197cd42313"


def test_xxh64_known_values():
    assert xxh64(b"") == 0xEF46DB3751D8E999
    assert xxh64(b"a") == 0xD24EC4F1A98C6E5B
    assert xxh64(b"abc") == 0x44BC2CF5AD770999


@pytest.mark.parametrize("k,m", sorted(GOLDEN_RS))
def test_rs_matches_upstream_golden(k, m):
    data = bytes(range(256))
    piece = -(-len(data) // k)
    padded = data + bytes(k * piece - len(data))
    shards = np.frombuffer(padded, dtype=np.uint8).reshape(k, piece)
    parity = gf_rs.encode(shards, k, m)
    buf = bytearray()
    for i, shard in enumerate(np.concatenate([shards, parity])):
        buf.append(i)
        buf += shard.tobytes()
    assert xxh64(bytes(buf)) == GOLDEN_RS[(k, m)]


def test_rs_matrix_is_systematic_and_control_is_not():
    mat = gf_rs.coding_matrix(8, 4)
    assert mat[:8] == [[int(i == j) for j in range(8)] for i in range(8)]
    assert gf_rs.raw_vandermonde_parity(8, 4) != mat[8:]
    inv = gf_rs.invert(gf_rs.vandermonde(4, 2)[:4])
    assert gf_rs.matmul(gf_rs.vandermonde(4, 2)[:4], inv) == \
        [[int(i == j) for j in range(4)] for i in range(4)]


def test_highwayhash_matches_upstream_golden():
    # bitrotSelfTest: digest the message, append the digest, 32 times
    msg = np.zeros((1, 0), dtype=np.uint8)
    digest = b""
    for _ in range(32):
        digest = highway.hash256_many(msg)[0].tobytes()
        msg = np.concatenate(
            [msg, np.frombuffer(digest, dtype=np.uint8).reshape(1, 32)],
            axis=1)
    assert digest.hex() == GOLDEN_HH


def test_highwayhash_many_equals_one_by_one_and_refuses_ragged():
    rng = np.random.default_rng(3)
    msgs = rng.integers(0, 256, (5, 256), dtype=np.uint8)
    many = highway.hash256_many(msgs)
    for i in range(5):
        assert many[i].tobytes() == highway.hash256(msgs[i].tobytes())
    assert len({many[i].tobytes() for i in range(5)}) == 5
    with pytest.raises(ValueError):
        highway.hash256(b"x" * 33)

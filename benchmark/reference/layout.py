"""Which shard of an object a drive holds: the placement rule, plain.

A key's n shards are spread over the set's n drives by a rotation that
depends on the key alone: with start = crc32("<bucket>/<key>") % n,
drive d (1-based) holds shard number 1 + (start + d - 1) % n (1-based;
shards 1..k are data, the rest parity). It is upstream's `hashOrder`
spread (cmd/erasure-metadata-utils.go) as the program under test
follows it; `benchmark/tests/test_degraded_slow.py` holds it against
the shard files the program wrote. Imports nothing of the program.

Used to count the work of a degraded read (which of a key's data
shards lay on the dead drives: `layers/kernel.reconstruct_roofline.py`)
and, in the tests, to say which loss patterns a mix's keys fall into.
"""

from __future__ import annotations

import zlib


def hash_order(name: str, n: int) -> list[int]:
    """[shard number (1-based) held by drive 1, drive 2, ... drive n]."""
    start = zlib.crc32(name.encode()) % n
    return [1 + (start + i) % n for i in range(n)]


def lost_shards(bucket: str, key: str, n: int, dead: list[int]) -> tuple:
    """The shard indexes (0-based, ascending) of `key` that lay on the
    `dead` drives (1-based drive numbers)."""
    order = hash_order(f"{bucket}/{key}", n)
    return tuple(sorted(order[d - 1] - 1 for d in dead))


def lost_data_shards(bucket: str, key: str, n: int, k: int,
                     dead: list[int]) -> int:
    """How many of `key`'s k data shards are gone with the dead
    drives: what a read has to rebuild."""
    return sum(s < k for s in lost_shards(bucket, key, n, dead))

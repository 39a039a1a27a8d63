"""The one general traffic generator. A mix is a data file
(`benchmark/traffic/<mix>.json`); this module turns (mix, seed, worker)
into the worker's operations and says which key and which bytes each
one carries. It knows no mix by name.

Every answer has exactly one right value, whatever the interleaving:

  * preloaded keys (`pre/NNNN`) are written in set-up, read by every
    worker (or, where the mix says `read` "own_in_order", each by the
    worker that wrote it), and never overwritten or deleted;
  * every other key (`wWW/NNNNNN`) belongs to ONE worker, which sends
    one operation at a time: a key enters that worker's live set when
    its PUT is acknowledged, leaves it before its DELETE is sent, and
    is never written twice;
  * a worker's operations come in cycles that hold each operation as
    often as the mix's `cycle` says, shuffled by (seed, worker, cycle
    number) — every seed sends the same work in another order. A
    DELETE drawn while the worker owns no live key trades places with
    the cycle's next PUT.

Bodies: `bodies` distinct seeded byte strings of the mix's `size`; an
object's body is one of them with its last 32 bytes replaced by a stamp
of (seed, key), so every object differs and both its SHA-256 (for the
signature) and its MD5 (the ETag the server must answer) cost O(1) per
object from a saved hash state.
"""

from __future__ import annotations

import hashlib
import json
import os
import random

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OPS = ("PUT", "GET", "STAT", "DELETE")
BUCKET = "bench"
STAMP = 32


def load_mix(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    unknown = set(mix["cycle"]) - set(OPS)
    if unknown:
        raise ValueError(f"{path}: unknown operations {sorted(unknown)}")
    if (mix["cycle"].get("GET") or mix["cycle"].get("STAT")) \
            and not mix["preload"]:
        raise ValueError(f"{path}: a mix that reads needs preloaded keys")
    if mix.get("read") == "own_in_order" and mix["preload"] < mix["workers"]:
        raise ValueError(f"{path}: own_in_order needs a preloaded key for "
                         "every worker")
    return mix


def cycle_ops(mix: dict, seed: int, worker: int, n: int) -> list[str]:
    ops = [op for op in OPS for _ in range(mix["cycle"].get(op, 0))]
    random.Random(f"{seed}/{worker}/{n}").shuffle(ops)
    return ops


class Schedule:
    """A worker's operations, one at a time: `next_op(live)` where
    `live` is how many keys the worker owns right now."""

    def __init__(self, mix: dict, seed: int, worker: int):
        self.mix, self.seed, self.worker = mix, seed, worker
        self._cycle: list[str] = []
        self._n = 0

    def next_op(self, live: int) -> str:
        if not self._cycle:
            self._cycle = cycle_ops(self.mix, self.seed, self.worker,
                                    self._n)
            self._n += 1
        op = self._cycle.pop(0)
        if op == "DELETE" and live == 0:
            if "PUT" in self._cycle:
                i = self._cycle.index("PUT")
                self._cycle[i] = "DELETE"
                return "PUT"
            # a mix that deletes more than it puts: the DELETE waits
            # for the next cycle's first PUT
            self._cycle = cycle_ops(self.mix, self.seed, self.worker,
                                    self._n) + ["DELETE"]
            self._n += 1
            return self.next_op(live)
        return op


def pre_key(i: int) -> str:
    return f"pre/{i:04d}"


def own_key(worker: int, n: int) -> str:
    return f"w{worker:02d}/{n:06d}"


def variant(key: str, bodies: int) -> int:
    return int(key.rsplit("/", 1)[1]) % bodies


def stamp(seed: int, key: str) -> bytes:
    return hashlib.sha256(f"{seed}/{key}".encode()).digest()


class Bodies:
    """The seeded body pool of one mix and what is known of each
    object before it is sent."""

    def __init__(self, seed: int, size: int, bodies: int):
        self.seed, self.size = seed, size
        self.pool = [np.random.default_rng([seed, 0xB0D1, b]).bytes(size)
                     for b in range(bodies)]
        self.heads = [memoryview(p)[:size - STAMP] for p in self.pool]
        self._sha = [hashlib.sha256(h) for h in self.heads]
        self._md5 = [hashlib.md5(h) for h in self.heads]

    def parts(self, key: str):
        """-> (head view, stamp, sha256 hex, etag) of `key`'s body."""
        v = variant(key, len(self.pool))
        st = stamp(self.seed, key)
        sha = self._sha[v].copy()
        sha.update(st)
        md5 = self._md5[v].copy()
        md5.update(st)
        return self.heads[v], st, sha.hexdigest(), md5.hexdigest()

    def body(self, key: str) -> bytes:
        head, st, _, _ = self.parts(key)
        return bytes(head) + st

#!/usr/bin/env python3
"""Synthetic namespace generator: fabricate N-object buckets directly on
the drives (xl.meta journals written straight to disk, no PUT path) so a
10M-object namespace builds in minutes instead of hours.

The high-cardinality listing tests (and any metadata-plane measurement)
need namespaces far past what put_object can build in a
test budget: a PUT pays erasure encode + staging + rename + fsync per
object (~1 ms floor), while a fabricated object is one makedirs + one
unsynced write of a ~400-byte journal. The journals are REAL — built by
the same msgpack layout `storage/meta.py` writes (magic + versions +
inline map, bitrot-framed inline payload with a true HighwayHash
digest), so every fabricated object HEADs, GETs and lists exactly like
a PUT object; only mtimes/etags are synthetic.

Profile (``mixed``) — shaped like production namespaces, with each
shape's pathology represented:

  kv    70%   kv/<aa>/<bb>/o<idx>      two-level 256-way fanout (the
                                       "many medium dirs" shape)
  deep  20%   deep/<a>/<b>/.../o<idx>  6-deep chains (prefix-descend
                                       cost)
  flat   9%   flat/o<idx>              one huge directory (listdir+sort
                                       pathology)
  ver    1%   ver/o<idx>               versioned churn: 5 versions per
                                       object, latest-first journal

Layout decisions ride the object INDEX (deterministic, seeded), so any
worker count produces the identical namespace and tests can predict key
names.

Usage:
  python scripts/namespace_gen.py --root /dev/shm/ns --objects 1000000 \
      [--drives 1] [--bucket ns] [--workers N] [--profile mixed]

As a library: `generate(root, objects, drives=1, ...)` returns a summary
dict (also printed as one JSON line by the CLI).
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUCKET = "ns"
# Version-id / data-dir style UUIDs, deterministic per index.
_HEX = "0123456789abcdef"


def _uuid_at(i: int, salt: int) -> str:
    h = f"{(i * 0x9e3779b97f4a7c15 + salt) & ((1 << 128) - 1):032x}"
    return f"{h[:8]}-{h[8:12]}-{h[12:16]}-{h[16:20]}-{h[20:32]}"


def key_at(i: int, objects: int, profile: str = "mixed") -> str:
    """Deterministic key for object index i (shared with tests)."""
    if profile == "flat":
        return f"flat/o{i:08d}"
    r = i % 100
    if r < 70:
        j = i
        return f"kv/{_HEX[(j >> 4) & 15]}{_HEX[j & 15]}/" \
               f"{_HEX[(j >> 12) & 15]}{_HEX[(j >> 8) & 15]}/o{i:08d}"
    if r < 90:
        j = i
        parts = [_HEX[(j >> (4 * d + 8)) & 7] for d in range(6)]
        return "deep/" + "/".join(parts) + f"/o{i:08d}"
    if r < 99:
        return f"flat/o{i:08d}"
    return f"ver/o{i:08d}"


def is_versioned(i: int) -> bool:
    return i % 100 == 99


def _build_blobs(drives: int, versions_mixed: bool):
    """Per-drive xl.meta payload templates.

    Returns (single_tmpl, ver_tmpl): callables (i) -> list of per-drive
    blob bytes. The inline payload and its bitrot digest are shared
    across all objects (identical payload => identical digest/etag, the
    dedup-friendly shape bench data takes); per-object fields (vid,
    mod-time, data-less journal entries) are packed fresh — msgpack of a
    ~10-key map is ~3 us, the file write dominates.
    """
    import msgpack
    import numpy as np

    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.object.erasure_object import hash_order
    from minio_tpu.storage.meta import MAGIC
    from minio_tpu.utils.highwayhash import MAGIC_KEY, highwayhash256

    payload = bytes(range(128))                      # 128 B inline body
    k, m = max(1, drives - drives // 2), drives // 2
    e = Erasure(k, m, 1 << 20)
    shards = e.encode_data(payload)                  # k data + m parity rows
    # Bitrot-framed shard per shard INDEX (one erasure block: the whole
    # payload fits in a single frame) — every object shares the payload,
    # so each drive's inline blob is one of these n precomputed frames.
    framed = [highwayhash256(MAGIC_KEY, bytes(s)) + bytes(np.asarray(s))
              for s in shards]
    etag = __import__("hashlib").md5(payload).hexdigest()
    base_ns = 1_700_000_000_000_000_000

    def ec_map(drive: int, dist) -> dict:
        return {"alg": "rs-vandermonde", "k": k, "m": m,
                "bs": 1 << 20, "idx": dist[drive], "dist": list(dist),
                "cks": []}

    def vmap(i: int, vid: str, mt: int, drive: int, dist) -> dict:
        return {
            "kind": 1, "vid": vid, "mt": mt, "ddir": "", "size": len(payload),
            "meta": {"etag": etag, "content-type": "application/octet-stream"},
            "parts": [{"n": 1, "s": len(payload), "as": len(payload),
                       "mt": 0, "etag": etag}],
            "ec": ec_map(drive, dist), "inline": True,
        }

    # 10M objects cannot afford a dict build + packb each (~100 us of
    # allocator churn per object): pre-pack one TEMPLATE blob per
    # (distribution rotation, drive) with sentinel mod-times/version-ids
    # whose msgpack encodings are fixed-width, record their byte
    # offsets, and emit each object as template-copy + struct patch.
    import struct

    SENT_MT = [(1 << 62) + 0x1234500 + v for v in range(5)]   # 0xcf + 8B
    SENT_VID = [f"ffffffff-ffff-4fff-8fff-fffffff1230{v}" for v in range(5)]

    def _mt_off(blob: bytes, v: int) -> int:
        off = blob.find(struct.pack(">BQ", 0xCF, SENT_MT[v]))
        assert off >= 0
        return off + 1

    def _vid_offs(blob: bytes, v: int) -> list[int]:
        # vid appears in the version map AND as the inline-map key.
        pat = SENT_VID[v].encode()
        offs, start = [], 0
        while True:
            off = blob.find(pat, start)
            if off < 0:
                return offs
            offs.append(off)
            start = off + 1

    # Templates are keyed by the EXACT distribution tuple hash_order
    # yields (one of `drives` rotations today) — the per-key lookup
    # calls hash_order itself, so the fabricated ec.dist/idx can never
    # drift from what the object layer computes for that key.
    single_tmpl: dict = {}   # dist -> [drive] -> (blob, mt_off)
    ver_tmpl: dict = {}      # dist -> [drive] -> (blob, mt_offs, vid_offs)
    for s in range(drives):
        # hash_order's contract: a rotation of [1..n]; enumerate every
        # start. _templates() looks rows up by hash_order's ACTUAL
        # output per key, so a changed spread fails loudly here
        # instead of fabricating mismatched layouts.
        dist = tuple(1 + (s + i) % drives for i in range(drives))
        srow, vrow = [], []
        for d in range(drives):
            blob = MAGIC + msgpack.packb(
                {"versions": [vmap(0, "null", SENT_MT[0], d, dist)],
                 "inline": {"null": framed[dist[d] - 1]}},
                use_bin_type=True)
            srow.append((blob, _mt_off(blob, 0)))
            versions = [vmap(0, SENT_VID[v], SENT_MT[v], d, dist)
                        for v in range(5)]
            vblob = MAGIC + msgpack.packb(
                {"versions": versions,
                 "inline": {SENT_VID[v]: framed[dist[d] - 1]
                            for v in range(5)}}, use_bin_type=True)
            vrow.append((vblob, [_mt_off(vblob, v) for v in range(5)],
                         [_vid_offs(vblob, v) for v in range(5)]))
        single_tmpl[dist] = srow
        ver_tmpl[dist] = vrow

    def _templates(kind: dict, key: str) -> list:
        dist = tuple(hash_order(f"{BUCKET}/{key}", drives))
        row = kind.get(dist)
        if row is None:      # hash_order spread changed: rebuild lazily
            raise KeyError(f"no template for distribution {dist}")
        return row

    def single(i: int, key: str) -> list[bytes]:
        row = _templates(single_tmpl, key)
        mt = base_ns + i * 1000
        out = []
        for d in range(drives):
            tmpl, off = row[d]
            b = bytearray(tmpl)
            struct.pack_into(">Q", b, off, mt)
            out.append(b)
        return out

    def ver(i: int, key: str) -> list[bytes]:
        row = _templates(ver_tmpl, key)
        vids = [_uuid_at(i, v).encode() for v in range(5)]
        out = []
        for d in range(drives):
            tmpl, mt_offs, vid_offs = row[d]
            b = bytearray(tmpl)
            for v in range(5):
                struct.pack_into(">Q", b, mt_offs[v],
                                 base_ns + i * 1000 + (4 - v))
                for off in vid_offs[v]:
                    b[off:off + 36] = vids[v]
            out.append(b)
        return out

    return single, ver


def _worker(root: str, drives: int, bucket: str, objects: int,
            profile: str, lo: int, hi: int, progress=None) -> int:
    single, ver = _build_blobs(drives, True)
    roots = [os.path.join(root, f"d{d}", bucket) for d in range(drives)]
    wrote = 0
    flags = os.O_WRONLY | os.O_CREAT | os.O_TRUNC
    for i in range(lo, hi):
        key = key_at(i, objects, profile)
        blobs = ver(i, key) if (profile == "mixed" and is_versioned(i)) \
            else single(i, key)
        for d in range(drives):
            # Syscall-lean commit: this loop runs tens of millions of
            # times, so probe nothing — mkdir optimistically, create
            # missing parents only on the miss.
            obj_dir = f"{roots[d]}/{key}"
            try:
                os.mkdir(obj_dir)
            except FileExistsError:
                pass
            except FileNotFoundError:
                os.makedirs(obj_dir, exist_ok=True)
            fd = os.open(f"{obj_dir}/xl.meta", flags, 0o644)
            os.write(fd, blobs[d])
            os.close(fd)
        wrote += 1
        if progress is not None and wrote % 200_000 == 0:
            progress(wrote)
    return wrote


def generate(root: str, objects: int, drives: int = 1, bucket: str = BUCKET,
             workers: int | None = None, profile: str = "mixed") -> dict:
    """Fabricate the namespace; idempotent over an existing root."""
    t0 = time.time()
    workers = workers or min(8, (os.cpu_count() or 1))
    for d in range(drives):
        os.makedirs(os.path.join(root, f"d{d}", ".mtpu.sys", "tmp"),
                    exist_ok=True)
        os.makedirs(os.path.join(root, f"d{d}", bucket), exist_ok=True)
    if workers <= 1 or objects < 50_000:
        _worker(root, drives, bucket, objects, profile, 0, objects)
    else:
        step = (objects + workers - 1) // workers
        procs = []
        for w in range(workers):
            lo, hi = w * step, min(objects, (w + 1) * step)
            if lo >= hi:
                continue
            p = multiprocessing.Process(
                target=_worker,
                args=(root, drives, bucket, objects, profile, lo, hi))
            p.start()
            procs.append(p)
        for p in procs:
            p.join()
            if p.exitcode:
                raise RuntimeError(f"namespace_gen worker rc={p.exitcode}")
    dt = time.time() - t0
    return {"root": root, "bucket": bucket, "objects": objects,
            "drives": drives, "profile": profile,
            "seconds": round(dt, 1),
            "objects_per_sec": round(objects / max(dt, 1e-9))}


def attach(root: str, drives: int = 1):
    """An ErasureSet over a generated root (1 drive => parity 0)."""
    from minio_tpu.object.erasure_object import ErasureSet
    from minio_tpu.storage.local import LocalStorage
    return ErasureSet([LocalStorage(os.path.join(root, f"d{d}"))
                       for d in range(drives)])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--objects", type=int, required=True)
    ap.add_argument("--drives", type=int, default=1)
    ap.add_argument("--bucket", default=BUCKET)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--profile", default="mixed",
                    choices=("mixed", "flat"))
    ap.add_argument("--self-test", action="store_true",
                    help="HEAD+GET+LIST a few fabricated objects through "
                         "the real object layer before reporting")
    args = ap.parse_args()
    summary = generate(args.root, args.objects, drives=args.drives,
                       bucket=args.bucket, workers=args.workers,
                       profile=args.profile)
    if args.self_test:
        es = attach(args.root, args.drives)
        probe = [0, 1, args.objects - 1]
        for i in probe:
            key = key_at(i, args.objects, args.profile)
            info = es.get_object_info(args.bucket, key)
            assert info.size == 128, (key, info.size)
            _, got = es.get_object(args.bucket, key)
            assert len(got) == 128, key
        page = es.list_objects(args.bucket, max_keys=10)
        assert page.objects, "empty first page"
        es.close()
        summary["self_test"] = "ok"
    print(json.dumps(summary))


if __name__ == "__main__":
    main()

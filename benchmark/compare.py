"""What decides `correct`, beyond the answers the generators judged as
they arrived: the bucket's listing against the acknowledged-minus-
deleted key set, and — because a healthy GET reads only the k data
shards — the shard files on the drives, parity and bitrot digests
included, against the plain reference (`benchmark/reference/`), read
after the server has stopped cleanly.

The store is held to what the configuration guarantees, not to more:
an object is durable when at least `write_quorum` drives each hold one
of its shard files, byte for byte the reference's; no drive may hold a
shard file of it that is anything else. Which drive holds which shard
is not assumed: a file is matched against all n reference files, and no
shard index may be met twice.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

from benchmark.reference import gf_rs, highway


def reference_shard_files(body: bytes, k: int, m: int, block: int,
                          parity_rows=None) -> list[bytes]:
    """The n files upstream's layout holds for `body`: each erasure
    block split into k pieces and encoded on its own; shard i's file is
    HighwayHash-256(piece) || piece, block after block."""
    if len(body) % block or block % k:
        raise ValueError("the reference handles whole erasure blocks only")
    blocks, piece = len(body) // block, block // k
    data = np.frombuffer(body, dtype=np.uint8).reshape(blocks, k, piece)
    flat = np.ascontiguousarray(data.transpose(1, 0, 2)).reshape(k, -1)
    parity = gf_rs.encode(flat, k, m, parity_rows).reshape(m, blocks, piece)
    shards = np.concatenate([flat.reshape(k, blocks, piece), parity])
    digests = highway.hash256_many(
        shards.reshape((k + m) * blocks, piece)).reshape(k + m, blocks, 32)
    framed = np.concatenate([digests, shards], axis=2)
    return [framed[i].tobytes() for i in range(k + m)]


def shard_files_on_disk(drive_root: str, drives: int, bucket: str,
                        key: str) -> dict[int, str]:
    """{drive number: path of the key's part.1 there}."""
    out = {}
    for d in range(1, drives + 1):
        hits = glob.glob(os.path.join(drive_root, f"d{d}", bucket, key,
                                      "*", "part.1"))
        if len(hits) > 1:
            raise ValueError(f"{key}: d{d} holds {len(hits)} part.1 files")
        if hits:
            out[d] = hits[0]
    return out


def check_object_on_disk(drive_root: str, cfg: dict, bucket: str, key: str,
                         body: bytes, parity_rows=None,
                         reference=None) -> dict:
    """-> {"right": drives holding a right shard, "wrong": files that
    are no shard of this object or a shard met twice}. `reference` is
    a configuration's own `reference_shard_files(body, cfg)`, where it
    brings one (benchmark/configs/<name>.py)."""
    k, m = cfg["data_shards"], cfg["parity_shards"]
    want = reference(body, cfg) if reference is not None else \
        reference_shard_files(body, k, m, cfg["erasure_block_bytes"],
                              parity_rows)
    seen: set[int] = set()
    right = wrong = 0
    for path in shard_files_on_disk(drive_root, cfg["drives"], bucket,
                                    key).values():
        with open(path, "rb") as f:
            got = f.read()
        idx = next((i for i, w in enumerate(want)
                    if i not in seen and got == w), None)
        if idx is None:
            wrong += 1
        else:
            seen.add(idx)
            right += 1
    return {"right": right, "wrong": wrong}


_CONTENTS = re.compile(
    r"<Contents><Key>([^<]+)</Key>.*?<ETag>(?:&quot;|\")?([0-9a-f]+)"
    r"(?:&quot;|\")?</ETag><Size>(\d+)</Size>", re.S)


def list_bucket(cli, bucket: str) -> dict[str, tuple[str, int]]:
    """{key: (etag, size)} over every page of ListObjectsV2."""
    out, token = {}, None
    while True:
        q = {"list-type": "2", "max-keys": "1000"}
        if token:
            q["continuation-token"] = token
        st, _, data = cli.request("GET", f"/{bucket}", query=q)
        if st != 200:
            raise RuntimeError(f"ListObjectsV2: HTTP {st}")
        text = bytes(data).decode()
        for key, etag, size in _CONTENTS.findall(text):
            out[key] = (etag, int(size))
        if "<IsTruncated>true</IsTruncated>" not in text:
            return out
        token = re.search(r"<NextContinuationToken>([^<]+)<", text).group(1)


def listing_diff(listed: dict, expected: dict) -> int:
    """Keys missing, keys that should not be there, and keys listed
    with another ETag or size."""
    missing = set(expected) - set(listed)
    extra = set(listed) - set(expected)
    differ = [k for k in set(listed) & set(expected)
              if listed[k] != expected[k]]
    return len(missing) + len(extra) + len(differ)

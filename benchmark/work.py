"""Bytes and operations the ALGORITHM needs for the erasure windows of
an interval, from (k, m, erasure block, number of blocks) alone —
whatever kernel ran, however it padded its batch.

frame (PUT side), per erasure block of B bytes, k data and m parity
shards: read B, write the m parity pieces (B*m/k) and k+m digests of
32 bytes; GF(2^8) multiply-accumulates: k*m per byte column, B/k
columns -> B*m; HighwayHash of all k+m pieces: B*(k+m)/k bytes, one
32-byte packet update each 32 bytes.

Operation counts are given for the record; on a v5e the framer is
bound by bytes (an int8 MAC rate of 393 T/s against 819 GB/s puts the
ridge at ~480 operations per byte; framing needs m = 2..4 MACs per
byte plus the hash), so `least_seconds` says which bound it took.

deframe (GET side: a healthy read verifies its k data shards), per
erasure block: read the k framed pieces (B + 32 k bytes: each piece
behind its stored digest) and write k verdict bytes; HighwayHash of
the B payload bytes; no GF work. The payload does not come back from
the device: the host serves it from the bytes it read off the drives.
"""

from __future__ import annotations

# HighwayHash: per 32-byte packet, 4 lanes x (2 32x32 multiplies + ~10
# adds/xors/shifts) + the two zipper merges (~28 byte moves each).
HH_OPS_PER_BYTE = (4 * 12 + 2 * 28) / 32


def frame_work(k: int, m: int, block: int, blocks: float) -> dict:
    n = k + m
    return {"bytes": blocks * (block + block * m / k + 32 * n),
            "ops": blocks * (block * m + HH_OPS_PER_BYTE * block * n / k)}


def deframe_work(k: int, m: int, block: int, blocks: float) -> dict:
    del m                        # a healthy read touches no parity
    return {"bytes": blocks * (block + 32 * k + k),
            "ops": blocks * HH_OPS_PER_BYTE * block}


WORK = {"frame": frame_work, "deframe": deframe_work}


def least_seconds(kind: str, cfg: dict, blocks: float, peaks: dict) -> dict:
    w = WORK[kind](cfg["data_shards"], cfg["parity_shards"],
                   cfg["erasure_block_bytes"], blocks)
    by_bytes = w["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = w["ops"] / peaks["int8_ops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "bytes" if by_bytes >= by_ops else "ops", **w}

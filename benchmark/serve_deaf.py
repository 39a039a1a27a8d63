"""serve_traced.py with the program broken in one place, for the
control (`faults.py`, `deaf_deframer`) and the tests alone: the
bitrot verify on the read path is deaf. The `get` route hands every
window the verdict "all frames verify", whatever the de-framer found;
and the rebuild path's own verify (`bitrot.read_framed_blocks_many`:
the survivors of a window with a shard missing) hands back every
shard of the right length, whatever its digests said. Every frame is
still hashed, so nothing else of the run changes — which is what a
later change would look like that saves the hash, or the look at its
answer, on the read path. Same arguments as serve_traced.py.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import serve_traced  # noqa: E402


def unframed(blob, shard_size: int, data_size: int, hsize: int = 32):
    """The payload of a framed shard blob (digest || block, block by
    block) with the digests cut off and not looked at, or None where
    the blob has not the length of `data_size` framed bytes."""
    nb = -(-data_size // shard_size)
    if blob is None or len(blob) != data_size + nb * hsize:
        return None
    arr = np.frombuffer(blob, dtype=np.uint8)
    return np.concatenate(
        [arr[b * (hsize + shard_size) + hsize:(b + 1) * (hsize + shard_size)]
         for b in range(nb)]) if nb else arr[:0]


def main() -> int:
    sys.path.insert(0, os.getcwd())
    from minio_tpu.object import erasure_object   # imports no JAX
    from minio_tpu.storage import bitrot
    heard = erasure_object._get_split
    heard_many = bitrot.read_framed_blocks_many

    def deaf(ok, off, c, member):
        verdict, payload = heard(ok, off, c, member)
        return np.ones_like(verdict), payload

    def deaf_many(blobs, shard_size, data_size, *args, **kw):
        out = heard_many(blobs, shard_size, data_size, *args, **kw)
        return [got if got is not None
                else unframed(blob, shard_size, data_size)
                for blob, got in zip(blobs, out)]
    erasure_object._get_split = deaf
    bitrot.read_framed_blocks_many = deaf_many
    return serve_traced.main()


if __name__ == "__main__":
    sys.exit(main())

"""Faults planted under a run, for the control and the tests: each
breaks one thing the configuration guarantees, at the place where the
program produces it, and `correct` has to come out false.

  wrong_matrix     after the clean stop, the parity shard files of the
                   sampled objects are replaced by the REFERENCE's
                   encoding under a Vandermonde matrix that was never
                   made systematic (digests recomputed to match): the
                   reference in the program's place with one guarantee
                   broken — parity that is not upstream's;
  below_quorum     shard files of the sampled objects are removed until
                   one drive fewer than the write quorum holds them: an
                   acknowledged write that is not durable.

Both alter what a PUT produced where it lies: the cells shipped so far
only write. A cell that reads brings its own fault (a GET's body
altered on the wire) with the readings that prove it.
"""

from __future__ import annotations

import os

from benchmark import compare
from benchmark.traffic import BUCKET
from benchmark.reference import gf_rs


def wrong_matrix(srv, cfg: dict, sample: list[str], bodies) -> None:
    k, m = cfg["data_shards"], cfg["parity_shards"]
    for key in sample:
        body = bodies.body(key)
        right = compare.reference_shard_files(
            body, k, m, cfg["erasure_block_bytes"])
        broken = compare.reference_shard_files(
            body, k, m, cfg["erasure_block_bytes"],
            gf_rs.raw_vandermonde_parity(k, m))
        for path in compare.shard_files_on_disk(
                srv.drive_root, cfg["drives"], BUCKET, key).values():
            with open(path, "rb") as f:
                got = f.read()
            for i in range(k, k + m):
                if got == right[i]:
                    with open(path, "wb") as f:
                        f.write(broken[i])


def below_quorum(srv, cfg: dict, sample: list[str], bodies) -> None:
    for key in sample:
        paths = sorted(compare.shard_files_on_disk(
            srv.drive_root, cfg["drives"], BUCKET, key).items())
        for _, path in paths[cfg["write_quorum"] - 1:]:
            os.unlink(path)


FAULTS = {"wrong_matrix": wrong_matrix, "below_quorum": below_quorum}


def hooks_for(name: str) -> dict:
    """-> hooks for run_cell that plant the fault."""
    return {"before_disk_check": FAULTS[name]}

"""`mesh.least_chip_real_share.put`: how evenly the clients' data was
spread over the chips of the mesh.

The mesh framer counts, per dispatch and per chip, the erasure blocks
of the chip's slice that carried a client's data
(`minio_tpu_mesh_blocks_total{chip, kind="real"}`) and those that were
bucket padding. This is the smallest chip's delta between the window's
two scrapes over the mean of all chips' deltas, in percent: 100 when
the shares are even, 0 when some chip framed nothing but padding. A
program without the counter (or on one device, where the series is
absent) gives nothing to read.
"""


def read(ctx, spec):
    a, b = ctx.get("scrape_a"), ctx.get("scrape_b")
    if a is None or b is None:
        return None
    per_chip: dict = {}
    for key, value in b.get(spec["series"], {}).items():
        labels = dict(key)
        if labels.get("kind") == spec["kind"] and "chip" in labels:
            before = a.get(spec["series"], {}).get(key, 0.0)
            per_chip[labels["chip"]] = per_chip.get(labels["chip"], 0.0) \
                + value - before
    total = sum(per_chip.values())
    if len(per_chip) < 2 or total <= 0:
        return None
    ctx.setdefault("notes", {})["mesh_real_blocks_by_chip"] = dict(
        sorted(per_chip.items()))
    return min(per_chip.values()) / (total / len(per_chip)) * 100.0

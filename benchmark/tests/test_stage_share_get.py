"""The two Batcher metrics PR 35 appended for `ec8p4-12d.get-64m`:
`batcher.stage_share.get` and `bufpool.hit_share.get`, both data files
for the generic reader, read here from the recorded pair of scrapes
that test_get_cell.py uses (six 40 MiB GETs between them)."""

import pytest

from benchmark import cells, readers
from benchmark.tests.test_get_cell import CELL, ctx, layer  # noqa: F401

NEW = ("batcher.stage_share.get", "bufpool.hit_share.get")


def test_the_stage_share_and_the_pool_hit_share_on_the_recorded_pair(ctx):
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # appended, in this order, after everything that was there
    assert tuple(m["name"] for m in bench["per_layer"][-2:]) == NEW
    for name in NEW:
        m = by_name[name]
        assert (m["moves"], m["layer"], m["unit"]) == \
            ("get_mib_s", "Batcher", "%")
        assert m["workloads"] == [CELL]
        spec = cells.load_layer(name)
        assert spec["what"] and spec["reader"] == "prometheus_delta"
    assert by_name[NEW[0]]["better"] == "lower"
    assert by_name[NEW[1]]["better"] == "higher"
    # 12 staging copies of a few microseconds in a window of 1.1 s
    window_s = readers.scraped_seconds(ctx["scrape_a"], ctx["scrape_b"])
    got = layer(ctx, NEW[0])
    assert got == pytest.approx((5.6e-05 - 2.2e-05) / window_s * 100)
    # 119 hits, 2 misses, nothing oversized between the scrapes
    got = layer(ctx, NEW[1])
    assert got == pytest.approx((164 - 45) / ((164 - 45) + (17 - 15)) * 100)
    # every oversized lease is a lease that was not a hit
    b = {k: dict(v) for k, v in ctx["scrape_b"].items()}
    b["minio_tpu_bufpool_oversized_total"][frozenset()] += 29
    got = layer({**ctx, "scrape_b": b}, NEW[1])
    assert got == pytest.approx(119 / 150 * 100)


def test_a_program_without_the_series_reports_nothing(ctx):
    """No lease and no pool between the scrapes: nothing to divide by,
    and the metric is left out of the line, never written as 0."""
    gone = {k: {n: v for n, v in ctx[k].items() if "bufpool" not in n}
            for k in ("scrape_a", "scrape_b")}
    assert layer({**ctx, **gone}, NEW[1]) is None

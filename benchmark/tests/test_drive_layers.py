"""The eight metrics of the drives' side of a PUT (PR 32): each one's
file, its entry in `BENCHMARK.json`, the number it reads from two
scrapes written out by hand, and nothing from a program that lacks the
series (the parent commit)."""

import os

import pytest

from benchmark import cells, readers

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = ["ec8p4-12d.put-64m", "ec4p2-6d.put-64m", "ec8p4-12d-4chip.put-64m"]
SEC = "minio_tpu_stage_seconds_total"
ENT = "minio_tpu_stage_entries_total"
SLOW = "minio_tpu_drive_slow_syncs_total"
MODES = "minio_tpu_drive_streams_total"


def key(**labels):
    return frozenset(labels.items())


def scrape(uptime, stages, slow_shard, modes):
    """stages: {stage: (seconds, entries)}."""
    return {readers.UPTIME: {key(): uptime},
            SEC: {key(stage=n): s for n, (s, _) in stages.items()},
            ENT: {key(stage=n): e for n, (_, e) in stages.items()},
            SLOW: {key(kind="shard"): slow_shard, key(kind="meta"): 0},
            MODES: {key(mode=m): v for m, v in modes.items()}}


# 20 s of a 12-drive server: 70 PUTs = 840 streams
A = scrape(100.0, {"disk.stream": (1000.0, 400),
                   "disk.stream.open": (1.0, 400),
                   "disk.stream.row_wait": (900.0, 1200),
                   "disk.stream.write": (8.0, 4000),
                   "disk.stream.sync": (20.0, 400),
                   "disk.meta.sync": (2.0, 400),
                   "put.frame": (500.0, 60)},
           3, {"direct": 390, "direct_dropped": 0, "buffered": 10})
B = scrape(120.0, {"disk.stream": (9000.0, 1240),
                   "disk.stream.open": (5.0, 1240),
                   "disk.stream.row_wait": (8100.0, 3720),
                   "disk.stream.write": (38.0, 12400),
                   "disk.stream.sync": (74.6, 1240),
                   "disk.meta.sync": (6.2, 1240),
                   "put.frame": (1500.0, 200)},
           45, {"direct": 1209, "direct_dropped": 21, "buffered": 10})
EXPECTED = {
    "drive.shard_sync_ms.put": 54.6 / 840 * 1000,
    "drive.meta_sync_ms.put": 4.2 / 840 * 1000,
    "drive.shard_write_ms.put": 30.0 / 8400 * 1000,
    "drive.syncs_in_flight.put": (54.6 + 4.2) / 20.0,
    "drive.shard_sync_over_1s_share.put": 42 / 840 * 100,
    "drive.stream_row_wait_share.put": 7200.0 / 8000.0 * 100,
    "drive.stream_attributed_share.put":
        (4.0 + 7200.0 + 30.0 + 54.6) / 8000.0 * 100,
    "drive.direct_stream_share.put": 819 / 840 * 100,
}


def layer(ctx, name):
    return readers.read_layer(ctx, cells.load_layer(name))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_metric_is_in_benchmark_json_with_its_file(name):
    bench = cells.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == CELLS
    assert entry["layer"] == "Drives" and entry["moves"] == "put_mib_s"
    assert entry["better"] == ("higher" if name in (
        "drive.stream_attributed_share.put",
        "drive.direct_stream_share.put") else "lower")
    spec = cells.load_layer(name)
    assert spec["what"]
    assert "read" in spec or spec["reader"] == "prometheus_delta"
    # nothing was put before the metrics the benchmark had (later PRs
    # append after these in their turn)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(name) > names.index("batcher.lane_wait_ms.put")


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_metric_reads_its_number_from_two_scrapes(name):
    ctx = {"scrape_a": A, "scrape_b": B, "drives": 12, "workers": 32}
    assert layer(ctx, name) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_program_without_the_series_reports_nothing(name):
    """The parent commit: the stage series are there, under other
    stages' names, and the two counters are not. Nothing, and not 0."""
    parent_a = {readers.UPTIME: {key(): 100.0},
                SEC: {key(stage="put.frame"): 500.0},
                ENT: {key(stage="put.frame"): 60}}
    parent_b = {readers.UPTIME: {key(): 120.0},
                SEC: {key(stage="put.frame"): 1500.0},
                ENT: {key(stage="put.frame"): 200}}
    ctx = {"scrape_a": parent_a, "scrape_b": parent_b, "drives": 12,
           "workers": 32}
    assert layer(ctx, name) is None
    assert layer({"drives": 12, "workers": 32}, name) is None   # no scrape
    # and a window in which no stream ran
    assert layer({"scrape_a": B, "scrape_b": B, "drives": 12, "workers": 32},
                 name) is None

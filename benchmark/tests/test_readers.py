"""The Prometheus-delta reader on two recorded scrapes (a CPU boot of
the server, EC 4+2: between them 3 PUTs and 5 GETs of 10 MiB and one
HEAD), and the arithmetic of work.py."""

import os

import pytest

from benchmark import cells, readers, work
from benchmark.server import parse_scrape

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def ctx():
    with open(os.path.join(DATA, "scrape_a.txt")) as f:
        a = parse_scrape(f.read())
    with open(os.path.join(DATA, "scrape_b.txt")) as f:
        b = parse_scrape(f.read())
    # the server's own clock says 4.0 s lie between the two scrapes
    return {"scrape_a": a, "scrape_b": b, "drives": 6, "workers": 1}


def layer(ctx, name):
    return readers.read_layer(ctx, cells.load_layer(name))


def test_counts_are_read_as_deltas(ctx):
    d = readers.series_sum(ctx["scrape_b"],
                           "minio_tpu_api_request_duration_seconds_count",
                           {"api": "PUT:object"}) - readers.series_sum(
        ctx["scrape_a"], "minio_tpu_api_request_duration_seconds_count",
        {"api": "PUT:object"})
    assert d == 3
    assert layer(ctx, "batcher.put_fill_ratio") == pytest.approx(62.5)
    assert layer(ctx, "batcher.put_device_share") == pytest.approx(100.0)
    assert 50 < layer(ctx, "frontend.put_ms") < 2000
    assert 0 < layer(ctx, "drive.ops_in_service.put") < 12
    assert 0 < layer(ctx, "lane.busy_share.put") < 100


def test_label_filters_take_lists_and_unlabelled_series(ctx):
    b = ctx["scrape_b"]
    both = readers.series_sum(b, "minio_tpu_batcher_batched_blocks_total",
                              {"route": ["get", "transform"]})
    assert both == readers.series_sum(
        b, "minio_tpu_batcher_batched_blocks_total", {"route": "get"}) \
        + readers.series_sum(b, "minio_tpu_batcher_batched_blocks_total",
                             {"route": "transform"})
    assert readers.series_sum(
        b, "minio_tpu_kernel_lane_op_duration_seconds_sum") > 0


def test_nothing_to_read_is_none_not_zero(ctx):
    same = {**ctx, "scrape_b": ctx["scrape_a"]}
    assert layer(same, "frontend.put_ms") is None
    assert layer(same, "batcher.put_fill_ratio") is None
    assert layer({**ctx, "trace": None}, "device.idle_share.put") is None
    assert layer({**ctx, "trace": {"chips": 0}},
                 "kernel.frame_roofline") is None


def test_the_time_between_two_scrapes_is_the_servers_own(ctx):
    assert readers.scraped_seconds(ctx["scrape_a"], ctx["scrape_b"]) == \
        pytest.approx(4.0)
    lane = readers.series_sum(
        ctx["scrape_b"], "minio_tpu_kernel_lane_op_duration_seconds_sum") \
        - readers.series_sum(
            ctx["scrape_a"], "minio_tpu_kernel_lane_op_duration_seconds_sum")
    assert layer(ctx, "lane.busy_share.put") == pytest.approx(lane / 4 * 100)


def test_device_trace_reader_on_a_reduced_trace(ctx):
    peaks = cells.load_peaks()["TPU v5 lite"]
    cfg = cells.load_config("ec4p2-6d")
    tr = {"chips": 1, "window_s": 2.0, "busy_s": 0.05,
          "modules": [{"name": "jit_fused32", "count": 3,
                       "device_s": 0.004, "span_s": 0.004},
                      {"name": "jit_other", "count": 1, "device_s": 0.046,
                       "span_s": 0.05}]}
    c = {**ctx, "trace": tr, "config": cfg, "peaks": peaks,
         "payload_mib_s": {"PUT": 7.5, "GET": 0.0}}
    assert layer(c, "device.idle_share.put") == pytest.approx(97.5)
    # the clients moved 7.5 MiB/s of PUTs, all of it on the device (the
    # scrapes' share: 100 %), 1 MiB to a block; the trace holds 0.05 s
    # of device time in 2.0 s: two rates, each over its own interval
    least = work.least_seconds("frame", cfg, 7.5, peaks)
    assert least["bound"] == "bytes"
    assert layer(c, "kernel.frame_roofline") == \
        pytest.approx(least["seconds"] / (0.05 / 2.0) * 100)
    note = c["notes"]["frame_roofline"]
    assert note["blocks_per_s"] == pytest.approx(7.5)
    assert note["programs_run"] == {"jit_fused32": 3, "jit_other": 1}
    # a metric's file may pick its kernel's programs by name
    picked = {**cells.load_layer("kernel.frame_roofline"),
              "modules": "^jit_fused"}
    assert readers.read_layer(c, picked) == \
        pytest.approx(least["seconds"] / (0.004 / 2.0) * 100)


def test_work_counted_and_no_program_to_hold_it_against_is_loud(ctx):
    """A metric that names its programs must not vanish quietly when
    they are renamed."""
    tr = {"chips": 1, "window_s": 2.0, "busy_s": 0.05,
          "modules": [{"name": "jit_framer_v2", "count": 3,
                       "device_s": 0.004, "span_s": 0.004}]}
    c = {**ctx, "trace": tr, "config": cells.load_config("ec4p2-6d"),
         "peaks": cells.load_peaks()["TPU v5 lite"],
         "payload_mib_s": {"PUT": 7.5}}
    picked = {**cells.load_layer("kernel.frame_roofline"),
              "modules": "^jit_fused"}
    with pytest.raises(LookupError, match="jit_framer_v2"):
        readers.read_layer(c, picked)
    # nothing put in the window: nothing to read, and no error
    assert readers.read_layer({**c, "payload_mib_s": {"PUT": 0.0}},
                              picked) is None
    assert readers.read_layer({**c, "scrape_b": ctx["scrape_a"]},
                              picked) is None


def test_work_counts_what_the_algorithm_needs():
    w = work.frame_work(8, 4, 1 << 20, 32)
    assert w["bytes"] == 32 * ((1 << 20) * 1.5 + 32 * 12)
    # bytes bind on a v5e: 819 GB/s against 393 T int8 operations/s
    peaks = cells.load_peaks()["TPU v5 lite"]
    least = work.least_seconds("frame", cells.load_config("ec8p4-12d"),
                               32, peaks)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(w["bytes"] / 819e9)


def test_an_unknown_device_has_no_peaks():
    assert "cpu" not in cells.load_peaks()
    assert cells.load_peaks()["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9

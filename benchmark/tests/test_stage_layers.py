"""The per-layer metrics that read the program's stage counters
(`minio_tpu_stage_*`, PR 26), on a recorded pair of scrapes — a CPU
boot of the server (EC 4+2, portable framer, batcher pinned to the
device): between them two 40 MiB PUTs at once and one of 1000 bytes —
and the reader of what the trace leaves unnamed, on reductions made by
hand."""

import glob
import os

import pytest

from benchmark import cells, readers
from benchmark.server import parse_scrape

DATA = os.path.join(os.path.dirname(__file__), "data")
STAGE_LAYERS = ("frontend.put_attributed_share", "frontend.body_read_ms",
                "frontend.body_read_cpu_share", "frontend.process_cores.put",
                "object.frame_wait_ms", "object.shard_queue_ms",
                "object.commit_ms", "batcher.put_wait_ms",
                "lane.upload_share.put", "lane.kernel_share.put",
                "lane.readback_share.put", "frontend.put_prepare_ms",
                "object.prepare_ms", "object.writers_start_ms",
                "batcher.stage_ms", "lane.rows_share.put")
UNNAMED = "host.unnamed_idle_share.put"


def scrapes(a: str, b: str) -> dict:
    out = {"drives": 6, "workers": 2}
    for key, name in (("scrape_a", a), ("scrape_b", b)):
        with open(os.path.join(DATA, name)) as f:
            out[key] = parse_scrape(f.read())
    return out


@pytest.fixture(scope="module")
def ctx():
    return scrapes("scrape_stage_a.txt", "scrape_stage_b.txt")


def layer(ctx, name):
    return readers.read_layer(ctx, cells.load_layer(name))


def delta(ctx, series, **labels):
    return readers.series_sum(ctx["scrape_b"], "minio_tpu_" + series,
                              labels) \
        - readers.series_sum(ctx["scrape_a"], "minio_tpu_" + series, labels)


def stage_s(ctx, *names, kind="stage_seconds_total"):
    return sum(delta(ctx, kind, stage=n) for n in names)


def test_every_new_metric_is_in_benchmark_json_with_its_file():
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in STAGE_LAYERS + (UNNAMED,):
        m = by_name[name]
        assert m["moves"] == "put_mib_s"
        # "contains": later PRs append their cells (PR 28 a third)
        assert m["workloads"][:2] == ["ec8p4-12d.put-64m",
                                      "ec4p2-6d.put-64m"]
        spec = cells.load_layer(name)
        assert ("read" in spec) == (name == UNNAMED)
    # appended: what was there keeps its place
    assert [m["name"] for m in bench["per_layer"]][:7] == [
        "frontend.put_ms", "batcher.put_device_share",
        "batcher.put_fill_ratio", "lane.busy_share.put",
        "kernel.frame_roofline", "device.idle_share.put",
        "drive.ops_in_service.put"]


def test_the_recorded_pair_reads_the_expected_numbers(ctx):
    puts = delta(ctx, "api_request_duration_seconds_count", api="PUT:object")
    put_s = delta(ctx, "api_request_duration_seconds_sum", api="PUT:object")
    lane_s = delta(ctx, "kernel_lane_op_duration_seconds_sum")
    assert puts == 3 and put_s > 0 and lane_s > 0
    request_stages = ("s3.auth", "s3.put_prepare", "put.prepare",
                      "put.writers_start", "put.body_read", "put.frame",
                      "put.md5", "put.shard_enqueue", "put.shard_drain",
                      "put.commit")
    assert layer(ctx, "frontend.put_attributed_share") == pytest.approx(
        stage_s(ctx, *request_stages) / put_s * 100)
    # a request's stage seconds are credited when it ends, beside its
    # own: the stages never hold more than the requests they are of
    # (s3.auth is every API's: here the scrapes' own, under a ms)
    assert 95 < layer(ctx, "frontend.put_attributed_share") <= 100.1
    assert layer(ctx, "frontend.body_read_ms") == pytest.approx(
        stage_s(ctx, "put.body_read") / puts * 1000)
    assert layer(ctx, "frontend.body_read_cpu_share") == pytest.approx(
        stage_s(ctx, "put.body_read", kind="stage_cpu_seconds_total")
        / stage_s(ctx, "put.body_read") * 100)
    assert 0 < layer(ctx, "frontend.body_read_cpu_share") <= 100
    assert layer(ctx, "frontend.process_cores.put") == pytest.approx(
        delta(ctx, "process_cpu_seconds_total")
        / readers.scraped_seconds(ctx["scrape_a"], ctx["scrape_b"]))
    assert layer(ctx, "object.frame_wait_ms") == pytest.approx(
        stage_s(ctx, "put.frame") / puts * 1000)
    assert layer(ctx, "object.shard_queue_ms") == pytest.approx(
        stage_s(ctx, "put.shard_enqueue", "put.shard_drain") / puts * 1000)
    assert layer(ctx, "object.commit_ms") == pytest.approx(
        stage_s(ctx, "put.commit") / puts * 1000)
    assert layer(ctx, "frontend.put_prepare_ms") == pytest.approx(
        stage_s(ctx, "s3.put_prepare") / puts * 1000)
    assert layer(ctx, "object.prepare_ms") == pytest.approx(
        stage_s(ctx, "put.prepare") / puts * 1000)
    assert layer(ctx, "object.writers_start_ms") == pytest.approx(
        stage_s(ctx, "put.writers_start") / puts * 1000)
    assert layer(ctx, "batcher.put_wait_ms") == pytest.approx(
        delta(ctx, "batcher_wait_seconds_sum", route="put")
        / delta(ctx, "batcher_wait_seconds_count", route="put") * 1000)
    assert layer(ctx, "batcher.stage_ms") == pytest.approx(
        stage_s(ctx, "batcher.stage")
        / stage_s(ctx, "batcher.stage", kind="stage_entries_total") * 1000)
    shares = [layer(ctx, f"lane.{part}_share.put")
              for part in ("upload", "kernel", "readback", "rows")]
    assert shares[1] == pytest.approx(
        stage_s(ctx, "lane.kernel") / lane_s * 100)
    assert shares[3] == pytest.approx(
        stage_s(ctx, "lane.rows") / lane_s * 100)
    # the four are inside the lane's own service time, and all of it
    # but the framer's few lines of Python between them
    assert 95 < sum(shares) <= 100


def test_no_series_at_all_is_none_for_every_one_of_them(ctx):
    """A scrape that holds nothing of what a metric reads — numerator
    or denominator — gives nothing to read."""
    bare = {"minio_tpu_process_uptime_seconds": {frozenset(): 1.0}}
    for name in STAGE_LAYERS:
        assert layer({**ctx, "scrape_a": bare, "scrape_b": bare},
                     name) is None, name
        assert layer({**ctx, "scrape_b": None}, name) is None, name


def test_a_program_without_the_stage_counters(ctx):
    """The scrapes of a program from before PR 26 (`scrape_a.txt`,
    `scrape_b.txt`: no `minio_tpu_stage_*`): a metric whose denominator
    is a stage series has nothing to read; one held against a series
    that program already had (the PUTs' count and seconds, the lane's
    seconds, the server's seconds) reads the 0 it attributes — the
    generic reader's arithmetic, said in PERF.md — and never raises;
    `batcher.put_wait_ms` reads a series that program had, though not
    on a route these two scrapes used."""
    old = scrapes("scrape_a.txt", "scrape_b.txt")
    nothing = ("frontend.body_read_cpu_share", "batcher.stage_ms",
               "batcher.put_wait_ms")
    for name in STAGE_LAYERS:
        assert layer(old, name) == (None if name in nothing else 0.0), name


GAPS = [["s3.PUT:object", 310.2], ["engine.op", 120.0],
        ["disk.create_file", 118.5], ["put.body_read", 150.1],
        ["put.frame", 90.0], ["put.shard_enqueue", 40.0],
        ["put.commit", 12.0], ["disk.rename_data", 11.0],
        ["put.shard_drain", 6.0]]


def trace_of(gaps):
    return {"chips": 1, "window_s": 11.0, "busy_s": 0.05,
            "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10]}


@pytest.fixture
def kept_trace(tmp_path, monkeypatch):
    """The small recorded trace where benchmark/run.py keeps a run's:
    <tmp>/mtpu-bench-*/trace (readers are handed the reduction alone)."""
    import tempfile

    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    d = tmp_path / "mtpu-bench-abc" / "trace" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(raw)
    return str(tmp_path / "mtpu-bench-abc" / "trace")


TEN = [[f"stage.{i}", 1.0 + i] for i in range(10)]


def test_unnamed_idle_share_with_the_entry_among_the_ten():
    name = "no host span (the program's Python is not annotated)"
    c = {"trace": trace_of(GAPS + [[name, 10.51]])}
    assert layer(c, UNNAMED) == pytest.approx(10.51 / 10.95 * 100)
    assert c["notes"]["unnamed_idle"] == {"idle_s": pytest.approx(10.95),
                                          "unnamed_s": 10.51}
    c = {"trace": trace_of(GAPS[:3] + [[name, 0.0]])}
    assert layer(c, UNNAMED) == 0.0      # measured, and nothing unnamed


def test_unnamed_idle_share_when_ten_names_hold_more(kept_trace):
    """The entry has fallen out of the ten listed: it is computed
    again from the trace itself — exactly, whatever the ten hold, and
    never a stand-in."""
    from benchmark import trace
    whole = trace.reduce(kept_trace)
    listed = next(s for n, s in whole["idle_gaps"]
                  if n.startswith("no host span"))
    assert listed == pytest.approx(3.004e-3)
    c = {"trace": {**whole, "idle_gaps": TEN}}
    assert layer(c, UNNAMED) == pytest.approx(
        listed / (whole["window_s"] - whole["busy_s"]) * 100)
    note = c["notes"]["unnamed_idle"]
    assert note["unnamed_s"] == pytest.approx(listed) and "from" in note
    # the same whether read from the list or from the trace
    assert layer({"trace": whole}, UNNAMED) == pytest.approx(
        layer(c, UNNAMED))


def test_unnamed_idle_share_with_no_trace_to_reduce(kept_trace):
    """No trace kept, or one that cannot be read: nothing is reported,
    the notes say why, and nothing raises."""
    import shutil

    from benchmark import trace
    whole = trace.reduce(kept_trace)
    for f in glob.glob(os.path.join(kept_trace, "**", "*.pb"),
                       recursive=True):
        with open(f, "wb") as out:
            out.write(b"not a trace")
    c = {"trace": {**whole, "idle_gaps": TEN}}
    assert layer(c, UNNAMED) is None
    assert "CalledProcessError" in c["notes"]["unnamed_idle"]["unread"]
    shutil.rmtree(kept_trace)
    c = {"trace": {**whole, "idle_gaps": TEN}}
    assert layer(c, UNNAMED) is None
    assert c["notes"]["unnamed_idle"]["unread"] == \
        "no trace directory found"


def test_unnamed_idle_share_with_nothing_to_read():
    assert layer({"trace": None}, UNNAMED) is None
    assert layer({"trace": {"chips": 0}}, UNNAMED) is None
    assert layer({"trace": {"chips": 1, "window_s": 2.0, "busy_s": 0.1,
                            "idle_gaps": []}}, UNNAMED) is None

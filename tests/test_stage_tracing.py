"""The stage primitive (utils/tracing.stage) and its three sinks: the
always-on per-stage accumulator and its /metrics export, the
request-scoped span tree, and the profiler annotation that puts the
program's stages on the device trace's clock — with a fake annotator
and with the real jax.profiler on the CPU backend."""

import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from minio_tpu.object import erasure_object as eo
from minio_tpu.object.erasure_object import ErasureSet
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.health import wrap_disks
from minio_tpu.storage.local import LocalStorage
from minio_tpu.utils import tracing
from tests.batcher_rig import until
from tests.s3client import S3Client

MiB = 1 << 20
REQUEST_STAGES = ("put.prepare", "put.writers_start", "put.body_read",
                  "put.frame", "put.md5", "put.shard_enqueue",
                  "put.shard_drain", "put.commit")


def totals_since(before: dict) -> dict:
    """What the process-wide accumulator gained since `before`."""
    out = {}
    for name, (wall, cpu, n) in tracing.stage_totals().items():
        w0, c0, n0 = before.get(name, (0.0, 0.0, 0))
        if n > n0:
            out[name] = (wall - w0, cpu - c0, n - n0)
    return out


class FakeAnnotator:
    """Stands where jax.profiler.TraceAnnotation stands: built from a
    name, entered and left. Keeps every event, with its thread."""

    events: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        FakeAnnotator.events.append(
            ("enter", self.name, threading.get_ident(), time.perf_counter()))
        return self

    def __exit__(self, *exc):
        FakeAnnotator.events.append(
            ("exit", self.name, threading.get_ident(), time.perf_counter()))
        return False


@pytest.fixture
def annotator():
    prev = tracing._annotator
    FakeAnnotator.events = []
    tracing.set_annotator(FakeAnnotator)
    try:
        yield FakeAnnotator
    finally:
        tracing.set_annotator(prev)


@pytest.fixture
def small_windows(monkeypatch):
    """Streaming PUTs in 8-block windows, so that a 16 MiB body is two
    windows and nobody sends 64 MiB."""
    monkeypatch.setattr(eo, "STREAM_WINDOW_BLOCKS", 8)
    monkeypatch.setattr(eo, "STREAM_THRESHOLD", 8 * MiB)


def make_set(tmp_path, n=4, parity=2, backend=None):
    """Drives wrapped as the server's boot wraps them."""
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(n)]
    for d in disks:
        d.make_vol("bkt")
    return ErasureSet(wrap_disks(disks), parity=parity, backend=backend)


def body_of(nbytes: int, seed: int = 7) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, size=nbytes, dtype=np.uint8).tobytes()


# -- sink 2: the accumulator ---------------------------------------------------

def test_counters_hold_wall_cpu_and_entries():
    before = tracing.stage_totals()
    for _ in range(3):
        with tracing.stage("t.sleep"):
            time.sleep(0.02)
    with tracing.stage("t.spin"):
        t_end = time.thread_time() + 0.02
        while time.thread_time() < t_end:
            pass
    got = totals_since(before)
    wall, cpu, n = got["t.sleep"]
    assert n == 3
    assert wall >= 0.06                  # never less than what was slept
    assert cpu < wall / 2                # a sleeping thread is off the CPU
    wall, cpu, n = got["t.spin"]
    assert n == 1 and cpu >= 0.02 and wall >= cpu * 0.99


def test_two_threads_lose_no_updates():
    before = tracing.stage_totals()
    n_threads, each = 8, 20_000
    start = threading.Event()

    def work():
        start.wait(10)
        for _ in range(each):
            with tracing.stage("t.race"):
                pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        start.set()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert totals_since(before)["t.race"][2] == n_threads * each


def test_totals_outlive_the_threads_that_made_them():
    before = tracing.stage_totals()

    def once():
        with tracing.stage("t.gone"):
            pass

    for _ in range(200):                 # past the registry's sweep mark
        t = threading.Thread(target=once)
        t.start()
        t.join(10)
    assert totals_since(before)["t.gone"][2] == 200
    with tracing._stage_mu:
        held = len(tracing._stage_cells)
    # exited threads' cells were folded, not kept one dict each
    assert held <= threading.active_count() + 1
    assert totals_since(before)["t.gone"][2] == 200


def test_nothing_to_feed_is_the_shared_noop(annotator):
    tracing.set_annotator(None)
    assert not tracing.ACTIVE
    assert tracing.stage("t.free", count=False) is tracing.NOOP
    assert tracing.stage("t.counted") is not tracing.NOOP
    tracing.set_annotator(annotator)
    before = tracing.stage_totals()
    with tracing.request_root("t.root"):
        with tracing.stage("t.uncounted", count=False):
            pass
    assert [e[:2] for e in annotator.events] == [
        ("enter", "t.root"), ("enter", "t.uncounted"),
        ("exit", "t.uncounted"), ("exit", "t.root")]
    assert totals_since(before) == {}    # neither is counted


def test_a_requests_stages_are_credited_when_it_ends():
    """Inside a request root the thread's stage seconds wait in the
    request's own cell and reach the totals when the request ends —
    beside its api_request_duration_seconds — while another thread's
    stages (the batcher's, the lane's) are credited at once."""
    before = tracing.stage_totals()
    seen = {}

    def elsewhere():
        with tracing.stage("t.lane"):
            pass
        seen["mid"] = totals_since(before)

    with tracing.request_root("t.request"):
        for _ in range(2):
            with tracing.stage("t.held"):
                time.sleep(0.01)
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join(10)
    assert seen["mid"]["t.lane"][2] == 1 and "t.held" not in seen["mid"]
    wall, cpu, n = totals_since(before)["t.held"]
    assert n == 2 and wall >= 0.02 and cpu < wall
    # outside any request a stage is credited at once, as before
    with tracing.stage("t.held"):
        pass
    assert totals_since(before)["t.held"][2] == 3


# -- sink 3: the request's span tree ---------------------------------------------

def test_armed_and_bound_the_same_names_land_in_the_span_tree():
    tracing.arm("test-stage")
    try:
        ctx = tracing.TraceContext()
        with tracing.bind(ctx):
            with tracing.stage("t.outer", {"k": 1}, type_="storage") as st:
                st.tag(extra=2)
                with tracing.stage("t.inner", type_="kernel", count=False):
                    pass
            with tracing.span("storage", "t.plain"):
                pass
    finally:
        tracing.disarm("test-stage")
    by_name = {s["name"]: s for s in ctx.spans}
    assert set(by_name) == {"t.outer", "t.inner", "t.plain"}
    outer, inner = by_name["t.outer"], by_name["t.inner"]
    assert outer["parent"] == 0 and inner["parent"] == outer["span"]
    assert outer["type"] == "storage" and inner["type"] == "kernel"
    assert outer["tags"] == {"k": 1, "extra": 2}
    # the record's shape is span()'s own
    assert set(by_name["t.plain"]) <= set(outer)
    # disarmed, or with no context bound, a stage records no span
    with tracing.stage("t.unbound"):
        pass
    assert "t.unbound" not in {s["name"] for s in ctx.spans}


# -- sink 1: the annotation, through a streaming PUT -----------------------------

def test_streaming_put_enters_its_stages_in_order(tmp_path, annotator,
                                                  small_windows):
    es = make_set(tmp_path)
    body = body_of(16 * MiB)
    before = tracing.stage_totals()
    me = threading.get_ident()
    t0 = time.perf_counter()
    try:
        es.put_object("bkt", "two-windows", body)
        wall = time.perf_counter() - t0
        _, got = es.get_object("bkt", "two-windows")
    finally:
        es.close()
    assert got == body
    mine = [(kind, name) for kind, name, tid, _ in annotator.events
            if tid == me and name in REQUEST_STAGES]
    # stages of the request thread follow one another and never nest
    assert all(mine[i][0] == "enter" and mine[i + 1] == ("exit", mine[i][1])
               for i in range(0, len(mine), 2))
    order = [name for kind, name in mine if kind == "enter"]
    window = ["put.body_read", "put.frame", "put.shard_enqueue"]
    assert [n for n in order if n != "put.md5"] == \
        ["put.prepare", "put.writers_start"] + window * 2 \
        + ["put.shard_drain", "put.commit"]
    # the drive workers' spans are on the same clock: one create_file
    # per drive from the writers, one engine.op + rename_data each
    # from the commit's fan-out
    names = [name for kind, name, _, _ in annotator.events
             if kind == "enter"]
    assert names.count("disk.create_file") == 4
    assert names.count("disk.rename_data") == 4
    assert names.count("engine.op") >= 4
    got = totals_since(before)
    assert {n: got[n][2] for n in window} == {n: 2 for n in window}
    assert got["put.shard_drain"][2] == got["put.commit"][2] == 1
    assert "disk.create_file" not in got and "engine.op" not in got
    # the stages do not overlap: together no longer than the call
    assert sum(got[n][0] for n in REQUEST_STAGES if n in got) <= wall


# -- the export ------------------------------------------------------------------

def test_metrics_export_the_stage_series(tmp_path, small_windows):
    srv = S3Server(make_set(tmp_path), address="127.0.0.1:0")
    srv.start()
    reads_before = tracing.stage_totals().get("put.body_read", [0, 0, 0])[2]
    try:
        cli = S3Client(srv.address)
        assert cli.request("PUT", "/bkt/o", body=body_of(9 * MiB))[0] == 200
        cli.request("GET", "/minio/v2/metrics/cluster", sign=False)
        st, _, text = cli.request("GET", "/minio/v2/metrics/cluster",
                                  sign=False)
    finally:
        srv.stop()
    assert st == 200
    series = {}
    for line in text.decode().splitlines():
        if line.startswith("minio_tpu_stage_") or \
                line.startswith("minio_tpu_process_"):
            key, value = line.rsplit(" ", 1)
            series[key] = float(value)
    for stage in ("s3.auth", "s3.put_prepare", "put.prepare",
                  "put.writers_start", "put.body_read", "put.frame",
                  "put.shard_enqueue", "put.shard_drain", "put.commit"):
        lab = f'{{stage="{stage}"}}'
        assert series["minio_tpu_stage_entries_total" + lab] >= 1, stage
        assert series["minio_tpu_stage_seconds_total" + lab] > 0, stage
        assert series["minio_tpu_stage_cpu_seconds_total" + lab] >= 0
    assert series["minio_tpu_process_cpu_seconds_total"] > 0
    assert "minio_tpu_process_uptime_seconds" in series
    # 9 MiB: a whole window and a tail
    assert series['minio_tpu_stage_entries_total{stage="put.body_read"}'] \
        == reads_before + 2
    # uncounted: the scrape's own span is for the trace alone
    assert 'minio_tpu_stage_entries_total{stage="s3.metrics_render"}' \
        not in series


def test_a_puts_stages_never_pass_its_own_seconds(tmp_path, small_windows):
    """Stage seconds are credited when the request ends, with its
    api_request_duration_seconds: over any interval the PUTs' stages
    hold no more than the PUTs' seconds, in flight or not."""
    srv = S3Server(make_set(tmp_path), address="127.0.0.1:0")
    srv.start()
    before = tracing.stage_totals()
    try:
        cli = S3Client(srv.address)
        for i in range(3):
            assert cli.request("PUT", f"/bkt/o{i}",
                               body=body_of(9 * MiB))[0] == 200
        # A PUT's seconds and stages are credited after its response
        # is sent: the third is counted once the server has left
        # `_route`, not when the client has its 200.
        def puts():
            return srv.metrics.state()["latency_hist"].get(
                "PUT:object", {"count": 0, "sum": 0.0})
        until(lambda: puts()["count"] == 3, "the third PUT is recorded")
        put_s = puts()["sum"]
    finally:
        srv.stop()
    got = totals_since(before)
    staged = sum(got[n][0] for n in REQUEST_STAGES + ("s3.auth",
                                                      "s3.put_prepare")
                 if n in got)
    assert got["put.commit"][2] == 3
    assert 0 < staged <= put_s + 1e-5    # the sum is rounded to 1 us


def test_worker_states_sum_into_the_fleet_series():
    from minio_tpu.s3.metrics import Metrics
    m = Metrics()
    with tracing.stage("t.fleet"):
        pass
    st = m.state()
    assert st["stages"]["t.fleet"][2] >= 1 and st["process_cpu_s"] > 0
    peers = [{"metrics": {**st, "stages": {"t.fleet": [1.5, 0.5, 3]},
                          "process_cpu_s": 2.0}},
             {"metrics": {**st, "stages": {"t.fleet": [2.5, 1.0, 4]},
                          "process_cpu_s": 3.0}}]
    text = m.render(peer_states=peers)
    assert 'minio_tpu_stage_seconds_total{stage="t.fleet"} 4.0' in text
    assert 'minio_tpu_stage_cpu_seconds_total{stage="t.fleet"} 1.5' in text
    assert 'minio_tpu_stage_entries_total{stage="t.fleet"} 7' in text
    assert "minio_tpu_process_cpu_seconds_total 5.0" in text


def test_a_held_stage_credits_its_parts_when_it_ends():
    """A nameless `request_root` around a stage: the whole and what
    this thread entered inside it reach the totals at one instant — a
    part over the whole is then a ratio of whole things, whatever is
    in flight at a scrape."""
    before = tracing.stage_totals()
    with tracing.request_root(), tracing.stage("t.whole"):
        for _ in range(3):
            with tracing.stage("t.part"):
                pass
        assert "t.part" not in totals_since(before)
    got = totals_since(before)
    assert got["t.whole"][2] == 1 and got["t.part"][2] == 3
    assert got["t.part"][0] <= got["t.whole"][0]
    # inside a request they join the request's cell, not the thread's
    before = tracing.stage_totals()
    with tracing.request_root("t.request"):
        with tracing.request_root(), tracing.stage("t.whole"):
            with tracing.stage("t.part"):
                pass
        assert totals_since(before) == {}
    got = totals_since(before)
    assert got["t.whole"][2] == got["t.part"][2] == 1


def test_a_stage_can_leave_the_cpu_clock_unread():
    """`cpu=False`: wall seconds and entries as ever, CPU seconds 0 —
    and the thread's CPU clock is not asked."""
    before = tracing.stage_totals()
    real, asked = time.thread_time, []
    time.thread_time = lambda: asked.append(1) or real()
    try:
        with tracing.stage("t.nocpu", cpu=False):
            sum(range(20000))
        assert asked == []
        with tracing.stage("t.cpu"):
            pass
        assert len(asked) == 2
    finally:
        time.thread_time = real
    got = totals_since(before)
    assert got["t.nocpu"][0] > 0 and got["t.nocpu"][2] == 1
    assert got["t.nocpu"][1] == 0.0 and got["t.cpu"][2] == 1


# -- the shard streams' stages (PR 32) ----------------------------------------------

STREAM_PARTS = ("disk.stream.open", "disk.stream.row_wait",
                "disk.stream.write", "disk.stream.sync")


def test_a_streaming_put_names_each_streams_stages(tmp_path, annotator,
                                                   small_windows):
    """Two windows on four drives: a stream a drive, each pulling its
    queue three times (two rows and the sentinel), on the drive's own
    thread — inside `disk.stream`, one after the other."""
    from minio_tpu.storage.local import STREAM_STATS
    es = make_set(tmp_path)
    before, stats = tracing.stage_totals(), STREAM_STATS.snapshot()
    me = threading.get_ident()
    try:
        es.put_object("bkt", "two-windows", body_of(16 * MiB))
    finally:
        es.close()
    got = totals_since(before)
    assert got["disk.stream"][2] == got["disk.stream.open"][2] == 4
    assert got["disk.stream.sync"][2] == 4
    assert got["disk.stream.row_wait"][2] == 3 * 4
    assert got["disk.stream.write"][2] >= 4
    assert got["disk.meta.sync"][2] >= 4       # an xl.meta a drive
    assert sum(got[n][0] for n in STREAM_PARTS) <= got["disk.stream"][0]
    by_thread: dict = {}
    for kind, name, tid, _ in annotator.events:
        if name.startswith("disk.stream"):
            by_thread.setdefault(tid, []).append((kind, name))
    assert len(by_thread) == 4 and me not in by_thread
    for events in by_thread.values():
        assert events[0] == ("enter", "disk.stream")
        assert events[-1] == ("exit", "disk.stream")
        inner = events[1:-1]
        # never nested inside each other: every enter is left at once
        assert all(inner[i][0] == "enter"
                   and inner[i + 1] == ("exit", inner[i][1])
                   for i in range(0, len(inner), 2))
        order = [name for kind, name in inner if kind == "enter"]
        assert order[0] == "disk.stream.open"
        assert order[1] == "disk.stream.row_wait"
        assert order[-1] == "disk.stream.sync"
        assert order.count("disk.stream.row_wait") == 3
    after = STREAM_STATS.snapshot()
    assert sum(after["streams"].values()) - sum(stats["streams"].values()) == 4
    assert after["streams_open"] == 0
    assert after["syncs_in_flight"] == {"shard": 0, "meta": 0}


def test_a_withheld_row_is_row_wait_and_nothing_else(tmp_path, annotator,
                                                     small_windows):
    """The first window is held back until every stream is inside its
    row wait: what the streams waited is there, not in a write or a
    sync (stage against stage, and against what the test itself held)."""
    es = make_set(tmp_path)
    real = es._frame_windows
    held = []

    def frame(*a, **kw):
        if not held:
            def waiting():
                names = [(k, n) for k, n, _, _ in annotator.events
                         if n == "disk.stream.row_wait"]
                return names.count(("enter", "disk.stream.row_wait")) == 4
            until(waiting, "four streams wait for their first row")
            t0 = time.perf_counter()
            time.sleep(0.3)
            held.append(time.perf_counter() - t0)
        return real(*a, **kw)
    es._frame_windows = frame
    before = tracing.stage_totals()
    try:
        es.put_object("bkt", "held", body_of(16 * MiB))
    finally:
        es.close()
    got = totals_since(before)
    assert got["disk.stream.row_wait"][0] >= 4 * held[0]
    assert got["disk.stream"][0] >= got["disk.stream.row_wait"][0]
    rest = sum(got[n][0] for n in STREAM_PARTS if n != "disk.stream.row_wait")
    assert got["disk.stream.row_wait"][0] + rest <= got["disk.stream"][0]
    # a thread that waits burns no CPU
    assert got["disk.stream.row_wait"][1] < got["disk.stream.row_wait"][0]


def test_a_streams_seconds_outlive_its_thread(tmp_path):
    """The health pool's worker exits when its drive is closed; what its
    stream entered stays in the totals."""
    disk = wrap_disks([LocalStorage(str(tmp_path / "d"))])[0]
    disk.make_vol("v")
    before = tracing.stage_totals()
    seen = []

    def chunks():
        seen.append(threading.current_thread())
        yield b"x" * (MiB + 5)
    disk.create_file("v", "f", chunks())
    disk.close()
    (worker,) = seen
    assert worker is not threading.current_thread()
    worker.join(10)
    assert not worker.is_alive()
    got = totals_since(before)
    assert got["disk.stream"][2] == got["disk.stream.sync"][2] == 1
    assert got["disk.stream"][0] > 0
    assert totals_since(before) == got          # retired, and counted once


def test_metrics_export_the_drive_stream_series(tmp_path, small_windows):
    srv = S3Server(make_set(tmp_path), address="127.0.0.1:0")
    srv.start()

    def scrape(cli):
        st, _, text = cli.request("GET", "/minio/v2/metrics/cluster",
                                  sign=False)
        assert st == 200
        series = {}
        for line in text.decode().splitlines():
            if line.startswith(("minio_tpu_drive_s",
                                "minio_tpu_stage_entries_total")):
                key, value = line.rsplit(" ", 1)
                series[key] = float(value)
        return series
    try:
        cli = S3Client(srv.address)
        before = scrape(cli)
        assert cli.request("PUT", "/bkt/o", body=body_of(9 * MiB))[0] == 200
        series = scrape(cli)
    finally:
        srv.stop()

    def gained(key):            # the process has run other files' tests
        return series[key] - before.get(key, 0)
    assert series["minio_tpu_drive_streams_open"] == 0
    for kind in ("shard", "meta"):
        lab = f'{{kind="{kind}"}}'
        assert series["minio_tpu_drive_syncs_in_flight" + lab] == 0
        assert gained("minio_tpu_drive_sync_seconds_count" + lab) >= 4
        assert gained("minio_tpu_drive_sync_seconds_sum" + lab) > 0
        assert gained("minio_tpu_drive_slow_syncs_total" + lab) >= 0
        finite = [float(k.split('le="')[1].rstrip('"}'))
                  for k in series
                  if k.startswith("minio_tpu_drive_sync_seconds_bucket"
                                  f'{{kind="{kind}",le="')
                  and "+Inf" not in k]
        assert max(finite) >= 30.0 and len(finite) == len(set(finite)) >= 14
        assert series["minio_tpu_drive_sync_seconds_bucket"
                      f'{{kind="{kind}",le="+Inf"}}'] == \
            series["minio_tpu_drive_sync_seconds_count" + lab]
    modes = sum(gained(f'minio_tpu_drive_streams_total{{mode="{m}"}}')
                for m in ("direct", "direct_dropped", "buffered"))
    assert modes == 4
    # a sync a stream, and the histogram counts what the stage counts
    assert gained('minio_tpu_stage_entries_total{stage="disk.stream"}') \
        == gained('minio_tpu_stage_entries_total{stage="disk.stream.sync"}') \
        == gained('minio_tpu_drive_sync_seconds_count{kind="shard"}') == 4
    assert gained('minio_tpu_stage_entries_total{stage="disk.meta.sync"}') \
        == gained('minio_tpu_drive_sync_seconds_count{kind="meta"}')


def test_worker_states_sum_into_the_fleet_drive_series():
    from minio_tpu.s3.metrics import Metrics
    from minio_tpu.storage import local as local_mod
    m = Metrics()
    one = local_mod._StreamStats()
    one.stream(+1)
    one.stream(-1, "direct")
    one.sync_hist["shard"].observe(45.0)
    one.slow_syncs["shard"] = 2
    one.syncs_in_flight["meta"] = 3
    st = {**m.state(), "drive_streams": one.snapshot()}
    text = m.render(peer_states=[{"metrics": st}, {"metrics": st},
                                 {"metrics": {**st, "drive_streams": {}}}])
    assert 'minio_tpu_drive_streams_total{mode="direct"} 2' in text
    assert 'minio_tpu_drive_slow_syncs_total{kind="shard"} 4' in text
    assert 'minio_tpu_drive_syncs_in_flight{kind="meta"} 6' in text
    assert 'minio_tpu_drive_sync_seconds_bucket{kind="shard",le="30"} 0' \
        in text
    assert 'minio_tpu_drive_sync_seconds_bucket{kind="shard",le="60"} 2' \
        in text
    assert 'minio_tpu_drive_sync_seconds_sum{kind="shard"} 90.0' in text


# -- the real profiler -----------------------------------------------------------

# -- the read side's dispatches (ops/hh_device, ops/rs_device, batcher) ------

LANE = ("lane.upload", "lane.kernel", "lane.readback")


def test_decode_dispatches_enter_the_lanes_three_stages():
    """The de-framer's and the GF matrix's run() go through the framer's
    round trip: each dispatch enters lane.upload, lane.kernel and
    lane.readback once (and not lane.rows, the framer's own), with the
    verdicts and the rebuilt rows what the host computes."""
    import jax

    from minio_tpu.object.erasure_object import (_host_apply_rows,
                                                 _host_deframe)
    from minio_tpu.ops import gf256
    from minio_tpu.ops.hh_device import make_deframer
    from minio_tpu.ops.rs_device import make_mesh_matrix
    from tests import batcher_rig as rig
    framed = rig.window(8, 31, shard=rig.SHARD + 32, route="get")
    rows = np.ascontiguousarray(
        gf256.decode_matrix(rig.K, rig.M, rig.USE)[list(rig.LOST), :])
    stacked = rig.window(8, 32)
    for run, arg, want in (
            (make_deframer(rig.K), framed, _host_deframe(framed)[0]),
            (make_mesh_matrix(rows, devices=jax.devices()[:1]), stacked,
             _host_apply_rows(rows, stacked))):
        before = tracing.stage_totals()
        assert np.array_equal(run(arg), want)
        got = totals_since(before)
        assert {n: got[n][2] for n in LANE} == dict.fromkeys(LANE, 1)
        assert "lane.rows" not in got


@pytest.mark.parametrize("route", ("put", "get", "reconstruct"))
@pytest.mark.parametrize("path", ("pipelined", "lane", "host"))
def test_every_dispatch_enters_batcher_finish_once(route, path, annotator):
    """Each dispatch's finish — from the rows' return to the members'
    release — is the counted stage batcher.finish, once, on every route
    and every path: the dispatcher's (its finisher thread), a lone
    window's through the lane, and the host route's. batcher.demux, the
    uncounted name it replaced, is entered nowhere."""
    from minio_tpu.ops.batcher import StripeBatcher, _Pending
    from tests import batcher_rig as rig

    def finishes():
        return tracing.stage_totals().get("batcher.finish",
                                          [0.0, 0.0, 0])[2]
    n0 = finishes()
    if path == "pipelined":
        with rig.Rig(route) as r:
            members = r.send(40)          # two members, one batch
            for m in members:
                assert m.returned().exc is None
            until(lambda: finishes() == n0 + 1, "the finish is counted")
    else:
        r = rig.Rig(route)
        r.end()
        sb = StripeBatcher(r.fn, r.fn, probe_fn=lambda: True,
                           min_device_blocks=8, route=r.sb.route,
                           split_fn=r.sb._split_fn)
        sb.force(path == "lane")
        pend = [_Pending(rig.window(8, 41, route=route), None)]
        sb._run_batch(pend)
        assert pend[0].exc is None and pend[0].route_taken == (
            "device" if path == "lane" else "host")
        assert finishes() == n0 + 1
    entered = {e[1] for e in annotator.events}
    assert "batcher.finish" in entered and "batcher.demux" not in entered
    assert "batcher.demux" not in tracing.stage_totals()


def test_program_stages_are_in_a_jax_profiler_trace(tmp_path, monkeypatch,
                                                    small_windows):
    """With jax.profiler.start_trace on the CPU backend and the
    portable framer, the trace's host plane holds the program's
    stages: what benchmark/serve_traced.py's trace will show beside
    the device's operations."""
    import jax
    from jax.profiler import ProfileData

    from minio_tpu.ops import device
    from minio_tpu.ops.rs_device import DeviceBackend
    monkeypatch.setenv("MTPU_BATCH_FORCE", "device")
    # The CPU backend writes an event per XLA thunk per device: one
    # device (the suite's virtual mesh has 8) and a geometry no other
    # test frames (EC 4+1), since the framer is cached per geometry.
    monkeypatch.setenv("MTPU_MESH_DEVICES", "1")
    device.info()                        # this process holds the device,
    tracing.set_annotator(jax.profiler.TraceAnnotation)  # as info() left it
    es = make_set(tmp_path, n=5, parity=1, backend=DeviceBackend("auto"))
    sb = eo._batcher_for(4, 1)
    sb.reset_calibration()               # re-pin the cached batcher
    trace_dir = str(tmp_path / "trace")
    before = tracing.stage_totals()
    try:
        es.put_object("bkt", "warm", body_of(8 * MiB))   # compiles
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        except Exception as e:  # noqa: BLE001 - no session on this box
            pytest.skip(f"profiler session cannot start: {e}")
        try:
            # one device-sized window and a 1 MiB tail: the CPU
            # backend writes an event per XLA thunk, so keep it short
            es.put_object("bkt", "traced", body_of(9 * MiB))
        finally:
            jax.profiler.stop_trace()
    finally:
        es.close()
        monkeypatch.delenv("MTPU_BATCH_FORCE", raising=False)
        sb.reset_calibration()           # un-pin for suite-mates
    got = totals_since(before)
    lane = [got[n][2] for n in ("lane.upload", "lane.kernel",
                                "lane.readback", "lane.rows")]
    assert lane == [2, 2, 2, 2]          # one of each per device window
    assert got["batcher.stage"][2] == lane[0]
    # the finish is counted, once a dispatch (it holds the demux)
    assert got["batcher.finish"][2] == lane[0]
    assert "batcher.demux" not in got
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    data = ProfileData.from_file(files[-1])
    names = set()
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    for want in ("put.body_read", "put.frame", "put.shard_enqueue",
                 "put.commit", "batcher.stage", "batcher.finish",
                 "lane.upload",
                 "lane.kernel", "lane.readback", "lane.rows",
                 "disk.create_file"):
        assert want in names, (want, sorted(names)[:40])


def test_tracing_imports_no_jax_and_the_device_owner_installs_it():
    code = ("import sys; from minio_tpu.utils import tracing; "
            "from minio_tpu.ops import device; "
            "tracing.stage('x').__enter__(); "
            "assert not device.held() and tracing._annotator is None; "
            "assert 'jax' not in sys.modules, 'jax imported'; "
            # the process that takes the device installs the annotator
            "device.info(); import jax; assert device.held(); "
            "assert tracing._annotator is jax.profiler.TraceAnnotation")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         capture_output=True, timeout=120)
    assert out.returncode == 0, out.stderr.decode()[-2000:]

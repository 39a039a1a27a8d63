"""The read side of the yardstick (PR 33), on the CPU: the `get-64m`
mix, `get_mib_s` and which cell reports it, the de-framer's work, the
GET cell's readers on a recorded pair of scrapes — a CPU boot of the
server (EC 8+4 on 12 drives, portable de-framer, batcher pinned to the
device) with six 40 MiB GETs, three at a time, between them — the wire
fault, and a configuration's own module."""

import hashlib
import http.server
import json
import os
import shutil
import threading

import pytest

from benchmark import (cells, compare, faults, loadgen, readers, run, traffic,
                       work)
from benchmark.server import parse_scrape

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "ec8p4-12d.get-64m"
PUT_CELLS = ["ec8p4-12d.put-64m", "ec4p2-6d.put-64m",
             "ec8p4-12d-4chip.put-64m"]
GET_LAYERS = {
    "frontend.get_ms": "Front end", "batcher.get_device_share": "Batcher",
    "batcher.get_fill_ratio": "Batcher", "batcher.get_wait_ms": "Batcher",
    "lane.busy_share.get": "Device boundary",
    "kernel.deframe_roofline": "Kernels", "device.idle_share.get": "Device",
    "host.unnamed_idle_share.get": "Device",
    "drive.ops_in_service.get": "Drives",
    "frontend.process_cores.get": "Front end",
    "loadgen.cpu_share.get": "Load generator"}


# -- the mix -----------------------------------------------------------------

def test_the_mix_is_the_speedtest_get_phase():
    mix = traffic.load_mix("get-64m")
    want = {"loop": "closed", "size": 67108864, "workers": 32,
            "processes": 8, "cycle": {"GET": 1}, "preload": 32,
            "read": "own_in_order", "rotten": [7], "bodies": 2,
            "stagger_s": 8, "ramp_s": 14, "trace_s": 12, "warm_ladder": 8,
            "disk_sample": 2, "timeout_s": 120,
            "limits": {"minio_tpu_hot_cache_hits_total": 0}}
    assert {k: mix[k] for k in want} == want
    assert mix["size"] % cells.load_config("ec8p4-12d")[
        "erasure_block_bytes"] == 0
    # the rotten object is the one the ladder's top rung reads first
    assert mix["rotten"] == [min(mix["warm_ladder"], mix["workers"]) - 1]


def _worker(mix, wid, total=32, address="127.0.0.1:9"):
    spec = {"mix": mix, "seed": 2**31 + 5, "address": address,
            "bucket": traffic.BUCKET, "timeout": 1, "workers_total": total}
    return loadgen.Worker(spec, wid,
                          traffic.Bodies(2**31 + 5, mix["size"], 2))


def test_its_schedule_is_all_gets_each_worker_over_its_own_uploads():
    mix = {**traffic.load_mix("get-64m"), "size": 1 << 20}
    for wid in (0, 7, 31):
        sched = traffic.Schedule(mix, 2**31 + 5, wid)
        assert {sched.next_op(0) for _ in range(50)} == {"GET"}
        # speedtest: a thread reads back what it uploaded, and no two
        # threads are ever on one object
        assert {_worker(mix, wid).read_key() for _ in range(5)} == \
            {traffic.pre_key(wid)}
    # more uploads than workers: round and round, in order
    w = _worker({**mix, "preload": 80}, 7)
    assert [w.read_key() for _ in range(5)] == [
        traffic.pre_key(i) for i in (7, 39, 71, 7, 39)]
    # a mix without `read` draws uniformly from every preloaded key
    w = _worker({k: v for k, v in mix.items() if k != "read"}, 7)
    assert {w.read_key() for _ in range(400)} == \
        {traffic.pre_key(i) for i in range(32)}


def test_own_in_order_needs_a_key_for_every_worker(tmp_path, monkeypatch):
    mix = {**traffic.load_mix("get-64m"), "preload": 16}
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "few.json").write_text(json.dumps(mix))
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="own_in_order"):
        traffic.load_mix("few")


# -- the end-to-end metric and who reports it -----------------------------------

def test_a_cell_reports_the_rate_its_mix_can_move():
    bench = cells.load_benchmark()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["put_mib_s"]["workloads"] == PUT_CELLS
    assert e2e["get_mib_s"]["workloads"][0] == CELL    # lists only grow
    assert "workloads" not in e2e["setup_s"]
    assert cells.load_end_to_end("get_mib_s")["op"] == "GET"
    for cell in PUT_CELLS:
        assert cells.reports(e2e["put_mib_s"], cell)
        assert not cells.reports(e2e["get_mib_s"], cell)
    assert cells.reports(e2e["get_mib_s"], CELL)
    assert not cells.reports(e2e["put_mib_s"], CELL)
    # nothing of a PUT cell but that list has changed
    assert e2e["put_mib_s"]["bound"] == e2e["setup_s"]["bound"] == 0.25
    assert [m["name"] for m in bench["end_to_end"]][:2] == ["put_mib_s",
                                                            "setup_s"]


def test_the_rate_of_gets_is_spread_over_each_operation():
    ops = [["GET", "pre/0000", 1.0, 3.0, "ok", 4 << 20, ""],
           ["GET", "pre/0001", 2.5, 3.5, "wrong", 0, "body differs"],
           ["PUT", "w00/000000", 1.0, 2.0, "ok", 4 << 20, ""]]
    assert run.end_to_end("get_mib_s", {"ops": ops, "t0": 2.0, "t1": 4.0}) \
        == pytest.approx(1.0)           # half of 4 MiB inside 2 s


# -- the de-framer's work -------------------------------------------------------

@pytest.mark.parametrize("k,m", [(8, 4), (4, 2)])
def test_deframe_work_against_a_hand_count(k, m):
    block, blocks = 1 << 20, 10
    w = work.deframe_work(k, m, block, blocks)
    # per block: k pieces of block/k bytes behind a 32-byte digest each
    # are read, one verdict byte a piece is written; the payload is
    # hashed once; parity is not touched, so m is nowhere
    read = k * (32 + block // k)
    assert w["bytes"] == blocks * (read + k)
    assert w["bytes"] == {8: 10 * (1048576 + 256 + 8),
                          4: 10 * (1048576 + 128 + 4)}[k]
    assert w["ops"] == blocks * block * (4 * 12 + 2 * 28) / 32
    assert w == work.deframe_work(k, m + 3, block, blocks)
    cfg = {"data_shards": k, "parity_shards": m, "erasure_block_bytes": block}
    least = work.least_seconds("deframe", cfg, blocks,
                               cells.load_peaks()["TPU v5 lite"])
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(w["bytes"] / 819e9)


# -- the readers ----------------------------------------------------------------

@pytest.fixture(scope="module")
def ctx():
    out = {"drives": 12, "workers": 32}
    for key, name in (("scrape_a", "scrape_get_a.txt"),
                      ("scrape_b", "scrape_get_b.txt")):
        with open(os.path.join(DATA, name)) as f:
            out[key] = parse_scrape(f.read())
    return out


def layer(ctx, name):
    return readers.read_layer(ctx, cells.load_layer(name))


def delta(ctx, series, **labels):
    return readers.series_sum(ctx["scrape_b"], "minio_tpu_" + series,
                              labels) \
        - readers.series_sum(ctx["scrape_a"], "minio_tpu_" + series, labels)


def test_every_get_metric_is_in_benchmark_json_with_its_file():
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, where in GET_LAYERS.items():
        m = by_name[name]
        assert m["moves"] == "get_mib_s" and m["workloads"][0] == CELL
        assert m["layer"] == where
        spec = cells.load_layer(name)
        assert spec["what"]
        assert "read" in spec or spec["reader"] in readers.GENERIC
    # appended: what was there keeps its place, and moves what it moved
    names = [m["name"] for m in bench["per_layer"]]
    assert min(names.index(n) for n in GET_LAYERS) == \
        names.index("drive.direct_stream_share.put") + 1
    first = min(names.index(n) for n in GET_LAYERS)
    assert all(m["moves"] == "put_mib_s" and CELL not in m["workloads"]
               for m in bench["per_layer"][:first])


def test_the_recorded_pair_reads_the_expected_numbers(ctx):
    gets = delta(ctx, "api_request_duration_seconds_count", api="GET:object")
    window_s = readers.scraped_seconds(ctx["scrape_a"], ctx["scrape_b"])
    assert gets == 6 and window_s == pytest.approx(1.1)
    assert layer(ctx, "frontend.get_ms") == pytest.approx(
        (3.008928 - 0.716041) / 6 * 1000)
    # 40 MiB = a 32-block and an 8-block window a GET, all on the device
    assert delta(ctx, "batcher_requests_total", route="get",
                 path="device") == 12
    assert layer(ctx, "batcher.get_device_share") == pytest.approx(100.0)
    assert layer(ctx, "batcher.get_fill_ratio") == pytest.approx(
        (280 - 40) / (280 - 40) * 100)
    assert layer(ctx, "batcher.get_wait_ms") == pytest.approx(
        (0.120871 - 3.9e-05) / 12 * 1000)
    assert layer(ctx, "lane.busy_share.get") == pytest.approx(
        (3.135034 - 2.60209) / 1.1 * 100)
    assert layer(ctx, "drive.ops_in_service.get") == pytest.approx(
        delta(ctx, "drive_op_duration_seconds_sum") / (12 * 1.1))
    assert 0 < layer(ctx, "drive.ops_in_service.get") < 1
    assert layer(ctx, "frontend.process_cores.get") == pytest.approx(
        (11.59 - 10.0) / 1.1)


def test_a_demoted_verify_shows_in_the_device_share(ctx):
    """`reconstruct` stands beside `get` below the line: windows that a
    failed verify sent to the rebuild path on the host pull it down."""
    b = {k: dict(v) for k, v in ctx["scrape_b"].items()}
    key = frozenset({("route", "reconstruct"), ("path", "host")})
    assert key in b["minio_tpu_batcher_requests_total"]
    b["minio_tpu_batcher_requests_total"][key] += 4
    got = layer({**ctx, "scrape_b": b}, "batcher.get_device_share")
    assert got == pytest.approx(12 / 16 * 100)


def test_the_lane_stages_are_not_entered_on_the_read_path(ctx):
    """Why there is no lane.upload/kernel/readback_share.get: the
    de-framer's dispatch does not go through `_lane_round_trip`, so
    between two scrapes with GETs alone the lane's seconds move and its
    stages do not: a `.get` twin of the `.put` file would read 0."""
    assert delta(ctx, "kernel_lane_op_duration_seconds_sum") > 0.5
    for stage in ("lane.upload", "lane.kernel", "lane.readback"):
        assert delta(ctx, "stage_entries_total", stage=stage) == 0
    assert layer(ctx, "lane.upload_share.put") == 0    # a misleading 0


def test_the_generators_cpu_share_is_read_from_what_they_send_back(ctx):
    assert layer(ctx, "loadgen.cpu_share.get") is None
    got = layer({**ctx, "loadgen": {"cpu_s": 3 * 5.0, "wall_s": 3 * 20.0}},
                "loadgen.cpu_share.get")
    assert got == pytest.approx(25.0)


def test_the_deframe_roofline_on_a_reduction_made_by_hand(ctx):
    cfg = cells.load_config("ec8p4-12d")
    tr = {"chips": 1, "window_s": 10.0, "busy_s": 0.05,
          "modules": [{"name": "jit_verify32", "device_s": 0.04,
                       "count": 9}],
          "idle_gaps": [["s3.GET:object", 300.0],
                        ["no host span (device idle, nothing named)", 0.5]]}
    c = {**ctx, "trace": tr, "config": cfg,
         "peaks": cells.load_peaks()["TPU v5 lite"],
         "payload_mib_s": {"GET": 200.0, "PUT": 0.0}}
    got = layer(c, "kernel.deframe_roofline")
    least = 200 * (1048576 + 256 + 8) / 819e9       # seconds a second
    assert got == pytest.approx(least / (0.04 / 10.0) * 100)
    assert 0 < got < 100
    assert c["notes"]["deframe_roofline"]["bound"] == "bytes"
    assert layer(c, "device.idle_share.get") == pytest.approx(99.5)
    assert layer(c, "host.unnamed_idle_share.get") == pytest.approx(
        0.5 / 9.95 * 100)
    # no GET rode the device: nothing to read, never a 0
    c["payload_mib_s"] = {"GET": 0.0}
    assert layer(c, "kernel.deframe_roofline") is None
    # work rode the device and no program is in the trace: an error
    c["payload_mib_s"] = {"GET": 200.0}
    c["trace"] = {**tr, "modules": []}
    with pytest.raises(LookupError):
        layer(c, "kernel.deframe_roofline")


# -- rot on a drive ---------------------------------------------------------------

def test_rot_is_planted_in_data_shard_0_and_nowhere_else(tmp_path):
    cfg = cells.load_config("ec4p2-6d")
    body = traffic.Bodies(2**31 + 7, 2 << 20, 2).body("pre/0001")
    want = compare.reference_shard_files(body, 4, 2, 1 << 20)
    # which drive holds which shard is the program's business
    for d, i in zip(range(1, 7), (3, 5, 0, 1, 4, 2)):
        path = tmp_path / f"d{d}" / "bench" / "pre/0001" / "uuid"
        path.mkdir(parents=True)
        (path / "part.1").write_bytes(want[i])

    class Srv:
        drive_root = str(tmp_path)
    where = run.plant_rot(Srv, cfg, "pre/0001", body)
    assert where == str(tmp_path / "d3/bench/pre/0001/uuid/part.1")
    with open(where, "rb") as f:
        got = f.read()
    assert got[32] == want[0][32] ^ 0x01
    assert got[:32] + want[0][32:33] + got[33:] == want[0]
    assert compare.check_object_on_disk(
        str(tmp_path), cfg, "bench", "pre/0001", body) == \
        {"right": 5, "wrong": 1}
    with pytest.raises(run.ServerError):
        run.plant_rot(Srv, cfg, "pre/0002", body)


# -- the wire fault ----------------------------------------------------------------

class _Stub(http.server.BaseHTTPRequestHandler):
    """Answers GET /bench/<key> with the key's seeded body and ETag."""
    protocol_version = "HTTP/1.1"
    bodies = None

    def do_GET(self):                                    # noqa: N802
        body = self.bodies.body(self.path.split("/", 2)[2])
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.send_header("ETag", f'"{hashlib.md5(body).hexdigest()}"')
        self.end_headers()
        self.wfile.write(body)

    def do_HEAD(self):                                   # noqa: N802
        self.send_response(404)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def log_message(self, *a):
        pass


def test_the_proxy_turns_one_byte_of_one_get_and_a_generator_sees_it():
    size, seed = 3 << 20, 2**31 + 11
    mix = {**traffic.load_mix("get-64m"), "size": size}
    bodies = traffic.Bodies(seed, size, mix["bodies"])
    _Stub.bodies = bodies
    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    proxy = faults.hooks_for("flip_get_byte")["proxy"](
        "127.0.0.1:%d" % httpd.server_address[1])
    try:
        spec = {"mix": mix, "seed": seed, "address": proxy.address,
                "bucket": traffic.BUCKET, "timeout": 20, "workers_total": 2}
        w = loadgen.Worker(spec, 0, bodies)
        key = traffic.pre_key(3)
        assert w.op("GET", key) == ("ok", size, "")        # not armed yet
        assert w.op("GONE", key)[0] == "ok"                # a HEAD goes by
        proxy.arm()
        assert w.op("STAT", key)[0] == "wrong"             # the stub's 404
        assert proxy.flipped is None                       # no GET, no flip
        assert w.op("GET", key) == ("wrong", 0, "body differs")
        assert proxy.flipped == {"path": f"/{traffic.BUCKET}/{key}",
                                 "offset": size // 2}
        # one byte, by one bit; every later GET goes by untouched
        assert w.buf[size // 2] == bodies.body(key)[size // 2] ^ 0x01
        assert w.op("GET", key) == ("ok", size, "")
        assert w.op("GET", traffic.pre_key(4)) == ("ok", size, "")
        w.cli.close()
    finally:
        proxy.close()
        httpd.shutdown()
        httpd.server_close()


# -- a configuration's own module -----------------------------------------------------

def test_a_configuration_without_a_module_has_none():
    bench = cells.load_benchmark()
    for name in PUT_CELLS + [CELL]:
        assert cells.load_cell(name, bench)["module"] is None


def test_a_configuration_may_bring_a_module_of_its_own(tmp_path, monkeypatch):
    shutil.copytree(os.path.join(HERE, "configs"), tmp_path / "configs")
    cfg = cells.load_config("ec4p2-6d")
    cfg.update(name="toy-3d", drives=3, data_shards=2, parity_shards=1,
               write_quorum=2)
    (tmp_path / "configs" / "toy-3d.json").write_text(json.dumps(cfg))
    shutil.copy(os.path.join(DATA, "toy_config.py"),
                tmp_path / "configs" / "toy-3d.py")
    monkeypatch.setattr(cells, "HERE", str(tmp_path))
    bench = cells.load_benchmark()
    bench["workloads"].append({"name": "toy-3d.get-64m", "config": "toy-3d",
                               "traffic": "get-64m", "chips": 1})
    loaded = cells.load_cell("toy-3d.get-64m", bench)
    mod = loaded["module"]
    assert loaded["config"]["drives"] == 3 and mod is not None
    mod.after_preload(None, loaded["config"], None)
    assert mod.CALLS == [("after_preload", "toy-3d")]
    # its reference stands in compare's place: a 1000-byte body that
    # compare.reference_shard_files would refuse (no whole block)
    body = bytes(range(250)) * 4
    with pytest.raises(ValueError):
        compare.check_object_on_disk(str(tmp_path), loaded["config"],
                                     "bench", "pre/0000", body)
    want = mod.reference_shard_files(body, loaded["config"])
    for d, data in zip((1, 2, 3), (want[2], want[0], b"not a shard")):
        path = tmp_path / f"d{d}" / "bench" / "pre/0000" / "uuid"
        path.mkdir(parents=True)
        (path / "part.1").write_bytes(data)
    got = compare.check_object_on_disk(
        str(tmp_path), loaded["config"], "bench", "pre/0000", body,
        reference=mod.reference_shard_files)
    assert got == {"right": 2, "wrong": 1}
    assert mod.CALLS[-1] == ("reference_shard_files", 1000)

"""The degraded read (PR 34), on the CPU: a configuration's drives may
be dead when the server stops — the stop's arithmetic, how a drive
dies and what counts as touching it, the placement rule and the loss
patterns of the cell's keys, the rebuild's work by hand, where the rot
goes on a node with drives dead, the four readers and the
configuration's own notes on a recorded pair of scrapes (a CPU boot of the cell at the
slow test's sizes: 16 MiB objects, 4 workers, drives 2, 5, 9 dead, 24
GETs between them), the serial read-back, and where BENCHMARK.json
lists the cell."""

import collections
import json
import os

import pytest

from benchmark import cells, compare, readers, run, serve_deaf, traffic, work
from benchmark.reference import layout
from benchmark.server import Server, parse_scrape

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "ec8p4-12d-3dead.degraded-get-64m"
HEALTHY = "ec8p4-12d.get-64m"
SHARED = ["frontend.get_ms", "batcher.get_device_share",
          "batcher.get_fill_ratio", "lane.busy_share.get",
          "device.idle_share.get", "host.unnamed_idle_share.get",
          "drive.ops_in_service.get", "frontend.process_cores.get",
          "loadgen.cpu_share.get"]
OWN = {"kernel.reconstruct_roofline": "Kernels",
       "batcher.reconstruct_wait_ms": "Batcher",
       "object.rebuilt_window_share.get": "Object layer",
       "heal.mrf_pending.get": "Healing"}


# -- the stop ------------------------------------------------------------------

@pytest.mark.parametrize("code,stamped,dead,unclean", [
    (0, 12, None, 0),          # no key: exactly as before
    (0, 11, None, 1),
    (1, 12, None, 1),
    (0, 12, [], 0),
    (0, 9, [2, 5, 9], 0),      # every drive that is alive stamped
    (0, 12, [2, 5, 9], 1),     # a stamp on a dead drive: it was made again
    (0, 8, [2, 5, 9], 1),
    (1, 9, [2, 5, 9], 1),
])
def test_the_stop_counts_the_drives_that_are_alive(code, stamped, dead,
                                                   unclean):
    cfg = {"drives": 12}
    if dead is not None:
        cfg["dead_drives"] = dead
    assert run.stop_is_unclean(code, stamped, cfg) == unclean


# -- how a drive dies, and what touches it -----------------------------------------

def _server(tmp_path, drives=4):
    """A Server's drive helpers without its subprocess."""
    srv = object.__new__(Server)
    srv.drive_root = str(tmp_path / "drives")
    for d in range(1, drives + 1):
        sys_dir = tmp_path / "drives" / f"d{d}" / ".mtpu.sys"
        sys_dir.mkdir(parents=True)
        (sys_dir / "format.json").write_text("{}")
        obj = tmp_path / "drives" / f"d{d}" / "bench" / "pre" / "0000" / "u"
        obj.mkdir(parents=True)
        (obj / "part.1").write_bytes(b"shard")
    return srv


def test_a_dead_drive_is_a_file_where_its_root_was(tmp_path):
    srv = _server(tmp_path)
    srv.kill_drive(2)
    path = srv.drive_path(2)
    assert os.path.isfile(path) and os.path.getsize(path) == 0
    assert not os.path.exists(tmp_path / "gone-d2")
    # every call on the drive fails, and nothing makes the root again
    with pytest.raises(NotADirectoryError):
        os.makedirs(os.path.join(path, ".mtpu.sys"), exist_ok=True)
    with pytest.raises(NotADirectoryError):
        open(os.path.join(path, "bench", "pre", "0000", "u", "part.1"), "rb")
    with pytest.raises(FileExistsError):
        os.makedirs(path)
    assert os.path.isdir(srv.drive_path(1))
    assert srv.stamped_clean() == 0
    # the control's drive: gone, and the path free
    srv.kill_drive(3, block=False)
    assert not os.path.lexists(srv.drive_path(3))
    os.makedirs(os.path.join(srv.drive_path(3), ".mtpu.sys"))


def test_what_counts_as_touching_a_dead_drive(tmp_path):
    srv = _server(tmp_path, drives=6)
    dead = [1, 2, 3, 4, 5]
    for d in dead:
        srv.kill_drive(d, block=d != 5)
    assert srv.drives_touched(dead) == 0          # files, and one free path
    os.unlink(srv.drive_path(1))
    os.makedirs(srv.drive_path(1))                # a root made again
    assert srv.drives_touched(dead) == 1
    os.unlink(srv.drive_path(2))
    os.makedirs(os.path.join(srv.drive_path(2), ".mtpu.sys"))
    with open(os.path.join(srv.drive_path(2), ".mtpu.sys",
                           "clean.shutdown"), "w") as f:
        f.write("stamp")                          # a stamp inside it
    assert srv.drives_touched(dead) == 2
    assert srv.stamped_clean() == 1
    with open(srv.drive_path(3), "w") as f:
        f.write("x")                              # the file holds something
    assert srv.drives_touched(dead) == 3
    os.makedirs(os.path.join(srv.drive_path(5), "bench"))   # a healed shard's
    assert srv.drives_touched(dead) == 4                    # directory
    assert srv.drives_touched([4]) == 0 and srv.drives_touched([]) == 0


# -- the placement rule and the cell's loss patterns ---------------------------------

def test_the_cells_keys_fall_into_ten_loss_patterns():
    cfg = cells.load_config("ec8p4-12d-3dead")
    mix = traffic.load_mix("degraded-get-64m")
    n, k, dead = cfg["drives"], cfg["data_shards"], cfg["dead_drives"]
    assert (n, k, dead) == (12, 8, [2, 5, 9])
    keys = [traffic.pre_key(i) for i in range(mix["preload"])]
    patterns = collections.Counter(
        layout.lost_shards(traffic.BUCKET, key, n, dead) for key in keys)
    assert len(patterns) == 10
    assert sorted(patterns.values()) == [1, 1, 3, 3, 3, 4, 4, 4, 4, 5]
    lost = collections.Counter(
        layout.lost_data_shards(traffic.BUCKET, key, n, k, dead)
        for key in keys)
    # none loses parity alone: every window of every GET is rebuilt
    assert lost == {2: 25, 3: 4, 1: 3}
    assert sum(d * c for d, c in lost.items()) / 32 == pytest.approx(2.03125)


def test_the_placement_is_a_rotation_by_the_keys_crc():
    order = layout.hash_order("bench/pre/0000", 12)
    assert sorted(order) == list(range(1, 13))
    assert all(order[(i + 1) % 12] == order[i] % 12 + 1 for i in range(12))
    # crc32("bench/pre/0000") % 12 == 3: drive 1 holds shard 4, drive 2
    # shard 5 (index 4), drive 5 index 7, drive 9 index 11
    assert order[0] == 4
    assert layout.lost_shards("bench", "pre/0000", 12, [2, 5, 9]) == \
        (4, 7, 11)
    assert layout.lost_data_shards("bench", "pre/0000", 12, 8,
                                   [2, 5, 9]) == 2
    assert layout.lost_data_shards("bench", "pre/0000", 12, 8, []) == 0
    assert layout.hash_order("bench/pre/0000", 6) != order[:6]


# -- the rebuild's work ---------------------------------------------------------------

def _rebuild_work():
    return cells._load_module("layers", "kernel.reconstruct_roofline") \
        .rebuild_work


@pytest.mark.parametrize("lost", [1, 2, 3])
def test_the_rebuilds_work_against_a_hand_count(lost):
    rebuild_work = _rebuild_work()
    k, m, block, blocks = 8, 4, 1 << 20, 10
    w = rebuild_work(k, m, block, blocks, lost)
    # per block: k surviving pieces of 128 KiB, verified and un-framed
    # by the host, are read, `lost` pieces of 128 KiB are written
    assert w["bytes"] == blocks * (8 * 131072 + lost * 131072)
    assert w["bytes"] == {1: 10 * 1179648, 2: 10 * 1310720,
                          3: 10 * 1441792}[lost]
    # k multiply-accumulates a rebuilt byte, and no hash: the verify is
    # not the work of the route this metric names
    assert w["ops"] == blocks * lost * 131072 * 8
    assert w == rebuild_work(k, m + 3, block, blocks, lost)
    # the mean over a window's GETs need not be whole
    mid = rebuild_work(k, m, block, blocks, 2.03125)
    assert rebuild_work(k, m, block, blocks, 2)["bytes"] \
        < mid["bytes"] < rebuild_work(k, m, block, blocks, 3)["bytes"]
    # the kind is the metric's own: the harness's table learns it from
    # the reader, and the parent's kinds are as they were
    assert set(work.WORK) - {"reconstruct"} == {"frame", "deframe"}


# -- the readers ------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ctx():
    out = {"drives": 9, "workers": 4}
    for key, name in (("scrape_a", "scrape_degraded_a.txt"),
                      ("scrape_b", "scrape_degraded_b.txt")):
        with open(os.path.join(DATA, name)) as f:
            out[key] = parse_scrape(f.read())
    return out


def layer(ctx, name):
    return readers.read_layer(ctx, cells.load_layer(name))


def test_the_recorded_pair_reads_the_expected_numbers(ctx):
    assert readers.scraped_seconds(ctx["scrape_a"], ctx["scrape_b"]) == \
        pytest.approx(3.0)
    # 24 GETs of 16 MiB = 24 windows of 16 blocks, every one rebuilt
    assert layer(ctx, "object.rebuilt_window_share.get") == \
        pytest.approx((39 - 15) / (39 - 15) * 100)
    assert layer(ctx, "batcher.reconstruct_wait_ms") == pytest.approx(
        (0.011642 - 0.00406) / (37 - 13) * 1000)
    assert layer(ctx, "heal.mrf_pending.get") == 4
    # the denominator of the drives' metric is the drives alive
    busy = sum(ctx["scrape_b"]["minio_tpu_drive_op_duration_seconds_sum"]
               .values()) \
        - sum(ctx["scrape_a"]["minio_tpu_drive_op_duration_seconds_sum"]
              .values())
    assert layer(ctx, "drive.ops_in_service.get") == pytest.approx(
        busy / (9 * 3.0))
    assert layer(ctx, "frontend.get_ms") == pytest.approx(
        (16.906726 - 6.700701) / 24 * 1000)
    # route get moves too, though no GET window rides it: the heals that
    # the rebuilt windows queued verify what they can through `get:1+0`
    assert layer(ctx, "batcher.get_device_share") == pytest.approx(100.0)


def test_a_window_served_healthy_pulls_the_rebuilt_share_down(ctx):
    b = {k: dict(v) for k, v in ctx["scrape_b"].items()}
    b["minio_tpu_get_kernel_windows_total"][
        frozenset({("path", "device")})] += 8
    got = layer({**ctx, "scrape_b": b}, "object.rebuilt_window_share.get")
    assert got == pytest.approx(24 / 32 * 100)


def test_a_gauge_is_read_at_one_scrape_and_absent_is_nothing(ctx):
    spec = cells.load_layer("heal.mrf_pending.get")
    assert spec["reader"] == "prometheus_gauge" and spec["at"] == "scrape_b"
    assert readers.read_layer(ctx, {**spec, "at": "scrape_a"}) == 4
    b = {k: v for k, v in ctx["scrape_b"].items()
         if k != "minio_tpu_mrf_pending"}
    assert layer({**ctx, "scrape_b": b}, "heal.mrf_pending.get") is None
    assert layer({"scrape_a": ctx["scrape_a"]}, "heal.mrf_pending.get") \
        is None
    # a gauge that reads 0 is a reading
    b["minio_tpu_mrf_pending"] = {frozenset(): 0.0}
    assert layer({**ctx, "scrape_b": b}, "heal.mrf_pending.get") == 0


def test_the_configurations_notes_are_its_own(ctx):
    """What only this deployment's result line says (`cell.config_notes`)
    comes from its module: run.py names none of these series or routes."""
    notes = cells.load_config_module("ec8p4-12d-3dead").cell_notes
    cfg = cells.load_config("ec8p4-12d-3dead")
    device = {"calibration": [
        {"name": "get:8+4", "route": "get", "device_ms": 3.0, "host_ms": 5.0},
        {"name": "rec:8+4:1,2,3,5,6,8,9,10", "route": "reconstruct",
         "device_ms": 4.1, "host_ms": 12.5},
        {"name": "rec:8+4:0,1,2,3,5,6,7,8", "route": "reconstruct",
         "verdict": "probing"}]}
    got = notes(cfg, ctx["scrape_a"], ctx["scrape_b"], device)
    assert got == {
        "offline_by_the_program_at_t0": 0,
        "get_windows_by_path": {"native": 0, "numpy": 24, "demoted": 0,
                                "device": 0},
        "probes_ms": {"rec:8+4:1,2,3,5,6,8,9,10": [4.1, 12.5],
                      "rec:8+4:0,1,2,3,5,6,7,8": [None, None]}}
    assert notes(cfg, {}, {}, {}) == {
        "offline_by_the_program_at_t0": 0, "get_windows_by_path": {},
        "probes_ms": {}}
    with open(run.__file__) as f:
        harness = f.read()
    for theirs in ("minio_tpu_drives_offline",
                   "minio_tpu_get_kernel_windows_total", '"demoted"'):
        assert theirs not in harness, theirs


def test_the_reconstruct_roofline_on_a_reduction_made_by_hand(ctx):
    cfg = cells.load_config("ec8p4-12d-3dead")
    tr = {"chips": 1, "window_s": 10.0, "busy_s": 0.2,
          "modules": [{"name": "jit_verify32", "device_s": 0.12, "count": 90},
                      {"name": "jit_matrix_apply", "device_s": 0.06,
                       "count": 40}]}
    size = 64 << 20
    # pre/0000 loses 2 data shards, pre/0002 3, pre/0009 1; the third
    # GET lies half inside the window and weighs half
    ops = [["GET", "pre/0000", 1.0, 3.0, "ok", size, ""],
           ["GET", "pre/0002", 2.0, 4.0, "ok", size, ""],
           ["GET", "pre/0009", 9.0, 11.0, "ok", size, ""],
           ["GET", "pre/0007", 2.0, 4.0, "wrong", 0, "body differs"],
           ["GET", "pre/0007", 12.0, 14.0, "ok", size, ""]]
    lost = [layout.lost_data_shards("bench", k, 12, 8, [2, 5, 9])
            for k in ("pre/0000", "pre/0002", "pre/0009")]
    assert lost == [2, 3, 1]
    inside = run.inside_by_key(ops, "GET", 0.0, 10.0)
    assert inside == {"pre/0000": size, "pre/0002": size,
                      "pre/0009": size / 2}
    c = {**ctx, "trace": tr, "config": cfg,
         "payload_by_key": {"GET": inside, "PUT": {}},
         "peaks": cells.load_peaks()["TPU v5 lite"],
         "payload_mib_s": {"GET": 160.0, "PUT": 0.0}}
    got = layer(c, "kernel.reconstruct_roofline")
    mean = (2 + 3 + 0.5 * 1) / 2.5
    assert c["notes"]["reconstruct_roofline"]["lost"] == pytest.approx(mean)
    least = 160 * (1048576 + mean * 131072) / 819e9
    assert got == pytest.approx(least / (0.18 / 10.0) * 100)
    assert c["notes"]["reconstruct_roofline"]["least_s_per_s"] == \
        pytest.approx(least)
    assert 0 < got < 100
    assert c["notes"]["reconstruct_roofline"]["bound"] == "bytes"
    # half of the rebuilds went to the host codec: half the work rode
    b = {k: dict(v) for k, v in ctx["scrape_b"].items()}
    b["minio_tpu_batcher_requests_total"][
        frozenset({("route", "reconstruct"), ("path", "bypass")})] += 24
    assert layer({**c, "scrape_b": b}, "kernel.reconstruct_roofline") == \
        pytest.approx(got / 2)
    # none did, no GET in the window, or a configuration whose drives
    # all live: nothing to read, never a 0
    b["minio_tpu_batcher_requests_total"][
        frozenset({("route", "reconstruct"), ("path", "device")})] = 13
    assert layer({**c, "scrape_b": b}, "kernel.reconstruct_roofline") is None
    assert layer({**c, "payload_by_key": {"GET": run.inside_by_key(
        ops[-1:], "GET", 0.0, 10.0)}}, "kernel.reconstruct_roofline") is None
    healthy = cells.load_config("ec8p4-12d")
    assert layer({**c, "config": healthy}, "kernel.reconstruct_roofline") \
        is None


# -- the serial read-back -------------------------------------------------------------------

class _Cli:
    """Answers object GETs from the seeded bodies (one of them wrong)
    and admin info with a route that probes for the first two asks."""

    def __init__(self, bodies, wrong_key):
        self.bodies, self.wrong_key = bodies, wrong_key
        self.log, self.probing = [], 0

    def request(self, method, path, **kw):
        self.log.append(path)
        if path == "/minio/admin/v3/info":
            verdict = "probing" if self.probing > 0 else "device"
            self.probing -= 1
            return 200, {}, json.dumps({"device": {"calibration": [
                {"route": "reconstruct", "verdict": verdict}]}}).encode()
        key = path.split("/", 2)[2]
        body = self.bodies.body(key)
        etag = self.bodies.parts(key)[3]
        if key == self.wrong_key:
            body = body[:100] + bytes([body[100] ^ 1]) + body[101:]
        self.probing = 2          # a first window starts a probe
        return 200, {"etag": f'"{etag}"'}, body


def test_the_read_back_is_one_get_at_a_time_and_waits_out_each_probe(
        monkeypatch):
    monkeypatch.setattr(run.time, "sleep", lambda s: None)
    mix = {"preload": 3, "size": 1 << 20, "bodies": 2}
    bodies = traffic.Bodies(2**31 + 13, mix["size"], mix["bodies"])
    cli = _Cli(bodies, "pre/0001")
    assert run.read_back_degraded(cli, bodies, mix) == 1
    info = "/minio/admin/v3/info"
    assert cli.log == ["/bench/pre/0000", info, info, info,
                       "/bench/pre/0001", info, info, info,
                       "/bench/pre/0002", info, info, info]


def test_the_ladder_is_the_mixes_own_as_in_every_cell():
    """After the serial read-back the degraded cell climbs the ladder
    every cell climbs: bursts of the first n workers, n = 1 .. the
    mix's `warm_ladder`; the harness knows no loss pattern."""
    mix = traffic.load_mix("degraded-get-64m")

    class Gens:
        sent = []

        def send(self, req):
            self.sent.append((req["op"], req["n"]))

        def collect(self):
            pass
    monkey = pytest.MonkeyPatch()
    monkey.setattr(run, "scrape", lambda cli: {})
    try:
        run.warm_ladder(None, Gens(), mix)
        assert Gens.sent == [("GET", n) for n in range(1, 9)]
    finally:
        monkey.undo()
    assert not hasattr(run, "loss_groups") and not hasattr(run, "layout")


# -- rot on a living drive of a degraded node --------------------------------------------------

def _drives_with(tmp_path, cfg, key, body, dead):
    """The reference's shard files of `body` where the placement rule
    puts them, the `dead` drives' left out. -> (Srv, {drive: shard})"""
    n, k = cfg["drives"], cfg["data_shards"]
    want = compare.reference_shard_files(body, k, cfg["parity_shards"],
                                         cfg["erasure_block_bytes"])
    order = layout.hash_order(f"bench/{key}", n)
    held = {}
    for d in range(1, n + 1):
        if d in dead:
            (tmp_path / f"d{d}").write_bytes(b"")     # a dead drive: a file
            continue
        where = tmp_path / f"d{d}" / "bench" / key / "uuid"
        where.mkdir(parents=True)
        (where / "part.1").write_bytes(want[order[d - 1] - 1])
        held[d] = order[d - 1] - 1

    class Srv:
        drive_root = str(tmp_path)
    return Srv, held, want


def test_rot_goes_into_the_first_data_shard_a_living_drive_holds(tmp_path):
    cfg = cells.load_config("ec8p4-12d-3dead")
    mix = traffic.load_mix("degraded-get-64m")
    assert mix["rotten"] == [7] and "rot_noticed_by" not in mix
    key = traffic.pre_key(mix["rotten"][0])
    # its data shard 0 lay on a dead drive: with the rot, 8 of 12 left
    assert layout.lost_shards("bench", key, 12, cfg["dead_drives"]) == \
        (0, 4, 9)
    body = traffic.Bodies(2**31 + 5, 2 << 20, 2).body(key)
    Srv, held, want = _drives_with(tmp_path, cfg, key, body,
                                   cfg["dead_drives"])
    where = run.plant_rot(Srv, cfg, key, body)
    drive = int(os.path.relpath(where, Srv.drive_root).split(os.sep)[0][1:])
    assert held[drive] == 1
    with open(where, "rb") as f:
        got = f.read()
    shard = want[1]
    assert got[:32] == shard[:32] and got[33:] == shard[33:]
    assert got[32] == shard[32] ^ 1
    # every other file is as it was
    for d, s_ in held.items():
        if d != drive:
            with open(compare.shard_files_on_disk(
                    Srv.drive_root, 12, "bench", key)[d], "rb") as f:
                assert f.read() == want[s_]


def test_with_every_drive_alive_the_rot_is_in_data_shard_0(tmp_path):
    cfg = cells.load_config("ec8p4-12d")
    body = traffic.Bodies(2**31 + 6, 2 << 20, 2).body("pre/0007")
    Srv, held, want = _drives_with(tmp_path, cfg, "pre/0007", body, [])
    where = run.plant_rot(Srv, cfg, "pre/0007", body)
    drive = int(os.path.relpath(where, Srv.drive_root).split(os.sep)[0][1:])
    assert held[drive] == 0


def test_the_deaf_verify_hands_back_what_the_digests_refuse():
    """serve_deaf.py's un-framing: the payload behind the digests, which
    nobody looks at; a blob of another length is still no shard."""
    shard_size, data = 8, bytes(range(20))
    blob = b"".join(b"D" * 32 + data[i:i + shard_size]
                    for i in range(0, len(data), shard_size))
    assert bytes(serve_deaf.unframed(blob, shard_size, len(data))) == data
    whole = b"".join(b"D" * 32 + data[i:i + 8] for i in (0, 8))
    assert bytes(serve_deaf.unframed(whole, 8, 16)) == data[:16]
    assert serve_deaf.unframed(blob[:-1], shard_size, len(data)) is None
    assert serve_deaf.unframed(None, shard_size, len(data)) is None


def test_a_mix_opts_into_the_programs_word_that_it_noticed():
    get = traffic.load_mix("get-64m")
    assert get["rot_noticed_by"] == {
        "name": "windows_demoted",
        "series": "minio_tpu_get_kernel_windows_total",
        "labels": {"path": "demoted"}}
    assert not hasattr(run, "DEMOTED")


def test_a_connection_idle_for_a_minute_is_opened_anew():
    import http.server
    import threading

    from benchmark import s3client

    class Ok(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_GET(self):                                # noqa: N802
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"ok")

        def log_message(self, *a):
            pass
    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Ok)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    cli = s3client.S3("127.0.0.1:%d" % httpd.server_address[1], timeout=5)
    try:
        assert cli.request("GET", "/b/k")[0] == 200
        conn = cli._conn
        assert cli.request("GET", "/b/k")[0] == 200 and cli._conn is conn
        cli._used -= s3client.IDLE_S + 1       # the server may have reaped it
        assert cli.request("GET", "/b/k")[0] == 200
        assert cli._conn is not conn
    finally:
        cli.close()
        httpd.shutdown()
        httpd.server_close()


# -- BENCHMARK.json ----------------------------------------------------------------------------

def test_discovery_finds_every_file_of_the_cell():
    bench = cells.load_benchmark()
    loaded = cells.load_cell(CELL, bench)
    assert loaded["cell"]["chips"] == 1
    cfg, healthy = loaded["config"], cells.load_config("ec8p4-12d")
    assert cfg["dead_drives"] == [2, 5, 9]
    for key in ("server_argv", "drives", "data_shards", "parity_shards",
                "erasure_block_bytes", "write_quorum", "chips", "bitrot",
                "reduced"):
        assert cfg[key] == healthy[key], key
    assert "dead_drives" not in healthy
    assert all(cfg["guarantees"][k] == v
               for k, v in healthy["guarantees"].items())
    assert "nothing is written to a dead drive" in \
        cfg["guarantees"]["degraded_read"]
    assert callable(loaded["module"].after_preload)
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["speedtest_duration_s"]
    assert callable(loaded["module"].cell_notes)
    # the mix is get-64m, rot and all, less the counter that says the
    # `get` route noticed it: no window of this cell rides that route
    mix, get = loaded["mix"], traffic.load_mix("get-64m")
    assert {k: v for k, v in mix.items()
            if k not in ("source", "assumed")} == \
        {k: v for k, v in get.items()
         if k not in ("source", "assumed", "rot_noticed_by")}


def test_benchmark_json_lists_the_cell_where_it_finds_something_to_read():
    bench = cells.load_benchmark()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["get_mib_s"]["workloads"] == [HEALTHY, CELL]
    assert e2e["get_mib_s"]["bound"] == 0.22
    assert CELL not in e2e["put_mib_s"]["workloads"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in SHARED:
        assert by_name[name]["workloads"] == [HEALTHY, CELL], name
    # no window of it rides route `get`
    for name in ("kernel.deframe_roofline", "batcher.get_wait_ms"):
        assert by_name[name]["workloads"] == [HEALTHY], name
    for name, where in OWN.items():
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "get_mib_s"
        assert m["layer"] == where
        spec = cells.load_layer(name)
        assert spec["what"]
        assert "read" in spec or spec["reader"] in readers.GENERIC
    assert by_name["heal.mrf_pending.get"]["better"] == "lower"
    # appended: what was there keeps its place
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-4:] == list(OWN)
    assert sorted(m["name"] for m in bench["per_layer"]
                  if CELL in m["workloads"]) == sorted(SHARED + list(OWN))


def test_no_shipped_file_pins_a_route():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for sub in ("configs", "traffic", "layers", "end_to_end"):
        for name in os.listdir(os.path.join(here, sub)):
            with open(os.path.join(here, sub, name)) as f:
                assert "MTPU_BATCH_FORCE" not in f.read(), name

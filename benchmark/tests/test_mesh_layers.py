"""The four-chip cell's files and the readers of its four metrics, on a
recorded reduction of a four-plane trace (`data/four_plane_reduction.
json`: `benchmark/trace.py reduce` of a traced run of
`ec8p4-12d-4chip.put-64m` on a v5e 2x2 host, beside the numbers the
run's own line carried: the payload rate and the planes' busy seconds)
and on scrapes written out by hand."""

import importlib.util
import json
import os

import pytest

from benchmark import cells, readers, work

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "ec8p4-12d-4chip.put-64m"
NEW = ("kernel.mesh_frame_roofline", "mesh.pad_share.put",
       "mesh.least_chip_real_share.put", "mesh.chip_busy_skew.put")
REQ = "minio_tpu_batcher_requests_total"
MESH = "minio_tpu_mesh_blocks_total"


def key(**labels):
    return frozenset(labels.items())


def mesh_scrape(real, pad):
    return {MESH: {**{key(chip=str(c), kind="real"): v
                      for c, v in enumerate(real)},
                   **{key(chip=str(c), kind="pad"): v
                      for c, v in enumerate(pad)}}}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "four_plane_reduction.json")) as f:
        return json.load(f)


@pytest.fixture
def ctx(recorded):
    cfg = cells.load_config("ec8p4-12d-4chip")
    peaks = cells.load_peaks()["TPU v5 lite"]
    return {"trace": recorded["trace"], "config": cfg, "peaks": peaks,
            "drives": cfg["drives"], "workers": 32,
            "payload_mib_s": {"PUT": recorded["payload_mib_s"], "GET": 0.0},
            "scrape_a": {REQ: {key(route="put", path="device"): 10.0}},
            "scrape_b": {REQ: {key(route="put", path="device"): 90.0}}}


def layer(ctx, name):
    return readers.read_layer(ctx, cells.load_layer(name))


def test_the_cell_and_its_files_are_found():
    bench = cells.load_benchmark()
    loaded = cells.load_cell(CELL, bench)
    assert loaded["cell"]["chips"] == loaded["config"]["chips"] == 4
    assert loaded["mix"] == cells.load_cell("ec8p4-12d.put-64m")["mix"]
    twin = cells.load_config("ec8p4-12d")
    for same in ("server_argv", "drives", "data_shards", "parity_shards",
                 "erasure_block_bytes", "bitrot", "write_quorum",
                 "guarantees", "reduced"):
        assert loaded["config"][same] == twin[same], same
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "put_mib_s"
        assert "read" in cells.load_layer(name) or \
            cells.load_layer(name)["reader"] in readers.GENERIC
    # one chip's peak is the yardstick of kernel.frame_roofline
    assert CELL not in by_name["kernel.frame_roofline"]["workloads"]
    assert all(CELL in m["workloads"] for m in bench["per_layer"]
               if m["moves"] == "put_mib_s"
               and m["name"] != "kernel.frame_roofline")


def test_the_mesh_roofline_divides_by_the_chips_in_the_trace(ctx, recorded):
    assert recorded["trace"]["chips"] == 4
    got = layer(ctx, "kernel.mesh_frame_roofline")
    one_chip_arithmetic = layer(dict(ctx), "kernel.frame_roofline")
    assert got == pytest.approx(one_chip_arithmetic / 4)
    assert 0 < got <= 100
    # what the run's own line carried, on the chip machine
    assert got == pytest.approx(
        recorded["reported"]["kernel.mesh_frame_roofline"])
    assert layer(ctx, "device.idle_share.put") == pytest.approx(
        recorded["reported"]["device.idle_share.put"])
    note = ctx["notes"]["mesh_frame_roofline"]
    assert note["chips"] == 4 and note["bound"] == "bytes"
    assert note["blocks_per_s_a_chip"] == pytest.approx(
        recorded["payload_mib_s"] / 4)         # 1 MiB erasure blocks
    # by hand: a chip's blocks a second over the HBM peak
    w = work.frame_work(8, 4, 1 << 20, note["blocks_per_s_a_chip"])
    assert note["least_s_per_s_a_chip"] == pytest.approx(
        w["bytes"] / 819e9)


def test_the_mesh_roofline_reads_nothing_without_a_trace_or_work(ctx):
    assert layer({**ctx, "trace": {"chips": 0}},
                 "kernel.mesh_frame_roofline") is None
    assert layer({**ctx, "scrape_b": ctx["scrape_a"]},
                 "kernel.mesh_frame_roofline") is None
    empty = {**ctx, "trace": {**ctx["trace"], "modules": []}}
    with pytest.raises(LookupError):
        layer(empty, "kernel.mesh_frame_roofline")


def test_pad_share_and_least_chip_share_from_the_counters():
    a = mesh_scrape([100, 100, 100, 100], [0, 0, 0, 28])
    b = mesh_scrape([116, 116, 108, 100], [0, 0, 8, 44])    # 40 in a 64
    c = {"scrape_a": a, "scrape_b": b}
    assert layer(c, "mesh.pad_share.put") == pytest.approx(24 / 64 * 100)
    assert layer(c, "mesh.least_chip_real_share.put") == pytest.approx(0.0)
    assert c["notes"]["mesh_real_blocks_by_chip"] == {
        "0": 16, "1": 16, "2": 8, "3": 0}
    even = {"scrape_a": a,
            "scrape_b": mesh_scrape([164, 164, 164, 164], [0, 0, 0, 28])}
    assert layer(even, "mesh.pad_share.put") == pytest.approx(0.0)
    assert layer(even, "mesh.least_chip_real_share.put") == \
        pytest.approx(100.0)


def test_a_program_without_the_counter_reports_neither():
    """The parent commit, and any one-device boot: no series."""
    c = {"scrape_a": {}, "scrape_b": {}}
    assert layer(c, "mesh.pad_share.put") is None
    assert layer(c, "mesh.least_chip_real_share.put") is None
    same = mesh_scrape([1, 1, 1, 1], [0, 0, 0, 0])
    assert layer({"scrape_a": same, "scrape_b": same},
                 "mesh.least_chip_real_share.put") is None


def test_busy_skew_is_nothing_for_equal_planes(recorded):
    path = os.path.join(os.path.dirname(HERE), "layers",
                        "mesh.chip_busy_skew.put.py")
    spec = importlib.util.spec_from_file_location("chip_busy_skew", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.skew([0.03, 0.03, 0.03, 0.03]) == 0.0
    assert mod.skew([0.04, 0.02, 0.03, 0.03]) == pytest.approx(
        0.02 / 0.03 * 100)
    assert mod.skew([0.03]) is None and mod.skew([0.0, 0.0]) is None
    busy = list(recorded["busy_s_by_plane"].values())
    assert len(busy) == 4
    # the recorded planes' mean is the reduction's busy_s
    assert sum(busy) / 4 == pytest.approx(recorded["trace"]["busy_s"])
    assert mod.skew(busy) == pytest.approx(
        recorded["reported"]["mesh.chip_busy_skew.put"])
    # one plane, or no trace kept: nothing to report, and no error
    assert mod.read({"trace": {"chips": 1, "window_s": 12.0}}, {}) is None
    assert mod.read({}, {}) is None

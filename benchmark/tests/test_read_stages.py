"""The read path's seventeen per-layer metrics, data files for
the generic reader, on recorded pairs of scrapes: the window of a traced
run of each GET cell on the program that names the read path's stages —
a CPU boot through `run.run_cell` with the slow tests' hooks (portable
de-framer, batcher pinned to the device, 4 workers) and 40 MiB objects,
two windows a GET (`ec8p4-12d.get-64m`, seed 2147483701; the degraded
`ec8p4-12d-3dead.degraded-get-64m`, seed 2147483702) — and the pairs
that test_get_cell.py and test_degraded_cell.py recorded from programs
without them."""

import math
import os

import pytest

from benchmark import cells, readers
from benchmark.server import parse_scrape

DATA = os.path.join(os.path.dirname(__file__), "data")
HEALTHY = "ec8p4-12d.get-64m"
DEGRADED = "ec8p4-12d-3dead.degraded-get-64m"
H, D = [HEALTHY], [DEGRADED]
# name: (layer, unit, better, source, cells), in BENCHMARK.json's order
NEW = {
    "frontend.get_attributed_share": ("Front end", "%", "higher",
                                      "program_span", H + D),
    "frontend.get_send_ms": ("Front end", "ms", "lower", "program_span",
                             H + D),
    "object.get_prepare_ms": ("Object layer", "ms", "lower", "program_span",
                              H + D),
    "object.get_window_wait_ms": ("Object layer", "ms", "lower",
                                  "program_span", H + D),
    "object.window_pool_wait_ms": ("Object layer", "ms", "lower",
                                   "program_counter", H + D),
    "object.get_window_ms": ("Object layer", "ms", "lower", "program_span",
                             H + D),
    "object.window_attributed_share.get": ("Object layer", "%", "higher",
                                           "program_span", H + D),
    "object.get_fetch_ms": ("Object layer", "ms", "lower", "program_span",
                            H + D),
    "object.get_deframe_ms": ("Object layer", "ms", "lower", "program_span",
                              H),
    "object.get_interleave_ms": ("Object layer", "ms", "lower",
                                 "program_span", H),
    "object.survivor_verify_ms": ("Object layer", "ms", "lower",
                                  "program_span", D),
    "object.rebuild_ms": ("Object layer", "ms", "lower", "program_span", D),
    "object.rebuild_join_ms": ("Object layer", "ms", "lower", "program_span",
                               D),
    "batcher.finish_ms.get": ("Batcher", "ms", "lower", "program_span",
                              H + D),
    "lane.upload_share.get": ("Device boundary", "%", "lower",
                              "program_span", H + D),
    "lane.kernel_share.get": ("Device boundary", "%", "higher",
                              "program_span", H + D),
    "lane.readback_share.get": ("Device boundary", "%", "lower",
                                "program_span", H + D),
}
# The metrics whose denominator only the new program exports: a program
# without the stages reports nothing. The rest divide by series every
# program exports (the GETs' count or seconds, the lane's seconds) and
# read, there, only what the older program names itself: no second of a
# new stage.
NEW_DENOMINATOR = {"object.window_pool_wait_ms", "object.get_window_ms",
                   "object.window_attributed_share.get",
                   "object.get_fetch_ms", "object.get_deframe_ms",
                   "object.get_interleave_ms", "object.survivor_verify_ms",
                   "object.rebuild_ms", "object.rebuild_join_ms",
                   "batcher.finish_ms.get"}
LANE = ("lane.upload_share.get", "lane.kernel_share.get",
        "lane.readback_share.get")


def _pair(prefix):
    out = {"drives": 12, "workers": 32}
    for key, tag in (("scrape_a", "a"), ("scrape_b", "b")):
        with open(os.path.join(DATA, f"{prefix}_{tag}.txt")) as f:
            out[key] = parse_scrape(f.read())
    return out


@pytest.fixture(scope="module")
def recorded():
    return {HEALTHY: _pair("scrape_read_stages"),
            DEGRADED: _pair("scrape_read_stages_degraded")}


@pytest.fixture(scope="module")
def older():
    return {HEALTHY: _pair("scrape_get"), DEGRADED: _pair("scrape_degraded")}


def layer(ctx, name):
    return readers.read_layer(ctx, cells.load_layer(name))


def delta(ctx, series, labels=None):
    return readers.series_sum(ctx["scrape_b"], series, labels) \
        - readers.series_sum(ctx["scrape_a"], series, labels)


def test_they_are_appended_with_the_cells_that_read_them():
    bench = cells.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    # appended after everything that was there, in this order
    assert [n for n in names if n in NEW] == list(NEW)
    assert names.index("object.get_stack_ms") < names.index(next(iter(NEW)))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (lay, unit, better, source, where) in NEW.items():
        m = by_name[name]
        assert (m["layer"], m["unit"], m["better"], m["source"],
                m["moves"], m["workloads"]) \
            == (lay, unit, better, source, "get_mib_s", where), name
        spec = cells.load_layer(name)
        assert spec["what"] and spec["reader"] == "prometheus_delta", name
        assert "read" not in spec, name       # a data file, no code


def test_each_reads_a_number_in_every_cell_it_lists(recorded):
    for name, (*_, where) in NEW.items():
        for cell in where:
            got = layer(recorded[cell], name)
            assert got is not None and math.isfinite(got) and got > 0, \
                (name, cell, got)


@pytest.mark.parametrize("cell", [HEALTHY, DEGRADED])
def test_the_named_parts_hold_their_wholes(recorded, cell):
    """The request thread's four stages hold >= 95 % of the GETs'
    seconds, the window's parts >= 95 % of the windows', and the lane's
    three 85-100 % of its seconds (what they leave is the round trip's
    Python): none can pass 100."""
    ctx = recorded[cell]
    assert 95 <= layer(ctx, "frontend.get_attributed_share") <= 100
    assert 95 <= layer(ctx, "object.window_attributed_share.get") <= 100
    assert 85 <= sum(layer(ctx, n) for n in LANE) <= 100


@pytest.mark.parametrize("cell", [HEALTHY, DEGRADED])
def test_a_window_is_whole_or_rebuilt_and_a_get_is_two(recorded, cell):
    """A 64 MiB GET is two 32 MiB windows: the windows, their fetches
    (two a rebuilt window: data, then parity) and the sends counted
    between the scrapes say so within the GETs in flight at the
    scrapes."""
    ctx = recorded[cell]

    def entries(stage):
        return delta(ctx, "minio_tpu_stage_entries_total", {"stage": stage})
    gets = delta(ctx, "minio_tpu_api_request_duration_seconds_count",
                 {"api": "GET:object"})
    windows = entries("get.window")
    assert entries("get.prepare") == gets
    assert entries("get.send") == entries("get.window_wait") == 2 * gets
    assert abs(windows - 2 * gets) <= 2 * 32      # in flight at a scrape
    rebuilt = cell == DEGRADED
    assert entries("get.fetch") == (2 if rebuilt else 1) * windows
    for stage in ("get.stack", "get.deframe", "get.interleave"):
        assert (entries(stage) == 0) == rebuilt, stage
    for stage in ("get.rebuild_stack", "get.rebuild", "get.join"):
        assert entries(stage) == (windows if rebuilt else 0), stage
    assert entries("get.survivor_verify") == (2 * windows if rebuilt else 0)


def test_a_program_without_the_stages_reports_none_of_them(older):
    """The pairs recorded before the read path had stages: what divides
    by a new series is left out of the line; what divides by a series
    every program exports reads no second of a new stage (0, and the
    request thread's share is `s3.auth`'s alone)."""
    for name, (*_, where) in NEW.items():
        for cell in where:
            got = layer(older[cell], name)
            if name in NEW_DENOMINATOR:
                assert got is None, (name, cell, got)
            elif name == "frontend.get_attributed_share" and got:
                auth = delta(older[cell], "minio_tpu_stage_seconds_total",
                             {"stage": "s3.auth"})
                gets_s = delta(older[cell],
                               "minio_tpu_api_request_duration_seconds_sum",
                               {"api": "GET:object"})
                assert got == pytest.approx(auth / gets_s * 100), cell
            else:
                assert got in (None, 0.0), (name, cell, got)

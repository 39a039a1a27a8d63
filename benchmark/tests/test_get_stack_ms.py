"""`object.get_stack_ms`, the Object layer's metric of the healthy GET
window's stack (the stage `get.stack`: a pooled lease and k strided
copies), a data file for the generic reader. Read here from a recorded
pair of scrapes of a CPU boot of the server (EC 8+4 on 12 drives,
portable de-framer, batcher pinned to the device) with six 40 MiB GETs,
three at a time, between them; and from test_get_cell.py's pair, whose
program had no such stage."""

import os

import pytest

from benchmark import cells, readers
from benchmark.server import parse_scrape
from benchmark.tests.test_get_cell import CELL, ctx, layer  # noqa: F401

NAME = "object.get_stack_ms"
DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture
def stacked():
    out = {"drives": 12, "workers": 32}
    for key, name in (("scrape_a", "scrape_get_stack_a.txt"),
                      ("scrape_b", "scrape_get_stack_b.txt")):
        with open(os.path.join(DATA, name)) as f:
            out[key] = parse_scrape(f.read())
    return out


def test_it_is_appended_for_the_healthy_get_cell():
    bench = cells.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    # appended after everything that was there, in this order
    assert names.index("bufpool.hit_share.get") < names.index(NAME)
    m = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert (m["moves"], m["layer"], m["unit"], m["better"], m["source"]) \
        == ("get_mib_s", "Object layer", "ms", "lower", "program_span")
    assert m["workloads"] == [CELL]
    spec = cells.load_layer(NAME)
    assert spec["what"] and spec["reader"] == "prometheus_delta"


def test_the_stack_per_window_on_the_recorded_pair(stacked):
    # six GETs of 40 MiB, two windows each: twelve stacks
    entries = readers.series_sum(
        stacked["scrape_b"], "minio_tpu_stage_entries_total",
        {"stage": "get.stack"}) - readers.series_sum(
        stacked["scrape_a"], "minio_tpu_stage_entries_total",
        {"stage": "get.stack"})
    assert entries == 12
    got = layer(stacked, NAME)
    assert got == pytest.approx((0.215169 - 0.038238) / 12 * 1000)


def test_a_program_without_the_stage_reports_nothing(ctx, stacked):
    """The pair recorded before the stage existed: no entries between
    the scrapes, nothing to divide by, and the metric is left out of
    the line, never written as 0."""
    assert layer(ctx, NAME) is None
    gone = {k: {n: {lab: v for lab, v in s.items()
                    if ("stage", "get.stack") not in lab}
                for n, s in stacked[k].items()}
            for k in ("scrape_a", "scrape_b")}
    assert layer({**stacked, **gone}, NAME) is None

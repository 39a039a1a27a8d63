"""A configuration, a mix, a cell and a per-layer metric are found by
the names in BENCHMARK.json — and can be ADDED as new files and new
entries, with no edit to a file that is there."""

import json
import os
import shutil

import pytest

from benchmark import cells, readers, run, traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def test_every_name_in_benchmark_json_has_its_files():
    bench = cells.load_benchmark()
    for cell in bench["workloads"]:
        loaded = cells.load_cell(cell["name"], bench)
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        assert loaded["config"]["chips"] == cell["chips"]
    for cfg in bench["configs"]:
        with open(os.path.join(ROOT, cfg["file"])) as f:
            data = json.load(f)
        assert data["name"] == cfg["name"]
        for key in cfg["reduced"]:
            assert key in data["reduced"], (cfg["name"], key)
    names = {m["name"] for m in bench["end_to_end"]}
    for name in names:
        assert cells.load_end_to_end(name)["quantity"] in (*run.RATES,
                                                           "setup_s")
    cell_names = {c["name"] for c in bench["workloads"]}
    for m in bench["per_layer"]:
        spec = cells.load_layer(m["name"])
        assert "read" in spec or spec["reader"] in readers.GENERIC
        assert m["moves"] in names
        assert set(m.get("workloads", [])) <= cell_names
        moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
        for cell in m.get("workloads", cell_names):
            assert cells.reports(moved, cell), (m["name"], cell)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    bench = cells.load_benchmark()
    for cell in bench["workloads"]:
        e2e = [m["name"] for m in bench["end_to_end"]
               if cells.reports(m, cell["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cells.reports(m, cell["name"])
                   for m in bench["per_layer"])


def test_a_later_pr_adds_files_and_entries_only(tmp_path, monkeypatch):
    """Copy the benchmark's data, add one configuration, one mix, one
    cell and one per-layer metric as NEW files and entries, and find
    them all — nothing that was there is edited."""
    for sub in ("configs", "traffic", "layers", "end_to_end"):
        shutil.copytree(os.path.join(HERE, sub), tmp_path / sub)
    shutil.copy(os.path.join(HERE, "peaks.json"), tmp_path / "peaks.json")
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}

    cfg = json.loads((tmp_path / "configs" / "ec4p2-6d.json").read_text())
    cfg.update(name="ec2p2-4d", drives=4, data_shards=2, parity_shards=2,
               write_quorum=3)
    (tmp_path / "configs" / "ec2p2-4d.json").write_text(json.dumps(cfg))
    mix = json.loads((tmp_path / "traffic" / "put-64m.json").read_text())
    mix.update(size=1 << 20, workers=16)
    (tmp_path / "traffic" / "put-1m.json").write_text(json.dumps(mix))
    (tmp_path / "layers" / "frontend.head_ms.json").write_text(json.dumps({
        "reader": "prometheus_delta", "scale": 1000,
        "numerator": [{"series": "minio_tpu_api_request_duration_seconds_sum",
                       "labels": {"api": "HEAD:object"}}],
        "denominator": [{"series":
                         "minio_tpu_api_request_duration_seconds_count",
                         "labels": {"api": "HEAD:object"}}]}))
    (tmp_path / "end_to_end" / "get_again_mib_s.json").write_text(json.dumps(
        {"quantity": "rate_spread_over_each_operation", "op": "GET"}))
    bench = cells.load_benchmark()
    bench["configs"].append({"name": "ec2p2-4d", "reduced": [],
                             "file": "benchmark/configs/ec2p2-4d.json"})
    bench["workloads"].append({"name": "ec2p2-4d.put-1m",
                               "config": "ec2p2-4d", "traffic": "put-1m",
                               "chips": 1})
    monkeypatch.setattr(cells, "HERE", str(tmp_path))
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))

    loaded = cells.load_cell("ec2p2-4d.put-1m", bench)
    assert loaded["config"]["drives"] == 4
    assert loaded["mix"]["size"] == 1 << 20
    ops = [["GET", "pre/0000", 1.0, 3.0, "ok", 4 << 20, ""],
           ["PUT", "w00/000000", 1.0, 2.0, "ok", 4 << 20, ""]]
    assert run.end_to_end("get_again_mib_s", {"ops": ops, "t0": 2.0, "t1": 4.0}) \
        == pytest.approx(1.0)           # half of 4 MiB inside 2 s
    assert run.RATES["rate_of_whole_operations"](ops, "GET", 2.0, 4.0) == \
        pytest.approx(2.0)              # it ended inside: all 4 MiB
    spec = cells.load_layer("frontend.head_ms")
    a = {"minio_tpu_api_request_duration_seconds_sum":
         {frozenset({("api", "HEAD:object")}): 1.0},
         "minio_tpu_api_request_duration_seconds_count":
         {frozenset({("api", "HEAD:object")}): 10.0}}
    b = {"minio_tpu_api_request_duration_seconds_sum":
         {frozenset({("api", "HEAD:object")}): 1.5},
         "minio_tpu_api_request_duration_seconds_count":
         {frozenset({("api", "HEAD:object")}): 60.0}}
    assert readers.read_layer({"scrape_a": a, "scrape_b": b}, spec) == \
        pytest.approx(10.0)
    for p, data in before.items():
        assert p.read_bytes() == data


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        cells.load_cell("ec8p4-12d.nothing")

"""Finding a cell's files by name. `BENCHMARK.json` names cells,
configurations and metrics; everything that belongs to one of them is
a file of its own that this module finds by that name:

    workloads[].name "<config>.<traffic>"
        -> benchmark/configs/<config>.json, benchmark/traffic/<traffic>.json
           (+ configs/<config>.py where the deployment needs code of
           its own: `after_preload`, `reference_shard_files`)
    end_to_end[].name "<metric>"
        -> benchmark/end_to_end/<metric>.json (which quantity of the
           run's own operation log it is)
    per_layer[].name "<metric>"
        -> benchmark/layers/<metric>.json (+ <metric>.py where the two
           generic readers do not reach)

so a later PR adds a configuration, a mix, a cell or a per-layer metric
by adding files and entries, and edits nothing here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

from benchmark import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def _load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module of its own, or None."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config_module(name: str):
    """The configuration's own code, where it brings any
    (benchmark/configs/<name>.py), else None. `run.py` calls what the
    module has of:

        after_preload(srv, cfg, cli)      between the preload and the
            warm-up ladder, on the live server (`srv`: server.Server,
            `cli`: its S3 client): where a degraded deployment loses
            its drives;
        reference_shard_files(body, cfg) -> the n shard files the
            drives must hold for `body`, in place of
            compare.reference_shard_files (an encrypted bucket's, or a
            geometry whose k does not divide the erasure block);
        cell_notes(cfg, scrape_a, scrape_b, device) -> what the
            deployment wants said beside the numbers (the window's two
            scrapes, the admin info's device section): the result
            line's `cell.config_notes`, reported and never compared."""
    return _load_module("configs", name)


def load_peaks() -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def load_cell(name: str, bench: dict | None = None) -> dict:
    bench = bench or load_benchmark()
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(have: {[w['name'] for w in bench['workloads']]})")
    return {"cell": cell, "config": load_config(cell["config"]),
            "module": load_config_module(cell["config"]),
            "mix": traffic.load_mix(cell["traffic"]), "bench": bench}


def reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_end_to_end(name: str) -> dict:
    with open(os.path.join(HERE, "end_to_end", f"{name}.json")) as f:
        return json.load(f)


def load_layer(name: str) -> dict:
    """The metric's own file; `read` is its own reader where it brings
    one (benchmark/layers/<name>.py: read(ctx, spec) -> number|None)."""
    with open(os.path.join(HERE, "layers", f"{name}.json")) as f:
        spec = json.load(f)
    mod = _load_module("layers", name)
    if mod is not None:
        spec["read"] = mod.read
    return spec

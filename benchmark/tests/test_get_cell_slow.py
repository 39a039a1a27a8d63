"""The rest of a run of the cell that reads, with the look for a chip
skipped: a sound run comes out correct; with one byte of one GET's
body turned on the wire it does not, nor with a `get` route that hears
no verdict (the rot set-up plants is then served); a configuration's
own module is
called where a degraded deployment would lose its drives, and its
reference decides the disk check. Boots the server on the CPU (portable
XLA path, batcher pinned to the device so the de-framer runs):

    python -m pytest benchmark/tests -q -m slow
"""

import pytest

from benchmark import cells, compare, faults, run, traffic

pytestmark = pytest.mark.slow

HOOKS = {"allow_platform": True,
         "server_env": {"JAX_PLATFORMS": "cpu", "MTPU_BATCH_FORCE": "device",
                        "MTPU_HTTP_WORKERS": "1"},
         # the cell's own mix at a size a test run can hold; 16 MiB is
         # still over the hot cache's per-object cap
         "mix": {**traffic.load_mix("get-64m"), "size": 16 << 20,
                 "workers": 4, "processes": 2, "preload": 4,
                 "warm_ladder": 2, "rotten": [1], "stagger_s": 0.5,
                 "ramp_s": 1,
                 "disk_sample": 2, "trace_s": 1}}
CELL = "ec8p4-12d.get-64m"
HITS = "minio_tpu_hot_cache_hits_total"


def go(seed, extra=None):
    res, _ = run.run_cell(CELL, seed, 4, False, {**HOOKS, **(extra or {})})
    return res


def test_a_sound_run_is_correct():
    res = go(2**31 + 31)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 8
    assert {"get_mib_s", "setup_s"} == set(res["metrics"])
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["cell"]["ops_in_window"]["PUT"] == 0
    assert res["compared"][HITS] == {"value": 0, "limit": 0}
    # the rot planted after the read-back was noticed, and nobody was
    # served it
    assert res["compared"]["rot_not_noticed"] == {"value": 0, "limit": 0}
    assert res["cell"]["rot"]["planted"] == 1
    assert res["cell"]["rot"]["windows_demoted"] >= 1
    assert "get:8+4" in res["cell"]["calibration"]
    assert list(res)[-1] == "compared"


def test_one_byte_turned_on_the_wire_is_not_correct():
    res = go(2**31 + 37, faults.hooks_for("flip_get_byte"))
    assert not res["correct"]
    assert res["compared"]["wrong_answers"]["value"] == 1
    assert res["failed"] == 1
    assert res["cell"]["wire_fault"]["path"].startswith("/bench/pre/")
    others = {k: v for k, v in res["compared"].items()
              if k != "wrong_answers"}
    assert all(v["value"] <= v["limit"] for v in others.values()), others


def test_a_get_route_that_hears_no_verdict_is_not_correct():
    """The program with bitrot-on-read switched off: the device hashes
    and nobody looks. The turned byte reaches the reader."""
    res = go(2**31 + 39, faults.hooks_for("deaf_deframer"))
    assert not res["correct"]
    assert res["compared"]["rot_not_noticed"]["value"] == 1
    assert res["compared"]["wrong_answers"]["value"] >= 1
    assert res["cell"]["rot"]["windows_demoted"] == 0
    others = {k: v for k, v in res["compared"].items()
              if k not in ("wrong_answers", "rot_not_noticed")}
    assert all(v["value"] <= v["limit"] for v in others.values()), others


def test_an_object_served_by_the_hot_cache_is_not_this_cell():
    """8 MiB objects fit the hot cache: the mix's limit says so."""
    res = go(2**31 + 41, {"mix": {**HOOKS["mix"], "size": 8 << 20}})
    assert not res["correct"]
    assert res["compared"][HITS]["value"] > 0


def test_a_configurations_module_is_called_in_its_two_places(monkeypatch):
    calls = []

    class Module:
        @staticmethod
        def after_preload(srv, cfg, cli):
            # the preload is on the drives, the ladder has not run
            listed = compare.list_bucket(cli, traffic.BUCKET)
            calls.append(("after_preload", sorted(listed)))

        @staticmethod
        def reference_shard_files(body, cfg):
            calls.append(("reference", len(body)))
            return compare.reference_shard_files(
                body, cfg["data_shards"], cfg["parity_shards"],
                cfg["erasure_block_bytes"])

    monkeypatch.setattr(cells, "load_config_module", lambda name: Module)
    res = go(2**31 + 43)
    assert res["correct"], res["compared"]
    assert calls[0] == ("after_preload", [
        "pre/0000", "pre/0001", "pre/0002", "pre/0003", "warm/0000"])
    assert calls[1:] == [("reference", 16 << 20)] * 2

"""The rest of a run of the degraded cell, with the look for a chip
skipped, at the slow tests' sizes (16 MiB objects, 4 workers, EC 8+4 on
12 drives of which 2, 5 and 9 die after the preload; portable XLA
path, batcher pinned to the device so the rebuild runs there; one bit
of a surviving data shard of pre/0002 turned after the drives died,
which leaves that object 8 of its 12 shards): a sound run comes out
correct with 9 of 9 living drives stamped, every window rebuilt, and
the drives' shard files where the placement rule says; the same run
with the roots removed and NOT blocked is a drive replaced and healed
under load, and comes out not correct; nor does the run of a program
whose rebuild path trusts its survivors unverified (the turned bit is
served).

    python -m pytest benchmark/tests -q -m slow
"""

import pytest

from benchmark import compare, faults, run, traffic
from benchmark.reference import layout

pytestmark = pytest.mark.slow

HOOKS = {"allow_platform": True,
         "server_env": {"JAX_PLATFORMS": "cpu", "MTPU_BATCH_FORCE": "device",
                        "MTPU_HTTP_WORKERS": "1"},
         "mix": {**traffic.load_mix("degraded-get-64m"), "size": 16 << 20,
                 "workers": 4, "processes": 2, "preload": 4,
                 "warm_ladder": 2, "rotten": [2], "stagger_s": 0.5,
                 "ramp_s": 1,
                 "disk_sample": 2, "trace_s": 1}}
CELL = "ec8p4-12d-3dead.degraded-get-64m"


def go(seed, extra=None):
    res, _ = run.run_cell(CELL, seed, 4, False, {**HOOKS, **(extra or {})})
    return res


def test_a_sound_run_is_correct_with_nine_of_nine_drives_stamped():
    placed, rot_seen = [], []

    def the_files_lie_where_the_placement_rule_says(srv, cfg, sample, bodies):
        """After the stop: drive d holds shard hash_order[d - 1] of every
        preloaded key, byte for byte the reference's, and a dead drive
        nothing. The shard that was rotted may have been healed where
        it lies or not (the program's policy while drives are dead): it
        is the reference's but for that one bit."""
        n, k = cfg["drives"], cfg["data_shards"]
        for i in range(HOOKS["mix"]["preload"]):
            key = traffic.pre_key(i)
            want = compare.reference_shard_files(
                bodies.body(key), k, cfg["parity_shards"],
                cfg["erasure_block_bytes"])
            order = layout.hash_order(f"{traffic.BUCKET}/{key}", n)
            files = compare.shard_files_on_disk(srv.drive_root, n,
                                                traffic.BUCKET, key)
            assert sorted(files) == [d for d in range(1, n + 1)
                                     if d not in cfg["dead_drives"]]
            for d, path in files.items():
                with open(path, "rb") as f:
                    got = bytearray(f.read())
                shard = want[order[d - 1] - 1]
                if key == "pre/0002" and order[d - 1] == 2:
                    rot_seen.append(got[32] != shard[32])
                    got[32] = shard[32]
                assert got == shard, (key, d)
            gone = {order[d - 1] - 1 for d in cfg["dead_drives"]}
            assert tuple(sorted(gone)) == layout.lost_shards(
                traffic.BUCKET, key, n, cfg["dead_drives"])
            placed.append(key)

    res = go(2**31 + 51, {
        "before_disk_check": the_files_lie_where_the_placement_rule_says})
    assert res["correct"], res["compared"]
    assert len(placed) == 4 and len(rot_seen) == 1
    assert res["failed"] == 0 and res["attempted"] > 8
    assert {"get_mib_s", "setup_s"} == set(res["metrics"])
    assert res["compared"]["unclean_stop"] == {"value": 0, "limit": 0}
    assert res["compared"]["dead_drives_touched"] == {"value": 0, "limit": 0}
    dead = res["cell"]["dead_drives"]
    assert dead["dead"] == [2, 5, 9] and dead["stamped"] == 9
    # every window of the window's GETs took the rebuild path (the
    # configuration's own notes: reported, not compared)
    notes = res["cell"]["config_notes"]
    assert notes["offline_by_the_program_at_t0"] in (0, 3)
    by_path = notes["get_windows_by_path"]
    assert by_path["numpy"] > 8 and res["cell"]["ops_in_window"]["GET"] > 8
    assert {p for p, v in by_path.items() if v} == {"numpy"}
    assert res["cell"]["fewest_right_shards_of_a_sampled_object"] == 9
    assert any(name.startswith("rec:8+4:")
               for name in res["cell"]["calibration"])
    assert set(notes["probes_ms"]) == {
        n for n in res["cell"]["calibration"] if n.startswith("rec:")}
    # the rot was planted in data shard 1 of pre/0002 (its shard 0 lay
    # on dead drive 2), every answer was right all the same, and the
    # mix names no counter of the program's to hold it to
    assert res["cell"]["rot"]["planted"] == 1
    assert "rot_not_noticed" not in res["compared"]
    assert res["compared"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert list(res)[-1] == "compared"


def test_roots_removed_and_not_blocked_are_drives_replaced_not_dead():
    res = go(2**31 + 53, faults.hooks_for("unblocked_roots"))
    assert not res["correct"]
    assert res["compared"]["dead_drives_touched"]["value"] == 3
    # every answer was right all the same: this fault is that number's
    assert res["compared"]["wrong_answers"]["value"] == 0
    assert res["failed"] == 0


def test_one_byte_turned_on_the_wire_of_a_rebuilt_get_is_not_correct():
    res = go(2**31 + 57, faults.hooks_for("flip_get_byte"))
    assert not res["correct"]
    assert res["compared"]["wrong_answers"]["value"] == 1
    others = {k: v for k, v in res["compared"].items()
              if k != "wrong_answers"}
    assert all(v["value"] <= v["limit"] for v in others.values()), others


def test_a_rebuild_that_trusts_its_survivors_unverified_is_not_correct():
    """The program with bitrot-on-read switched off on the rebuild path
    too (serve_deaf.py): every survivor is hashed and nobody looks.
    The turned bit reaches the reader, first in the serial read-back."""
    res = go(2**31 + 59, faults.hooks_for("deaf_deframer"))
    assert not res["correct"]
    assert res["compared"]["wrong_answers"]["value"] >= 1
    others = {k: v for k, v in res["compared"].items()
              if k != "wrong_answers"}
    assert all(v["value"] <= v["limit"] for v in others.values()), others

"""The rest of a run, with the look for a chip skipped and the timed
path broken underneath: `correct` comes out false, once for each fault
a cell that writes can have (an answer altered where it is produced:
what a PUT left on the drives, in what it says and in how many drives
hold it). A sound run of the same small size comes out true. Boots the server on the CPU (portable XLA
path, batcher pinned to the device so the framer and de-framer run):

    python -m pytest benchmark/tests -q -m slow
"""

import pytest

from benchmark import faults, run, traffic

pytestmark = pytest.mark.slow

HOOKS = {"allow_platform": True,
         "server_env": {"JAX_PLATFORMS": "cpu", "MTPU_BATCH_FORCE": "device",
                        "MTPU_HTTP_WORKERS": "1"},
         # the cell's own mix at a size a test run can hold
         "mix": {**traffic.load_mix("put-64m"), "size": 8 << 20,
                 "workers": 4, "processes": 2, "warm_ladder": 2,
                 "stagger_s": 0.5, "ramp_s": 1, "disk_sample": 2,
                 "trace_s": 1}}
CELL = "ec4p2-6d.put-64m"


def go(seed, extra=None):
    res, _ = run.run_cell(CELL, seed, 4, False, {**HOOKS, **(extra or {})})
    return res


def test_a_sound_run_is_correct():
    res = go(2**31 + 17)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 8
    assert {"put_mib_s", "setup_s"} == set(res["metrics"])
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "compared"


def test_a_boot_whose_calibration_chose_the_host_is_thrown_away(monkeypatch):
    """The route's verdict holds for the life of a server, and a cell
    the host codec serves is not this cell: set-up boots again. (The
    CPU boot makes no probe, so the first boot's verdict is planted.)"""
    real, boots = run.settle, []

    def settle(cli, timeout=180.0):
        dev = real(cli, timeout)
        boots.append(dev)
        if len(boots) == 1:
            return {**dev, "calibration": [
                {"route": "put", "verdict": "host", "device_ms": 119.4,
                 "host_ms": 33.3}]}
        return dev
    monkeypatch.setattr(run, "settle", settle)
    res = go(2**31 + 23)
    assert len(boots) == 2 and res["cell"]["boots"] == 2
    assert res["correct"], res["compared"]
    assert res["failed"] == 0


@pytest.mark.parametrize("fault,number", [
    ("wrong_matrix", "disk_wrong_shards"),
    ("below_quorum", "disk_objects_below_write_quorum")])
def test_a_planted_fault_is_not_correct(fault, number):
    res = go(2**31 + 19, faults.hooks_for(fault))
    assert not res["correct"]
    assert res["compared"][number]["value"] > res["compared"][number]["limit"]

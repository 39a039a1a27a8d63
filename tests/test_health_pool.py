"""The health wrapper's call pool (storage/health._DaemonPool): a call
is handed to a worker at once — an idle one, or a new one — and never
queues for one. Events and counts only; no wall-clock thresholds (the
timeouts below are what a FAILING run waits, not what a passing one
measures)."""

import subprocess
import sys
import threading

import pytest

from minio_tpu.s3.metrics import Metrics
from minio_tpu.storage import health
from minio_tpu.storage.health import DiskHealthWrapper
from minio_tpu.storage.local import FaultyDisk, LocalStorage
from minio_tpu.storage.meta import ErasureInfo, FileInfo

LONG = 30.0     # a failing run's wait; never the measure of a passing one


@pytest.fixture
def disk(tmp_path):
    return LocalStorage(str(tmp_path / "d0"))


class _Watch:
    """Worker starts and exits of ONE wrapper's pool (CALL_STATS is the
    process's: another test file's leftover threads may move it)."""

    def __init__(self):
        self.started: dict = {}
        self.exited = threading.Semaphore(0)

    def starts(self, w) -> int:
        return self.started.get(id(w._pool), 0)


@pytest.fixture
def watch(monkeypatch):
    wt = _Watch()
    work = health._DaemonPool._work

    def watched(self, job):
        wt.started[id(self)] = wt.started.get(id(self), 0) + 1
        try:
            work(self, job)
        finally:
            wt.exited.release()

    monkeypatch.setattr(health._DaemonPool, "_work", watched)
    return wt


class _Parkable:
    """Delegates to a real disk; `read_all` parks on an Event."""

    endpoint = "parkable"

    def __init__(self, real):
        self._real = real
        self.entered = threading.Semaphore(0)
        self.release = threading.Event()

    def __getattr__(self, name):
        return getattr(self._real, name)

    def read_all(self, volume, path):
        self.entered.release()
        self.release.wait(LONG)
        return self._real.read_all(volume, path)


def _parked_writers(w, n, vol):
    """n iterator-form create_file calls on `w`, each parked inside its
    generator (so: inside the pool worker that runs it) until `go`."""
    go = threading.Event()
    parked = threading.Semaphore(0)
    errors: list = []

    def gen():
        yield b"first window"
        parked.release()
        if not go.wait(LONG):
            raise TimeoutError("never released")
        yield b"second window"

    def write(i):
        try:
            w.create_file(vol, f"staging/u{i}/dd/part.1", gen())
        except Exception as e:  # noqa: BLE001 - reported by the test
            errors.append(e)

    threads = [threading.Thread(target=write, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for _ in range(n):
        assert parked.acquire(timeout=LONG), "a writer never got a worker"
    return go, threads, errors


def test_short_ops_return_while_ten_streaming_writers_are_parked(disk):
    """The regression guard: with eight fixed workers the ninth and
    tenth create_file never start, and stat_vol / rename_data wait
    behind them until their timeout."""
    w = DiskHealthWrapper(disk, op_timeout=LONG, bulk_timeout=LONG)
    w.make_vol_if_missing("bkt")
    w.make_vol_if_missing(".mtpu.sys")
    go, threads, errors = _parked_writers(w, 10, ".mtpu.sys")
    try:
        assert w.stat_vol("bkt").name == "bkt"
        w.create_file(".mtpu.sys", "staging/c/dd-1/part.1", b"data")
        fi = FileInfo(volume="bkt", name="obj", data_dir="dd-1", mod_time=5,
                      size=4, erasure=ErasureInfo(
                          data_blocks=1, parity_blocks=0, block_size=1 << 20,
                          index=1, distribution=(1,)))
        w.rename_data(".mtpu.sys", "staging/c", fi, "bkt", "obj")
        assert w.read_version("bkt", "obj").data_dir == "dd-1"
        assert not go.is_set() and all(t.is_alive() for t in threads), \
            "the short ops must have returned with all ten still parked"
    finally:
        go.set()
    for t in threads:
        t.join(LONG)
        assert not t.is_alive()
    assert errors == []
    for i in range(10):
        assert w.read_file(".mtpu.sys", f"staging/u{i}/dd/part.1") \
            == b"first windowsecond window"
    w.close()


def test_slow_ops_still_fault_and_the_open_breaker_starts_no_worker(
        disk, watch):
    real = _Parkable(disk)
    w = DiskHealthWrapper(real, op_timeout=0.05, trip_after=3, cooldown=LONG)
    w.make_vol_if_missing("bkt")
    w.write_all("bkt", "x", b"v")
    for _ in range(3):
        assert w.is_online()
        with pytest.raises(FaultyDisk, match="exceeded"):
            w.read_all("bkt", "x")
        assert real.entered.acquire(timeout=LONG)   # abandoned, still parked
    assert not w.is_online()
    started = watch.starts(w)
    assert started >= 3     # each hung call kept its worker
    for _ in range(20):
        with pytest.raises(FaultyDisk, match="breaker open"):
            w.read_all("bkt", "x")
        with pytest.raises(FaultyDisk, match="breaker open"):
            w.stat_vol("bkt")
    assert watch.starts(w) == started
    assert not real.entered.acquire(blocking=False)     # none reached it
    assert w.health_info()["ops"]["read_all"]["errors"] == 3
    real.release.set()
    w.close()


def test_idle_workers_exit_and_the_next_call_starts_one(
        disk, watch, monkeypatch):
    monkeypatch.setattr(health, "IDLE_EXIT_S", 0.02)
    w = DiskHealthWrapper(disk)
    w.make_vol_if_missing("bkt")
    assert watch.exited.acquire(timeout=LONG), "the idle worker never exited"
    assert watch.starts(w) == 1
    assert w.stat_vol("bkt").name == "bkt"
    assert watch.starts(w) == 2
    assert watch.exited.acquire(timeout=LONG)
    w.close()


def test_an_idle_worker_is_reused(disk, watch):
    w = DiskHealthWrapper(disk)
    w.make_vol_if_missing("bkt")
    for i in range(50):
        w.write_all("bkt", f"k{i}", b"v")
        assert w.read_all("bkt", f"k{i}") == b"v"
    assert watch.starts(w) == 1
    w.close()


def test_close_wakes_idle_workers_and_does_not_wait_for_a_hung_one(
        disk, watch):
    real = _Parkable(disk)
    w = DiskHealthWrapper(real, op_timeout=0.05, trip_after=100)
    w.make_vol_if_missing("bkt")
    w.write_all("bkt", "x", b"v")
    with pytest.raises(FaultyDisk):
        w.read_all("bkt", "x")
    assert real.entered.acquire(timeout=LONG)
    assert w.stat_vol("bkt").name == "bkt"  # a second worker, left idle
    assert watch.starts(w) == 2
    closer = threading.Thread(target=w.close, daemon=True)
    closer.start()
    closer.join(LONG)
    assert not closer.is_alive(), "close() waited for the hung call"
    assert watch.exited.acquire(timeout=LONG), "close() left the idle one"
    assert not watch.exited.acquire(blocking=False)     # the other: parked
    real.release.set()
    assert watch.exited.acquire(timeout=LONG)   # it saw the pool closed


_EXIT_SCRIPT = """
import sys, threading
from minio_tpu.storage.health import DiskHealthWrapper
from minio_tpu.storage.local import FaultyDisk, LocalStorage

class Hung:
    endpoint = "hung"
    def __init__(self, real): self._real = real
    def __getattr__(self, name): return getattr(self._real, name)
    def read_all(self, volume, path):
        threading.Event().wait()        # for ever

w = DiskHealthWrapper(Hung(LocalStorage(sys.argv[1])), op_timeout=0.05)
w.make_vol_if_missing("bkt")
try:
    w.read_all("bkt", "x")
except FaultyDisk:
    print("abandoned", flush=True)
"""


def test_interpreter_exit_does_not_join_a_worker_parked_in_a_hung_op(
        tmp_path):
    p = subprocess.run([sys.executable, "-c", _EXIT_SCRIPT,
                        str(tmp_path / "d0")],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "abandoned" in p.stdout


def test_the_wait_counter_and_the_workers_gauge_are_exported_and_move(disk):
    def scrape() -> dict:
        out = {}
        for line in Metrics().render().splitlines():
            if line.startswith("minio_tpu_drive_call_"):
                name, value = line.split()
                out[name] = float(value)
        return out

    w = DiskHealthWrapper(disk, op_timeout=LONG, bulk_timeout=LONG)
    w.make_vol_if_missing(".mtpu.sys")
    a = scrape()
    assert set(a) == {"minio_tpu_drive_call_wait_seconds_sum",
                      "minio_tpu_drive_call_wait_seconds_count",
                      "minio_tpu_drive_call_workers",
                      "minio_tpu_drive_call_workers_started_total"}
    go, threads, errors = _parked_writers(w, 3, ".mtpu.sys")
    try:
        b = scrape()
        assert b["minio_tpu_drive_call_workers"] >= 3
        assert b["minio_tpu_drive_call_wait_seconds_count"] \
            >= a["minio_tpu_drive_call_wait_seconds_count"] + 3
        assert b["minio_tpu_drive_call_wait_seconds_sum"] \
            > a["minio_tpu_drive_call_wait_seconds_sum"]
        assert b["minio_tpu_drive_call_workers_started_total"] \
            >= a["minio_tpu_drive_call_workers_started_total"] + 2
    finally:
        go.set()
    for t in threads:
        t.join(LONG)
    assert errors == []
    # A fleet's scrape sums its workers' snapshots (io/workers.py).
    st = Metrics().state()["drive_calls"]
    text = Metrics().render(peer_states=[{"metrics": {"drive_calls": st}},
                                         {"metrics": {"drive_calls": st}}])
    line = [ln for ln in text.splitlines()
            if ln.startswith("minio_tpu_drive_call_wait_seconds_count ")]
    assert float(line[0].split()[1]) == 2 * st["calls"]
    w.close()

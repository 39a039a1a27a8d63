"""Bring-up guards (CPU side): nothing serves under a `tpu` label from
anything but a TPU, a device fault is never a quiet host verdict, the
compile cache sits where it is told, and chip_smoke.py refuses to
pass without a chip. The chip side is chip_smoke.py itself."""

import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from minio_tpu.object.erasure_object import _host_rows
from minio_tpu.ops import batcher as batcher_mod
from minio_tpu.ops import device
from minio_tpu.ops.batcher import DeviceRouteError, StripeBatcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K, M, SHARD = 8, 4, 4096
_HAS_CHIP = bool(glob.glob("/dev/vfio/[0-9]*") or glob.glob("/dev/accel*"))


def _env(**over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "MTPU_HTTP_WORKERS",
                        "MTPU_BATCH_FORCE", "XLA_FLAGS")}
    env["PYTHONPATH"] = REPO
    env.update(over)
    return env


# -- chip_smoke.py without a chip --------------------------------------------

def test_chip_smoke_names_cpu_and_fails_fast():
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=_env(JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert time.monotonic() - t0 < 10, "must fail before loading data"
    assert "cpu" in out.stderr
    assert out.stdout == "", "no result line without an accelerator"


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = _env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=60)
    assert out.returncode != 0
    assert out.stdout == ""


def test_chip_smoke_last_line_is_ok_and_device_only(capsys):
    """The driver parses the LAST stdout line and refuses anything but
    exactly {ok, device:{platform, kind, count}}; the report goes on
    the line before it."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    chip_smoke.emit({"ok": True, "seed": 0, "boot_s": {"cold": 1.5},
                     "device": {"platform": "tpu", "kind": "TPU v5 lite",
                                "count": 1}})
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0])["report"]["boot_s"] == {"cold": 1.5}
    assert json.loads(lines[1]) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


# -- compile cache placement -------------------------------------------------

def test_compile_cache_dir_env_then_fixed_checkout_path():
    assert device.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}) \
        == "/somewhere/else"
    fixed = device.compile_cache_dir({})
    assert fixed == os.path.join(REPO, ".jax_cache")
    assert fixed == device.compile_cache_dir({})       # never moves
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# -- a device function that raises -------------------------------------------

def _window(b, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size=(b, K, SHARD), dtype=np.uint8)


def _probe_settled(sb, timeout=20.0):
    t_end = time.monotonic() + timeout
    while sb._device_ok is None and time.monotonic() < t_end:
        time.sleep(0.01)
    assert sb._device_ok is not None, "probe never settled"


def test_raising_device_fn_is_counted_surfaced_and_fatal_when_required(
        monkeypatch, capfd):
    monkeypatch.delenv("MTPU_BATCH_FORCE", raising=False)

    def boom(stacked):
        raise RuntimeError("Mosaic refused the kernel")

    before = device.stats()["faults"].get("probe:put", 0)
    sb = StripeBatcher(boom, lambda s: _host_rows(K, M, s),
                       min_device_blocks=8, name="8+4")
    win = _window(8)
    rows = sb.frame(win)               # device-sized: starts the probe
    assert len(rows) == K + M          # served by host meanwhile
    _probe_settled(sb)
    # Not a quiet "host wins": the verdict says raised, the fault is
    # counted and its traceback is on stderr.
    assert isinstance(sb.probe_error, RuntimeError)
    cal = sb.calibration()
    assert cal["verdict"] == "raised" and "Mosaic refused" in cal["error"]
    assert device.stats()["faults"]["probe:put"] == before + 1
    assert "Mosaic refused the kernel" in device.stats()["last_fault"]
    assert "Traceback" in capfd.readouterr().err
    assert any(c["verdict"] == "raised" for c in
               batcher_mod.aggregate_stats()["calibration"])
    # Nobody asked for the device: the route resolves to host, loudly.
    assert sb.wants_device() is False
    assert len(sb.frame(win)) == K + M
    # The operator asked for it: an error, not a route.
    monkeypatch.setattr(device, "_required", True)
    with pytest.raises(DeviceRouteError, match="Mosaic refused"):
        sb.wants_device()
    with pytest.raises(DeviceRouteError):
        sb.worth_batching(8)
    with pytest.raises(DeviceRouteError):
        sb.frame(win)


def test_slow_device_is_a_verdict_not_a_fault(monkeypatch):
    monkeypatch.delenv("MTPU_BATCH_FORCE", raising=False)
    monkeypatch.setattr(device, "_required", True)

    def slow(stacked):
        time.sleep(0.3)
        return _host_rows(K, M, stacked)

    before = dict(device.stats()["faults"])
    sb = StripeBatcher(slow, lambda s: _host_rows(K, M, s),
                       min_device_blocks=8)
    sb.frame(_window(8))
    _probe_settled(sb)
    cal = sb.calibration()
    assert cal["verdict"] == "host" and cal["device_ms"] > cal["host_ms"]
    assert sb.probe_error is None and sb.wants_device() is False
    assert device.stats()["faults"] == before


def test_dispatch_fault_is_counted(monkeypatch):
    monkeypatch.delenv("MTPU_BATCH_FORCE", raising=False)

    def boom(stacked):
        raise RuntimeError("device fell over")

    before = device.stats()["faults"].get("dispatch:get", 0)
    sb = StripeBatcher(boom, lambda s: _host_rows(K, M, s),
                       min_device_blocks=2, route="get")
    sb.force(True)
    with pytest.raises(RuntimeError, match="fell over"):
        sb.frame(_window(4))
    assert device.stats()["faults"]["dispatch:get"] == before + 1


# -- no silent interpreter, no JAX in a host process -------------------------

def test_pallas_off_tpu_raises_and_interpret_is_by_name():
    from minio_tpu.ops.hh_device import hash_blocks_device
    from minio_tpu.ops.rs_device import DeviceBackend
    from minio_tpu.utils.highwayhash import MAGIC_KEY
    if device.on_tpu():
        pytest.skip("off-TPU behaviour")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        DeviceBackend("pallas")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        hash_blocks_device(MAGIC_KEY, np.zeros((1, 64), np.uint8),
                           mode="pallas")
    assert DeviceBackend("interpret")._interpret is True
    assert DeviceBackend("auto").mode == "xla"


def test_host_codec_object_layer_never_imports_jax(tmp_path):
    code = (
        "import sys, os\n"
        "from minio_tpu.object.erasure_object import ErasureSet\n"
        "from minio_tpu.storage.local import LocalStorage\n"
        f"disks = [LocalStorage(r'{tmp_path}/d%d' % i) for i in range(4)]\n"
        "[d.make_vol('bkt') for d in disks]\n"
        "es = ErasureSet(disks, parity=2)\n"
        "body = os.urandom(9 << 20)\n"
        "es.put_object('bkt', 'o', body)\n"
        "assert es.get_object('bkt', 'o')[1] == body\n"
        "es.close()\n"
        "assert 'jax' not in sys.modules, 'host path imported JAX'\n")
    out = subprocess.run([sys.executable, "-c", code], env=_env(),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


# -- the native artifact is tied to its source and host ----------------------

def test_native_artifact_name_tracks_source_and_host(tmp_path, monkeypatch):
    from minio_tpu import native
    base = native._so_path()
    assert os.path.basename(base).startswith("_native-")
    assert native._so_path() == base
    src = tmp_path / "native.cc"
    with open(native._SRC, "rb") as f:
        src.write_bytes(f.read() + b"\n// edited\n")
    monkeypatch.setattr(native, "_SRC", str(src))
    edited = native._so_path()
    assert os.path.basename(edited) != os.path.basename(base)
    monkeypatch.undo()
    monkeypatch.setattr(native, "_host_cpu", lambda: "another cpu")
    assert native._so_path() != base


# -- the boot line tells the truth -------------------------------------------

def _boot(tmp_path, env, backend="tpu", python=("-m", "minio_tpu.server")):
    proc = subprocess.Popen(
        [sys.executable, *python, "--ec-backend", backend,
         "--address", "127.0.0.1:0", str(tmp_path / "d{1...4}")],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    lines: list[str] = []
    err: list[str] = []
    ready = threading.Event()

    def drain(stream, sink):
        for line in stream:
            sink.append(line)
            if "serving S3" in line:
                ready.set()
        ready.set()
    for stream, sink in ((proc.stdout, lines), (proc.stderr, err)):
        threading.Thread(target=drain, args=(stream, sink),
                         daemon=True).start()
    return proc, lines, err, ready


def test_boot_line_under_explicit_cpu_names_cpu_not_tpu(tmp_path):
    # Four workers asked for: a device-backed boot must still be ONE
    # process, and must say which platform it really runs on.
    proc, lines, err, ready = _boot(
        tmp_path, _env(JAX_PLATFORMS="cpu", MTPU_HTTP_WORKERS="4"))
    try:
        assert ready.wait(180) and proc.poll() is None, "".join(err[-20:])
        boot = next(l for l in lines if "serving S3" in l)
        assert "ec-backend=tpu" not in boot
        assert "ec-backend=portable, platform=cpu" in boot
        assert "JAX_PLATFORMS=cpu" in boot
        assert any("not pre-forking" in l for l in lines)
        kids = subprocess.run(["pgrep", "-P", str(proc.pid)],
                              capture_output=True, text=True).stdout.split()
        assert kids == [], "a device-backed boot pre-forked workers"
        from minio_tpu.s3.client import RemoteS3
        addr = boot.split("serving S3 on ")[1].split()[0]
        cli = RemoteS3(addr, "minioadmin", "minioadmin", timeout=60)
        st, _, data = cli.request("GET", "/minio/admin/v3/info")
        dev = json.loads(data)["device"]
        assert (dev["ec_backend"], dev["platform"]) == ("portable", "cpu")
        assert dev["required"] is True and dev["pid"] == proc.pid
        st, _, mx = cli.request("GET", "/minio/v2/metrics/cluster")
        assert 'minio_tpu_device_info{backend="portable",platform="cpu"' \
            in mx.decode()
        # A degraded read leaves an MRF heal in flight (device calls
        # included). SIGTERM waits for it, THEN stamps the drives clean,
        # and leaves through normal interpreter exit: rc 0 means no
        # abort at finalisation, not an os._exit over one.
        from minio_tpu.object.erasure_object import hash_order
        body = os.urandom(24 << 20)
        assert cli.request("PUT", "/bkt1")[0] == 200
        assert cli.request("PUT", "/bkt1/obj", body=body)[0] == 200
        holder = hash_order("bkt1/obj", 4).index(1)     # shard 0: data
        part, = glob.glob(str(tmp_path / f"d{holder + 1}" / "bkt1" / "obj"
                              / "*" / "part.1"))
        os.unlink(part)
        st, _, got = cli.request("GET", "/bkt1/obj")
        assert st == 200 and got == body
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(120) == 0, "SIGTERM must exit 0"
        assert os.path.exists(part), "stamped clean over an unfinished heal"
        assert _clean_stamps(tmp_path) == 4
        assert not any("Traceback" in l or "terminate called" in l
                       for l in err), "".join(l[:300] for l in err[-20:])
    finally:
        if proc.poll() is None:
            proc.kill()


def _clean_stamps(tmp_path) -> int:
    return len(glob.glob(str(tmp_path / "d*" / ".mtpu.sys"
                             / "clean.shutdown")))


def test_unquiesced_stop_is_not_stamped_clean_and_exits_nonzero(tmp_path):
    """A background healer that has not ended when its wait runs out
    (here: a wait of zero) makes the stop unclean: no stamp — the next
    boot runs the recovery sweep — and a non-zero exit."""
    code = ("import sys, minio_tpu.server as s; s._QUIESCE_S = 0.0; "
            "sys.exit(s.main(sys.argv[1:]))")
    proc, lines, err, ready = _boot(
        tmp_path, _env(JAX_PLATFORMS="cpu", MTPU_HTTP_WORKERS="1"),
        backend="host", python=("-c", code))
    try:
        assert ready.wait(120) and proc.poll() is None, "".join(err[-20:])
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(60) == 1
        assert _clean_stamps(tmp_path) == 0
        assert any("NOT stamped clean" in l for l in err)
    finally:
        if proc.poll() is None:
            proc.kill()


def test_mrf_stop_reports_a_heal_still_in_flight(tmp_path, monkeypatch):
    from minio_tpu.object import healing
    started, release = threading.Event(), threading.Event()

    def slow_heal(es, bucket, object_, vid, deep=False):
        started.set()
        assert release.wait(30)

    monkeypatch.setattr(healing, "heal_object", slow_heal)
    q = healing.MRFQueue(es=None, persist=False)
    q.enqueue("bkt", "obj")
    assert started.wait(10)
    assert q.stop(timeout=0.05) is False        # still healing
    release.set()
    assert q.stop(timeout=10) is True
    assert q.healed == 1                        # ran to its end


@pytest.mark.skipif(_HAS_CHIP, reason="this machine has an accelerator")
def test_ec_backend_tpu_refuses_to_boot_on_a_silent_cpu_fallback(tmp_path):
    """JAX_PLATFORMS unset and no chip: JAX falls back to the CPU with a
    warning. Asked for the TPU, the server must not serve."""
    proc, lines, err, ready = _boot(tmp_path, _env())
    try:
        assert proc.wait(180) == 1
        assert not any("serving S3" in l for l in lines)
        assert any("FATAL: --ec-backend tpu" in l and "cpu" in l
                   for l in err), "".join(err[-20:])
    finally:
        if proc.poll() is None:
            proc.kill()

"""Admin profiling (reference: cmd/admin-handlers.go:1021): start a
CPU profile, run load, download the per-node bundle."""

import io
import marshal
import os
import zipfile

import pytest

from minio_tpu.object.erasure_object import ErasureSet
from minio_tpu.s3.profiling import Profiler, bundle, make_profile_handler
from minio_tpu.s3.server import S3Server
from minio_tpu.storage.local import LocalStorage
from tests.s3client import S3Client


def test_profile_start_load_download(tmp_path):
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    srv = S3Server(ErasureSet(disks), address="127.0.0.1:0")
    srv.start()
    try:
        cli = S3Client(srv.address)
        assert cli.request("PUT", "/profbkt")[0] == 200
        st, _, b = cli.request("POST", "/minio/admin/v3/start-profiling")
        assert st == 200, b
        # Double start is refused.
        assert cli.request("POST",
                           "/minio/admin/v3/start-profiling")[0] == 400
        for i in range(5):
            cli.request("PUT", f"/profbkt/o{i}", body=os.urandom(20_000))
        st, h, body = cli.request("GET",
                                  "/minio/admin/v3/download-profiling")
        assert st == 200
        assert h.get("Content-Type") == "application/zip"
        z = zipfile.ZipFile(io.BytesIO(body))
        names = z.namelist()
        assert "local/profile.txt" in names
        assert "local/profile.pstats" in names
        text = z.read("local/profile.txt").decode()
        # The profile saw the PUT handler run.
        assert "put_object" in text
        stats = marshal.loads(z.read("local/profile.pstats"))
        assert stats                        # loadable pstats table
        # Download without a running profile is a clean 400.
        assert cli.request("GET",
                           "/minio/admin/v3/download-profiling")[0] == 400
    finally:
        srv.stop()


def test_peer_profile_handler_roundtrip():
    p = Profiler()
    h = make_profile_handler(p)
    assert h({"action": "start"})["ok"]
    with p.request_profile():              # what a request's thread does
        sum(i * i for i in range(50_000))  # some work to profile
    rec = h({"action": "stop"})
    assert rec["ok"] and rec["text"]
    import base64
    assert marshal.loads(base64.b64decode(rec["stats_b64"]))
    assert not h({"action": "stop"})["ok"]  # nothing running now
    blob = bundle({"n1": {"stats": b"x", "text": "t"}})
    assert zipfile.ZipFile(io.BytesIO(blob)).namelist() == \
        ["n1/profile.pstats", "n1/profile.txt"]


def wait_until(pred, seconds: float = 10.0) -> None:
    import time
    deadline = time.monotonic() + seconds
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.01)


@pytest.fixture
def srv(tmp_path):
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    server = S3Server(ErasureSet(disks), address="127.0.0.1:0")
    server.start()
    try:
        yield server
    finally:
        server.stop()


def test_requests_are_answered_while_a_profile_runs(srv):
    """Since Python 3.12 a second cProfile.Profile().enable() anywhere
    in the process raises: one request is captured at a time, the
    others run unprofiled, and none of them fails for it."""
    import threading
    cli = S3Client(srv.address)
    assert cli.request("PUT", "/busybkt")[0] == 200
    assert cli.request("POST", "/minio/admin/v3/start-profiling")[0] == 200
    statuses: list = []

    def load(w):
        c = S3Client(srv.address)
        for i in range(6):
            statuses.append(c.request("PUT", f"/busybkt/w{w}-{i}",
                                      body=os.urandom(50_000))[0])
            statuses.append(c.request("GET", f"/busybkt/w{w}-{i}")[0])

    threads = [threading.Thread(target=load, args=(w,)) for w in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not any(t.is_alive() for t in threads)
    assert statuses == [200] * 72
    st, _, body = cli.request("GET", "/minio/admin/v3/download-profiling")
    assert st == 200
    text = zipfile.ZipFile(io.BytesIO(body)).read(
        "local/profile.txt").decode()
    assert "put_object" in text or "get_object" in text
    # a profile left enabled by somebody else in the process costs the
    # request its capture, never its answer
    import cProfile
    # the download's own capture ends after its answer is sent
    wait_until(lambda: not srv.profiler._slot.locked())
    foreign = cProfile.Profile()
    foreign.enable()
    try:
        assert cli.request("POST",
                           "/minio/admin/v3/start-profiling")[0] == 200
        assert cli.request("GET", "/busybkt/w0-0")[0] == 200
    finally:
        foreign.disable()
    assert cli.request("GET",
                       "/minio/admin/v3/download-profiling")[0] == 200


def test_trace_profile_is_refused_where_no_device_is_held(srv, monkeypatch):
    from minio_tpu.ops import device
    monkeypatch.setattr(device, "held", lambda: False)
    cli = S3Client(srv.address)
    st, _, body = cli.request("POST", "/minio/admin/v3/start-profiling",
                              query={"profilerType": "trace"})
    assert st == 400 and b"holds the device" in body
    st, _, body = cli.request("POST", "/minio/admin/v3/start-profiling",
                              query={"profilerType": "heap"})
    assert st == 400 and b"profilerType" in body
    # neither left anything running
    assert cli.request("GET",
                       "/minio/admin/v3/download-profiling")[0] == 400
    assert cli.request("PUT", "/stillserving")[0] == 200


def test_trace_profile_zips_the_profilers_directory(srv):
    """`profilerType=trace` in the process that holds the device (here:
    JAX on the CPU backend): jax.profiler's own directory comes back
    under <node>/trace/, with the program's stages in it."""
    import glob

    import jax

    from minio_tpu.ops import device
    from minio_tpu.utils import tracing
    device.info()                        # this process holds the device,
    tracing.set_annotator(jax.profiler.TraceAnnotation)  # as info() left it
    try:
        cli = S3Client(srv.address)
        st, _, body = cli.request(
            "POST", "/minio/admin/v3/start-profiling",
            query={"profilerType": "trace"})
        if st == 400 and b"did not start" in body:
            pytest.skip(f"no profiler session on this box: {body!r}")
        assert st == 200, body
        # a second start of either type is refused; requests are served
        assert cli.request("POST",
                           "/minio/admin/v3/start-profiling")[0] == 400
        assert cli.request("PUT", "/tracebkt")[0] == 200
        assert cli.request("PUT", "/tracebkt/o",
                           body=os.urandom(400_000))[0] == 200
        # an answer is sent from inside the request's root annotation:
        # let the handler's thread leave it before the trace is ended
        wait_until(lambda: not srv._inflight)
        st, h, body = cli.request("GET",
                                  "/minio/admin/v3/download-profiling")
    finally:
        if srv.profiler.kind:            # failed half-way: end the session
            srv.profiler.stop()
    assert st == 200 and h.get("Content-Type") == "application/zip"
    z = zipfile.ZipFile(io.BytesIO(body))
    planes = [n for n in z.namelist()
              if n.startswith("local/trace/") and n.endswith(".xplane.pb")]
    assert planes, z.namelist()
    out = os.path.join(os.path.dirname(srv.object_layer.disks[0].root),
                       "unzipped")
    z.extractall(out)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(glob.glob(
        os.path.join(out, "local", "trace", "**", "*.xplane.pb"),
        recursive=True)[0])
    names = {e.name for p in data.planes if p.name.startswith("/host:CPU")
             for line in p.lines for e in line.events}
    assert "s3.PUT:object" in names and "s3.auth" in names
    assert "engine.op" in names          # the drive workers' threads too
    # the temp dir is gone and nothing is running
    assert cli.request("GET",
                       "/minio/admin/v3/download-profiling")[0] == 400

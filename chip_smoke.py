#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that minio-tpu still starts and
serves on the chip.

    python chip_smoke.py [--seed N]

Boots `python -m minio_tpu.server --ec-backend tpu` exactly as a user
would (one node, one erasure set of 12 drives, EC 8+4 — BASELINE.json
config 2 at BASELINE.md's operating points), drives ~2 GiB of seeded
S3 traffic through it, and exits 0 only if every byte came back right
AND the live server's own admin info and metrics say a TPU did the
work. Stdout is two lines: `{"report": {...}}` with everything that
was measured, then — last — `{"ok": true, "device": {"platform": "tpu",
"kind": "...", "count": 1}}`, exactly those keys.

This process never imports JAX: the server subprocess is the one
process that owns the chip, and everything here is checked with the
JAX-free host codec (erasure/codec.py, storage/bitrot.py) and
s3/client.py. With no accelerator (JAX_PLATFORMS=cpu, or JAX finding
none) or outside a checkout it exits non-zero and prints no result.

Phase A pins MTPU_BATCH_FORCE=device so every eligible window must
ride the chip; phase B reboots the same drives with no pin, replays a
short burst, and records — does not assert — what each route's
calibration probe decided (a probe that RAISED is still fatal). Phase
B's boot also shows the compile cache hitting. Phase C boots once more
with no --ec-backend at all: the default (`auto`) must find the chip
through its probe child, own it alone and label itself truthfully.
Every phase ends in a SIGTERM that must exit 0 and leave the drives
stamped clean — phase A's with a background heal in flight.

On a four-chip host the same run is the mesh framer's bring-up proof
(`mesh_devices: 4` in the report, bytes identical) and nothing more:
how fast the mesh serves is the benchmark's cell
`ec8p4-12d-4chip.put-64m`, not this script.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import glob
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MiB = 1 << 20
BUCKET = "smoke"
K, M, BLOCK = 8, 4, MiB            # EC 8+4, 1 MiB erasure block
DRIVES = K + M

# Traffic — the full contract; cut counts only, listing each cut in
# `reduced` (FULL is what the numbers are compared against).
FULL = {"big_objects": 24, "small_objects": 256, "mp_parts": 16}
BIG_OBJECTS, BIG_SIZE, BIG_CONC = 24, 64 * MiB, 4
SMALL_OBJECTS, SMALL_SIZE, SMALL_CONC = 256, 1 * MiB, 16
MP_PARTS, MP_PART_SIZE = 16, 5 * MiB
B_SMALL, B_BIG = 64, 4             # phase B's short burst
DEADLINE_S = 1100                  # the contract allows 1200


class SmokeError(Exception):
    pass


class NoAccelerator(Exception):
    """JAX found no TPU: exit non-zero and print no result."""


def log(msg: str) -> None:
    print(f"[smoke {time.monotonic() - T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def check(cond, msg: str) -> None:
    if time.monotonic() - T0 > DEADLINE_S:
        raise SmokeError(f"out of time ({DEADLINE_S}s) before: {msg}")
    if not cond:
        raise SmokeError(msg)


def body_for(seed: int, idx: int, size: int) -> bytes:
    """Object bytes from (--seed, object index): regenerated at compare
    time instead of held, so 2 GiB of traffic costs no 2 GiB of RAM."""
    import numpy as np
    return np.random.default_rng([seed, idx]).bytes(size)


# ---------------------------------------------------------------------------
# the server under test
# ---------------------------------------------------------------------------

class Server:
    """One `python -m minio_tpu.server` subprocess, pipes drained by
    reader threads (an undrained pipe eventually blocks the server)."""

    def __init__(self, root: str, env_extra: dict, backend: str = "tpu"):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.address = f"127.0.0.1:{self.port}"
        env = dict(os.environ)
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("MTPU_BATCH_FORCE", None)      # only what a phase pins
        env.update(env_extra)
        self.out: list[str] = []
        self.err: list[str] = []
        self.boot_line = ""
        self._ready = threading.Event()
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "minio_tpu.server",
             *(("--ec-backend", backend) if backend else ()),
             "--address", self.address,
             os.path.join(root, "d{1...%d}" % DRIVES)],
            env=env, cwd=HERE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, start_new_session=True)
        self.boot_s = None
        for stream, sink in ((self.proc.stdout, self.out),
                             (self.proc.stderr, self.err)):
            threading.Thread(target=self._drain, args=(stream, sink),
                             daemon=True).start()

    def _drain(self, stream, sink) -> None:
        for line in stream:
            sink.append(line)
            if "serving S3" in line and not self.boot_line:
                self.boot_line = line.strip()
                self.boot_s = time.monotonic() - self.t_spawn
                self._ready.set()
        self._ready.set()              # EOF: the process is gone

    def wait_ready(self, timeout: float) -> None:
        self._ready.wait(timeout)
        if not self.boot_line:
            raise SmokeError(
                "server did not come up "
                f"(exit={self.proc.poll()}); stderr tail:\n"
                + "".join(self.err[-30:]))

    def pids(self) -> list[int]:
        """The server and every descendant, from /proc."""
        kids: dict[int, list[int]] = {}
        for d in os.listdir("/proc"):
            if d.isdigit():
                try:
                    with open(f"/proc/{d}/stat") as f:
                        ppid = int(f.read().rsplit(")", 1)[1].split()[1])
                except (OSError, ValueError, IndexError):
                    continue
                kids.setdefault(ppid, []).append(int(d))
        out, todo = [], [self.proc.pid]
        while todo:
            p = todo.pop()
            out.append(p)
            todo.extend(kids.get(p, ()))
        return out

    def stop(self) -> int:
        """SIGTERM, wait for the exit code (the chip frees with it).
        The server first lets the heal in flight finish, so this can
        take as long as one object's heal."""
        t0 = time.monotonic()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            code = self.proc.wait(120)
        except subprocess.TimeoutExpired:
            self.kill()
            raise SmokeError("server ignored SIGTERM for 120 s")
        self.stop_s = round(time.monotonic() - t0, 2)
        return code

    def kill(self) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(10)
        except subprocess.TimeoutExpired:
            pass


def tpu_holders(pids: list[int]) -> list[int]:
    """Which of `pids` have an accelerator device node open."""
    held = []
    for pid in pids:
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue
        for fd in fds:
            try:
                tgt = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if re.match(r"/dev/(vfio/\d+|accel\d*)", tgt):
                held.append(pid)
                break
    return held


# ---------------------------------------------------------------------------
# reading the live server
# ---------------------------------------------------------------------------

def admin_device(cli) -> dict:
    st, _, data = cli.request("GET", "/minio/admin/v3/info")
    check(st == 200, f"admin info: HTTP {st}")
    dev = json.loads(data).get("device")
    check(isinstance(dev, dict), "admin info has no device section")
    return dev


_SERIES = re.compile(r"^(minio_tpu_\w+)\{([^}]*)\}\s+(\S+)$")


def scrape(cli) -> dict:
    """{metric: {frozenset(label items): value}} for the families the
    smoke reads."""
    st, _, data = cli.request("GET", "/minio/v2/metrics/cluster")
    check(st == 200, f"metrics: HTTP {st}")
    out: dict = {}
    for line in data.decode().splitlines():
        m = _SERIES.match(line)
        if m and m.group(1).startswith(("minio_tpu_batcher_",
                                        "minio_tpu_get_kernel_",
                                        "minio_tpu_device_")):
            labels = dict(re.findall(r'(\w+)="([^"]*)"', m.group(2)))
            out.setdefault(m.group(1), {})[
                frozenset(labels.items())] = float(m.group(3))
    return out


def series(metrics: dict, name: str, **labels) -> float:
    want = set(labels.items())
    return sum(v for k, v in metrics.get(name, {}).items() if want <= k)


def route_split(metrics: dict) -> dict:
    """Per route: device/host dispatches and device/host/bypass
    windows, as the server counted them."""
    out = {}
    for route in ("put", "transform", "get", "reconstruct"):
        out[route] = {
            "dispatches": {p: int(series(
                metrics, "minio_tpu_batcher_dispatches_total",
                route=route, path=p)) for p in ("device", "host")},
            "windows": {p: int(series(
                metrics, "minio_tpu_batcher_requests_total",
                route=route, path=p))
                for p in ("device", "host", "bypass")}}
    return out


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def put_get_many(cli, seed: int, specs: list, conc: int, verb: str):
    """specs: [(key, idx, size)]. PUT or GET+compare each at `conc`-way
    concurrency; returns per-object seconds in spec order."""
    def one(spec):
        key, idx, size = spec
        body = body_for(seed, idx, size)
        t0 = time.monotonic()
        if verb == "PUT":
            st, _, resp = cli.request("PUT", f"/{BUCKET}/{key}", body=body)
            check(st == 200, f"PUT {key}: HTTP {st} {resp[:200]!r}")
        else:
            st, _, got = cli.request("GET", f"/{BUCKET}/{key}")
            check(st == 200, f"GET {key}: HTTP {st} {got[:200]!r}")
            check(got == body, f"GET {key}: body differs from seed bytes")
        return time.monotonic() - t0
    with cf.ThreadPoolExecutor(conc) as ex:
        return list(ex.map(one, specs))


def multipart_put(cli, seed: int, key: str, idx0: int) -> None:
    st, _, data = cli.request("POST", f"/{BUCKET}/{key}",
                              query={"uploads": ""})
    check(st == 200, f"CreateMultipartUpload: HTTP {st}")
    uid = re.search(rb"<UploadId>([^<]+)</UploadId>", data).group(1).decode()

    def part(n):
        st, hdr, resp = cli.request(
            "PUT", f"/{BUCKET}/{key}",
            query={"partNumber": str(n), "uploadId": uid},
            body=body_for(seed, idx0 + n, MP_PART_SIZE))
        check(st == 200, f"UploadPart {n}: HTTP {st} {resp[:200]!r}")
        etag = {k.lower(): v for k, v in hdr.items()}["etag"]
        return n, etag
    with cf.ThreadPoolExecutor(MP_PARTS) as ex:   # all parts at once
        parts = list(ex.map(part, range(1, MP_PARTS + 1)))
    xml = "<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{n}</PartNumber><ETag>{e}</ETag></Part>"
        for n, e in parts) + "</CompleteMultipartUpload>"
    st, _, resp = cli.request("POST", f"/{BUCKET}/{key}",
                              query={"uploadId": uid}, body=xml.encode())
    check(st == 200 and b"<Error>" not in resp,
          f"CompleteMultipartUpload: HTTP {st} {resp[:200]!r}")


def list_all(cli) -> list[str]:
    keys, token = [], None
    while True:
        q = {"list-type": "2", "max-keys": "100"}
        if token:
            q["continuation-token"] = token
        st, _, data = cli.request("GET", f"/{BUCKET}", query=q)
        check(st == 200, f"ListObjectsV2: HTTP {st}")
        text = data.decode()
        keys += re.findall(r"<Key>([^<]+)</Key>", text)
        if "<IsTruncated>true</IsTruncated>" not in text:
            return keys
        token = re.search(
            r"<NextContinuationToken>([^<]+)</NextContinuationToken>",
            text).group(1)


def reference_shard_files(body: bytes) -> list[bytes]:
    """The 12 on-disk shard files of `body`, by the host codec: each
    1 MiB block encoded on its own, shard i's blocks concatenated, then
    `digest || block` framed. Independent of the code under test."""
    import numpy as np
    from minio_tpu.erasure.codec import Erasure
    from minio_tpu.storage import bitrot
    e = Erasure(K, M, BLOCK)
    per_block = [e.encode_data(body[o:o + BLOCK])
                 for o in range(0, len(body), BLOCK)]
    shards = np.stack([np.concatenate([blk[i] for blk in per_block])
                       for i in range(DRIVES)])
    return bitrot.frame_shards_batch(shards, e.shard_size())


def shard_order(key: str) -> list[int]:
    """Drive index holding each shard: drive i holds shard
    hash_order(bucket/key)[i] - 1 (drive 1 usually holds PARITY)."""
    from minio_tpu.object.erasure_object import hash_order
    dist = hash_order(f"{BUCKET}/{key}", DRIVES)
    order = [0] * DRIVES
    for drive, shard1 in enumerate(dist):
        order[shard1 - 1] = drive
    return order


def shard_paths(root: str, key: str) -> list[str]:
    """part.1 of `key` in SHARD order (objects past the inline
    threshold only)."""
    paths = []
    for drive in shard_order(key):
        hits = glob.glob(os.path.join(root, f"d{drive + 1}", BUCKET, key,
                                      "*", "part.1"))
        check(len(hits) == 1, f"{key}: drive d{drive + 1} holds "
                              f"{len(hits)} part.1 files, want 1")
        paths.append(hits[0])
    return paths


def read_shards(root: str, key: str, inline: bool) -> list[bytes]:
    """The framed shard bytes each drive holds, in SHARD order: part.1,
    or — shard files of <= 128 KiB, which is every 1 MiB object at 8+4 —
    the blob inlined into the drive's xl.meta."""
    if not inline:
        out = []
        for path in shard_paths(root, key):
            with open(path, "rb") as f:
                out.append(f.read())
        return out
    from minio_tpu.storage.meta import XLMeta
    out = []
    for drive in shard_order(key):
        path = os.path.join(root, f"d{drive + 1}", BUCKET, key, "xl.meta")
        with open(path, "rb") as f:
            blobs = list(XLMeta.load(f.read()).inline.values())
        check(len(blobs) == 1, f"{key}: {path} inlines {len(blobs)} "
                               "versions, want 1")
        out.append(bytes(blobs[0]))
    return out


def check_on_disk(root: str, key: str, body: bytes) -> None:
    want = reference_shard_files(body)
    inline = len(want[0]) - 32 * (len(body) // BLOCK) <= 128 * 1024
    for shard, got in enumerate(read_shards(root, key, inline)):
        kind = "data" if shard < K else "PARITY"
        check(got == want[shard],
              f"{key}: on-disk {kind} shard {shard} differs from the host "
              f"codec's frame_shards_batch(encode_data(...))")


def stop_clean(srv: Server, root: str, phase: str, res: dict) -> None:
    """SIGTERM -> exit 0 through normal interpreter exit (a thread left
    inside a device call would abort it), nothing on stderr that looks
    like a crash, every drive stamped clean."""
    code = srv.stop()
    check(code == 0, f"phase {phase} server exit code {code} on SIGTERM, "
                     "want 0; stderr tail:\n" + "".join(srv.err[-15:]))
    check(not any("Traceback" in l or "terminate called" in l
                  for l in srv.err),
          f"crash text in phase {phase} server stderr:\n"
          + "".join(srv.err[-40:]))
    stamps = glob.glob(os.path.join(root, "d*", ".mtpu.sys",
                                    "clean.shutdown"))
    check(len(stamps) == DRIVES, f"phase {phase}: {len(stamps)} of {DRIVES} "
                                 "drives stamped clean after SIGTERM")
    res.setdefault("stop_s", {})[phase] = srv.stop_s


def cache_files(path: str) -> int:
    return sum(len(files) for _, _, files in os.walk(path))


# ---------------------------------------------------------------------------
# the phases
# ---------------------------------------------------------------------------

def phase_a(root: str, seed: int, res: dict) -> None:
    from minio_tpu.s3.client import RemoteS3
    log("phase A: cold boot, MTPU_BATCH_FORCE=device")
    srv = Server(root, {"MTPU_BATCH_FORCE": "device"})
    try:
        srv.wait_ready(420)
        log(srv.boot_line)
        cli = RemoteS3(srv.address, "minioadmin", "minioadmin", timeout=600)
        dev = admin_device(cli)
        # No accelerator -> no result: fail before any data is loaded.
        if dev.get("platform") != "tpu":
            raise NoAccelerator(
                f"server came up on platform {dev.get('platform')!r} "
                f"({dev.get('device_kind')!r}), not a TPU")
        check(dev.get("ec_backend") == "tpu" and dev.get("device_kind")
              and dev.get("devices", 0) >= 1,
              f"admin info device section is not a TPU's: {dev}")
        check("ec-backend=tpu, platform=tpu" in srv.boot_line,
              f"boot line does not name the TPU: {srv.boot_line}")
        check(dev.get("native_lib") is True,
              "native library failed to build/load in the server")
        res.update(
            platform=dev["platform"], device_kind=dev["device_kind"],
            devices=dev["devices"], mesh_devices=dev["mesh_devices"],
            device={"platform": dev["platform"], "kind": dev["device_kind"],
                    "count": dev["devices"]},
            jax=dev["jax"], jaxlib=dev["jaxlib"], libtpu=dev["libtpu"],
            native_lib=True, compile_cache_dir=dev["compile_cache_dir"],
            boot_s={"cold": round(srv.boot_s, 2)})
        # One process for the chip: of the server and all its
        # descendants exactly one has the device open, and it is the
        # one that answered admin info.
        pids = srv.pids()
        holders = tpu_holders(pids)
        check(holders == [dev["pid"]],
              f"TPU device nodes open in pids {holders} of server-side "
              f"pids {pids}; want exactly [{dev['pid']}]")
        res["server_pids"] = len(pids)
        res["chip_holder_pids"] = len(holders)

        st, _, _ = cli.request("PUT", f"/{BUCKET}")
        check(st == 200, f"MakeBucket: HTTP {st}")
        big = [(f"big/{i:03d}", i, BIG_SIZE) for i in range(BIG_OBJECTS)]
        small = [(f"small/{i:04d}", 1000 + i, SMALL_SIZE)
                 for i in range(SMALL_OBJECTS)]
        moved = 0

        t0 = time.monotonic()
        took = put_get_many(cli, seed, big, BIG_CONC, "PUT")
        res["first_put_s"] = round(took[0], 2)
        log(f"PUT {len(big)} x 64 MiB {BIG_CONC}-way: "
            f"{time.monotonic() - t0:.1f}s (first {took[0]:.1f}s)")
        t0 = time.monotonic()
        put_get_many(cli, seed, small, SMALL_CONC, "PUT")
        log(f"PUT {len(small)} x 1 MiB {SMALL_CONC}-way: "
            f"{time.monotonic() - t0:.1f}s")
        t0 = time.monotonic()
        multipart_put(cli, seed, "mp/checkpoint", 5000)
        log(f"multipart {MP_PARTS} x 5 MiB: {time.monotonic() - t0:.1f}s")
        moved += sum(s for _, _, s in big + small) + MP_PARTS * MP_PART_SIZE
        put_split = route_split(scrape(cli))

        t0 = time.monotonic()
        put_get_many(cli, seed, big, BIG_CONC, "GET")
        put_get_many(cli, seed, small, SMALL_CONC, "GET")
        st, _, got = cli.request("GET", f"/{BUCKET}/mp/checkpoint")
        want = b"".join(body_for(seed, 5000 + n, MP_PART_SIZE)
                        for n in range(1, MP_PARTS + 1))
        check(st == 200 and got == want, "multipart GET differs from seed")
        log(f"GET all back, byte-identical: {time.monotonic() - t0:.1f}s")
        moved += sum(s for _, _, s in big + small) + len(want)

        # Ranged GET across a block AND a 32-block window boundary.
        lo, hi = 32 * MiB - 5, 33 * MiB + 7
        st, _, got = cli.request("GET", f"/{BUCKET}/{big[1][0]}",
                                 headers={"Range": f"bytes={lo}-{hi}"})
        check(st == 206 and got == body_for(seed, 1, BIG_SIZE)[lo:hi + 1],
              f"ranged GET: HTTP {st}, {len(got)} bytes")
        st, hdr, _ = cli.request("HEAD", f"/{BUCKET}/{big[0][0]}")
        clen = {k.lower(): v for k, v in hdr.items()}.get("content-length")
        check(st == 200 and clen == str(BIG_SIZE),
              f"HEAD: HTTP {st} content-length {clen}")
        keys = list_all(cli)
        n_objects = len(big) + len(small) + 1
        check(len(keys) == n_objects and len(set(keys)) == n_objects,
              f"ListObjectsV2 paged: {len(keys)} keys, want {n_objects}")
        victim = small[-1][0]
        st, _, _ = cli.request("DELETE", f"/{BUCKET}/{victim}")
        check(st in (200, 204), f"DELETE: HTTP {st}")
        st, _, _ = cli.request("GET", f"/{BUCKET}/{victim}")
        check(st == 404, f"GET after DELETE: HTTP {st}, want 404")

        # A healthy GET reads only the k data shards, so wrong device
        # PARITY would pass everything above: compare all 12 shard
        # files on disk with the host codec's, for one object of each
        # shape.
        check_on_disk(root, big[2][0], body_for(seed, 2, BIG_SIZE))
        check_on_disk(root, small[3][0], body_for(seed, 1003, SMALL_SIZE))
        log("on-disk shard files (data + parity) match the host codec")

        # Degraded read (BASELINE config 3): drop three DATA shards.
        dkey = big[5][0]
        gone = shard_paths(root, dkey)[:3]
        for path in gone:
            os.unlink(path)
        st, _, got = cli.request("GET", f"/{BUCKET}/{dkey}")
        check(st == 200 and got == body_for(seed, 5, BIG_SIZE),
              f"degraded GET (3 data shards gone): HTTP {st}, differs")
        moved += BIG_SIZE
        log("degraded GET with 3 data shards missing: byte-identical")

        # Proof the chip did it, from the live server.
        mx = scrape(cli)
        split = route_split(mx)
        put_side = {r: split[r]["dispatches"]["device"]
                    for r in ("put", "transform")}
        check(sum(put_side.values()) > 0,
              f"no device dispatch on the PUT side: {put_split}")
        for route in ("get", "reconstruct"):
            check(split[route]["dispatches"]["device"] > 0,
                  f"no device dispatch on route {route}: {split[route]}")
        check(series(mx, "minio_tpu_get_kernel_windows_total",
                     path="device") > 0,
              "minio_tpu_get_kernel_windows_total{path=device} is 0")
        errors = sum(mx.get("minio_tpu_device_errors_total", {}).values())
        check(errors == 0, f"device errors counted: {int(errors)} "
                           f"({admin_device(cli).get('last_fault')})")
        calls = {tuple(sorted(k)): v for k, v in mx.get(
            "minio_tpu_device_kernel_calls_total", {}).items()}
        not_pallas = {str(k): v for k, v in calls.items()
                      if ("impl", "pallas") not in k and v}
        check(not not_pallas, "dispatches served by something other than "
                              f"the Pallas kernel: {not_pallas}")
        for kern in ("frame", "deframe", "matrix"):
            check(series(mx, "minio_tpu_device_kernel_calls_total",
                         kernel=kern, impl="pallas") > 0,
                  f"Pallas kernel {kern!r} never ran")
        res["put_side_device_dispatches"] = put_side
        res["routes"] = {"A": split}
        res["get_kernel_device_windows"] = int(series(
            mx, "minio_tpu_get_kernel_windows_total", path="device"))
        res["objects"] = n_objects
        res["bytes_moved"] = moved
        # The degraded read queued a background heal of `dkey`; the
        # SIGTERM lands while it runs (device reconstruct included) and
        # must wait for it before the drives are stamped clean.
        stop_clean(srv, root, "A", res)
        healed = [p for p in gone if os.path.exists(p)]
        res["healed_before_exit"] = f"{len(healed)} of {len(gone)} shards"
        if len(healed) == len(gone):
            check_on_disk(root, dkey, body_for(seed, 5, BIG_SIZE))
    finally:
        srv.kill()
    n = cache_files(res["compile_cache_dir"])
    check(n > 0, f"compile cache {res['compile_cache_dir']} is empty")
    res["compile_cache_files"] = {"after_A": n}


def phase_b(root: str, seed: int, res: dict) -> None:
    from minio_tpu.s3.client import RemoteS3
    log("phase B: warm boot on the same drives, no pin")
    srv = Server(root, {})
    try:
        srv.wait_ready(420)
        log(srv.boot_line)
        res["boot_s"]["warm"] = round(srv.boot_s, 2)
        cli = RemoteS3(srv.address, "minioadmin", "minioadmin", timeout=600)
        check(admin_device(cli).get("platform") == "tpu",
              "phase B server is not on the TPU")

        def burst(tag: str, idx0: int) -> None:
            """16-way 1 MiB and 4-way 64 MiB at once: PUT, then GET."""
            small = [(f"b/{tag}/small/{i:04d}", idx0 + i, SMALL_SIZE)
                     for i in range(B_SMALL)]
            big = [(f"b/{tag}/big/{i:03d}", idx0 + 500 + i, BIG_SIZE)
                   for i in range(B_BIG)]
            for verb in ("PUT", "GET"):
                with cf.ThreadPoolExecutor(2) as ex:
                    jobs = [ex.submit(put_get_many, cli, seed, specs, conc,
                                      verb)
                            for specs, conc in ((small, SMALL_CONC),
                                                (big, BIG_CONC))]
                    for j in jobs:
                        j.result()
            res["bytes_moved"] += 2 * sum(s for _, _, s in small + big)

        # Burst 1 meets unprobed routes (host until the background
        # probes settle) and is what starts them; burst 2 runs under
        # whatever they decided. Both splits are recorded.
        burst("probing", 7000)
        t_end = time.monotonic() + 120
        while True:
            dev = admin_device(cli)
            if not any(c["verdict"] == "probing"
                       for c in dev["calibration"]) \
                    or time.monotonic() > t_end:
                break
            time.sleep(1.0)
        res["calibration_B"] = dev["calibration"]
        res["routes"]["B_probing"] = route_split(scrape(cli))
        burst("settled", 8000)
        after = route_split(scrape(cli))
        res["routes"]["B_settled"] = {
            r: {kind: {p: after[r][kind][p]
                       - res["routes"]["B_probing"][r][kind][p]
                       for p in after[r][kind]} for kind in after[r]}
            for r in after}
        dev = admin_device(cli)
        raised = [c for c in dev["calibration"] if c["verdict"] == "raised"]
        check(not raised and not dev["faults"],
              f"device function raised in phase B: {raised or dev['faults']}"
              f" ({dev.get('last_fault')})")
        stop_clean(srv, root, "B", res)
    finally:
        srv.kill()
    res["compile_cache_files"]["after_B"] = cache_files(
        res["compile_cache_dir"])


def phase_c(root: str, seed: int, res: dict) -> None:
    """The default entry point: no --ec-backend at all (`auto`). A
    short-lived child probes the platform and frees the chip; the
    server then takes it, alone, and is held to the same rule as under
    `tpu`: it says tpu only on a TPU and absorbs no device fault."""
    from minio_tpu.s3.client import RemoteS3
    log("phase C: boot with no --ec-backend (auto)")
    srv = Server(root, {}, backend="")
    try:
        srv.wait_ready(420)
        log(srv.boot_line)
        res["boot_s"]["auto"] = round(srv.boot_s, 2)
        cli = RemoteS3(srv.address, "minioadmin", "minioadmin", timeout=600)
        dev = admin_device(cli)
        check("ec-backend=tpu, platform=tpu" in srv.boot_line
              and dev.get("platform") == "tpu"
              and dev.get("ec_backend") == "tpu",
              f"auto did not come up on the TPU: {srv.boot_line} / {dev}")
        check(dev.get("required") is True,
              "auto found the TPU but may absorb its faults "
              "(device.required() is false)")
        pids = srv.pids()
        check(tpu_holders(pids) == [dev["pid"]],
              f"auto: TPU open in pids {tpu_holders(pids)} of {pids}; "
              f"want exactly [{dev['pid']}]")
        # One streamed PUT and its GET; it also starts the unpinned
        # routes' probes, so the SIGTERM below meets them in flight.
        spec = [("c/big/000", 9000, BIG_SIZE)]
        put_get_many(cli, seed, spec, 1, "PUT")
        put_get_many(cli, seed, spec, 1, "GET")
        res["bytes_moved"] += 2 * BIG_SIZE
        dev = admin_device(cli)
        check(not dev["faults"], f"device faults in phase C: {dev['faults']} "
                                 f"({dev.get('last_fault')})")
        stop_clean(srv, root, "C", res)
    finally:
        srv.kill()


def emit(res: dict) -> None:
    """Two stdout lines: the full report, then — LAST — the verdict in
    the one shape the driver parses: exactly `ok` and `device`
    (platform, kind, count as the chip-owning server's JAX reported
    them). The report never goes last; the verdict carries nothing
    else."""
    report = dict(res)
    device = report.pop("device")
    print(json.dumps({"report": report}))
    print(json.dumps({"ok": bool(res["ok"]), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not os.path.isfile(os.path.join(HERE, "minio_tpu", "server.py")):
        print("chip_smoke: no minio_tpu/ next to this script — run it from "
              "a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    from minio_tpu.ops.device import explicit_cpu      # JAX-free
    if explicit_cpu():
        # Named before anything is booted or loaded: cpu is not a chip.
        print(f"chip_smoke: JAX_PLATFORMS={os.environ['JAX_PLATFORMS']}: "
              "platform cpu is not an accelerator; run this on the "
              "machine with the chip", file=sys.stderr)
        return 3
    res: dict = {"ok": False, "seed": args.seed}
    reduced = [f"{k}: {now} of {FULL[k]}" for k, now in (
        ("big_objects", BIG_OBJECTS), ("small_objects", SMALL_OBJECTS),
        ("mp_parts", MP_PARTS)) if now != FULL[k]]
    root = tempfile.mkdtemp(prefix="chip_smoke-")
    try:
        phase_a(root, args.seed, res)
        phase_b(root, args.seed, res)
        phase_c(root, args.seed, res)
        check("jax" not in sys.modules, "the smoke's parent imported JAX")
    except NoAccelerator as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 3
    except Exception as e:  # noqa: BLE001 - any failure is THE result
        if not isinstance(e, SmokeError):
            import traceback
            traceback.print_exc()
        log(f"FAILED: {e}")
        if "device" not in res:
            return 1                   # never saw a chip: no result line
        res["error"] = f"{type(e).__name__}: {e}"[:2000]
        res.update(reduced=reduced, claim=None)
        emit(res)
        return 1
    finally:
        shutil.rmtree(root, ignore_errors=True)
    res.update(ok=True, reduced=reduced, elapsed_s=round(
        time.monotonic() - T0, 1), claim=None)
    emit(res)
    return 0


T0 = time.monotonic()

if __name__ == "__main__":
    sys.exit(main())

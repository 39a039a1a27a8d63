"""The server under test as a subprocess, and how the benchmark reads
it while it lives: the Prometheus scrape, admin info, who holds the
chip. Copied in substance from `chip_smoke.py` (PR 21), which stays the
smoke test; this copy is the yardstick's and later PRs do not edit it.
Nothing here imports JAX or `minio_tpu`.
"""

from __future__ import annotations

import glob
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

from benchmark.s3client import S3

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ServerError(Exception):
    pass


class Server:
    """One `serve_traced.py <sock> <argv>` subprocess; pipes drained by
    reader threads (an undrained pipe eventually blocks the server).
    `launcher`: another script with serve_traced.py's arguments (the
    control's, benchmark/faults.py)."""

    def __init__(self, argv: list[str], drives: int, workdir: str,
                 env_extra: dict | None = None, launcher: str | None = None):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.address = f"127.0.0.1:{s.getsockname()[1]}"
        self.drive_root = os.path.join(workdir, "drives")
        os.makedirs(self.drive_root)
        self.drives = drives
        self.sock_path = os.path.join(workdir, "control.sock")
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        # The program keeps its compile cache where this says, else at
        # <checkout>/.jax_cache — the same fixed in-checkout path.
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(ROOT, ".jax_cache"))
        env.pop("MTPU_BATCH_FORCE", None)   # a user's boot pins nothing
        env.update(env_extra or {})
        self.out: list[str] = []
        self.err: list[str] = []
        self.boot_line = ""
        self._ready = threading.Event()
        self.t_spawn = time.monotonic()
        self.boot_s = None
        self.stop_s = None
        self.proc = subprocess.Popen(
            [sys.executable,
             launcher or os.path.join(HERE, "serve_traced.py"),
             self.sock_path, *argv, "--address", self.address,
             os.path.join(self.drive_root, "d{1...%d}" % drives)],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, start_new_session=True)
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
            raise ServerError(
                f"server did not come up (exit={self.proc.poll()}); "
                "stderr tail:\n" + self.err_tail())

    def err_tail(self, n: int = 30) -> str:
        return "".join(l for l in self.err
                       if "cpu_aot_loader" not in l)[-4000:][-n * 200:]

    def client(self, timeout: float = 120.0) -> S3:
        return S3(self.address, timeout=timeout)

    def control(self, req: dict, timeout: float = 120.0) -> dict:
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
            s.settimeout(timeout)
            s.connect(self.sock_path)
            s.sendall(json.dumps(req).encode() + b"\n")
            with s.makefile("rb") as f:
                reply = json.loads(f.readline())
        if not reply.get("ok"):
            raise ServerError(f"control {req.get('cmd')}: "
                              f"{reply.get('error')}")
        return reply

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
        """SIGTERM and wait: the server drains, stamps its drives clean
        and exits 0 by returning from main(); the chip frees with it."""
        t0 = time.monotonic()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            code = self.proc.wait(120)
        except subprocess.TimeoutExpired:
            self.kill()
            raise ServerError("server ignored SIGTERM for 120 s")
        self.stop_s = time.monotonic() - t0
        return code

    def stamped_clean(self) -> int:
        return len(glob.glob(os.path.join(
            self.drive_root, "d*", ".mtpu.sys", "clean.shutdown")))

    def drive_path(self, d: int) -> str:
        return os.path.join(self.drive_root, f"d{d}")

    def kill_drive(self, d: int, block: bool = True) -> None:
        """Drive `d` dies under the live server: its tree is taken away
        and a regular, empty file put at its path, so that every call
        on the drive fails with ENOTDIR and nothing can make the root
        again — a plain file system's nearest thing to a disk that
        answers EIO. The tree is renamed away first (one instant) and
        removed afterwards. `block=False` leaves the path free: the
        control's drive, which the program makes again and heals."""
        path = self.drive_path(d)
        gone = os.path.join(os.path.dirname(self.drive_root), f"gone-d{d}")
        os.rename(path, gone)
        if block:
            os.close(os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY))
        shutil.rmtree(gone)

    def drives_touched(self, dead: list[int]) -> int:
        """How many of the dead drives' paths are a directory again or
        hold anything: a root made again, a stamp, a healed shard."""
        paths = [self.drive_path(d) for d in dead]
        return sum(os.path.isdir(p) or (os.path.lexists(p)
                                         and os.path.getsize(p) > 0)
                   for p in paths)

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


def admin_device(cli: S3) -> dict:
    st, _, data = cli.request("GET", "/minio/admin/v3/info")
    if st != 200:
        raise ServerError(f"admin info: HTTP {st}")
    dev = json.loads(bytes(data)).get("device")
    if not isinstance(dev, dict):
        raise ServerError("admin info has no device section")
    return dev


_SERIES = re.compile(r"^(minio_tpu_\w+?)(?:\{([^}]*)\})?\s+(\S+)$")


def parse_scrape(text: str) -> dict:
    """{metric: {frozenset(label items): value}}."""
    out: dict = {}
    for line in text.splitlines():
        m = _SERIES.match(line)
        if m:
            labels = dict(re.findall(r'(\w+)="([^"]*)"', m.group(2) or ""))
            try:
                out.setdefault(m.group(1), {})[
                    frozenset(labels.items())] = float(m.group(3))
            except ValueError:
                continue
    return out


def scrape(cli: S3) -> dict:
    st, _, data = cli.request("GET", "/minio/v2/metrics/cluster")
    if st != 200:
        raise ServerError(f"metrics: HTTP {st}")
    return parse_scrape(bytes(data).decode())

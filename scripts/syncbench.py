#!/usr/bin/env python3
"""What the filesystem under the drives does with many shard files at
once, with no server and no JAX: T threads each write files the way
`LocalStorage._write_direct` does (open O_DIRECT, 1 MiB writes from a
page-aligned buffer, the ragged tail with the flag dropped, fdatasync)
into D directories under TMPDIR, the same total bytes in every row.

    python scripts/syncbench.py [--threads 6,12,24,48,96]
        [--sizes 8388608+2048,16777216+4096] [--dirs 6,12]
        [--total-mib 1536] [--direct on|off] [--burst] [--commit]

With `--burst` the T threads meet at a barrier before each `fdatasync`,
so that T syncs start at the same instant: what a dispatch of the
batcher releases on the drives' side. With `--commit` each file is
staged and committed the way `rename_data` does it (a fresh staging
directory a file, then `makedirs` of an object directory, the data
directory renamed into it, an `xl.meta` written tmp + `fdatasync` +
rename, the staging directory removed), and the files stay until the row
ends: the server's churn of directory entries. One line of JSON a row: sync
median / p90 / max ms, the most syncs in flight, write median ms, MiB/s,
and how the files were written (`direct`, `direct_dropped`: the mount
took the flag and refused the first write, `buffered`). If the larger files' syncs pile up here as they do behind
the server (PERF.md, PR 32), the pile-up is the filesystem's; if not,
it is the program's.
"""

import argparse
import fcntl
import json
import mmap
import os
import shutil
import statistics
import tempfile
import threading
import time

MiB = 1 << 20


def write_file(path, size, buf, direct, rec, meet=None):
    """One shard file; -> its mode. `rec` collects (write s, sync s)."""
    mode = "buffered"
    flags = os.O_CREAT | os.O_WRONLY | os.O_TRUNC
    fd = -1
    if direct:
        try:
            fd = os.open(path, flags | os.O_DIRECT, 0o644)
            mode = "direct"
        except OSError:
            pass
    if fd < 0:
        fd = os.open(path, flags, 0o644)

    def drop():
        fcntl.fcntl(fd, fcntl.F_SETFL,
                    fcntl.fcntl(fd, fcntl.F_GETFL) & ~os.O_DIRECT)
    try:
        left = size
        while left:
            take = min(left, MiB)
            if take < MiB and mode == "direct":
                drop()                      # the ragged tail
            t0 = time.perf_counter()
            try:
                os.write(fd, memoryview(buf)[:take])
            except OSError:
                if mode != "direct" or left != size:
                    raise
                mode = "direct_dropped"     # first write refused
                drop()
                os.write(fd, memoryview(buf)[:take])
            rec["write"].append(time.perf_counter() - t0)
            left -= take
        if meet is not None:
            meet.wait()
        with rec["mu"]:
            rec["inflight"] += 1
            rec["peak"] = max(rec["peak"], rec["inflight"])
        t0 = time.perf_counter()
        os.fdatasync(fd)
        dt = time.perf_counter() - t0
        with rec["mu"]:
            rec["inflight"] -= 1
        rec["sync"].append(dt)
    finally:
        os.close(fd)
    return mode


def commit(drive, i):
    """What `LocalStorage.rename_data` does after a stream's sync."""
    obj = os.path.join(drive, "bkt", f"obj{i}")
    os.makedirs(obj)
    os.replace(os.path.join(drive, "tmp", f"s{i}", "ddir"),
               os.path.join(obj, "ddir"))
    tmp = os.path.join(drive, "tmp", f"m{i}")
    with open(tmp, "wb") as f:
        f.write(b"x" * 600)
        f.flush()
        os.fdatasync(f.fileno())
    os.replace(tmp, os.path.join(obj, "xl.meta"))
    shutil.rmtree(os.path.join(drive, "tmp", f"s{i}"))


def row(root, threads, size, dirs, total, direct, burst, commits):
    files = max(threads, total // size)
    meet = None
    if burst:
        files -= files % threads            # the same number a thread
        meet = threading.Barrier(threads, timeout=300)
    ds = [os.path.join(root, f"d{i}") for i in range(dirs)]
    for d in ds:
        os.makedirs(os.path.join(d, "tmp"))
    rec = {"write": [], "sync": [], "mu": threading.Lock(),
           "inflight": 0, "peak": 0}
    nxt = iter(range(files))
    modes = set()

    def work(tid):
        buf = mmap.mmap(-1, MiB)
        buf.write(os.urandom(MiB))
        # next() is atomic under the GIL; a burst deals the files out
        for i in (range(tid, files, threads) if burst else nxt):
            d = ds[i % dirs]
            path = os.path.join(d, f"f{i}")
            if commits:
                os.makedirs(os.path.join(d, "tmp", f"s{i}", "ddir"))
                path = os.path.join(d, "tmp", f"s{i}", "ddir", "part.1")
            modes.add(write_file(path, size, buf, direct, rec, meet))
            if commits:
                commit(d, i)
    ts = [threading.Thread(target=work, args=(t,)) for t in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    for d in ds:
        shutil.rmtree(d)
    q = statistics.quantiles(rec["sync"], n=10)
    ms = lambda s: round(s * 1000, 3)  # noqa: E731
    return {"threads": threads, "file_bytes": size, "dirs": dirs,
            "burst": burst, "commit": commits, "files": files, "modes": sorted(modes),
            "mib_s": round(files * size / MiB / wall, 1),
            "write_median_ms": ms(statistics.median(rec["write"])),
            "sync_median_ms": ms(statistics.median(rec["sync"])),
            "sync_p90_ms": ms(q[8]), "sync_max_ms": ms(max(rec["sync"])),
            "syncs_in_flight_peak": rec["peak"]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--threads", default="6,12,24,48,96")
    ap.add_argument("--sizes", default="8388608+2048,16777216+4096")
    ap.add_argument("--dirs", default="6,12")
    ap.add_argument("--total-mib", type=int, default=1536)
    ap.add_argument("--direct", choices=("on", "off"), default="on")
    ap.add_argument("--burst", action="store_true")
    ap.add_argument("--commit", action="store_true")
    a = ap.parse_args()
    root = tempfile.mkdtemp(prefix="syncbench-")
    try:
        for size in (sum(map(int, s.split("+"))) for s in a.sizes.split(",")):
            for dirs in map(int, a.dirs.split(",")):
                for threads in map(int, a.threads.split(",")):
                    print(json.dumps(row(root, threads, size, dirs,
                                         a.total_mib * MiB,
                                         a.direct == "on", a.burst,
                                         a.commit)),
                          flush=True)
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()

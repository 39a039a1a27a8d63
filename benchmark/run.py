#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Boots `minio_tpu.server` with the cell's configuration as the one
process that owns the chip, drives it over HTTP from generator
processes with the cell's traffic mix in a closed loop, and prints as
the last line of standard output one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` in a traced
run), then every number that decided `correct` beside its limit.

This process and the generators never import JAX. With no TPU, or
outside a checkout that holds the program, it exits non-zero and
prints no result. See benchmark/README.md.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import cells, compare, readers, traffic  # noqa: E402
from benchmark.server import (Server, ServerError, admin_device,  # noqa: E402
                              scrape, tpu_holders)
from benchmark.trace import MARK_A, MARK_B  # noqa: E402

MiB = 1 << 20
BUCKET = traffic.BUCKET
T0 = time.monotonic()


class NoAccelerator(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for: exit
    non-zero and print no result."""


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


# -- the generator processes -------------------------------------------------

class Generators:
    def __init__(self, address: str, seed: int, mix: dict):
        n, procs = mix["workers"], min(mix["processes"], mix["workers"])
        self.procs = []
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        for p in range(procs):
            proc = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "loadgen.py")],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                cwd=ROOT, env=env)
            spec = {"address": address, "bucket": BUCKET, "seed": seed,
                    "mix": mix, "workers": list(range(p, n, procs)),
                    "workers_total": n, "timeout": mix["timeout_s"]}
            proc.stdin.write(json.dumps(spec) + "\n")
            proc.stdin.flush()
            self.procs.append(proc)

    def send(self, req: dict) -> None:
        for proc in self.procs:
            proc.stdin.write(json.dumps(req) + "\n")
            proc.stdin.flush()

    def collect(self) -> list[dict]:
        out = []
        for proc in self.procs:
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError(
                    f"generator {proc.pid} died (exit={proc.poll()})")
            reply = json.loads(line)
            if "error" in reply:
                raise RuntimeError(f"generator: {reply['error']}")
            out.append(reply)
        return out

    def close(self) -> None:
        for proc in self.procs:
            try:
                if proc.poll() is None:
                    proc.stdin.write('{"cmd": "exit"}\n')
                    proc.stdin.flush()
                    proc.stdin.close()
            except (OSError, ValueError):
                pass
        for proc in self.procs:
            try:
                proc.wait(20)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(10)


# -- end-to-end metrics: the benchmark's own arithmetic ------------------------

def _rate(ops, op, t0, t1):
    """MiB/s of payload inside the window, over the whole window. An
    operation's bytes are spread evenly over its send-to-last-byte
    interval and the part inside the window counts, for the ones that
    straddle an edge too (all are waited for and judged). Counting
    whole operations at their last byte (`_rate_whole`, printed beside
    it) jumps by a worker's object per edge: 32 workers send 64 MiB
    each in step, ~60 PUTs end in a 20 s window, and it read 163-224
    MiB/s where this read 184-202. The price is an edge bias: a PUT in
    flight at the close ends under the draining, lighter load, so more
    of it is credited to the window than at full load (PERF.md 2)."""
    return sum(inside_by_key(ops, op, t0, t1).values()) / MiB / (t1 - t0)


def inside_by_key(ops, op, t0, t1) -> dict:
    """{key: payload bytes of its `op`s inside the window}, each
    operation's bytes spread evenly over its interval."""
    out: dict = {}
    for e in ops:
        if e[0] == op and e[4] == "ok" and e[3] > e[2]:
            inside = min(e[3], t1) - max(e[2], t0)
            if inside > 0:
                out[e[1]] = out.get(e[1], 0.0) \
                    + e[5] * inside / (e[3] - e[2])
    return out


def _rate_whole(ops, op, t0, t1):
    """The same counting whole operations at their last byte (a
    diagnostic beside the metric)."""
    done = sum(e[5] for e in ops
               if e[0] == op and e[4] == "ok" and t0 <= e[3] <= t1)
    return done / MiB / (t1 - t0)


RATES = {"rate_spread_over_each_operation": _rate,
         "rate_of_whole_operations": _rate_whole}


def end_to_end(name: str, c: dict) -> float:
    """The metric's own file says which quantity of the run it is."""
    spec = cells.load_end_to_end(name)
    if spec["quantity"] in RATES:
        return RATES[spec["quantity"]](c["ops"], spec["op"], c["t0"], c["t1"])
    return c[spec["quantity"]]


# -- one run ---------------------------------------------------------------------

WARM_KEY = "warm/0000"
MAX_BOOTS = 3
PROGRAMS = "minio_tpu_batcher_bucket_dispatches_total"
BODY_OPS = ("PUT", "GET")


def plant_rot(srv, cfg: dict, key: str, body: bytes) -> str:
    """Bitrot on a drive: turn one bit of the first payload byte of the
    first DATA shard of `key` that a drive still holds, where it lies
    (a read fetches the data shards it can get before any parity, so
    rot in a parity shard might be seen by nobody). With every drive
    alive that is data shard 0; where the configuration's drives have
    died, the first that lay on a living one. The file of shard s is
    the one whose first frame, behind its 32 digest bytes, holds bytes
    [s, s + 1) * erasure_block_bytes / k of the body. -> its path."""
    piece = cfg["erasure_block_bytes"] // cfg["data_shards"]
    files = compare.shard_files_on_disk(
        srv.drive_root, cfg["drives"], BUCKET, key).values()
    for s in range(cfg["data_shards"]):
        want = body[s * piece:(s + 1) * piece]
        for path in files:
            with open(path, "r+b") as f:
                f.seek(32)
                if f.read(piece) == want:
                    f.seek(32)
                    f.write(bytes([want[0] ^ 0x01]))
                    return path
    raise ServerError(f"no drive holds a data shard of {key}")


def programs_met(scraped: dict) -> set:
    """Every (route, padded batch size) the batcher has dispatched so
    far, as the scrape labels them: one compiled program each."""
    return {k for k, v in scraped.get(PROGRAMS, {}).items() if v > 0}


def warm_ladder(cli, gens, mix: dict) -> None:
    """The program for a padded batch size is traced and lowered (on a
    cold cache: compiled) in the process that first dispatches it, ~2 s
    each, and the batcher picks the size from how many windows happen
    to wait together. So set-up sends bursts of 1, 2, ... up to the
    mix's `warm_ladder` PUTs at one instant each: the first window to
    arrive is dispatched alone and the rest wait together while the
    lane is busy, so the rungs walk through the batch sizes from one
    window up; the ramp's own start (all workers at once) is the top
    one. A rung is sent once with each operation of the mix's cycle
    that carries a body (PUT, GET): every route has programs of its
    own. The ladder is the mix's data and knows nothing of the
    program's sizes; what the window still met first is said in the
    result line (`buckets_first_used_in_window`). Nothing here is
    timed; all of it is judged."""
    seen = programs_met(scrape(cli))
    ops = [op for op in BODY_OPS if mix["cycle"].get(op)]
    for n in range(1, min(mix["warm_ladder"], mix["workers"]) + 1):
        for op in ops:
            gens.send({"cmd": "burst", "op": op, "n": n,
                       "at": time.monotonic() + 0.3})
            gens.collect()
            now = programs_met(scrape(cli))
            if now - seen:
                log(f"ladder rung {n}: first use of "
                    f"{sorted(_label(k) for k in now - seen)}")
            seen |= now


def _label(key) -> str:
    return "/".join(v for _, v in sorted(key))


def stop_is_unclean(code: int, stamped: int, cfg: dict) -> int:
    """The stop is clean when the server exits 0 with every drive that
    is alive stamped: a dead drive (the configuration's `dead_drives`)
    cannot be, its stamp would lie under its root."""
    return int(code != 0 or stamped != drives_alive(cfg))


def drives_alive(cfg: dict) -> int:
    return cfg["drives"] - len(cfg.get("dead_drives", []))


def read_back_degraded(cli, bodies, mix: dict) -> int:
    """A configuration with dead drives: every loss pattern (which of
    an object's shards lay on the dead drives) has a batcher of route
    `reconstruct` of its own, with a one-shot probe of its own that
    starts with the batcher's first device-sized window. So set-up
    reads every preloaded object back once, ONE GET at a time on an
    otherwise idle server, and after each waits until no route is
    probing: each probe times a quiet server, as `put`'s and `get`'s
    do, and no window starts on an object that was never read
    degraded. Judged like every GET (length, every byte, ETag).
    -> how many came back wrong."""
    wrong = 0
    for i in range(mix["preload"]):
        key = traffic.pre_key(i)
        t_get = time.monotonic()
        st, hdr, data = cli.request("GET", f"/{BUCKET}/{key}")
        t_got = time.monotonic()
        if st != 200:
            raise ServerError(
                f"degraded GET of {key}: HTTP {st} {bytes(data)[:200]!r}")
        wrong += int(bytes(data) != bodies.body(key)
                     or hdr.get("etag", "").strip('"')
                     != bodies.parts(key)[3])
        del data
        settle(cli)
        log(f"read back {key} in {t_got - t_get:.2f} s, no route probing "
            f"{time.monotonic() - t_got:.2f} s later")
    return wrong


def settle(cli, timeout: float = 180.0) -> dict:
    """Wait until no route's calibration probe is running; -> the
    admin info's device section."""
    deadline = time.monotonic() + timeout
    while True:
        dev = admin_device(cli)
        if not any(c.get("verdict") == "probing"
                   for c in dev.get("calibration", [])) \
                or time.monotonic() > deadline:
            return dev
        time.sleep(0.2)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             hooks: dict | None = None) -> tuple[dict, bool]:
    """-> (the result line's object, whether a chip was seen). `hooks`
    is for benchmark/control.py and the tests: `allow_platform` (skip
    the look for a chip), `server_env`, `before_disk_check`
    (fn(server, cfg, sample keys, bodies)), `mix` (overrides), `proxy`
    (fn(server address) -> what the generators talk to instead:
    `.address`, `.arm()` at the window's first instant, `.close()`),
    `launcher` (the script that runs the server's `main()`, in place of
    serve_traced.py), `after_preload` (in place of the configuration
    module's own)."""
    hooks = hooks or {}
    loaded = cells.load_cell(name)
    cell, cfg, bench = loaded["cell"], loaded["config"], loaded["bench"]
    module = loaded["module"]
    mix = {**loaded["mix"], **hooks.get("mix", {})}
    # the drives the configuration says are dead from after the preload
    # to the stop (killed by its module's `after_preload`)
    dead = cfg.get("dead_drives", [])
    alive = drives_alive(cfg)
    if not os.path.isfile(os.path.join(ROOT, "minio_tpu", "server.py")):
        raise NoAccelerator("no minio_tpu/ in this directory: nothing to "
                            "measure")
    workdir = tempfile.mkdtemp(prefix="mtpu-bench-")
    srv = gens = proxy = None
    t_spawn = time.monotonic()
    try:
        for boots in range(1, MAX_BOOTS + 1):
            srv = Server(cfg["server_argv"], cfg["drives"],
                         os.path.join(workdir, f"boot{boots}"),
                         hooks.get("server_env"), hooks.get("launcher"))
            if "proxy" in hooks:
                proxy = hooks["proxy"](srv.address)
            gens = Generators(proxy.address if proxy else srv.address,
                              seed, mix)            # build their bodies
            srv.wait_ready(900)                     # while the server boots
            log(srv.boot_line)
            cli = srv.client()
            dev = admin_device(cli)
            if not hooks.get("allow_platform"):
                if dev.get("platform") != "tpu" \
                        or dev.get("ec_backend") != "tpu":
                    raise NoAccelerator(
                        f"server came up as ec_backend="
                        f"{dev.get('ec_backend')!r} on platform "
                        f"{dev.get('platform')!r}, not a TPU")
                if dev.get("devices", 0) < cell["chips"]:
                    raise NoAccelerator(
                        f"{dev.get('devices')} chips, the cell asks for "
                        f"{cell['chips']}")
            st, _, _ = cli.request("PUT", f"/{BUCKET}")
            if st != 200:
                raise ServerError(f"MakeBucket: HTTP {st}")
            # A route's calibration probe starts in the background with
            # the route's first device-sized window: ONE timed device
            # call against ONE timed host call, and the verdict holds
            # for the life of the process. Traffic beside it is in both
            # timings (PERF.md, PR 25: under the preload the verdicts
            # flipped from boot to boot), and a window that starts
            # before it settles rides the host (PR 21). So the route
            # gets its first window from one quiet PUT, and the load
            # starts when no route is still probing.
            bodies = traffic.Bodies(seed, mix["size"], mix["bodies"])
            head, st_, sha, _ = bodies.parts(WARM_KEY)
            st, _, data = cli.request("PUT", f"/{BUCKET}/{WARM_KEY}",
                                      body=[head, st_], payload_sha256=sha)
            if st != 200:
                raise ServerError(
                    f"first PUT: HTTP {st} {bytes(data)[:200]!r}")
            dev = settle(cli)
            quiet_wrong = 0
            if mix["cycle"].get("GET"):
                # A mix that reads: the `get` route gets its first
                # device-sized windows the same way, from one quiet GET
                # of that object once the `put` route's probe is done,
                # and the boot-again rule below sees its verdict too.
                st, _, data = cli.request("GET", f"/{BUCKET}/{WARM_KEY}")
                if st != 200:
                    raise ServerError(
                        f"first GET: HTTP {st} {bytes(data)[:200]!r}")
                quiet_wrong = int(bytes(data) != bodies.body(WARM_KEY))
                del data
                dev = settle(cli)
            on_host = [c.get("route") for c in dev.get("calibration", [])
                       if str(c.get("verdict")).endswith("host")]
            if not on_host or boots == MAX_BOOTS:
                break
            # Even quiet, one hiccup in that one device call sends the
            # route to the host codec for good (first run in a fresh
            # checkout, PR 25: device 119 ms against host 33 ms, where
            # every other boot read 5-8 against 32-51). Such a boot
            # serves another cell than this one: it is stopped and the
            # server booted again, all of it counted in setup_s and
            # said in the result line (`cell.boots`). After MAX_BOOTS
            # the run goes on and comes out not correct.
            log(f"boot {boots}: calibration sent {on_host} to the host "
                f"codec ({json.dumps(dev.get('calibration'))}); "
                "booting again")
            gens.close()
            gens = None
            if proxy is not None:
                proxy.close()
            srv.stop()
        gens.send({"cmd": "preload"})
        gens.collect()
        log(f"preloaded {mix['preload']} objects")
        after_preload = hooks.get("after_preload") \
            or getattr(module, "after_preload", None)
        if after_preload is not None:
            after_preload(srv, cfg, cli)
        # A deployment's drives rot: after the read-back (every object
        # has been verified once), and after the configuration's drives
        # have died, one bit of a data shard of each object the mix
        # lists under `rotten` is turned where it lies on a living
        # drive. The program has to serve the right bytes all the same
        # (every GET is compared); where the mix names a counter of
        # the program's that says it noticed (`rot_noticed_by`), the
        # run is held to that too. Those objects are first read in
        # set-up (the serial read-back of a degraded configuration, or
        # the ladder: the mix picks the rung), so the programs of the
        # rebuild and the heal that follows are set-up's, not the
        # window's.
        rotten = [traffic.pre_key(i) for i in mix.get("rotten", [])]
        noticed_by = mix.get("rot_noticed_by")
        before_rot = scrape(cli) if rotten else {}
        for key in rotten:
            log("rot planted in " + os.path.relpath(
                plant_rot(srv, cfg, key, bodies.body(key)), srv.drive_root))
        readback_s = None
        if dead:
            t_back = time.monotonic()
            quiet_wrong += read_back_degraded(cli, bodies, mix)
            readback_s = time.monotonic() - t_back
            log(f"{mix['preload']} objects read back with drives {dead} "
                f"dead, one at a time, in {readback_s:.1f} s")
        warm_ladder(cli, gens, mix)
        # the heal that follows a rotten read that was noticed runs in
        # the background (and, in a checkout's first run, compiles): it
        # is set-up's too, so the ramp waits for it, a minute at the most
        healed = "minio_tpu_mrf_healed_total"

        def since_rot(now: dict, series: str, labels=None) -> float:
            return readers.series_sum(now, series, labels) \
                - readers.series_sum(before_rot, series, labels)
        t_heal = time.monotonic()
        deadline = t_heal + 60.0
        while rotten and noticed_by and time.monotonic() < deadline:
            now = scrape(cli)
            if since_rot(now, healed) >= min(
                    len(rotten), since_rot(now, noticed_by["series"],
                                           noticed_by.get("labels"))):
                break
            time.sleep(0.5)
        heal_wait_s = time.monotonic() - t_heal
        log("calibration: " + json.dumps(
            [{k: c.get(k) for k in ("route", "verdict", "device_ms",
                                    "host_ms")}
             for c in dev.get("calibration", [])]))
        t_go = time.monotonic()
        t0 = t_go + mix["ramp_s"]
        t1 = t0 + seconds
        gens.send({"cmd": "run", "t_go": t_go, "t0": t0, "t1": t1})
        time.sleep(max(0.0, t0 - time.monotonic()))
        if proxy is not None:
            proxy.arm()
        ctx = {"scrape_a": scrape(cli)}
        scrape_s = [time.monotonic() - t0]
        if trace:
            # start, mark ... mark, stop: the trace is reduced between
            # the two marks alone, so that starting it and writing it
            # out (seconds, in which the server goes on serving) are
            # not in the interval
            trace_dir = os.path.join(workdir, "trace")
            ta = time.monotonic()
            srv.control({"cmd": "trace_start", "dir": trace_dir})
            srv.control({"cmd": "mark", "name": MARK_A})
            tm = time.monotonic()
            time.sleep(max(1.0, min(mix["trace_s"], t1 - tm - 4.0)))
            srv.control({"cmd": "mark", "name": MARK_B})
            tb = time.monotonic()
            srv.control({"cmd": "trace_stop"}, timeout=300)
            tracing = {"start_s": tm - ta, "marked_s": tb - tm,
                       "stop_s": time.monotonic() - tb}
            log("traced: " + json.dumps(tracing))
        time.sleep(max(0.0, t1 - time.monotonic()))
        ctx["scrape_b"] = scrape(cli)
        scrape_s.append(time.monotonic() - t1)
        replies = gens.collect()           # waits for the ops in flight
        drained_s = time.monotonic() - t1
        ops = [e for r in replies for e in r["ops"]]
        log(f"window closed; {len(ops)} operations, drained "
            f"{drained_s:.1f} s after the close")

        # -- what the live server says, before it stops ------------------
        expected = {}
        for key in [WARM_KEY] + [traffic.pre_key(i) for i in range(mix["preload"])] + \
                [k for r in replies for ks in r["live"].values() for k in ks]:
            expected[key] = (bodies.parts(key)[3], mix["size"])
        undecided = {e[1] for e in ops if e[0] in ("PUT", "DELETE")
                     and e[4] != "ok"}
        listed = compare.list_bucket(cli, BUCKET)
        list_diff = compare.listing_diff(
            {k: v for k, v in listed.items() if k not in undecided},
            {k: v for k, v in expected.items() if k not in undecided})
        mem = srv.control({"cmd": "memory"})["devices"]
        dev = admin_device(cli)
        pids = srv.pids()
        holders = tpu_holders(pids)
        final = ctx["scrape_b"]
        device_errors = sum(final.get("minio_tpu_device_errors_total",
                                      {}).values())
        code = srv.stop()
        stamped = srv.stamped_clean()
        log(f"server stopped in {srv.stop_s:.1f} s, exit {code}, "
            f"{stamped} of {alive} living drives stamped clean")

        # -- the drives, after the clean stop ------------------------------
        in_window = [e[1] for e in ops if e[0] == "PUT" and e[4] == "ok"
                     and t0 <= e[3] <= t1 and e[1] in expected]
        pool = sorted(in_window) or sorted(set(expected) - set(rotten))
        sample = random.Random(f"{seed}/disk").sample(
            pool, min(mix["disk_sample"], len(pool)))
        if "before_disk_check" in hooks:
            hooks["before_disk_check"](srv, cfg, sample, bodies)
        t_ref = time.monotonic()
        wrong_shards = below_quorum = 0
        fewest_right = cfg["drives"]
        for key in sample:
            got = compare.check_object_on_disk(
                srv.drive_root, cfg, BUCKET, key, bodies.body(key),
                reference=getattr(module, "reference_shard_files", None))
            wrong_shards += got["wrong"]
            below_quorum += got["right"] < cfg["write_quorum"]
            fewest_right = min(fewest_right, got["right"])
        log(f"{len(sample)} objects' shard files compared with the "
            f"reference in {time.monotonic() - t_ref:.1f} s")

        # -- numbers ----------------------------------------------------------
        counted = [e for e in ops if e[0] != "GONE"]
        verdicts = {v: sum(e[4] == v for e in ops)
                    for v in ("ok", "wrong", "refused", "never")}
        requests = "minio_tpu_batcher_requests_total"
        dev_windows = readers.series_sum(final, requests, {"path": "device"}) \
            - readers.series_sum(ctx["scrape_a"], requests, {"path": "device"})
        checks = {
            "wrong_answers": [verdicts["wrong"] + quiet_wrong, 0],
            "never_answered": [verdicts["never"], 0],
            "listing_diff": [list_diff, 0],
            "disk_wrong_shards": [wrong_shards, 0],
            "disk_objects_below_write_quorum": [below_quorum, 0],
            "device_errors": [device_errors, 0],
            "chip_holders_besides_the_server": [
                len(set(holders) ^ {dev.get("pid")})
                if not hooks.get("allow_platform") else 0, 0],
            "no_window_on_the_device": [int(dev_windows <= 0), 0],
            "unclean_stop": [stop_is_unclean(code, stamped, cfg), 0],
        }
        if dead:
            # what keeps the deployment the one it says it is: a root
            # made again, a stamp, a healed shard on a dead drive
            checks["dead_drives_touched"] = [srv.drives_touched(dead), 0]
        # what the mix itself holds a run to: a counter of the
        # program, by its series' name, may rise by no more than this
        # between the window's two scrapes
        for series, lim in mix.get("limits", {}).items():
            checks[series] = [readers.series_sum(final, series)
                              - readers.series_sum(ctx["scrape_a"], series),
                              lim]
        if rotten:
            rot = {"planted": len(rotten)}
            if noticed_by:
                # the mix names a counter of the program's that rises
                # with every rotten window it noticed, and what to
                # call it here: the run is held to it
                rot[noticed_by["name"]] = since_rot(
                    final, noticed_by["series"], noticed_by.get("labels"))
                checks["rot_not_noticed"] = [
                    max(0, len(rotten) - int(rot[noticed_by["name"]])), 0]
            rot.update(reconstruct_requests=since_rot(
                           final, requests, {"route": "reconstruct"}),
                       mrf_healed=since_rot(final, healed),
                       heal_wait_s=heal_wait_s)
        correct = all(v <= lim for v, lim in checks.values())
        for e in [e for e in ops if e[4] != "ok"][:10]:
            log(f"not ok: {e[0]} {e[1]} {e[4]} {e[6]}")

        c = {"ops": counted, "t0": t0, "t1": t1, "setup_s": t0 - t_spawn}
        wanted = bench["per_layer"] if trace else bench["end_to_end"]
        metrics = {}
        if trace:
            tr = reduce_trace(os.path.join(workdir, "trace"))
            if not tr.get("chips"):
                if not hooks.get("allow_platform"):
                    raise ServerError("the trace holds no /device:TPU plane")
                tr.update(busy_s=0.0, window_s=tracing["marked_s"])
            kind = dev.get("device_kind", "")
            peaks = cells.load_peaks().get(kind)
            if peaks is None and hooks.get("allow_platform"):
                peaks = next(iter(cells.load_peaks().values()))
            if peaks is None:
                raise ServerError(f"no peaks for device kind {kind!r} in "
                                  "benchmark/peaks.json")
            ctx.update(trace=tr, drives=alive, workers=mix["workers"],
                       config=cfg, peaks=peaks,
                       payload_by_key={op: inside_by_key(counted, op, t0, t1)
                                       for op in ("PUT", "GET")},
                       loadgen={k: sum(r.get("cpu", {}).get(k, 0.0)
                                       for r in replies)
                                for k in ("cpu_s", "wall_s")},
                       payload_mib_s={op: _rate(counted, op, t0, t1)
                                      for op in ("PUT", "GET")})
            read = lambda m: readers.read_layer(  # noqa: E731
                ctx, cells.load_layer(m["name"]))
        else:
            read = lambda m: end_to_end(m["name"], c)  # noqa: E731
        for m in wanted:
            if cells.reports(m, name):
                value = read(m)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        peak = max((d["peak_bytes_in_use"] or 0) for d in mem)
        device = {"platform": dev.get("platform"),
                  "kind": dev.get("device_kind"),
                  "count": dev.get("devices"), "memory_peak_bytes": peak}
        result = {"correct": correct, "attempted": len(counted),
                  "failed": sum(e[4] != "ok" for e in counted),
                  "metrics": metrics, "device": device}
        if trace:
            device["busy_s"] = tr.get("busy_s", 0.0)
            device["window_s"] = tr["window_s"]
            result["breakdown"] = {"device_ops": tr.get("device_ops", []),
                                   "idle_gaps": tr.get("idle_gaps", [])}
        new_buckets = sorted(_label(k) for k in programs_met(final)
                             - programs_met(ctx["scrape_a"]))
        if new_buckets:
            log(f"padding buckets first used INSIDE the window (their "
                f"programs were traced there): {new_buckets}")
        result["cell"] = {
            "workload": name, "seed": seed, "seconds": seconds,
            "buckets_first_used_in_window": new_buckets,
            "rates_mib_s": {op: {q: fn(counted, op, t0, t1)
                                 for q, fn in RATES.items()}
                            for op in ("PUT", "GET") if mix["cycle"].get(op)},
            "ops_in_window": {op: sum(e[0] == op and t0 <= e[3] <= t1
                                      for e in counted)
                              for op in traffic.OPS},
            "disk_sample": len(sample),
            "fewest_right_shards_of_a_sampled_object": fewest_right,
            "boots": boots, "boot_s": srv.boot_s, "stop_s": srv.stop_s,
            "scrape_s_after_t0_and_t1": scrape_s,
            "drained_s": drained_s, "reference_s": time.monotonic() - t_ref,
            # by batcher, not by route: the heal that follows a rotten
            # read brings a second batcher of route `get` (one shard
            # file a member), probed under load, with a verdict of its own
            "calibration": {c_.get("name", c_["route"]): c_.get("verdict")
                            for c_ in dev.get("calibration", [])
                            if "route" in c_}}
        if rotten:
            result["cell"]["rot"] = rot
        if dead:
            result["cell"]["dead_drives"] = {
                "dead": dead, "stamped": stamped, "readback_s": readback_s}
        if hasattr(module, "cell_notes"):
            # what the configuration wants said of its deployment
            # beside the numbers: reported, not compared
            result["cell"]["config_notes"] = module.cell_notes(
                cfg, ctx["scrape_a"], final, dev)
        if proxy is not None:
            result["cell"]["wire_fault"] = proxy.flipped
        if trace:
            result["cell"]["tracing"] = tracing
            result["cell"]["layer_notes"] = ctx.get("notes", {})
        result["compared"] = {k: {"value": v, "limit": lim}
                              for k, (v, lim) in checks.items()}
        return result, True
    finally:
        if gens is not None:
            gens.close()
        if proxy is not None:
            proxy.close()
        if srv is not None:
            srv.kill()
        shutil.rmtree(workdir, ignore_errors=True)


def reduce_trace(trace_dir: str) -> dict:
    """benchmark/trace.py in a child that cannot touch the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace.py"), "reduce", trace_dir],
        stdout=subprocess.PIPE, env=env, cwd=ROOT, timeout=200, check=True)
    return json.loads(out.stdout.decode().strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower() \
            == "cpu":
        print("benchmark: JAX_PLATFORMS names cpu first: that is no "
              "accelerator; run this on the machine with the chip",
              file=sys.stderr)
        return 3
    try:
        result, _ = run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    except Exception as e:  # noqa: BLE001 - no result line without a run
        import traceback
        traceback.print_exc()
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    for k, v in result["compared"].items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

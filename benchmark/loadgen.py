"""One load-generator process: a few closed-loop workers, each a thread
with its own connection that sends its next operation when the last
one has returned. The parent (`run.py`) starts several of these, so the
generators never share an interpreter lock with each other.

Protocol, JSON lines: the first line on stdin is the spec; then
`{"cmd": "preload"}` (this process's share of the preloaded keys) and `{"cmd": "burst", "op":, "n":, "at":}`
(the first n workers send one operation at that instant), each answered by
`{"ready": ...}`; then `{"cmd": "run", "t_go":, "t1":}` (times on
CLOCK_MONOTONIC, which all processes of a machine share): worker w of
N starts its loop at t_go + w/N of the mix's `stagger_s`, so that the
workers' operations do not begin and end in step — the stretch up to
the window is the ramp — and no operation is started after t1; the one
in flight is finished, however long it takes. The answer is every operation of the run:
[op, key, t_send, t_done, verdict, bytes, detail], and under `cpu` this
process's own CPU seconds beside the wall seconds between the run's `t0`
(the window's first instant; `t_go` where none is given) and `t1`: the
generators receive and compare every byte they asked for on the
server's cores, and a line has to say when that is what binds.

verdict: "ok" | "wrong" (an answer that says the wrong thing) |
"refused" (an error status: an honest no) | "never" (no answer).
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import traffic  # noqa: E402
from benchmark.s3client import S3  # noqa: E402


class Worker:
    def __init__(self, spec: dict, wid: int, bodies: traffic.Bodies):
        self.spec, self.wid, self.bodies = spec, wid, bodies
        self.mix = spec["mix"]
        self.cli = S3(spec["address"], timeout=spec["timeout"])
        self.bucket = spec["bucket"]
        self.buf = bytearray(self.mix["size"])
        self.live: list[str] = []
        self.deleted: list[str] = []
        self.n_keys = self.n_reads = 0
        self.rng = random.Random(f"{spec['seed']}/{wid}/keys")
        self.sched = traffic.Schedule(self.mix, spec["seed"], wid)
        self.log: list = []

    # -- one operation, judged where its answer arrives ------------------

    def op(self, op: str, key: str):
        """-> (verdict, payload bytes, detail)."""
        path = f"/{self.bucket}/{key}"
        head, st, sha, etag = self.bodies.parts(key)
        size = self.mix["size"]
        try:
            if op == "PUT":
                status, hdr, _ = self.cli.request(
                    "PUT", path, body=[head, st], payload_sha256=sha)
                if status != 200:
                    return "refused", 0, f"HTTP {status}"
                if hdr.get("etag", "").strip('"') != etag:
                    return "wrong", 0, f"ETag {hdr.get('etag')}"
                return "ok", size, ""
            if op == "GET":
                status, hdr, data = self.cli.request("GET", path,
                                                     into=self.buf)
                if status != 200:
                    return ("wrong" if status == 404 else "refused"), 0, \
                        f"HTTP {status}"
                if len(data) != size:
                    return "wrong", 0, f"{len(data)} bytes"
                if data[:size - traffic.STAMP] != head \
                        or data[size - traffic.STAMP:] != st:
                    return "wrong", 0, "body differs"
                if hdr.get("etag", "").strip('"') != etag:
                    return "wrong", 0, f"ETag {hdr.get('etag')}"
                return "ok", size, ""
            if op == "STAT":
                status, hdr, _ = self.cli.request("HEAD", path)
                if status != 200:
                    return ("wrong" if status == 404 else "refused"), 0, \
                        f"HTTP {status}"
                if hdr.get("content-length") != str(size) \
                        or hdr.get("etag", "").strip('"') != etag:
                    return "wrong", 0, (f"length {hdr.get('content-length')}"
                                        f" ETag {hdr.get('etag')}")
                return "ok", 0, ""
            if op == "DELETE":
                status, _, _ = self.cli.request("DELETE", path)
                if status not in (200, 204):
                    return "refused", 0, f"HTTP {status}"
                return "ok", 0, ""
            if op == "GONE":           # a deleted key must answer 404
                status, _, _ = self.cli.request("HEAD", path)
                return ("ok" if status == 404 else "wrong"), 0, \
                    f"HTTP {status}"
        except Exception as e:  # noqa: BLE001 - no answer IS the result
            return "never", 0, f"{type(e).__name__}: {e}"[:200]
        raise ValueError(op)

    def read_key(self) -> str:
        """The key of a GET or STAT: drawn uniformly from the preloaded
        keys and this worker's live ones; or, where the mix says `read`
        "own_in_order" (speedtest: each thread reads back its own
        uploads), the preloaded keys this worker wrote, one after the
        other, round and round."""
        if self.mix.get("read") == "own_in_order":
            own = range(self.wid, self.mix["preload"],
                        self.spec["workers_total"])
            self.n_reads += 1
            return traffic.pre_key(own[(self.n_reads - 1) % len(own)])
        n = self.mix["preload"] + len(self.live)
        i = self.rng.randrange(n)
        return traffic.pre_key(i) if i < self.mix["preload"] \
            else self.live[i - self.mix["preload"]]

    def step(self) -> None:
        op = self.sched.next_op(len(self.live))
        if op == "PUT":
            key = traffic.own_key(self.wid, self.n_keys)
            self.n_keys += 1
        elif op == "DELETE":
            key = self.live.pop(self.rng.randrange(len(self.live)))
        else:
            key = self.read_key()
        t0 = time.monotonic()
        verdict, nbytes, detail = self.op(op, key)
        t1 = time.monotonic()
        if op == "PUT" and verdict == "ok":
            self.live.append(key)
        elif op == "DELETE" and verdict == "ok":
            self.deleted.append(key)
        elif op in ("PUT", "DELETE"):
            # neither acknowledged nor surely absent: owned by nobody,
            # judged by nothing afterwards, but reported
            detail = (detail + " (key left undecided)").strip()
        self.log.append([op, key, t0, t1, verdict, nbytes, detail])

    # -- phases ------------------------------------------------------------

    def preload(self, keys: list[str]) -> None:
        """This worker's share of the preloaded keys, written and — in
        a mix that reads — read back, all workers at once: the bursts
        that fill the batcher's largest padding buckets, so that their
        programs are traced and compiled before the window. All of it
        is judged like the window's operations; none of it is timed."""
        for op in ("PUT", "GET") if self.mix["cycle"].get("GET") \
                else ("PUT",):
            for key in keys:
                t0 = time.monotonic()
                verdict, _, detail = self.op(op, key)
                self.log.append([op, key, t0, time.monotonic(), verdict,
                                 0, detail])

    def burst(self, op: str, n: int, at: float) -> None:
        """The first `n` workers send one `op` at the same instant
        `at`: a batch of a chosen size for the batcher (set-up only)."""
        if self.wid >= n:
            return
        time.sleep(max(0.0, at - time.monotonic()))
        if op == "PUT":
            key = traffic.own_key(self.wid, self.n_keys)
            self.n_keys += 1
        else:
            key = traffic.pre_key(self.wid % self.mix["preload"])
        t0 = time.monotonic()
        verdict, _, detail = self.op(op, key)
        if op == "PUT" and verdict == "ok":
            self.live.append(key)
        self.log.append([op, key, t0, time.monotonic(), verdict, 0, detail])

    def run(self, t_go: float, t1: float) -> None:
        start = t_go + self.mix["stagger_s"] * self.wid \
            / self.spec["workers_total"]
        time.sleep(max(0.0, start - time.monotonic()))
        while time.monotonic() < t1:
            self.step()

    def after(self) -> None:
        """Past the window: every key this worker deleted answers 404
        (a sample of them where there are many)."""
        gone = self.deleted if len(self.deleted) <= 8 \
            else self.rng.sample(self.deleted, 8)
        for key in gone:
            t0 = time.monotonic()
            verdict, _, detail = self.op("GONE", key)
            self.log.append(["GONE", key, t0, time.monotonic(), verdict,
                             0, detail])


def _all(workers, fn, args=None) -> None:
    """fn(worker, *args[i]) on every worker's own thread; raises what
    any of them raised."""
    errs = []

    def call(w, a):
        try:
            fn(w, *a)
        except Exception as e:  # noqa: BLE001 - reported to the parent
            errs.append(f"worker {w.wid}: {type(e).__name__}: {e}")
    threads = [threading.Thread(target=call, args=(w, a))
               for w, a in zip(workers, args or [()] * len(workers))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise RuntimeError("; ".join(errs))


def _cpu_between(t0: float, t1: float, out: dict) -> None:
    """This process's CPU seconds (every thread, user + system) and
    the wall seconds, from t0 to t1."""
    time.sleep(max(0.0, t0 - time.monotonic()))
    c0, w0 = time.process_time(), time.monotonic()
    time.sleep(max(0.0, t1 - time.monotonic()))
    out.update(cpu_s=time.process_time() - c0, wall_s=time.monotonic() - w0)


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    mix = spec["mix"]
    bodies = traffic.Bodies(spec["seed"], mix["size"], mix["bodies"])
    workers = [Worker(spec, wid, bodies) for wid in spec["workers"]]
    out = sys.stdout
    for line in sys.stdin:
        req = json.loads(line)
        try:
            if req["cmd"] == "preload":
                total = spec["workers_total"]
                shares = [[traffic.pre_key(i)
                           for i in range(mix["preload"])
                           if i % total == w.wid] for w in workers]
                _all(workers, Worker.preload, [(sh,) for sh in shares])
                reply = {"ready": True}
            elif req["cmd"] == "burst":
                _all(workers, Worker.burst,
                     [(req["op"], req["n"], req["at"])] * len(workers))
                reply = {"ready": True}
            elif req["cmd"] == "run":
                cpu: dict = {}
                meter = threading.Thread(
                    target=_cpu_between,
                    args=(req.get("t0", req["t_go"]), req["t1"], cpu))
                meter.start()
                _all(workers, Worker.run,
                     [(req["t_go"], req["t1"])] * len(workers))
                _all(workers, Worker.after)
                meter.join()
                reply = {"ops": [e for w in workers for e in w.log],
                         "live": {str(w.wid): w.live for w in workers},
                         "cpu": cpu}
            elif req["cmd"] == "exit":
                break
            else:
                reply = {"error": f"unknown command {req['cmd']!r}"}
        except Exception as e:  # noqa: BLE001 - the parent decides
            reply = {"error": f"{type(e).__name__}: {e}"}
        out.write(json.dumps(reply) + "\n")
        out.flush()
    for w in workers:
        w.cli.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

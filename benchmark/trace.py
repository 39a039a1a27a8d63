"""Reduction of a JAX profiler trace (`*.xplane.pb`) to what the
per-layer metrics read. Runs in a process of its own with
JAX_PLATFORMS=cpu, after the server has stopped: `jax.profiler.
ProfileData` needs JAX, and the benchmark's parent never imports it.

    python benchmark/trace.py reduce <dir or file>   -> one JSON line
    python benchmark/trace.py dump   <dir or file>   -> what is in it

What a TPU trace holds (looked at by hand, PR 25): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event per HLO op run on
the TensorCore (named by its whole HLO line) and whose line `XLA
Modules` has one event per executed program (`jit_fused32(<hash>)` is
the framer, `jit_verify32(<hash>)` the de-framer); `/host:CPU` holds
the runtime's and JAX's own spans (`PjitFunction(fused32)`,
`XlaLinearize`, ...) on the same clock, in nanoseconds from the start
of the profile — not the host's clock. So the launcher's control
thread drops two marks into the trace (`MARK_A`, `MARK_B`:
`jax.profiler.TraceAnnotation`s on the host plane) around the two
scrapes that count the interval's work, and everything here is reduced
over the interval between the marks alone: work counted and device
time are of the same interval. Busy time is the union of the `XLA Ops`
intervals; a module's device time is the sum of the op events that
start inside its events; idle time is told by the host spans that were
open during it.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_A = "benchmark.interval_a"
MARK_B = "benchmark.interval_b"


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return hits[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(find_xplane(path))


def device_planes(data) -> list:
    return [p for p in data.planes if p.name.startswith("/device:TPU:")]


def _events(plane, line_name: str, lo: float,
            hi: float) -> list[tuple[float, float, str]]:
    """The line's events that START inside [lo, hi]."""
    out = []
    for line in plane.lines:
        if line.name == line_name:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if lo <= e.start_ns <= hi]
    out.sort()
    return out


def marked_interval(data) -> tuple[float, float]:
    """[start of the first MARK_A, end of the last MARK_B] on the
    trace's clock."""
    a, b = [], []
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARK_A:
                        a.append(e.start_ns)
                    elif e.name == MARK_B:
                        b.append(e.start_ns + e.duration_ns)
    if not a or not b or max(b) <= min(a):
        raise ValueError(f"the trace does not hold {MARK_A} before {MARK_B}"
                         f" ({len(a)} and {len(b)} found)")
    return min(a), max(b)


def module_name(event_name: str) -> str:
    """`jit_fused32(1234567)` -> `jit_fused32`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """An `XLA Ops` event is named by its whole HLO line; keep the
    result's name and shape: `%fused32.3 = u32[1,8,8,128]`."""
    m = re.match(r"(%[\w.\-]+ = [a-z0-9]+\[[\d,]*\])", event_name)
    return m.group(1) if m else event_name[:80]


class Intervals:
    """Disjoint sorted intervals with O(log n) overlap queries."""

    def __init__(self, intervals):
        self.a, self.b = [], []
        for a, b in sorted(intervals):
            if self.b and a <= self.b[-1]:
                self.b[-1] = max(self.b[-1], b)
            else:
                self.a.append(a)
                self.b.append(b)
        self.cum = [0.0]
        for a, b in zip(self.a, self.b):
            self.cum.append(self.cum[-1] + (b - a))

    def total(self) -> float:
        return self.cum[-1]

    def _upto(self, x: float) -> float:
        i = bisect.bisect_right(self.a, x)
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(x, self.b[i - 1]) - self.a[i - 1]

    def overlap(self, a: float, b: float) -> float:
        return self._upto(b) - self._upto(a)

    def complement(self, lo: float, hi: float) -> "Intervals":
        out, at = [], lo
        for a, b in zip(self.a, self.b):
            if a > at:
                out.append((at, min(a, hi)))
            at = max(at, b)
        if at < hi:
            out.append((at, hi))
        return Intervals([(a, b) for a, b in out if b > a])


def reduce_plane(plane, lo: float, hi: float) -> dict:
    ops = _events(plane, OPS_LINE, lo, hi)
    mods = _events(plane, MODULES_LINE, lo, hi)
    timed = ops if ops else mods
    starts = [m[0] for m in mods]
    per_mod: dict[str, dict] = {}
    for a, b, name in mods:
        st = per_mod.setdefault(module_name(name),
                                {"count": 0, "span_s": 0.0, "device_s": 0.0})
        st["count"] += 1
        st["span_s"] += (b - a) / 1e9
    per_op: dict[str, float] = {}
    for a, b, name in ops:
        per_op[op_name(name)] = per_op.get(op_name(name), 0.0) + (b - a) / 1e9
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and a < mods[i][1]:
            per_mod[module_name(mods[i][2])]["device_s"] += (b - a) / 1e9
    if not ops:                        # no op line: a module's span is
        for st in per_mod.values():    # all that is known of it
            st["device_s"] = st["span_s"]
    return {"busy": Intervals([(a, b) for a, b, _ in timed]),
            "modules": per_mod, "ops": per_op}


def host_events(data) -> list[tuple[float, float, str]]:
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events if e.duration_ns > 0
                        and e.name not in (MARK_A, MARK_B)]
    return out


def idle_by_host_span(busy: Intervals, host, lo: float, hi: float) -> dict:
    """Seconds of device idle time inside [lo, hi] during which each
    host span (by name) was open; spans nest, so the names overlap.
    What no host span covers is the program's own Python, which has no
    annotation yet."""
    idle = busy.complement(lo, hi)
    out: dict[str, float] = {}
    for a, b, name in host:
        s = idle.overlap(a, b)
        if s > 0:
            out[name] = out.get(name, 0.0) + s / 1e9
    uncovered = Intervals([(a, b) for a, b, _ in host]).complement(lo, hi)
    bare = sum(idle.overlap(a, b) for a, b in zip(uncovered.a, uncovered.b))
    out["no host span (the program's Python is not annotated)"] = bare / 1e9
    return out


def reduce(path: str) -> dict:
    data = load(path)
    if not device_planes(data):
        return {"chips": 0}
    lo, hi = marked_interval(data)
    planes = [reduce_plane(p, lo, hi) for p in device_planes(data)]
    host = host_events(data)
    n = len(planes)
    mods: dict[str, dict] = {}
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for pl in planes:
        for name, st in pl["modules"].items():
            agg = mods.setdefault(name, {"name": name, "count": 0,
                                         "span_s": 0.0, "device_s": 0.0})
            for k in ("count", "span_s", "device_s"):
                agg[k] += st[k] / n
        for name, s in pl["ops"].items():
            ops[name] = ops.get(name, 0.0) + s / n
        for name, s in idle_by_host_span(pl["busy"], host, lo, hi).items():
            gaps[name] = gaps.get(name, 0.0) + s / n
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]  # noqa: E731
    return {"chips": n,
            "busy_s": sum(p["busy"].overlap(lo, hi) for p in planes) / n / 1e9,
            "window_s": (hi - lo) / 1e9,
            "modules": sorted(mods.values(), key=lambda m: -m["device_s"]),
            "device_ops": [[k, v] for k, v in top(ops)],
            "idle_gaps": [[k, v] for k, v in top(gaps)]}


def dump(path: str) -> None:
    data = load(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names: dict[str, list] = {}
            for e in events:
                st = names.setdefault(e.name, [0, 0.0])
                st[0] += 1
                st[1] += e.duration_ns / 1e6
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{len(names)} names")
            for name, (cnt, ms) in sorted(names.items(),
                                          key=lambda kv: -kv[1][1])[:12]:
                print(f"    {cnt:6d} x {ms:10.3f} ms  {name[:100]}")
            if events and plane.name.startswith("/device"):
                e = events[len(events) // 2]
                print("    stats of one event:",
                      {k: (str(v)[:80]) for k, v in e.stats})


def main() -> int:
    if len(sys.argv) != 3 or sys.argv[1] not in ("reduce", "dump"):
        print(__doc__, file=sys.stderr)
        return 2
    if sys.argv[1] == "dump":
        dump(sys.argv[2])
    else:
        print(json.dumps(reduce(sys.argv[2])))
    return 0


if __name__ == "__main__":
    sys.exit(main())

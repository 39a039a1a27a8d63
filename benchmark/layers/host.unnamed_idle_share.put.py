"""`host.unnamed_idle_share.put`: of the seconds the device sat idle
between the trace's two marks, the share during which no host span at
all was open — neither a stage of the program (`utils/tracing.stage`
puts them on the profiler's clock) nor a span of the runtime.

`benchmark/trace.py` sums idle seconds per host span name, adds the
uncovered rest under a name that starts "no host span", and keeps the
ten largest. Where that entry is among the ten, this is its seconds
over the idle seconds. Where ten names each hold more — as they do
once the program names its stages on 40 threads — the entry is
computed again from the trace itself, by `trace.py`'s own
`idle_by_host_span`, in a child that cannot touch the chip: the same
number, whatever its rank. No stand-in: where the trace cannot be
found, nothing is reported and `ctx["notes"]` says why.

The harness keeps the trace under its run's work directory
(`<tmp>/mtpu-bench-*/trace`) until the line is printed, and gives
readers the reduction only: the directory is found by that name.
"""

import glob
import json
import os
import subprocess
import sys
import tempfile

UNNAMED = "no host span"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def unnamed_seconds(trace_dir: str) -> float:
    """Seconds of device idle time between the marks that no host span
    covers, a chip (as `trace.reduce` averages its planes)."""
    sys.path.insert(0, ROOT)
    from benchmark import trace
    data = trace.load(trace_dir)
    lo, hi = trace.marked_interval(data)
    host = trace.host_events(data)
    planes = trace.device_planes(data)
    total = 0.0
    for plane in planes:
        busy = trace.reduce_plane(plane, lo, hi)["busy"]
        gaps = trace.idle_by_host_span(busy, host, lo, hi)
        total += sum(s for name, s in gaps.items()
                     if name.startswith(UNNAMED))
    return total / len(planes)


def _trace_dir():
    """The run's own trace: the newest the harness keeps."""
    kept = [d for d in glob.glob(os.path.join(
        tempfile.gettempdir(), "mtpu-bench-*", "trace"))
        if glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)]
    return max(kept, key=os.path.getmtime) if kept else None


def read(ctx, spec):
    tr = ctx.get("trace")
    if not tr or not tr.get("chips") or tr.get("window_s", 0) <= 0:
        return None
    gaps = tr.get("idle_gaps") or []
    idle_s = tr["window_s"] - tr.get("busy_s", 0.0)
    if not gaps or idle_s <= 0:
        return None
    note = ctx.setdefault("notes", {})["unnamed_idle"] = {"idle_s": idle_s}
    named = next((s for name, s in gaps if name.startswith(UNNAMED)), None)
    if named is None:
        note["from"] = "the trace, reduced again: not among the ten listed"
        path = _trace_dir()
        if path is None:
            note["unread"] = "no trace directory found"
            return None
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), path],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, cwd=ROOT,
                env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=200,
                check=True)
            named = float(json.loads(
                out.stdout.decode().strip().splitlines()[-1]))
        except (subprocess.SubprocessError, OSError, ValueError,
                IndexError) as e:
            note["unread"] = f"{type(e).__name__}: {e}"[:200]
            return None
    note["unnamed_s"] = named
    return named / idle_s * 100.0


if __name__ == "__main__":
    print(json.dumps(unnamed_seconds(sys.argv[1])))

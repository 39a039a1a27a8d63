"""`mesh.chip_busy_skew.put`: how unevenly the chips of the mesh were
busy between the trace's two marks — the busiest plane's busy seconds
less the idlest's, over the mean of the planes, in percent.

`benchmark/trace.py reduce` averages the device planes and hands the
readers that average alone, so the kept trace is reduced a second
time here, per plane, with `trace.py`'s own `reduce_plane`, in a child
that cannot touch the chip (as `host.unnamed_idle_share.put.py` does).
The harness keeps the trace under its run's work directory
(`<tmp>/mtpu-bench-*/trace`) until the line is printed: the directory
is found by that name. A trace of one plane has no skew to report, and
where the trace cannot be found nothing is reported and `ctx["notes"]`
says why.
"""

import glob
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def busy_seconds_by_plane(trace_dir: str) -> dict:
    """{plane name: seconds of the union of its `XLA Ops` intervals
    between the marks}."""
    sys.path.insert(0, ROOT)
    from benchmark import trace
    data = trace.load(trace_dir)
    lo, hi = trace.marked_interval(data)
    return {p.name: trace.reduce_plane(p, lo, hi)["busy"].overlap(lo, hi)
            / 1e9 for p in trace.device_planes(data)}


def skew(busy: list) -> float | None:
    """(max - min) / mean of the planes' busy seconds, in percent."""
    if len(busy) < 2 or sum(busy) <= 0:
        return None
    return (max(busy) - min(busy)) / (sum(busy) / len(busy)) * 100.0


def _trace_dir():
    """The run's own trace: the newest the harness keeps."""
    kept = [d for d in glob.glob(os.path.join(
        tempfile.gettempdir(), "mtpu-bench-*", "trace"))
        if glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)]
    return max(kept, key=os.path.getmtime) if kept else None


def read(ctx, spec):
    tr = ctx.get("trace")
    if not tr or (tr.get("chips") or 0) < 2 or tr.get("window_s", 0) <= 0:
        return None
    note = ctx.setdefault("notes", {})["chip_busy"] = {}
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
        by_plane = json.loads(out.stdout.decode().strip().splitlines()[-1])
    except (subprocess.SubprocessError, OSError, ValueError,
            IndexError) as e:
        note["unread"] = f"{type(e).__name__}: {e}"[:200]
        return None
    note["busy_s_by_plane"] = by_plane
    return skew(list(by_plane.values()))


if __name__ == "__main__":
    print(json.dumps(busy_seconds_by_plane(sys.argv[1])))

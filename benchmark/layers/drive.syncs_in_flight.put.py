"""`drive.syncs_in_flight.put`: the mean number of `fdatasync` calls in
flight in the serving process, shard files' and journals' together.

The seconds inside the stages `disk.stream.sync` and `disk.meta.sync`
(`minio_tpu_stage_seconds_total`) between the window's two scrapes,
over the server's own seconds between them: the generic
`prometheus_delta` with `window_s` below the line. That reader gives 0
wherever the window has seconds, so a program that has no such stage
would read "no sync in flight"; this one looks first whether either
stage was entered at all (`present`, the entries' series) and reports
nothing where none was.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(ctx, spec):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import readers
    entered = {"numerator": spec["present"],
               "denominator": [{"series": readers.UPTIME}]}
    if not readers.prometheus_delta(ctx, entered):
        return None
    return readers.prometheus_delta(ctx, spec)

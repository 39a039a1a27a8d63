"""`loadgen.cpu_share.get`: how busy the load generator's own
processes were inside the window.

Each generator process (`benchmark/loadgen.py`) reads its CPU seconds
(`time.process_time()`: every thread, user + system) at the window's
first and last instant and sends the difference back with the run's
operations; `run.py` sums them into `ctx["loadgen"]`. A generator
receives every byte it asked for into a buffer and compares it with
the body it expects, on the cores the server runs on: near 100 % a
process (one interpreter lock each) the generators, not the server,
set `get_mib_s`, and the line has to say so. Nothing sent back (an
older generator): nothing reported.
"""


def read(ctx, spec):
    gen = ctx.get("loadgen") or {}
    if gen.get("wall_s", 0) <= 0:
        return None
    return gen["cpu_s"] / gen["wall_s"] * 100.0

"""Launcher of the server under test: `minio_tpu.server`'s `main()`,
unchanged, on the main thread (its graceful SIGTERM path is installed
only there), plus one control thread the benchmark can ask four
things of, over a Unix socket, one JSON line each way:

    {"cmd": "trace_start", "dir": ...}   jax.profiler.start_trace
    {"cmd": "mark", "name": ...}         an empty TraceAnnotation: an
                                         instant on the trace's own clock
    {"cmd": "trace_stop"}                jax.profiler.stop_trace
    {"cmd": "memory"}                    per-device memory_stats()

The program has no `jax.profiler` call and exports no device memory
reading, and only the process that owns the chip can give either. JAX
is imported here only when a command arrives — by then the server has
imported it itself — so the launcher adds nothing to the boot.

    python benchmark/serve_traced.py <control.sock> <server argv ...>
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading


def _answer(req: dict) -> dict:
    import jax
    cmd = req.get("cmd")
    if cmd == "trace_start":
        # device events and TraceMe spans only: the Python call tracer
        # (level 1 by default) stalls a one-process server for the
        # seconds it takes to write every call out
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(req["dir"], profiler_options=opts)
        return {"ok": True}
    if cmd == "mark":
        with jax.profiler.TraceAnnotation(req["name"]):
            pass
        return {"ok": True}
    if cmd == "trace_stop":
        jax.profiler.stop_trace()
        return {"ok": True}
    if cmd == "memory":
        out = []
        for d in jax.devices():
            st = d.memory_stats() or {}
            out.append({"id": d.id, "platform": d.platform,
                        "kind": d.device_kind,
                        "peak_bytes_in_use": st.get("peak_bytes_in_use"),
                        "bytes_in_use": st.get("bytes_in_use"),
                        "bytes_limit": st.get("bytes_limit")})
        return {"ok": True, "devices": out}
    return {"ok": False, "error": f"unknown command {cmd!r}"}


def _control(path: str) -> None:
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.bind(path)
    srv.listen(4)
    while True:
        conn, _ = srv.accept()
        with conn, conn.makefile("rwb") as f:
            for line in f:
                try:
                    reply = _answer(json.loads(line))
                except Exception as e:  # noqa: BLE001 - said to the asker
                    reply = {"ok": False,
                             "error": f"{type(e).__name__}: {e}"}
                f.write(json.dumps(reply).encode() + b"\n")
                f.flush()


def main() -> int:
    if len(sys.argv) < 3:
        print(__doc__, file=sys.stderr)
        return 2
    sys.path.insert(0, os.getcwd())
    threading.Thread(target=_control, args=(sys.argv[1],),
                     daemon=True).start()
    from minio_tpu.server import main as server_main
    return server_main(sys.argv[2:])


if __name__ == "__main__":
    sys.exit(main())

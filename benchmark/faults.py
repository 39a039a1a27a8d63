"""Faults planted under a run, for the control and the tests: each
breaks one thing the configuration guarantees, at the place where the
program produces it, and `correct` has to come out false.

  wrong_matrix     after the clean stop, the parity shard files of the
                   sampled objects are replaced by the REFERENCE's
                   encoding under a Vandermonde matrix that was never
                   made systematic (digests recomputed to match): the
                   reference in the program's place with one guarantee
                   broken — parity that is not upstream's;
  below_quorum     shard files of the sampled objects are removed until
                   one drive fewer than the write quorum holds them: an
                   acknowledged write that is not durable.

  flip_get_byte    a proxy between the generators and the server turns
                   one byte of the body of one GET inside the window:
                   an answer altered where it is produced, as far as a
                   harness that may not edit the program can reach — on
                   the wire behind the server's socket.

  deaf_deframer    the server is booted through serve_deaf.py: the `get`
                   route tells every window "all frames verify",
                   whatever the de-framer found, and the rebuild path's
                   own verify (a window with a shard missing) trusts
                   every survivor it fetched. In a mix that plants rot
                   (`rotten`) the turned byte is then served: the
                   program with its bitrot-on-read guarantee switched
                   off, an answer altered where it is produced.

  unblocked_roots  (a configuration with `dead_drives`) the drives'
                   roots are removed and NOT blocked: the program makes
                   them again, formats them as fresh drives and heals
                   the shards back, so within seconds the window
                   measures healthy reads — a drive replaced, not a
                   drive dead: the deployment is not the one the
                   configuration states (`dead_drives_touched`).

The first two alter what a PUT produced where it lies (in a cell that
only reads: what the preload's PUTs left); the other two are the faults
of a cell that reads.
"""

from __future__ import annotations

import os
import socket
import threading

from benchmark import compare
from benchmark.traffic import BUCKET
from benchmark.reference import gf_rs


def wrong_matrix(srv, cfg: dict, sample: list[str], bodies) -> None:
    k, m = cfg["data_shards"], cfg["parity_shards"]
    for key in sample:
        body = bodies.body(key)
        right = compare.reference_shard_files(
            body, k, m, cfg["erasure_block_bytes"])
        broken = compare.reference_shard_files(
            body, k, m, cfg["erasure_block_bytes"],
            gf_rs.raw_vandermonde_parity(k, m))
        for path in compare.shard_files_on_disk(
                srv.drive_root, cfg["drives"], BUCKET, key).values():
            with open(path, "rb") as f:
                got = f.read()
            for i in range(k, k + m):
                if got == right[i]:
                    with open(path, "wb") as f:
                        f.write(broken[i])


def below_quorum(srv, cfg: dict, sample: list[str], bodies) -> None:
    for key in sample:
        paths = sorted(compare.shard_files_on_disk(
            srv.drive_root, cfg["drives"], BUCKET, key).items())
        for _, path in paths[cfg["write_quorum"] - 1:]:
            os.unlink(path)


def _read_head(f) -> bytes:
    """One HTTP head, its blank line included; b"" at end of stream."""
    lines = []
    while True:
        line = f.readline(65536)
        if not line:
            return b""
        lines.append(line)
        if line in (b"\r\n", b"\n"):
            return b"".join(lines)


def _content_length(head: bytes):
    for line in head.split(b"\r\n")[1:]:
        name, _, value = line.partition(b":")
        if name.strip().lower() == b"content-length":
            return int(value)
    return None


class FlipProxy:
    """Between the generators and the server, one thread a connection,
    standard library only: every request and answer is passed on byte
    for byte, but for ONE byte — in the middle of the body of the first
    object GET answered 200 after `arm()` (run_cell arms it at the
    window's first instant). `flipped` says which GET it was. The
    generator that sent it must judge it `wrong`."""

    def __init__(self, upstream: str):
        host, port = upstream.rsplit(":", 1)
        self.upstream = (host, int(port))
        self._srv = socket.socket()
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(128)
        self.address = f"127.0.0.1:{self._srv.getsockname()[1]}"
        self.flipped: dict | None = None
        self._armed = False
        self._mu = threading.Lock()
        self._socks: set = set()
        self._acceptor = threading.Thread(target=self._accept, daemon=True)
        self._acceptor.start()

    def arm(self) -> None:
        self._armed = True

    def close(self) -> None:
        with self._mu:
            socks, self._socks = list(self._socks), set()
        for s in [self._srv, *socks]:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            s.close()
        self._acceptor.join(5)

    def _accept(self) -> None:
        while True:
            try:
                cli, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(cli,),
                             daemon=True).start()

    def _take(self, path: str, length: int) -> bool:
        with self._mu:
            if not self._armed or self.flipped is not None:
                return False
            self.flipped = {"path": path, "offset": length // 2}
            return True

    def _serve(self, cli: socket.socket) -> None:
        up = None
        try:
            up = socket.create_connection(self.upstream)
            with self._mu:
                self._socks.update((cli, up))
            cf, uf = cli.makefile("rb"), up.makefile("rb")
            buf = memoryview(bytearray(1 << 20))
            while True:
                head = _read_head(cf)
                if not head:
                    return
                method, path = head.split(b" ", 2)[:2]
                up.sendall(head)
                self._relay(cf, up, _content_length(head) or 0, buf)
                answer = _read_head(uf)
                if not answer:
                    return
                status = int(answer.split(b" ", 2)[1])
                length = _content_length(answer)
                cli.sendall(answer)
                if method == b"HEAD" or status in (204, 304):
                    continue
                if length is None:          # a body that ends with the
                    while True:             # connection (none is expected)
                        n = uf.readinto(buf)
                        if not n:
                            return
                        cli.sendall(buf[:n])
                obj = path.decode().split("?")[0]
                flip = method == b"GET" and status == 200 and length > 0 \
                    and obj.count("/") >= 2 and self._take(obj, length)
                self._relay(uf, cli, length, buf,
                            length // 2 if flip else None)
        except (OSError, ValueError):
            return
        finally:
            for s in (cli, up):
                if s is not None:
                    with self._mu:
                        self._socks.discard(s)
                    s.close()

    @staticmethod
    def _relay(src, dst: socket.socket, length: int, buf: memoryview,
               flip_at: int | None = None) -> None:
        done = 0
        while done < length:
            n = src.readinto(buf[:min(len(buf), length - done)])
            if not n:
                raise OSError("stream ended inside a body")
            if flip_at is not None and done <= flip_at < done + n:
                buf[flip_at - done] ^= 0x01
            dst.sendall(buf[:n])
            done += n


def unblocked_roots(srv, cfg: dict, cli) -> None:
    del cli
    for d in cfg["dead_drives"]:
        srv.kill_drive(d, block=False)


FAULTS = {"wrong_matrix": wrong_matrix, "below_quorum": below_quorum}


def hooks_for(name: str) -> dict:
    """-> hooks for run_cell that plant the fault."""
    if name == "flip_get_byte":
        return {"proxy": FlipProxy}
    if name == "deaf_deframer":
        return {"launcher": os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "serve_deaf.py")}
    if name == "unblocked_roots":
        return {"after_preload": unblocked_roots}
    return {"before_disk_check": FAULTS[name]}

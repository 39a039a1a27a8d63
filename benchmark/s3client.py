"""The benchmark's own S3 client: header-signed SigV4 over plain HTTP,
one keep-alive connection per client object, standard library only.

It is the yardstick's half of the wire, so it lives here and shares no
code with `minio_tpu/` (the program's `s3/client.py` was the model).
What it costs the load generator, stated once: the payload's SHA-256
goes into the signature (the caller may pass one it computed ahead);
a response is read to its last byte before `request` returns; a socket
that stays silent for `timeout` seconds fails the operation. Nothing is
retried: an operation that fails, failed. A connection that has lain
idle for IDLE_S is opened anew before the next request (no retry: the
request has not been sent): the server reaps a parked connection after
75 s (`MTPU_HTTP_KEEPALIVE_S`), as any server does, and a request sent
into a reaped one gets no answer — a set-up that keeps the generators
waiting that long (PR 34: the serial read-back of a degraded cell) would
otherwise cost every worker its next operation.
"""

from __future__ import annotations

import datetime
import hashlib
import hmac
import http.client
import time
import urllib.parse

EMPTY_SHA256 = hashlib.sha256(b"").hexdigest()
IDLE_S = 60.0


def _quote(s: str, safe: str) -> str:
    return urllib.parse.quote(s, safe=safe)


def _sign(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


class S3:
    def __init__(self, address: str, access_key: str = "minioadmin",
                 secret_key: str = "minioadmin", region: str = "us-east-1",
                 timeout: float = 60.0):
        self.address = address
        self.access_key = access_key
        self.secret_key = secret_key
        self.region = region
        self.timeout = timeout
        self._conn: http.client.HTTPConnection | None = None
        self._used = 0.0

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def _headers(self, method: str, path: str, query: dict,
                 payload_sha256: str, extra: dict) -> tuple[dict, str]:
        now = datetime.datetime.now(datetime.timezone.utc)
        amz_date = now.strftime("%Y%m%dT%H%M%SZ")
        scope = f"{amz_date[:8]}/{self.region}/s3/aws4_request"
        send = {"host": self.address, "x-amz-date": amz_date,
                "x-amz-content-sha256": payload_sha256}
        send.update({k.lower(): v for k, v in extra.items()})
        names = sorted(send)
        canon_query = "&".join(
            f"{_quote(k, '-_.~')}={_quote(v, '-_.~')}"
            for k, v in sorted(query.items()))
        url_path = _quote(path, "/-_.~")
        canon = "\n".join([
            method, url_path, canon_query,
            "".join(f"{n}:{' '.join(send[n].split())}\n" for n in names),
            ";".join(names), payload_sha256])
        to_sign = "\n".join(["AWS4-HMAC-SHA256", amz_date, scope,
                             hashlib.sha256(canon.encode()).hexdigest()])
        key = ("AWS4" + self.secret_key).encode()
        for part in (amz_date[:8], self.region, "s3", "aws4_request"):
            key = _sign(key, part)
        sig = hmac.new(key, to_sign.encode(), hashlib.sha256).hexdigest()
        send["Authorization"] = (
            f"AWS4-HMAC-SHA256 Credential={self.access_key}/{scope}, "
            f"SignedHeaders={';'.join(names)}, Signature={sig}")
        return send, url_path + ("?" + canon_query if canon_query else "")

    def request(self, method: str, path: str, query: dict | None = None,
                body=b"", payload_sha256: str | None = None,
                headers: dict | None = None, into: bytearray | None = None):
        """-> (status, headers, body). `body` is bytes-like or a list
        of bytes-like pieces sent in order; with `into`, a 200 body is
        read into that buffer and a memoryview of it is returned."""
        pieces = body if isinstance(body, (list, tuple)) else [body]
        if payload_sha256 is None:
            h = hashlib.sha256()
            for p in pieces:
                h.update(p)
            payload_sha256 = h.hexdigest()
        send, url = self._headers(method, path, query or {},
                                  payload_sha256, headers or {})
        send["Content-Length"] = str(sum(len(p) for p in pieces))
        if self._conn is not None \
                and time.monotonic() - self._used > IDLE_S:
            self.close()
        if self._conn is None:
            self._conn = http.client.HTTPConnection(self.address,
                                                    timeout=self.timeout)
        conn = self._conn
        try:
            conn.putrequest(method, url, skip_host=True,
                            skip_accept_encoding=True)
            for k, v in send.items():
                conn.putheader(k, v)
            conn.endheaders()
            for p in pieces:
                if len(p):
                    conn.send(p)
            resp = conn.getresponse()
            hdrs = {k.lower(): v for k, v in resp.getheaders()}
            if into is not None and resp.status == 200:
                view = memoryview(into)
                got = 0
                while got < len(view):
                    n = resp.readinto(view[got:])
                    if not n:
                        break
                    got += n
                rest = resp.read()      # must be empty: the buffer is
                data = view[:got] if not rest else bytes(view[:got]) + rest
            else:                       # sized by the caller
                data = resp.read()
            if resp.will_close or resp.status >= 400:
                # the program cuts the connection after any error
                # answer without saying `Connection: close`
                self.close()
            self._used = time.monotonic()
            return resp.status, hdrs, data
        except BaseException:
            self.close()
            raise

"""Plaintext-space read/write transforms over the object layer.

The reference routes every front end (S3 handlers, FTP/SFTP servers,
Select, replication) through one object-API layer that applies the
stored-representation transforms — SSE decryption (cmd/encryption-v1.go)
and transparent decompression (cmd/object-api-utils.go) — so a gateway
can never leak DARE ciphertext or compressed bytes to a client. This
module is that seam here: the S3 server's GET path and the FTP gateway
both resolve logical bytes through these functions.

All functions raise the crypto-layer errors (`sse.SSEError`,
`compress.CompressionError`); callers translate to their protocol's
error surface (S3Error / FTP 550).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Optional

from minio_tpu.object.types import GetOptions
from minio_tpu.utils.latency import Histogram

# ---------------------------------------------------------------------------
# Fused single-pass data plane (ROADMAP "single-pass device data
# plane"): one GIL-free native call per buffered PUT computes the etag
# md5 + declared checksums, deflates into the block scheme, seals into
# DARE packages, and frames the stored stream's full erasure blocks
# (native/native.cc mtpu_transform_frame) — instead of one Python walk
# of the body per stage. The S3 handler plans the stages into a
# TransformSpec; the erasure layer executes it next to the framer
# (erasure_object._transform_frame_windows) where the EC geometry and
# the pooled staging buffers live. MTPU_TRANSFORM_FUSED=off is the
# kill-switch back to the layered pipeline (byte-identical output).
# ---------------------------------------------------------------------------

STAGES = ("digest", "compress", "encrypt", "frame")

_stat_mu = threading.Lock()
_put_requests = {"fused": 0, "legacy": 0}
_get_requests = {"fused": 0, "legacy": 0}
_bytes = {"put": 0, "get": 0}
_stage_hists = {s: Histogram() for s in STAGES}


def fused_put_enabled() -> bool:
    """The fused PUT plane runs when the native library carries the
    transform kernel and MTPU_TRANSFORM_FUSED is not "off"
    (native.feature is the one shared gate)."""
    from minio_tpu import native
    return native.feature("mtpu_transform_frame") is not None


def note_put(path: str, nbytes: int = 0, stage_ns=None) -> None:
    with _stat_mu:
        _put_requests[path] = _put_requests.get(path, 0) + 1
        _bytes["put"] += nbytes
        if stage_ns:
            for stage, ns in zip(STAGES, stage_ns):
                if ns:
                    _stage_hists[stage].observe(ns / 1e9)


def note_get(path: str, nbytes: int = 0) -> None:
    with _stat_mu:
        _get_requests[path] = _get_requests.get(path, 0) + 1
        _bytes["get"] += nbytes


def stats() -> dict:
    """Fused/legacy path split + byte counters + per-stage service
    histograms (s3/metrics.py renders minio_tpu_transform_*)."""
    with _stat_mu:
        return {
            "put_requests": dict(_put_requests),
            "get_requests": dict(_get_requests),
            "bytes": dict(_bytes),
            "stage_hists": {s: h.state() for s, h in _stage_hists.items()},
            "fused_enabled": fused_put_enabled(),
        }


def reset_stats() -> None:
    """Test hook: zero the path-split counters."""
    with _stat_mu:
        for d in (_put_requests, _get_requests):
            for key in list(d):
                d[key] = 0
        for key in _bytes:
            _bytes[key] = 0


@dataclasses.dataclass
class TransformSpec:
    """The fused data-plane plan for ONE buffered PUT: which digest,
    compression, and encryption stages the single native pass runs,
    and (after the pass) what it produced. Built by the S3 handler
    (s3/server.py _put_object), executed by the erasure layer."""

    # Declared/trailer checksum algos beyond the etag md5 (any of
    # "sha256", "sha1", "crc32").
    algos: tuple = ()
    compress: bool = False
    enc_key: bytes = b""          # 32-byte DARE data key; b"" = no SSE
    enc_nonce: bytes = b""        # 12-byte DARE base nonce
    # Pre-commit verification hook (declared-checksum comparison): runs
    # right after the fused pass, BEFORE any disk write; raising aborts
    # the PUT with nothing committed — the layered path's
    # Payload-finish-hook timing, preserved.
    verify: Optional[Callable[["TransformSpec"], None]] = None
    # -- results (filled by the fused pass) --
    digests: dict = dataclasses.field(default_factory=dict)  # algo -> raw
    etag: str = ""
    plain_size: int = -1
    stored_size: int = -1
    comp_used: bool = False
    comp_ends: list = dataclasses.field(default_factory=list)
    # Internal-metadata updates the pass produced (compression index,
    # corrected DARE-stream size for compressed+encrypted objects).
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def encrypt(self) -> bool:
        return bool(self.enc_key)

    def run_verify(self) -> None:
        if self.verify is not None:
            self.verify(self)


def resolve_range(spec, size: int):
    """Parsed Range spec -> (start, length) against a logical size."""
    from minio_tpu.object.erasure_object import _resolve_range
    return _resolve_range(spec, size, "", "")


def sse_check_head(h: dict, info) -> None:
    """HEAD/GET of an SSE-C object requires the matching key."""
    from minio_tpu.crypto import sse as sse_mod
    alg = info.internal_metadata.get(sse_mod.META_ALG, "")
    if alg != sse_mod.ALG_SSE_C:
        return
    customer = sse_mod.parse_sse_c(h)
    if customer is None:
        raise sse_mod.SSEError("InvalidRequest",
                               "object is SSE-C encrypted; key headers "
                               "required")
    if customer[1] != info.internal_metadata.get(sse_mod.META_KEY_MD5):
        raise sse_mod.SSEError("AccessDenied", "wrong SSE-C key")


def _inflate_stream(raw, ends, first_block, stored_base, skip, length):
    """Windowed decompression: consume the STORED byte stream `raw`
    (starting at absolute stored offset `stored_base` = the start of
    `first_block`), inflate each run of whole compressed blocks the
    moment the window covers it — one GIL-free native call per run
    (crypto/compress.inflate_blocks) with the per-block Python loop as
    fallback — and yield plaintext, dropping `skip` leading bytes and
    stopping after `length`. Replaces the whole-blob
    decompress_range hop: memory stays O(window), never O(range)."""
    import zlib as _zl

    from minio_tpu.crypto import compress as comp
    produced = 0
    b = first_block
    base = stored_base
    buf = bytearray()
    try:
        for chunk in raw:
            buf += chunk
            nb = 0
            while b + nb < len(ends) and ends[b + nb] - base <= len(buf):
                nb += 1
            if not nb:
                continue
            window = bytes(buf[: ends[b + nb - 1] - base])
            plain = comp.inflate_blocks(window, ends, b, nb, base)
            if plain is None:
                parts = []
                for i in range(b, b + nb):
                    lo = (ends[i - 1] if i else 0) - base
                    try:
                        parts.append(_zl.decompress(window[lo:ends[i] -
                                                           base]))
                    except _zl.error:
                        raise comp.CompressionError(
                            f"block {i} fails decompression") from None
                plain = b"".join(parts)
            del buf[: len(window)]
            base += len(window)
            b += nb
            if skip:
                drop = min(skip, len(plain))
                plain = plain[drop:]
                skip -= drop
            take = min(len(plain), length - produced)
            if take:
                produced += take
                yield plain[:take]
            if produced >= length:
                return
        if produced < length:
            raise comp.CompressionError(
                "stored stream ended before the requested range")
    finally:
        close = getattr(raw, "close", None)
        if close is not None:
            close()


def get_compressed(ol, bucket, key, vid, spec, info):
    """Ranged read of a compressed object: STREAM the covering stored
    blocks and decompress window by window out of the pooled GET
    readahead (no whole-blob materialization). Returns
    (info, chunks, start, length)."""
    from minio_tpu.crypto import compress as comp
    start, length = (resolve_range(spec, info.size)
                     if spec else (0, info.size))
    info.range_start, info.range_length = start, length
    if length <= 0 or info.size == 0:
        return info, (b for b in ()), start, max(length, 0)
    imeta = info.internal_metadata
    lo, ln = comp.stored_range(imeta, start, length)
    ends = comp._index(imeta)
    pin = vid or info.version_id
    _, raw = ol.get_object_stream(
        bucket, key, GetOptions(version_id=pin, offset=lo, length=ln))
    first = start // comp.BLOCK
    note_get("fused" if comp._native_lib() is not None else "legacy",
             length)
    gen = _inflate_stream(raw, ends, first, lo,
                          start - first * comp.BLOCK, length)
    return info, gen, start, length


def get_encrypted(ol, kms, bucket, key, vid, spec, h, info):
    """Ranged decrypting GET: map the plaintext range onto
    package-aligned ciphertext, stream, decrypt, trim — window by
    window out of the pooled GET readahead (crypto/dare.py opens whole
    windows in one native call when the kernel library is present). An
    SSE multipart object is a sequence of independent per-part DARE
    streams (reference: cmd/encryption-v1.go:643 part-boundary
    decryption); a single PUT is one stream. A compressed+encrypted
    object layers verify -> decrypt -> decompress over the same
    windows: the plaintext range maps to compressed blocks, the block
    range to DARE packages, and both transforms run per window.
    Returns (info, chunks, start, length)."""
    from minio_tpu.crypto import compress as comp
    from minio_tpu.crypto import dare as dare_mod
    from minio_tpu.crypto import sse as sse_mod
    from minio_tpu.crypto.dare import (PACKAGE_SIZE, decrypt_packages,
                                       encrypt_stream_size, package_range)
    customer = sse_mod.parse_sse_c(h)
    data_key, nonce = sse_mod.decrypt_params(
        bucket, key, info.internal_metadata, kms, customer)
    start, length = (resolve_range(spec, info.size)
                     if spec else (0, info.size))
    info.range_start, info.range_length = start, length
    if length <= 0 or info.size == 0:
        return info, (b for b in ()), start, max(length, 0)
    imeta = info.internal_metadata
    fused = dare_mod._native_lib() is not None
    if imeta.get(comp.META_SCHEME):
        # Compressed-then-encrypted single stream: plaintext range ->
        # covering compressed blocks -> covering DARE packages.
        ends = comp._index(imeta)
        first_block = start // comp.BLOCK
        c_lo, c_ln = comp.stored_range(imeta, start, length)
        dare_plain = int(imeta.get(sse_mod.META_SIZE, "0"))
        first, p_off, p_len = package_range(c_lo, c_ln)
        p_len = min(p_len, encrypt_stream_size(dare_plain) - p_off)
        _, raw = ol.get_object_stream(
            bucket, key, GetOptions(version_id=vid, offset=p_off,
                                    length=p_len))
        comp_stream = decrypt_packages(
            raw, data_key, nonce, first,
            c_lo - first * PACKAGE_SIZE, c_ln)
        note_get("fused" if fused else "legacy", length)
        gen = _inflate_stream(comp_stream, ends, first_block, c_lo,
                              start - first_block * comp.BLOCK, length)
        return info, gen, start, length
    note_get("fused" if fused else "legacy", length)
    if imeta.get(sse_mod.META_MULTIPART) and info.parts:
        gen = decrypt_parts_gen(ol, bucket, key, vid or info.version_id,
                                info, data_key, nonce, start, length)
        return info, gen, start, length
    first, c_off, c_len = package_range(start, length)
    c_size = encrypt_stream_size(info.size)
    c_len = min(c_len, c_size - c_off)
    _, raw = ol.get_object_stream(
        bucket, key, GetOptions(version_id=vid, offset=c_off,
                                length=c_len))
    chunks = decrypt_packages(raw, data_key, nonce, first,
                              start - first * PACKAGE_SIZE, length)
    return info, chunks, start, length


def decrypt_parts_gen(ol, bucket, key, vid, info, data_key, nonce,
                      start, length):
    """Plaintext range [start, start+length) across per-part DARE
    streams. Part boundaries in the STORED stream are the summed
    ciphertext part sizes; in the plaintext space the summed logical
    sizes. The whole covering stored range is fetched in ONE
    get_object_stream call — the per-part slices are contiguous (first
    part reads to its stored end, middles whole, last from its start),
    and a single read means a single version resolution, so a concurrent
    overwrite in an unversioned bucket cannot interleave versions
    mid-response. Each part decrypts under its derived key and its own
    stored base nonce."""
    import base64 as _b64

    from minio_tpu.crypto import sse as sse_mod
    from minio_tpu.crypto.dare import (PACKAGE_SIZE, decrypt_packages,
                                       package_range)
    # Plan: (part, first_seq, skip, plain_len, stored_lo, stored_len)
    plan = []
    pos, remaining = start, length
    plain_off = stored_off = 0
    for p in info.parts:
        if remaining <= 0:
            break
        if pos >= plain_off + p.actual_size:
            plain_off += p.actual_size
            stored_off += p.size
            continue
        in_off = pos - plain_off
        in_len = min(remaining, p.actual_size - in_off)
        first, c_off, c_len = package_range(in_off, in_len)
        c_len = min(c_len, p.size - c_off)
        plan.append((p, first, in_off - first * PACKAGE_SIZE,
                     in_len, stored_off + c_off, c_len))
        pos += in_len
        remaining -= in_len
        plain_off += p.actual_size
        stored_off += p.size
    if not plan:
        return
    lo = plan[0][4]
    hi = plan[-1][4] + plan[-1][5]
    _, raw = ol.get_object_stream(
        bucket, key, GetOptions(version_id=vid, offset=lo,
                                length=hi - lo))
    carry = bytearray()
    raw_iter = iter(raw)

    def take(n):
        """Yield exactly n bytes from the shared stored stream."""
        nonlocal carry
        while n > 0:
            if carry:
                chunk = bytes(carry[:n])
                del carry[:len(chunk)]
            else:
                try:
                    chunk = next(raw_iter)
                except StopIteration:
                    return       # decryptor reports the shortfall
                if len(chunk) > n:
                    carry.extend(chunk[n:])
                    chunk = chunk[:n]
            n -= len(chunk)
            yield chunk

    try:
        for p, first, skip, plain_len, _s_lo, s_len in plan:
            part_nonce = _b64.b64decode(p.nonce) if p.nonce else nonce
            yield from decrypt_packages(
                take(s_len), sse_mod.part_key(data_key, p.number),
                part_nonce, first, skip, plain_len)
    finally:
        close = getattr(raw, "close", None)
        if close is not None:
            close()


def plaintext_stream(ol, kms, bucket, key, vid="", h=None):
    """(info, chunks) for the object's LOGICAL bytes, whatever its
    stored representation — the one entry point for gateways that have
    no transform headers of their own (FTP, SFTP). SSE-C objects raise
    SSEError (the server holds no key for them).

    The transform re-open is pinned to the version the first open
    resolved; in UNVERSIONED buckets there is no version to pin, so a
    concurrent overwrite between the two reads can tear — the same
    small window the S3 GET path (and the reference) accepts there."""
    h = h or {}
    info, chunks = ol.get_object_stream(bucket, key,
                                        GetOptions(version_id=vid))
    imeta = info.internal_metadata
    if imeta.get("x-internal-sse-alg"):
        chunks.close()
        sse_check_head(h, info)
        info, chunks, _, _ = get_encrypted(
            ol, kms, bucket, key, vid or info.version_id, None, h, info)
    elif imeta.get("x-internal-comp"):
        chunks.close()
        info, chunks, _, _ = get_compressed(
            ol, bucket, key, vid or info.version_id, None, info)
    return info, chunks


def sse_payload(ol, kms, bucket, key, payload, opts, h=None):
    """Wrap a put payload in DARE encryption when the request headers
    (SSE-C / SSE-S3) or the bucket's default-encryption config ask for
    it — the single put-side SSE seam for every writer (reference:
    cmd/bucket-encryption.go consulted by the object API layer, not
    just the S3 handler). Returns (payload, response headers)."""
    from minio_tpu.crypto import EncryptingPayload, encrypt_stream_size
    from minio_tpu.crypto import sse as sse_mod
    from minio_tpu.utils.streams import Payload
    h = h or {}
    customer = sse_mod.parse_sse_c(h)
    if customer is None:
        enc_cfg = ol.get_bucket_meta(bucket).get("config:encryption")
        if not sse_mod.wants_sse_s3(h, enc_cfg):
            return payload, {}
    payload = Payload.wrap(payload)
    data_key, nonce, imeta = sse_mod.encrypt_metadata(
        bucket, key, payload.size, kms, customer)
    opts.internal_metadata.update(imeta)
    enc = EncryptingPayload(payload, data_key, nonce)
    out = Payload(enc, encrypt_stream_size(payload.size))
    if customer is not None:
        return out, {sse_mod.H_C_ALG: "AES256",
                     sse_mod.H_C_MD5: customer[1]}
    return out, {sse_mod.H_SSE: "AES256"}

"""Build-on-first-import loader for the native kernel library.

Compiles native.cc with g++ -O3 -march=native next to this file and
exposes it via ctypes. The artifact's NAME carries a digest of what it
was built from and for — the source bytes, the flags, and this host's
CPU (model and feature flags from /proc/cpuinfo: -march=native bakes
them in) — so a tree copied to another machine, or a source edit with
an older mtime, can never load a stale or foreign .so (which would
SIGILL, not fail politely); it just builds its own. Falls back to None
if no compiler is available — pure-Python/numpy paths take over,
slower but byte-identical; `load() is not None` is reported in admin
info (`device.native_lib`) so that fallback is visible.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "native.cc")
_FLAGS = ["-O3", "-march=native", "-shared", "-fPIC"]
_lock = threading.Lock()
_lib = None
_tried = False


def _host_cpu() -> str:
    """What -march=native resolved against: the first model/flags lines
    of /proc/cpuinfo (x86 `flags`, arm `Features`)."""
    seen: dict[str, str] = {}
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in ("model name", "flags", "Features") \
                        and key not in seen:
                    seen[key] = line
    except OSError:
        pass
    return platform.machine() + "".join(sorted(seen.values()))


def _so_path() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_host_cpu().encode())
    return os.path.join(_DIR, f"_native-{h.hexdigest()[:16]}.so")


def _build(so: str) -> bool:
    # Per-process tmp name: concurrent builders must not interleave into
    # one tmp file; os.replace publishes the finished artifact atomically.
    tmp = f"{so}.{os.getpid()}.tmp"
    base = ["g++", *_FLAGS, "-o", tmp, _SRC]
    # zlib backs the fused transform's block compression; a container
    # without the headers still gets every other kernel (the deflate/
    # inflate entry points then answer -2 and Python keeps its own
    # zlib path).
    for cmd in (base + ["-lz"], base + ["-DMTPU_NO_ZLIB"]):
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)
            return True
        except Exception:
            try:
                os.unlink(tmp)
            except OSError:
                pass
    return False


def load():
    """The ctypes library handle, or None if unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            so = _so_path()
            if not os.path.exists(so) and not _build(so):
                return None
            lib = ctypes.CDLL(so)
            _declare(lib)
        except (OSError, AttributeError):
            return None
        _lib = lib
        return _lib


def _declare(lib) -> None:
    """ctypes prototypes for every exported symbol — the ONE place the
    C ABI is spelled on the Python side (raises AttributeError when the
    loaded .so lacks a symbol)."""
    u8p = ctypes.POINTER(ctypes.c_uint8)
    for name, argt in (
            ("mtpu_hh256", [u8p, u8p, ctypes.c_size_t, u8p]),
            ("mtpu_hh256_many", [u8p, u8p, ctypes.c_size_t,
                                 ctypes.c_size_t, ctypes.c_size_t, u8p]),
            ("mtpu_gf_apply", [u8p, ctypes.c_size_t, ctypes.c_size_t,
                               u8p, ctypes.c_size_t, ctypes.c_size_t,
                               u8p, ctypes.c_size_t]),
            ("mtpu_put_frame", [u8p, u8p, u8p, ctypes.c_size_t,
                                ctypes.c_size_t, ctypes.c_size_t,
                                ctypes.c_size_t, u8p])):
        fn = getattr(lib, name)
        fn.argtypes = argt
        fn.restype = None
    lib.mtpu_xxh64.argtypes = [u8p, ctypes.c_size_t, ctypes.c_uint64]
    lib.mtpu_xxh64.restype = ctypes.c_uint64
    # Serve hot loop: HTTP head framer + aws-chunked frame scanner.
    lib.mtpu_http_head.argtypes = [u8p, ctypes.c_size_t,
                                   ctypes.POINTER(ctypes.c_int32),
                                   ctypes.c_size_t]
    lib.mtpu_http_head.restype = ctypes.c_int64
    lib.mtpu_chunk_head.argtypes = [u8p, ctypes.c_size_t, ctypes.c_size_t,
                                    ctypes.POINTER(ctypes.c_int64)]
    lib.mtpu_chunk_head.restype = ctypes.c_int64
    lib.mtpu_get_frame.argtypes = [u8p, ctypes.POINTER(u8p),
                                   ctypes.c_size_t, ctypes.c_size_t,
                                   ctypes.c_size_t, ctypes.c_size_t,
                                   ctypes.c_size_t, ctypes.c_size_t, u8p]
    lib.mtpu_get_frame.restype = ctypes.c_uint64
    # Metadata plane: batched xl.meta journal scan (storage/meta_scan).
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.mtpu_meta_scan.argtypes = [u8p, i64p, ctypes.c_int64,
                                   ctypes.c_int64, i64p]
    lib.mtpu_meta_scan.restype = ctypes.c_int64
    # Fused data plane: streaming digests, AES-256-GCM / DARE, block
    # deflate/inflate, and the single-pass transform+frame kernels.
    sz = ctypes.c_size_t
    i64 = ctypes.c_int64
    lib.mtpu_digest_init.argtypes = [i64, u8p]
    lib.mtpu_digest_init.restype = None
    lib.mtpu_digest_update.argtypes = [i64, u8p, u8p, sz]
    lib.mtpu_digest_update.restype = None
    lib.mtpu_digest_final.argtypes = [i64, u8p, u8p]
    lib.mtpu_digest_final.restype = None
    lib.mtpu_crc32.argtypes = [ctypes.c_uint32, u8p, sz]
    lib.mtpu_crc32.restype = ctypes.c_uint32
    lib.mtpu_gcm_seal.argtypes = [u8p, u8p, u8p, sz, u8p, sz, u8p]
    lib.mtpu_gcm_seal.restype = None
    lib.mtpu_gcm_open.argtypes = [u8p, u8p, u8p, sz, u8p, sz, u8p]
    lib.mtpu_gcm_open.restype = i64
    lib.mtpu_dare_seal.argtypes = [u8p, u8p, ctypes.c_uint64, u8p, sz, u8p]
    lib.mtpu_dare_seal.restype = i64
    lib.mtpu_dare_open.argtypes = [u8p, u8p, ctypes.c_uint64, u8p, sz, u8p]
    lib.mtpu_dare_open.restype = i64
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.mtpu_deflate_blocks.argtypes = [u8p, sz, sz, i64, u8p, sz, i64p]
    lib.mtpu_deflate_blocks.restype = i64
    lib.mtpu_inflate_blocks.argtypes = [u8p, sz, i64p, i64, i64, i64,
                                        u8p, sz]
    lib.mtpu_inflate_blocks.restype = i64
    lib.mtpu_transform_frame.argtypes = [
        u8p, sz, i64, u8p, u8p, u8p, u8p, sz, u8p, sz, i64p, i64, sz,
        u8p, u8p, sz, sz, sz, sz, u8p, sz, i64p]
    lib.mtpu_transform_frame.restype = i64
    lib.mtpu_untransform.argtypes = [u8p, sz, i64, u8p, u8p, i64, i64p,
                                     i64, i64, i64, u8p, sz, u8p, sz]
    lib.mtpu_untransform.restype = i64
    lib.mtpu_put_frame_md5.argtypes = [u8p, u8p, u8p, u8p, sz, sz, sz,
                                       sz, sz, u8p]
    lib.mtpu_put_frame_md5.restype = None


def feature(symbol: str, gated: bool = True):
    """The library handle when it carries `symbol`, else None — the
    ONE gate every fused-transform-plane call site shares. With
    `gated` (the default) the MTPU_TRANSFORM_FUSED=off kill-switch
    also answers None, so "off" reverts the whole plane (fused
    orchestration AND the dare/compress native bulk paths) to the
    layered pipeline; pass gated=False for primitives that must keep
    working regardless (the AES-GCM backend — without it a wheel-less
    container loses SSE entirely, which is availability, not an
    optimization the switch governs)."""
    if gated and os.environ.get("MTPU_TRANSFORM_FUSED", "") \
            .strip().lower() in ("off", "0", "false", "no"):
        return None
    try:
        lib = load()
    except Exception:  # noqa: BLE001 - loader failure = unavailable
        return None
    return lib if lib is not None and hasattr(lib, symbol) else None


def _u8(arr) -> "ctypes.POINTER(ctypes.c_uint8)":
    import numpy as np
    a = arr if isinstance(arr, (bytes, bytearray)) else np.ascontiguousarray(arr)
    if isinstance(a, (bytes, bytearray)):
        return (ctypes.c_uint8 * len(a)).from_buffer_copy(a)
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))

"""Device-side HighwayHash-256 and the fused encode+bitrot pipeline.

The reference's PutObject hot loop interleaves Reed-Solomon encode with
per-shard-block HighwayHash-256 framing (`hash || block`, reference:
cmd/erasure-encode.go:69 feeding streamingBitrotWriter.Write,
cmd/bitrot-streaming.go:44-75, AVX2/AVX512 lane kernels in
github.com/minio/highwayhash). This module puts BOTH on the TPU:

  * `hash_blocks_device` — keyed HighwayHash-256 of S equal-length
    blocks as one XLA computation. 64-bit lane math is emulated with
    uint32 pairs (the TPU VPU is 32-bit): adds via explicit carries,
    the 32x32->64 multiplies via 16-bit limb products, the zipper
    merges as byte extract/deposit masks. The per-packet recurrence is
    sequential by construction, so parallelism comes from hashing many
    independent shard blocks in lockstep — one vector lane per stream,
    the same trick as the host numpy path (utils/highwayhash.py) but on
    the VPU and without leaving HBM.
  * `make_encode_framer` — the fused PUT pipeline: stripe batch in,
    parity via the RS bitplane matmul (ops/rs_device.py) and the
    HighwayHash of every shard block, one host<->device round trip per
    batch. The on-disk `hash || block` frame is assembled by the shard
    writers from (digest, block) pieces at write time, like the
    reference's streaming bitrot writer — no interleaved frame buffer
    exists anywhere.

State layout: each of v0/v1/mul0/mul1 is (lo, hi) uint32 arrays of
shape [2 pairs, 2 lanes, S streams] — lane pairs (0,1) and (2,3) are
the zipper/finalize grouping, S rides the minor (vector) axis.

Byte-identical to utils/highwayhash.py and therefore to the reference's
golden digests (cmd/bitrot.go:225-230) — enforced by tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from minio_tpu.ops import device
from minio_tpu.utils import tracing
from minio_tpu.utils.highwayhash import MAGIC_KEY

_U32 = jnp.uint32


# ---------------------------------------------------------------------------
# 64-bit primitives on (lo, hi) uint32 pairs
# ---------------------------------------------------------------------------

def _add64(alo, ahi, blo, bhi):
    lo = alo + blo
    carry = (lo < alo).astype(_U32)
    return lo, ahi + bhi + carry


def _mul_32x32(a, b):
    """Full 64-bit product of two uint32 vectors, via 16-bit limbs."""
    al = a & 0xFFFF
    ah = a >> 16
    bl = b & 0xFFFF
    bh = b >> 16
    p0 = al * bl
    p1 = al * bh
    p2 = ah * bl
    p3 = ah * bh
    mid = (p0 >> 16) + (p1 & 0xFFFF) + (p2 & 0xFFFF)
    lo = (p0 & 0xFFFF) | (mid << 16)
    hi = p3 + (p1 >> 16) + (p2 >> 16) + (mid >> 16)
    return lo, hi


def _shl64(lo, hi, c: int):
    return lo << c, (hi << c) | (lo >> (32 - c))


def _byte(x, k: int):
    """Byte k (0..3) of a uint32 vector, as a uint32 in bits 0-7."""
    if k == 0:
        return x & 0xFF
    if k == 3:
        return x >> 24
    return (x >> (8 * k)) & 0xFF


def _zipper(elo, ehi, olo, ohi):
    """Zipper-merge of one lane pair (even, odd) -> (even', odd').

    Output byte maps (derived from the reference scalar masks;
    utils/highwayhash.py _zipper_merge_add):
      even' = [e3, o4, e2, e5, o6, e1, o7, e0]
      odd'  = [o3, e4, o2, o5, o1, e6, o0, e7]
    where eN/oN = byte N of the even/odd 64-bit lane (0 = LSB).
    """
    ze_lo = (_byte(elo, 3) | (_byte(ohi, 0) << 8)
             | (_byte(elo, 2) << 16) | (_byte(ehi, 1) << 24))
    ze_hi = (_byte(ohi, 2) | (_byte(elo, 1) << 8)
             | (_byte(ohi, 3) << 16) | (_byte(elo, 0) << 24))
    zo_lo = (_byte(olo, 3) | (_byte(ehi, 0) << 8)
             | (_byte(olo, 2) << 16) | (_byte(ohi, 1) << 24))
    zo_hi = (_byte(olo, 1) | (_byte(ehi, 2) << 8)
             | (_byte(olo, 0) << 16) | (_byte(ehi, 3) << 24))
    return ze_lo, ze_hi, zo_lo, zo_hi


# ---------------------------------------------------------------------------
# Core permutation
# ---------------------------------------------------------------------------
# State: tuple of 8 uint32 arrays [2, 2, S]:
#   (v0lo, v0hi, v1lo, v1hi, m0lo, m0hi, m1lo, m1hi)

def _update(st, plo, phi):
    v0lo, v0hi, v1lo, v1hi, m0lo, m0hi, m1lo, m1hi = st
    tlo, thi = _add64(m0lo, m0hi, plo, phi)
    v1lo, v1hi = _add64(v1lo, v1hi, tlo, thi)
    xlo, xhi = _mul_32x32(v1lo, v0hi)          # (v1 & M32) * (v0 >> 32)
    m0lo, m0hi = m0lo ^ xlo, m0hi ^ xhi
    v0lo, v0hi = _add64(v0lo, v0hi, m1lo, m1hi)
    ylo, yhi = _mul_32x32(v0lo, v1hi)          # (v0 & M32) * (v1 >> 32)
    m1lo, m1hi = m1lo ^ ylo, m1hi ^ yhi
    # v0 += zipper(v1), then v1 += zipper(updated v0) — per lane pair,
    # even/odd = index 0/1 on axis 1.
    ze_lo, ze_hi, zo_lo, zo_hi = _zipper(
        v1lo[:, 0], v1hi[:, 0], v1lo[:, 1], v1hi[:, 1])
    zlo = jnp.stack([ze_lo, zo_lo], axis=1)
    zhi = jnp.stack([ze_hi, zo_hi], axis=1)
    v0lo, v0hi = _add64(v0lo, v0hi, zlo, zhi)
    ze_lo, ze_hi, zo_lo, zo_hi = _zipper(
        v0lo[:, 0], v0hi[:, 0], v0lo[:, 1], v0hi[:, 1])
    zlo = jnp.stack([ze_lo, zo_lo], axis=1)
    zhi = jnp.stack([ze_hi, zo_hi], axis=1)
    v1lo, v1hi = _add64(v1lo, v1hi, zlo, zhi)
    return (v0lo, v0hi, v1lo, v1hi, m0lo, m0hi, m1lo, m1hi)


def _permute_and_update(st):
    v0lo, v0hi = st[0], st[1]
    # permuted lane i = rot32(v0 lane (i+2) mod 4): pair axis flips,
    # parity is preserved; rot32 = swap (lo, hi).
    plo = v0hi[::-1]
    phi = v0lo[::-1]
    return _update(st, plo, phi)


@functools.lru_cache(maxsize=16)
def _init_state_np(key: bytes) -> np.ndarray:
    """Initial state as one uint32 array [8, 2, 2] (statevec, pair, parity)."""
    init0 = np.array([0xDBE6D5D5FE4CCE2F, 0xA4093822299F31D0,
                      0x13198A2E03707344, 0x243F6A8885A308D3], dtype=np.uint64)
    init1 = np.array([0x3BD39E10CB0EF593, 0xC0ACF169B5F18A8C,
                      0xBE5466CF34E90C6C, 0x452821E638D01377], dtype=np.uint64)
    k = np.frombuffer(key, dtype="<u8").astype(np.uint64)
    rot = (k >> np.uint64(32)) | (k << np.uint64(32))
    v0, v1, m0, m1 = init0 ^ k, init1 ^ rot, init0, init1
    out = np.empty((8, 4), dtype=np.uint32)
    for i, v in enumerate((v0, v1, m0, m1)):
        out[2 * i] = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        out[2 * i + 1] = (v >> np.uint64(32)).astype(np.uint32)
    # [statevec, lane] -> [statevec, pair, parity]
    return out.reshape(8, 2, 2)


def _words_from_bytes(blocks):
    """uint8 [S, L] -> little-endian uint32 words [S, L//4]."""
    s, l = blocks.shape
    r = blocks.reshape(s, l // 4, 4).astype(_U32)
    return r[..., 0] | (r[..., 1] << 8) | (r[..., 2] << 16) | (r[..., 3] << 24)


def _hash_impl(blocks, init, length: int):
    """blocks uint8 [S, L] (L static), init [8,2,2] -> digests uint8 [S, 32]."""
    s = blocks.shape[0]
    n_packets = length // 32
    mod = length % 32
    st = tuple(jnp.broadcast_to(init[i][:, :, None], (2, 2, s)).astype(_U32)
               for i in range(8))

    if n_packets:
        words = _words_from_bytes(blocks[:, :n_packets * 32])
        # [S, P*8] -> [P, 8, S]: packet p's 8 words on the leading axis so
        # the loop body is one dynamic slice; S stays minor (vectorized).
        words = words.reshape(s, n_packets, 8).transpose(1, 2, 0)

        def body(p, st):
            pk = jax.lax.dynamic_slice(words, (p, 0, 0), (1, 8, s))
            pk = pk.reshape(4, 2, s)          # [lane, lo/hi, S]
            plo = pk[:, 0].reshape(2, 2, s)   # [pair, parity, S]
            phi = pk[:, 1].reshape(2, 2, s)
            return _update(st, plo, phi)

        st = jax.lax.fori_loop(0, n_packets, body, st)

    if mod:
        st = _remainder(st, blocks[:, n_packets * 32:], mod)

    # Rolled loop: unrolling the 10 permute rounds balloons the traced
    # graph ~4x and makes CPU (LLVM) compiles take minutes.
    st = jax.lax.fori_loop(0, 10, lambda _, s: _permute_and_update(s), st)
    return _finalize(st)


def _remainder(st, tail, mod: int):
    """Final partial packet; `mod` = len mod 32 is static (compile-time)."""
    s = tail.shape[0]
    mod4 = mod & 3
    rem = mod & ~3
    packet = jnp.zeros((s, 32), dtype=jnp.uint8)
    if rem:
        packet = packet.at[:, :rem].set(tail[:, :rem])
    # v0 += (mod << 32) + mod
    v0lo, v0hi = _add64(st[0], st[1], _U32(mod), _U32(mod))
    # Rotate each 32-bit half of every v1 lane left by `mod` bits.
    v1lo, v1hi = st[2], st[3]
    if mod:
        v1lo = (v1lo << mod) | (v1lo >> (32 - mod))
        v1hi = (v1hi << mod) | (v1hi >> (32 - mod))
    st = (v0lo, v0hi, v1lo, v1hi) + st[4:]
    if mod & 16:
        for i in range(4):
            packet = packet.at[:, 28 + i].set(tail[:, rem + i + mod4 - 4])
    elif mod4:
        packet = packet.at[:, 16].set(tail[:, rem])
        packet = packet.at[:, 17].set(tail[:, rem + (mod4 >> 1)])
        packet = packet.at[:, 18].set(tail[:, rem + mod4 - 1])
    w = _words_from_bytes(packet)              # [S, 8]
    w = w.reshape(s, 4, 2).transpose(1, 2, 0)  # [lane, lo/hi, S]
    plo = w[:, 0].reshape(2, 2, s)
    phi = w[:, 1].reshape(2, 2, s)
    return _update(st, plo, phi)


def _finalize(st):
    """Modular reduction -> digests uint8 [S, 32]."""
    v0lo, v0hi, v1lo, v1hi, m0lo, m0hi, m1lo, m1hi = st
    # Per pair p: a3 = v1odd+mul1odd, a2 = v1even+mul1even,
    #             a1 = v0odd+mul0odd, a0 = v0even+mul0even.
    a3lo, a3hi = _add64(v1lo[:, 1], v1hi[:, 1], m1lo[:, 1], m1hi[:, 1])
    a2lo, a2hi = _add64(v1lo[:, 0], v1hi[:, 0], m1lo[:, 0], m1hi[:, 0])
    a1lo, a1hi = _add64(v0lo[:, 1], v0hi[:, 1], m0lo[:, 1], m0hi[:, 1])
    a0lo, a0hi = _add64(v0lo[:, 0], v0hi[:, 0], m0lo[:, 0], m0hi[:, 0])
    a3hi = a3hi & 0x3FFFFFFF                   # a3 &= 2^62 - 1
    s1lo, s1hi = _shl64(a3lo, a3hi, 1)
    s1lo = s1lo | (a2hi >> 31)                 # | (a2 >> 63)
    s2lo, s2hi = _shl64(a3lo, a3hi, 2)
    s2lo = s2lo | (a2hi >> 30)                 # | (a2 >> 62)
    odd_lo = a1lo ^ s1lo ^ s2lo
    odd_hi = a1hi ^ s1hi ^ s2hi
    t1lo, t1hi = _shl64(a2lo, a2hi, 1)
    t2lo, t2hi = _shl64(a2lo, a2hi, 2)
    even_lo = a0lo ^ t1lo ^ t2lo
    even_hi = a0hi ^ t1hi ^ t2hi
    # Assemble [S, 8] words in lane order (l0lo, l0hi, l1lo, l1hi, ...),
    # pairs stacked: lanes (0,1) from pair 0, (2,3) from pair 1.
    words = jnp.stack([even_lo[0], even_hi[0], odd_lo[0], odd_hi[0],
                       even_lo[1], even_hi[1], odd_lo[1], odd_hi[1]],
                      axis=1)                  # [S, 8]
    b = jnp.stack([(words & 0xFF), (words >> 8) & 0xFF,
                   (words >> 16) & 0xFF, (words >> 24) & 0xFF],
                  axis=2)                      # [S, 8, 4]
    return b.reshape(words.shape[0], 32).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Pallas kernel: the VPU-saturating HighwayHash path
# ---------------------------------------------------------------------------
# The jnp path above lays state out as [2, 2, S]: only 4 of 8 sublanes
# carry data and every elementwise op covers 4 HH lanes of S streams —
# XLA's fusions top out ~14 GiB/s on v5e. The kernel below instead makes
# the HH lane index an UNROLLED leading dim and packs 1024 streams per
# grid cell as full (8 sublane, 128 lane) vector tiles, so every VPU op
# is 100% dense. The packet recurrence runs inside the kernel (state in
# VMEM scratch, carried across the packet-chunk grid dim), so there is
# no per-packet dispatch overhead and data streams HBM -> VMEM once.
#
# State representation: each of v0/v1/mul0/mul1 is an (lo, hi) pair of
# uint32 [4, 8, 128] arrays — axis 0 is the HH 64-bit lane, (8, 128) is
# 1024 streams (stream = su*128 + ln).

_STREAM_TILE = 1024   # streams per grid cell: one (8, 128) tile set
_PCHUNK_MAX = 64      # packets per grid step (measured best on v5e:
                      # 64 beats 128 by ~3-10% across stream shapes)


def _k_add64(a, b):
    """(lo, hi) + (lo, hi) with explicit carry; any matching shapes."""
    lo = a[0] + b[0]
    carry = (lo < a[0]).astype(_U32)
    return lo, a[1] + b[1] + carry


def _k_mul64(a, b):
    """Full 64-bit product of uint32 arrays a*b via 16-bit limbs."""
    al, ah = a & 0xFFFF, a >> 16
    bl, bh = b & 0xFFFF, b >> 16
    p0 = al * bl
    p1 = al * bh
    p2 = ah * bl
    mid = (p0 >> 16) + (p1 & 0xFFFF) + (p2 & 0xFFFF)
    lo = (p0 & 0xFFFF) | (mid << 16)
    hi = ah * bh + (p1 >> 16) + (p2 >> 16) + (mid >> 16)
    return lo, hi


def _k_zipper(vlo, vhi):
    """Zipper-merge of [4, ...] lane arrays, both pairs at once.

    Same byte maps as _zipper (even' = [e3,o4,e2,e5,o6,e1,o7,e0],
    odd' = [o3,e4,o2,o5,o1,e6,o0,e7]) but in fused mask form: each
    output word is 4 mask/shift terms instead of per-byte extracts.
    """
    # Static leading-dim selection (strided slices lower to gathers,
    # which Mosaic does not support — stack register views instead).
    elo = jnp.stack([vlo[0], vlo[2]])   # lanes 0, 2  [2, ...]
    ehi = jnp.stack([vhi[0], vhi[2]])
    olo = jnp.stack([vlo[1], vlo[3]])   # lanes 1, 3
    ohi = jnp.stack([vhi[1], vhi[3]])
    ze_lo = ((elo >> 24) | ((ohi & 0xFF) << 8)
             | (elo & 0x00FF0000) | ((ehi & 0x0000FF00) << 16))
    ze_hi = (((ohi >> 16) & 0xFF) | (elo & 0xFF00)
             | ((ohi >> 8) & 0x00FF0000) | (elo << 24))
    zo_lo = ((olo >> 24) | ((ehi & 0xFF) << 8)
             | (olo & 0x00FF0000) | ((ohi & 0x0000FF00) << 16))
    zo_hi = (((olo >> 8) & 0xFF) | ((ehi >> 8) & 0xFF00)
             | ((olo & 0xFF) << 16) | (ehi & _U32(0xFF000000)))
    zlo = jnp.stack([ze_lo[0], zo_lo[0], ze_lo[1], zo_lo[1]])
    zhi = jnp.stack([ze_hi[0], zo_hi[0], ze_hi[1], zo_hi[1]])
    return zlo, zhi


def _k_update(st, plo, phi):
    """One packet: st = 8-tuple of [4, 8, 128] u32, p{lo,hi} [4, 8, 128]."""
    v0lo, v0hi, v1lo, v1hi, m0lo, m0hi, m1lo, m1hi = st
    tlo, thi = _k_add64((m0lo, m0hi), (plo, phi))
    v1lo, v1hi = _k_add64((v1lo, v1hi), (tlo, thi))
    xlo, xhi = _k_mul64(v1lo, v0hi)            # (v1 & M32) * (v0 >> 32)
    m0lo, m0hi = m0lo ^ xlo, m0hi ^ xhi
    v0lo, v0hi = _k_add64((v0lo, v0hi), (m1lo, m1hi))
    ylo, yhi = _k_mul64(v0lo, v1hi)            # (v0 & M32) * (v1 >> 32)
    m1lo, m1hi = m1lo ^ ylo, m1hi ^ yhi
    zlo, zhi = _k_zipper(v1lo, v1hi)
    v0lo, v0hi = _k_add64((v0lo, v0hi), (zlo, zhi))
    zlo, zhi = _k_zipper(v0lo, v0hi)
    v1lo, v1hi = _k_add64((v1lo, v1hi), (zlo, zhi))
    return (v0lo, v0hi, v1lo, v1hi, m0lo, m0hi, m1lo, m1hi)


def _k_permute_update(st):
    # permuted lane i = rot32(v0 lane (i+2) mod 4); rot32 = swap halves.
    plo = jnp.stack([st[1][2], st[1][3], st[1][0], st[1][1]])
    phi = jnp.stack([st[0][2], st[0][3], st[0][0], st[0][1]])
    return _k_update(st, plo, phi)


def _k_shl64(lo, hi, c: int):
    return lo << c, (hi << c) | (lo >> (32 - c))


def _hh_kernel(init_ref, w_ref, out_ref, st_ref, *, unroll: bool = True):
    """Grid cell (stream-tile is, packet-chunk ip); ip is innermost.

    init_ref: SMEM u32 [8, 4]  (statevec sv = 2*var + lo/hi, HH lane)
    w_ref:    VMEM u32 [1, 8su, 1, PCHUNK, 4, 2, 128]  (packet words,
              su-major so the feeding transpose kernel writes each
              sublane group contiguously)
    out_ref:  VMEM u32 [1, 8, 8, 128]  (digest words per stream)
    st_ref:   VMEM u32 [8, 4, 8, 128]  scratch, carried across ip
    """
    ip = pl.program_id(1)
    n_ip = pl.num_programs(1)
    pchunk = w_ref.shape[3]
    su = 8

    @pl.when(ip == 0)
    def _init():
        for sv in range(8):
            st_ref[sv] = jnp.stack(
                [jnp.full((su, 128), init_ref[sv, l], dtype=_U32)
                 for l in range(4)])

    st = tuple(st_ref[sv] for sv in range(8))

    def body(p, st):
        w = w_ref[0, :, 0, p]                 # [8su, 4, 2, 128]
        plo = jnp.stack([w[:, l, 0] for l in range(4)])   # [4, 8, 128]
        phi = jnp.stack([w[:, l, 1] for l in range(4)])
        return _k_update(st, plo, phi)

    # Full unroll (the only unroll factor Mosaic's for-loop lowering
    # supports besides 1): exposes the whole chunk to the scheduler so
    # w_ref loads pipeline ahead of the serial state chain. Interpret
    # mode (CPU tests) keeps the rolled loop — the unrolled trace is
    # minutes-slow under the Python interpreter.
    st = jax.lax.fori_loop(0, pchunk, body, st,
                           unroll=pchunk if unroll else 1)

    for sv in range(8):
        st_ref[sv] = st[sv]

    @pl.when(ip == n_ip - 1)
    def _finalize():
        # Digest words per stream, in byte order:
        # pair 0: even lo/hi, odd lo/hi; then pair 1.
        _hh_finalize_tail(st, out_ref)


def _hh_kernel_nt(init_ref, w_ref, out_ref, st_ref, wt_ref,
                  *, unroll: bool = True):
    """Transpose-fused variant of _hh_kernel: reads the NATURAL stream
    layout and transposes in VMEM, so packet words never round-trip
    through HBM twice (the standalone _t7_kernel pass is pure HBM
    bandwidth — ~0.4 ms per 128 MiB on v5e — and this kernel replaces
    it for free).

    init_ref: SMEM u32 [8, 4]
    w_ref:    VMEM u32 [1024, CT] or [BSUB, X, CT] with BSUB*X == 1024
              (CT = 8 * pchunk words; stream-major natural layout,
              stream = su*128 + ln within the tile — leading dims
              collapse for free, which is the whole point: a pallas
              operand fed through an XLA reshape is MATERIALISED (a full
              HBM copy), so 3-D [B, shard, W] arrays hash directly)
    out_ref:  VMEM u32 [1, 8, 8, 128]
    st_ref:   VMEM u32 [8, 4, 8, 128] scratch, carried across ip
    wt_ref:   VMEM u32 [8, PCHUNK, 4, 2, 128] scratch (transposed words)
    """
    ip = pl.program_id(1)
    n_ip = pl.num_programs(1)
    pchunk = wt_ref.shape[1]
    su = 8

    @pl.when(ip == 0)
    def _init():
        for sv in range(8):
            st_ref[sv] = jnp.stack(
                [jnp.full((su, 128), init_ref[sv, l], dtype=_U32)
                 for l in range(4)])

    w2 = w_ref[:].reshape(1024, w_ref.shape[-1])
    # In-VMEM transpose, same sub-tile decomposition as _t7_kernel.
    for g in range(su):
        t = w2[g * 128:(g + 1) * 128, :].T             # [CT, 128]
        wt_ref[g] = t.reshape(pchunk, 4, 2, 128)

    st = tuple(st_ref[sv] for sv in range(8))

    def body(p, st):
        w = wt_ref[:, p]                               # [8su, 4, 2, 128]
        plo = jnp.stack([w[:, l, 0] for l in range(4)])
        phi = jnp.stack([w[:, l, 1] for l in range(4)])
        return _k_update(st, plo, phi)

    st = jax.lax.fori_loop(0, pchunk, body, st,
                           unroll=pchunk if unroll else 1)

    for sv in range(8):
        st_ref[sv] = st[sv]

    @pl.when(ip == n_ip - 1)
    def _finalize():
        _hh_finalize_tail(st, out_ref)


def _hh_finalize_tail(st, out_ref):
    """Shared 10-round permute + modular reduction tail (see _hh_kernel)."""
    s = st
    for _ in range(10):
        s = _k_permute_update(s)
    v0lo, v0hi, v1lo, v1hi, m0lo, m0hi, m1lo, m1hi = s
    odd = lambda x: jnp.stack([x[1], x[3]])    # noqa: E731
    even = lambda x: jnp.stack([x[0], x[2]])   # noqa: E731
    a3 = _k_add64((odd(v1lo), odd(v1hi)), (odd(m1lo), odd(m1hi)))
    a2 = _k_add64((even(v1lo), even(v1hi)), (even(m1lo), even(m1hi)))
    a1 = _k_add64((odd(v0lo), odd(v0hi)), (odd(m0lo), odd(m0hi)))
    a0 = _k_add64((even(v0lo), even(v0hi)), (even(m0lo), even(m0hi)))
    a3lo, a3hi = a3[0], a3[1] & 0x3FFFFFFF           # a3 &= 2^62 - 1
    s1lo, s1hi = _k_shl64(a3lo, a3hi, 1)
    s1lo = s1lo | (a2[1] >> 31)
    s2lo, s2hi = _k_shl64(a3lo, a3hi, 2)
    s2lo = s2lo | (a2[1] >> 30)
    odd_lo, odd_hi = a1[0] ^ s1lo ^ s2lo, a1[1] ^ s1hi ^ s2hi
    t1lo, t1hi = _k_shl64(a2[0], a2[1], 1)
    t2lo, t2hi = _k_shl64(a2[0], a2[1], 2)
    even_lo, even_hi = a0[0] ^ t1lo ^ t2lo, a0[1] ^ t1hi ^ t2hi
    out_ref[0] = jnp.stack([even_lo[0], even_hi[0], odd_lo[0], odd_hi[0],
                            even_lo[1], even_hi[1], odd_lo[1], odd_hi[1]])


def _hash_words_pallas(words, init, pchunk: int,
                       interpret: bool = False):
    """Core u32 path: words u32 [S, W] or [B, X, W] (S = B*X streams;
    lane w = bytes 4w..4w+3 LE of the stream, W % (8*pchunk) == 0),
    init u32 [8, 4] -> digest words u32 [S, 8].

    A u32 shard array from make_encoder32 IS this word layout already —
    no byte bitcast (a ~35 GiB/s relayout on v5e) anywhere on the path.
    3-D inputs hash as-is: reshaping a pallas operand in XLA would
    MATERIALISE the reshape (a full HBM copy — measured 2x slowdown),
    so the block spec carves 1024-stream tiles out of the leading dims
    instead and the kernel collapses them for free.
    """
    n_words = words.shape[-1]
    x3 = words.shape[1] if words.ndim == 3 else None
    if words.ndim == 3 and (1024 % x3 != 0 or pchunk < 1):
        words = words.reshape(-1, n_words)       # rare shapes: pay the copy
        x3 = None
    s = int(np.prod(words.shape[:-1]))
    stile = 1024
    spad = -(-s // stile) * stile
    st_tiles = spad // stile
    pc = n_words // 8 // pchunk
    if (8 * pchunk) % 128 == 0 and n_words % (8 * pchunk) == 0:
        # Fast path: the kernel reads the NATURAL stream-major layout
        # and transposes in VMEM (_hh_kernel_nt) — no standalone
        # transpose pass over HBM. Stream padding comes free from OOB
        # edge-block reads (pad streams hash garbage; digests sliced).
        ct = 8 * pchunk
        if x3 is not None:
            bsub = 1024 // x3
            in_spec = pl.BlockSpec((bsub, x3, ct), lambda i, p: (i, 0, p),
                                   memory_space=pltpu.VMEM)
        else:
            in_spec = pl.BlockSpec((1024, ct), lambda i, p: (i, p),
                                   memory_space=pltpu.VMEM)
        out = pl.pallas_call(
            functools.partial(_hh_kernel_nt, unroll=not interpret),
            grid=(st_tiles, pc),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), in_spec],
            out_specs=pl.BlockSpec((1, 8, 8, 128), lambda i, p: (i, 0, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((st_tiles, 8, 8, 128), jnp.uint32),
            scratch_shapes=[pltpu.VMEM((8, 4, 8, 128), jnp.uint32),
                            pltpu.VMEM((8, pchunk, 4, 2, 128), jnp.uint32)],
            interpret=interpret,
        )(init, words)
    else:
        words = words.reshape(s, n_words)
        wt = words.T
        if spad != s:
            wt = jnp.pad(wt, ((0, 0), (0, spad - s)))
        wt = wt.reshape(pc, pchunk, 4, 2, st_tiles, 8, 128) \
            .transpose(4, 5, 0, 1, 2, 3, 6)
        out = pl.pallas_call(
            functools.partial(_hh_kernel, unroll=not interpret),
            grid=(st_tiles, pc),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 8, 1, pchunk, 4, 2, 128),
                             lambda i, p: (i, 0, p, 0, 0, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, 8, 8, 128), lambda i, p: (i, 0, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((st_tiles, 8, 8, 128), jnp.uint32),
            scratch_shapes=[pltpu.VMEM((8, 4, 8, 128), jnp.uint32)],
            interpret=interpret,
        )(init, wt)
    # [ST, word, su, ln] -> [S, 8] digest words.
    out = out.transpose(0, 2, 3, 1).reshape(spad, 8)
    return out[:s] if spad != s else out


@functools.partial(jax.jit, static_argnames=("pchunk", "interpret"))
def _hash_pallas(blocks, init, pchunk: int, interpret: bool = False):
    """Byte-API wrapper: blocks uint8 [S, L] -> digests uint8 [S, 32].
    The u8 -> u32 bitcast here is itself a device relayout; hot callers
    (the fused framer) use _hash_words_pallas on u32 arrays directly."""
    s, l = blocks.shape
    w = jax.lax.bitcast_convert_type(
        blocks.reshape(s, l // 4, 4), jnp.uint32)         # [S, W]
    out = _hash_words_pallas(w, init, pchunk, interpret)
    return jax.lax.bitcast_convert_type(out, jnp.uint8).reshape(s, 32)


def _init_smem_np(key: bytes) -> np.ndarray:
    """Initial state as u32 [8, 4]: rows 2*var + (0 lo, 1 hi), cols lane."""
    return _init_state_np(key).reshape(8, 4)


def _pick_pchunk(n_packets: int) -> int:
    """Largest divisor of n_packets <= _PCHUNK_MAX (1 if prime-ish)."""
    for c in range(min(_PCHUNK_MAX, n_packets), 0, -1):
        if n_packets % c == 0:
            return c
    return 1


def _pallas_eligible(s: int, l: int) -> bool:
    """The kernel needs whole packets and enough streams to fill tiles
    without the zero-padding overhead dominating."""
    return l > 0 and l % 32 == 0 and s >= _STREAM_TILE // 2 \
        and _pick_pchunk(l // 32) >= 8


def hash_blocks_pallas(blocks, init, interpret: bool = False) -> jax.Array:
    """Pallas HH-256 of S blocks: uint8 [S, L] (device or host) ->
    uint8 [S, 32] device array. Requires L % 32 == 0; stream padding is
    handled internally."""
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    s, l = blocks.shape
    return _hash_pallas(blocks, init, pchunk=_pick_pchunk(l // 32),
                        interpret=interpret)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("length",))
def _hash_jit(blocks, init, length: int):
    return _hash_impl(blocks, init, length)


def hash_blocks_device(key: bytes, blocks, mode: str = "auto") -> np.ndarray:
    """Keyed HighwayHash-256 of S equal-length blocks on device.

    blocks: uint8 [S, L] (numpy or device array) -> uint8 [S, 32] numpy.
    mode: "auto" (Pallas kernel on TPU when eligible, else the portable
    jnp path), "pallas" (forced; needs a TPU), "interpret" (the Pallas
    kernel through the interpreter — tests, never serving), or "xla".
    """
    if len(key) != 32:
        raise ValueError("HighwayHash-256 requires a 32-byte key")
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    s, l = blocks.shape
    on_tpu = device.on_tpu()
    if mode in ("pallas", "interpret") and l % 32 != 0:
        raise ValueError(
            f"pallas HH kernel requires whole 32-byte packets (L % 32 == 0), "
            f"got L={l}; use mode='auto' or 'xla' for ragged lengths")
    if mode == "pallas" and not on_tpu:
        raise RuntimeError("mode='pallas' needs a TPU; the interpreter is "
                           "mode='interpret'")
    if mode in ("pallas", "interpret") or (mode == "auto" and on_tpu
                                           and _pallas_eligible(s, l)):
        init = jnp.asarray(_init_smem_np(key))
        device.note_kernel("digest", "interpret" if mode == "interpret"
                           else "pallas")
        return np.asarray(hash_blocks_pallas(blocks, init,
                                             interpret=mode == "interpret"))
    init = jnp.asarray(_init_state_np(key))
    device.note_kernel("digest", "xla")
    return np.asarray(_hash_jit(blocks, init, l))


# ---------------------------------------------------------------------------
# Device digests of bitrot-framed shard windows (the GET/heal read path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("pchunk", "interpret"))
def _framed_digests_jit(blobs, init, pchunk: int, interpret: bool = False):
    """blobs: tuple of u32 [nb_i, fw] framed-frame arrays (fw = 8 digest
    words + block words). One concat + slice on device (HBM-speed), then
    the Pallas hash over all blocks as one stream set."""
    stacked = jnp.concatenate(blobs, axis=0) if len(blobs) > 1 else blobs[0]
    words = stacked[:, 8:]
    return _hash_words_pallas(words, init, pchunk, interpret=interpret)


# Device rows per hash dispatch: exactly one 1024-stream tile. Bounds
# HBM use per call (~3x 128 MiB at 128 KiB blocks) so multi-GiB heal
# reads can never OOM the chip, and keeps the jit cache to a handful of
# keys per frame width: (1024, fw) for full chunks plus (pad, fw) with
# pad a multiple of _FRAMED_PAD for the combined remainder.
_FRAMED_CHUNK = 1024
_FRAMED_PAD = 256


def framed_digests_device(blobs: list[np.ndarray],
                          interpret: bool = False) -> np.ndarray:
    """HighwayHash-256 digests of every framed block across shard blobs.

    blobs: u32 arrays [nb_i, fw], each row one on-disk frame
    (`digest || block`, reference cmd/bitrot-streaming.go:44-75) — pass
    zero-copy views of the raw shard-file bytes. Returns uint8
    [sum(nb_i), 32] recomputed digests of the block payloads, hashed on
    device in batched kernel passes (the read-side counterpart of the
    fused PUT pipeline: GETs dominate object-store traffic, so per-block
    host hashing is the wrong place to spend CPU).

    Dispatch shape discipline: whole _FRAMED_CHUNK-row slices of each
    blob go to the device as zero-copy views; the sub-chunk remainders
    of all blobs are packed into ONE host-padded array (rounded up to a
    _FRAMED_PAD multiple — pad rows hash garbage, sliced off). Every
    compiled shape is therefore from a small fixed set, not one per
    distinct shard-file size."""
    fw = blobs[0].shape[1]
    w = fw - 8
    pchunk = _pick_pchunk(w // 8)
    init = jnp.asarray(_init_smem_np(MAGIC_KEY))
    device.note_kernel("digest", "interpret" if interpret else "pallas")
    parts: list[tuple[int, int, np.ndarray]] = []  # (out_off, rows, view)
    rem: list[tuple[int, np.ndarray]] = []         # (out_off, view)
    off = 0
    for b in blobs:
        nb = b.shape[0]
        whole = (nb // _FRAMED_CHUNK) * _FRAMED_CHUNK
        for lo in range(0, whole, _FRAMED_CHUNK):
            parts.append((off + lo, _FRAMED_CHUNK,
                          b[lo:lo + _FRAMED_CHUNK]))
        if whole < nb:
            rem.append((off + whole, b[whole:]))
        off += nb
    out = np.empty((off, 32), dtype=np.uint8)
    for out_off, rows, view in parts:
        d = _framed_digests_jit((jnp.asarray(view),), init, pchunk,
                                interpret=interpret)
        out[out_off:out_off + rows] = \
            np.ascontiguousarray(np.asarray(d)).view(np.uint8)
    if rem:
        total = sum(v.shape[0] for _, v in rem)
        pad = -(-total // _FRAMED_PAD) * _FRAMED_PAD
        packed = np.zeros((pad, fw), dtype=np.uint32)
        pos = 0
        for _, v in rem:
            packed[pos:pos + v.shape[0]] = v
            pos += v.shape[0]
        d = np.ascontiguousarray(np.asarray(_framed_digests_jit(
            (jnp.asarray(packed),), init, pchunk,
            interpret=interpret))).view(np.uint8)
        pos = 0
        for out_off, v in rem:
            out[out_off:out_off + v.shape[0]] = d[pos:pos + v.shape[0]]
            pos += v.shape[0]
    return out                                    # [S, 32]


def framed_digests_eligible(n_blocks: int, shard_size: int) -> bool:
    """Worth dispatching to the device: enough streams to fill vector
    tiles and a whole-packet block length."""
    return (device.on_tpu() and shard_size % 1024 == 0
            and n_blocks >= 256 and _pick_pchunk(shard_size // 4 // 8) >= 8)


# ---------------------------------------------------------------------------
# Fused encode + bitrot digests
# ---------------------------------------------------------------------------

def _lane_round_trip(upload, step) -> tuple:
    """One dispatch of the framer, de-framer or GF matrix, cut where the
    host waits, into the lane's three stages (tracing.stage): `lane.upload`
    (the host's part of moving the arrays onto the device: JAX returns
    once the transfer is under way), `lane.kernel` (the jitted step
    until its outputs are ready, which also waits out what is left of
    the transfer) and `lane.readback` (outputs back as contiguous
    numpy, and the batch's device buffers let go). The one
    `block_until_ready` stands where `np.asarray` would block anyway;
    the device trace tells transfer from kernel."""
    with tracing.stage("lane.upload", type_="kernel"):
        args = upload()
    with tracing.stage("lane.kernel", type_="kernel"):
        outs = jax.block_until_ready(step(*args))
    with tracing.stage("lane.readback", type_="kernel"):
        # ascontiguousarray: device arrays can come back with a
        # non-contiguous minor axis for some batch shapes, and .view
        # of a wider dtype requires contiguity.
        rows = tuple(np.ascontiguousarray(np.asarray(o)) for o in outs)
        # Released here, not by the return: freeing a batch-sized
        # device buffer can take as long as reading the outputs back,
        # and would otherwise be lane time no stage holds.
        del args, outs
        return rows


def _rows32(data, parity32, dig_d32, dig_p32) -> list[list[tuple]]:
    """The u32 framer's outputs as per-drive lists of (digest, block)
    pieces; data blocks are views of `data` (zero copy). The lane's
    fourth stage: B x n numpy slices under the GIL."""
    with tracing.stage("lane.rows", type_="kernel"):
        parity = parity32.view(np.uint8)             # [B, m, L]
        dig_d = dig_d32.view(np.uint8)               # [B, k, 32]
        dig_p = dig_p32.view(np.uint8)               # [B, m, 32]
        b, k = data.shape[:2]
        return ([[(dig_d[bi, i], data[bi, i]) for bi in range(b)]
                 for i in range(k)]
                + [[(dig_p[bi, j], parity[bi, j]) for bi in range(b)]
                   for j in range(parity.shape[1])])


def _rows8(data, parity, digests) -> list[list[tuple]]:
    """The byte framer's outputs (digests [B, n, 32]) as per-drive
    lists of (digest, block) pieces."""
    with tracing.stage("lane.rows", type_="kernel"):
        b, k = data.shape[:2]
        shards = [data[:, i] for i in range(k)] \
            + [parity[:, j] for j in range(parity.shape[1])]
        return [[(digests[bi, i], shards[i][bi]) for bi in range(b)]
                for i in range(len(shards))]


def make_mesh_framer(matrix: np.ndarray, mode: str = "auto", devices=None):
    """Fused PUT pipeline on device, one call per stripe batch.

    Returns fn(data uint8 [B, k, L]) -> per-drive lists of per-block
    piece tuples: Reed-Solomon parity (ops/rs_device) plus the
    HighwayHash-256 bitrot digest of each of the B*n shard blocks. Like
    the reference's streaming bitrot writer (cmd/bitrot-streaming.go:
    44-75 writes the hash, then the block, per erasure block), the
    `hash || block` frame is assembled AT WRITE TIME from the pieces —
    the device never materialises interleaved frames (that copy is pure
    HBM bandwidth, ~0.75 ms per 128 MiB on v5e), data blocks are served
    as zero-copy views of the caller's buffer, and only parity +
    digests ride the device->host link. Digest algorithm is the bitrot
    default HighwayHash-256S under the magic key (cmd/bitrot.go:37,
    105-110).

    The batch dimension ("stripes from MANY concurrent PutObject
    requests", coalesced by ops/batcher.StripeBatcher) runs where
    `device.batch_placement(devices)` puts it: on one device as one
    jitted step, on several sharded over the chips, each framing its
    local stripe slice (stripes are independent, the same property the
    reference exploits with per-goroutine encode,
    cmd/erasure-encode.go:27). One compile per (padding bucket, EC
    config): callers pad the batch dim to the fixed buckets, never to
    raw concurrency levels. `run.mesh_devices` says how many chips.
    """
    from minio_tpu.ops.rs_device import make_encoder, make_encoder32
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    m, k = matrix.shape
    n = k + m
    encode = make_encoder(matrix, mode=mode)
    encode32 = make_encoder32(matrix, mode=mode)
    on_tpu = device.on_tpu()

    def fused32(data32, init, pchunk: int):
        """u32 hot path: data [B, k, L4] u32 -> (parity [B, m, L4],
        dig_d [B, k, 8], dig_p [B, m, 8]) u32.

        Everything stays in u32 lanes (lane t = shard bytes 4t..4t+3 LE):
        the encoder's output IS the word layout the hash wants, the hash
        kernel transposes in VMEM (no standalone transpose pass), and
        data and parity hash as two separate stream sets (no shards
        concatenate). No u8<->u32 relayouts and no XLA copies anywhere.
        """
        b = data32.shape[0]
        parity = encode32(data32)                  # [B, m, L4]
        dig_d = _hash_words_pallas(data32, init,
                                   pchunk=pchunk).reshape(b, k, 8)
        dig_p = _hash_words_pallas(parity, init,
                                   pchunk=pchunk).reshape(b, m, 8)
        return parity, dig_d, dig_p

    def fused8(data, init):
        """Portable byte path (off-TPU / ineligible shapes)."""
        b, _, l = data.shape
        parity = encode(data)                      # [B, m, L]
        shards = jnp.concatenate([data, parity], axis=1)  # [B, n, L]
        digests = _hash_impl(shards.reshape(b * n, l), init, l)
        return parity, digests.reshape(b, n, 32)

    jit_body, upload, ndev = device.batch_placement(devices)
    step32 = jit_body(fused32, static_argnames=("pchunk",))
    step8 = jit_body(fused8)

    def run(data) -> list[list[tuple]]:
        """data uint8 [B, k, L] numpy -> n per-drive lists; entry i is
        [(digest32, block_bytes), ...] per erasure block, concatenation
        of which is drive i's framed shard-file bytes. Data-block pieces
        are views of `data` (zero copy)."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        b, _, l = data.shape
        device.note_mesh_blocks(b, ndev)
        pchunk = _pick_pchunk(l // 32) if l and l % 32 == 0 else 0
        if on_tpu and l % 1024 == 0 and pchunk >= 8:
            device.note_kernel("frame", "pallas")
            return _rows32(data, *_lane_round_trip(
                lambda: (upload(data.view(np.uint32)),
                         jnp.asarray(_init_smem_np(MAGIC_KEY))),
                lambda data32, init: step32(data32, init, pchunk=pchunk)))
        device.note_kernel("frame", "xla")
        return _rows8(data, *_lane_round_trip(
            lambda: (upload(data), jnp.asarray(_init_state_np(MAGIC_KEY))),
            step8))

    def device_step(data32):
        """Device-resident fused pipeline: u32 [B, k, L4] -> (parity,
        data digests, parity digests) device arrays. The exact jitted
        step the PUT hot path runs, without the host round trip:
        `__graft_entry__.entry()` returns it as the flagship step."""
        l4 = data32.shape[2]
        return step32(data32, jnp.asarray(_init_smem_np(MAGIC_KEY)),
                      pchunk=_pick_pchunk(l4 // 8))

    run.device_step = device_step
    run.mesh_devices = ndev
    return run


def make_encode_framer(matrix: np.ndarray, mode: str = "auto"):
    """make_mesh_framer on the default device alone: same run()
    contract, same bytes, `run.mesh_devices == 1`."""
    return make_mesh_framer(matrix, mode=mode, devices=jax.devices()[:1])


# ---------------------------------------------------------------------------
# Fused GET verify (the device de-framer)
# ---------------------------------------------------------------------------
# The read-side mirror of make_mesh_framer: the GET hot loop's cost on
# the host is HighwayHashing every fetched framed shard block
# (native.cc mtpu_get_frame does it GIL-free; the numpy path in
# storage/bitrot.read_framed_blocks_many does it vectorized). The
# de-framer moves that hashing onto the accelerator: ONE dispatch takes
# a stacked window of on-disk frames (`digest || block`,
# cmd/bitrot-streaming.go:44-75) across the k data shards, recomputes
# every block digest on device, and returns the per-(block, shard)
# verification verdicts. The interleaved plaintext is then served as
# zero-copy views of the caller's own framed bytes at demux time
# (ops/batcher split_fn) — the payload never rides the device->host
# link back (the digests are 32 bytes/block; the blocks are 128 KiB),
# which is strictly less PCIe than the PUT direction pays. Byte
# identity with the host kernels is therefore exactly the question
# "does the device hash agree", asserted by tests/test_decode_route.py.


def make_mesh_deframer(k: int, mode: str = "auto", devices=None):
    """Fused GET verifier for k-data-shard stripes.

    Returns fn(framed uint8 [B, k, F]) -> ok bool numpy [B, k], where
    F = 32 + shard_size and row b holds erasure block b's k on-disk
    frames. ok[b, i] is True when shard i's block b digest verifies —
    the same verdict mtpu_get_frame's bad-mask encodes, batched. The
    batch dimension ("erasure blocks from MANY concurrent GetObject
    windows", coalesced by ops/batcher's get route) runs where
    `device.batch_placement(devices)` puts it, exactly the encode
    framer's dispatch shape mirrored; only the B*k verdicts ride back.
    One compile per (padding bucket, k, frame width).
    """
    del k, mode  # shape-generic: the stream count is B*k either way
    on_tpu = device.on_tpu()

    def verify32(framed32, init, pchunk: int):
        """u32 hot path: framed [B, k, F4] u32 -> ok bool [B, k]."""
        b, kk, f4 = framed32.shape
        words = framed32[:, :, 8:].reshape(b * kk, f4 - 8)
        digs = _hash_words_pallas(words, init, pchunk=pchunk)  # [B*k, 8]
        stored = framed32[:, :, :8].reshape(b * kk, 8)
        return jnp.all(digs == stored, axis=1).reshape(b, kk)

    def verify8(framed, init):
        """Portable byte path: framed [B, k, F] u8 -> ok bool [B, k]."""
        b, kk, f = framed.shape
        blocks = framed[:, :, 32:].reshape(b * kk, f - 32)
        digs = _hash_impl(blocks, init, f - 32)                # [B*k, 32]
        stored = framed[:, :, :32].reshape(b * kk, 32)
        return jnp.all(digs == stored, axis=1).reshape(b, kk)

    jit_body, upload, ndev = device.batch_placement(devices)
    step32 = jit_body(verify32, static_argnames=("pchunk",))
    step8 = jit_body(verify8)

    def run(framed) -> np.ndarray:
        framed = np.ascontiguousarray(framed, dtype=np.uint8)
        f = framed.shape[2]
        s = f - 32
        pchunk = _pick_pchunk(s // 32) if s and s % 32 == 0 else 0
        if on_tpu and f % 4 == 0 and s % 1024 == 0 and pchunk >= 8:
            device.note_kernel("deframe", "pallas")
            ok, = _lane_round_trip(
                lambda: (upload(framed.view(np.uint32)),
                         jnp.asarray(_init_smem_np(MAGIC_KEY))),
                lambda framed32, init: (step32(framed32, init,
                                               pchunk=pchunk),))
        else:
            device.note_kernel("deframe", "xla")
            ok, = _lane_round_trip(
                lambda: (upload(framed),
                         jnp.asarray(_init_state_np(MAGIC_KEY))),
                lambda framed8, init: (step8(framed8, init),))
        return ok

    run.mesh_devices = ndev
    return run


def make_deframer(k: int, mode: str = "auto"):
    """make_mesh_deframer on the default device alone."""
    return make_mesh_deframer(k, mode=mode, devices=jax.devices()[:1])

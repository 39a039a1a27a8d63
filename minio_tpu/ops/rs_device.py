"""TPU device path for the Reed-Solomon GF(2^8) transform.

This is the north-star kernel (BASELINE.json): the reference runs its
erasure math through hand-written AVX2/AVX512/GFNI Galois kernels inside
github.com/klauspost/reedsolomon (reference: cmd/erasure-coding.go:59-71);
we run the *same* linear transform on the TPU MXU instead.

Formulation — bitplane decomposition to GF(2):
  GF(2^8) multiplication by a constant is GF(2)-linear on the 8 bits of the
  input byte, so an (r x k) GF(2^8) coding matrix expands to an
  (r*8 x k*8) 0/1 matrix over GF(2) (minio_tpu/ops/gf256.bit_matrix). With
  data bytes unpacked into bitplanes, the whole Reed-Solomon transform
  becomes ONE int8 matmul (contraction length k*8 <= 128 for k <= 16 — a
  perfect fit for one MXU pass) followed by `& 1` (the mod-2) and a
  shift-sum repack to bytes. Accumulation must be int32
  (preferred_element_type): dot sums reach k*8 ones, exact in int32, NOT
  exact in bf16 past k=16. This mirrors how GFNI expresses GF(2^8) ops as
  8x8 bit-matrix affine transforms, mapped onto a 128x128 systolic array.

Three implementations of the one transform:
  * `_xla_apply` — pure jax.numpy, runs anywhere (CPU tests, the virtual
    8-device mesh); materialises the 8x bitplane expansion in HBM.
  * `_pallas_apply` — fused Pallas kernel on u8 arrays: unpack -> matmul
    -> mod2 -> pack inside VMEM per tile, so HBM traffic is bytes-in +
    parity-out; PLANE-major bit rows/cols (row = plane*width + byte), as
    Mosaic has no strided sublane access. Behind `DeviceBackend` (whose
    portable mode is `_xla_apply`): the `reconstruct` route, `apply_matrix`.
  * `_pallas_apply32` — the same matmul on u32 lanes (`make_encoder32`,
    below): what the PUT framer (hh_device `fused32`) runs on the chip.

All three produce the host numpy backend's bytes and therefore the
reference's shards (golden digests, cmd/erasure-coding.go:163).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from minio_tpu.ops import device, gf256

# Lane width of the TPU vector unit; tiles are sized in multiples of this.
_LANES = 128
# Lane-tile ceiling for the Pallas kernels.
_TILE_L_MAX = 131072
# Scoped VMEM per grid cell: the tile choosers budget against this figure
# and every pallas_call hands Mosaic the SAME figure as its
# vmem_limit_bytes, so nothing depends on the
# toolchain's default (16 MiB on v5e, of 128 MiB physical —
# pltpu.get_tpu_info().vmem_capacity_bytes) and an estimate that is off
# fails the compile instead of being caught and retried smaller.
# Measured on the v5e under jax 0.9.0 / libtpu 0.0.34: both kernels
# compile at every power-of-two tile from 8k to 128k lanes under limits
# of 16, 32, 64 and 100 MiB, and run time is flat across them (within
# ~5%), so the per-lane estimates below only have to be sane, not tight.
_VMEM_LIMIT = 32 * 1024 * 1024


def _choose_tile(k: int, r: int, l: int, b: int) -> tuple[int, int]:
    """(lane_tile, batch_rows_per_cell) for the u8 kernel.

    Per-lane estimate: bits int8 [k8, T] + acc int32 [r8, T] + data/out
    tiles, doubled for the pipeline's second buffer. The tile is a
    power of two, so padding l up to a tile multiple and then
    re-deriving the tile from the padded l is a fixed point — the
    wrapper and the jitted body always agree.
    """
    per_lane = k * 8 + r * 8 * 4 + 2 * (k + r)
    tile = _LANES
    while tile < _TILE_L_MAX and tile * 2 * per_lane <= _VMEM_LIMIT and tile < l:
        tile *= 2
    bb = 2 if b % 2 == 0 else 1
    return tile, bb


# ---------------------------------------------------------------------------
# Matrix preprocessing (host side, cached)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4096)
def _prep_cached(key: bytes, r: int, k: int) -> tuple[np.ndarray, np.ndarray]:
    """(byte-major bitmatrix [r8,k8], plane-major bitmatrix [r8,k8]) int8."""
    matrix = np.frombuffer(key, dtype=np.uint8).reshape(r, k)
    bm = gf256.bit_matrix(matrix).astype(np.int8)  # rows j*8+c, cols i*8+b
    col_perm = np.arange(k * 8).reshape(k, 8).T.reshape(-1)  # b*k+i <- i*8+b
    row_perm = np.arange(r * 8).reshape(r, 8).T.reshape(-1)  # c*r+j <- j*8+c
    bm_plane = bm[row_perm][:, col_perm]
    return bm, bm_plane


@functools.lru_cache(maxsize=64)
def _repack_weights(r: int) -> np.ndarray:
    """int8 [r, r8] weights matmul that packs plane-major mod-2 planes
    back to bytes on the MXU: out[j] = sum_c acc[c*r+j] * 2^c. The 2^7
    weight stores as int8 -128; consumers mask the product with & 0xFF,
    which recovers the byte exactly under two's complement."""
    w = np.zeros((r, r * 8), dtype=np.uint8)
    for c in range(8):
        for j in range(r):
            w[j, c * r + j] = 1 << c
    return w.view(np.int8)


def _prep(matrix: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    return _prep_cached(matrix.tobytes(), matrix.shape[0], matrix.shape[1])


# ---------------------------------------------------------------------------
# Pure-XLA path (portable)
# ---------------------------------------------------------------------------

@jax.jit
def _xla_apply(bmat: jax.Array, data: jax.Array) -> jax.Array:
    """bmat int8 [r8, k8] (byte-major), data uint8 [B, k, L] -> uint8 [B, r, L]."""
    b, k, l = data.shape
    r = bmat.shape[0] // 8
    x = data.astype(jnp.int32)
    shifts = jnp.arange(8, dtype=jnp.int32)
    bits = ((x[:, :, None, :] >> shifts[None, None, :, None]) & 1)  # [B,k,8,L]
    bits = bits.reshape(b, k * 8, l).astype(jnp.int8)
    acc = jnp.einsum("rk,bkl->brl", bmat, bits,
                     preferred_element_type=jnp.int32)
    outbits = (acc & 1).reshape(b, r, 8, l)
    weights = (jnp.int32(1) << shifts)[None, None, :, None]
    out = jnp.sum(outbits * weights, axis=2)
    return out.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Fused Pallas kernel
# ---------------------------------------------------------------------------

def _rs_kernel(bmat_ref, wrep_ref, data_ref, out_ref):
    """One (batch, lane-tile) cell: fused unpack -> GF(2) matmul -> pack.

    bmat_ref: int8 [r8, k8] PLANE-major both axes (row c*r+j, col b*k+i).
    wrep_ref: int8 [r, r8] repack weights (_repack_weights).
    data_ref: uint8 [bb, k, TL]; out_ref: uint8 [bb, r, TL].

    Two measured v5e rules shape this kernel: (a) int8 arrays tile as
    (32, 128) per vreg, so concatenating 8-row int8 pieces forces
    sublane shuffles — build the bitplanes in int32 (natural (8, 128)
    tiles) and cast ONCE; (b) the mod-2 repack as shift/or loops is
    ~25% of kernel time — one tiny weights matmul does it on the MXU
    instead (0.92 ms vs 1.38 ms for EC 8+4 on 128 MiB).
    """
    k = data_ref.shape[1]
    r = out_ref.shape[1]
    for i in range(data_ref.shape[0]):
        x = data_ref[i].astype(jnp.int32)  # [k, TL]
        # Plane-major unpack: row b*k+i holds bit b of shard i. Static
        # concat — no sublane interleaving needed. (Shifts must be int32:
        # Mosaic cannot legalize arith.shrui on 8-bit vectors.)
        bits = jnp.concatenate(
            [(x >> b) & 1 for b in range(8)], axis=0).astype(jnp.int8)
        acc = jax.lax.dot_general(
            bmat_ref[:], bits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)  # [r8, TL]
        accb = (acc & 1).astype(jnp.int8)
        packed = jax.lax.dot_general(
            wrep_ref[:], accb,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)  # [r, TL] byte values
        out_ref[i] = (packed & 0xFF).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("tile", "bb", "interpret"))
def _pallas_apply(bmat_plane: jax.Array, data: jax.Array, tile: int,
                  bb: int, interpret: bool = False) -> jax.Array:
    """bmat_plane int8 [r8, k8] (plane-major), data uint8 [B, k, L_padded]."""
    b, k, l = data.shape
    r8 = bmat_plane.shape[0]
    r = r8 // 8
    # Loud failure beats silently-unwritten output tails: callers must pad
    # (DeviceBackend.apply_matrix_device / make_encoder do).
    assert l % tile == 0, f"lane dim {l} not a multiple of tile {tile}"
    assert b % bb == 0, f"batch dim {b} not a multiple of {bb}"
    grid = (b // bb, l // tile)
    wrep = jnp.asarray(_repack_weights(r))
    return pl.pallas_call(
        _rs_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((r8, k * 8), lambda ib, il: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, r8), lambda ib, il: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, k, tile), lambda ib, il: (ib, 0, il),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bb, r, tile), lambda ib, il: (ib, 0, il),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, l), jnp.uint8),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(bmat_plane, wrep, data)


# ---------------------------------------------------------------------------
# u32-lane variant (for the fused encode+bitrot pipeline)
# ---------------------------------------------------------------------------
# Byte-level device arrays pay a hidden tax: TPU tiles uint8 along
# sublanes, so bitcasting u8 shards to the u32 words HighwayHash needs
# is a ~35 GiB/s relayout — slower than the hash itself. This variant
# keeps the WHOLE pipeline in u32 lanes: each lane holds 4 consecutive
# shard bytes and the output is directly the word layout the hash
# kernel consumes. Byte-identical to the u8 path.
#
# The kernel unpacks bits in the i8 DOMAIN: pltpu.bitcast reinterprets
# the u32 tile as u8 rows in-register (row = 4*shard + byte_slot,
# measured v5e layout), where each bit extraction is and/cmp/select on
# (32, 128)-dense i8 vregs — 4x the elements per op of the old
# u32-domain shift+mask unpack (which cost 64 VPU ops per word, the
# kernel's former governor). The byte slots ride the ROW axis, so the
# GF(2) matrix expands block-diagonally per slot (_prep8), and the
# mod-2 repack is a slice/or tree straight out of the i32 accumulator —
# measured faster than the weights-matmul repack here because it skips
# the [r8, lanes] i32->i8 cast relayout. 131 -> ~170 GiB/s on v5e for
# EC 8+4 on 1 MiB blocks.

@functools.lru_cache(maxsize=4096)
def _prep8_cached(key: bytes, r: int, k: int) -> np.ndarray:
    """Plane-PAIR-packed block-diagonal bit matrix int8 [16*rp, 32k]
    for the i8-row layout (rp = r rounded up to even so byte rows tile
    in 8s): row a = p*4rp + 4*jr + slot carries bit planes 2p (weight
    +1) and 2p+1 (weight -128) of output byte row 4*jr + slot; col =
    b*4k + 4*i + slot. Packing two GF(2) planes per accumulator row —
    recoverable because the +1 part sums to < 128 for k <= 15 — halves
    the [rows, lanes] i32 accumulator, whose VMEM round-trip is the
    kernel's real cost on v5e."""
    matrix = np.frombuffer(key, dtype=np.uint8).reshape(r, k)
    assert k <= 15, "plane-pair packing requires k <= 15"
    bm = gf256.bit_matrix(matrix)          # [r8, k8]: row jr*8+c, col i*8+b
    rp = r + (r & 1)
    planes = np.zeros((8, 4 * rp, 32 * k), dtype=np.int32)
    for c in range(8):
        for jr in range(r):
            for j in range(4):
                for b in range(8):
                    for i in range(k):
                        planes[c, 4 * jr + j, b * 4 * k + 4 * i + j] = \
                            bm[jr * 8 + c, i * 8 + b]
    out = np.zeros((16 * rp, 32 * k), dtype=np.int32)
    for p in range(4):
        out[p * 4 * rp:(p + 1) * 4 * rp] = \
            planes[2 * p] - 128 * planes[2 * p + 1]
    return out.astype(np.int8)


def _rs_kernel32(bmat_ref, data_ref, out_ref):
    """One (batch, lane-tile) cell on u32 lanes.

    bmat_ref: int8 [16*rp, 32k] pair-packed bit matrix (_prep8_cached).
    data_ref: uint32 [bb, k, TL4]; out_ref: uint32 [bb, r, TL4].

    acc row (p, row4) = lo - 128*hi where lo/hi are the GF(2) dot sums
    of planes 2p / 2p+1 (each in [0, 120]): lo parity = acc & 1 (the
    -128*hi part is even), hi = (127 - acc) >> 7 exactly.
    """
    r = out_ref.shape[1]
    rp = bmat_ref.shape[0] // 16
    r4 = 4 * rp
    for i in range(data_ref.shape[0]):
        xb = pltpu.bitcast(data_ref[i], jnp.uint8)       # [4k, TL4]
        bits = jnp.concatenate(
            [jnp.where((xb & jnp.uint8(1 << b)) != 0,
                       jnp.int8(1), jnp.int8(0)) for b in range(8)],
            axis=0)                                      # [32k, TL4]
        acc = jax.lax.dot_general(
            bmat_ref[:], bits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)            # [16rp, TL4]
        packed = None
        for p in range(4):
            t = acc[p * r4:(p + 1) * r4]
            lo = (t & 1) << (2 * p)
            hi = (((127 - t) >> 7) & 1) << (2 * p + 1)
            contrib = lo | hi
            packed = contrib if packed is None else (packed | contrib)
        words = pltpu.bitcast(packed.astype(jnp.uint8),
                              jnp.uint32)                # [rp, TL4]
        out_ref[i] = words[0:r]


@functools.partial(jax.jit,
                   static_argnames=("r", "tile4", "bb", "interpret"))
def _pallas_apply32(bmat8: jax.Array, data: jax.Array, r: int, tile4: int,
                    bb: int, interpret: bool = False) -> jax.Array:
    """bmat8 int8 [16*rp, 32k] pair-packed (_prep8_cached), data uint32
    [B, k, L4_padded]."""
    b, k, l4 = data.shape
    assert l4 % tile4 == 0, f"lane dim {l4} not a multiple of tile {tile4}"
    assert b % bb == 0, f"batch dim {b} not a multiple of {bb}"
    grid = (b // bb, l4 // tile4)
    return pl.pallas_call(
        _rs_kernel32,
        grid=grid,
        in_specs=[
            pl.BlockSpec(tuple(bmat8.shape), lambda ib, il: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, k, tile4), lambda ib, il: (ib, 0, il),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bb, r, tile4), lambda ib, il: (ib, 0, il),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, l4), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(bmat8, data)


def make_encoder32(matrix: np.ndarray, mode: str = "auto"):
    """u32-lane encoder: fn(data uint32 [B, k, L4]) -> uint32 [B, r, L4].

    Lane t of shard i holds bytes 4t..4t+3 (little-endian), i.e. the
    same bytes as the u8 path's lanes 4t..4t+3 — outputs bitcast-equal.
    Pads lanes to a tile multiple internally (zeros are a fixed point).
    """
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    r, k = matrix.shape
    backend = DeviceBackend(mode)
    if backend.mode == "xla" or k > 15:
        # k > 15 would break the pair-packing overflow bound (never hit
        # in practice: erasure sets cap at 16 drives with m >= 1).
        def run_xla(data):
            # Portable fallback: via the byte path.
            b, kk, l4 = data.shape
            bytes_ = jax.lax.bitcast_convert_type(data, jnp.uint8) \
                .reshape(b, kk, l4 * 4)
            out = _xla_apply(jnp.asarray(_prep(matrix)[0]), bytes_)
            return jax.lax.bitcast_convert_type(
                out.reshape(b, r, l4, 4), jnp.uint32)
        return run_xla
    interpret = backend._interpret
    bmat = jnp.asarray(_prep8_cached(matrix.tobytes(), r, k))
    rp = r + (r & 1)

    def run(data):
        b, kk, l4 = data.shape
        # Per-lane estimate: bits i8 [32k, T] + acc i32 [16rp, T] + io
        # u32. Capped at 16k lanes: the shape the serving path has
        # always compiled for 8+4 on 1 MiB blocks (and, measured on
        # v5e, as fast as any other tile).
        tile4 = 128
        per_lane4 = 32 * k + 16 * rp * 4 + (k + r) * 4 + 4 * rp
        while tile4 < _TILE_L_MAX // 8 and tile4 * per_lane4 <= _VMEM_LIMIT \
                and tile4 < l4:
            tile4 *= 2
        pad = (-l4) % tile4
        padded = jnp.pad(data, ((0, 0), (0, 0), (0, pad))) if pad else data
        out = _pallas_apply32(bmat, padded, r=r, tile4=tile4, bb=1,
                              interpret=interpret)
        return out[..., :l4] if pad else out
    return run


# ---------------------------------------------------------------------------
# Backend
# ---------------------------------------------------------------------------

class DeviceBackend:
    """ECBackend that runs the GF(2^8) transform on the default JAX device.

    mode: "pallas" (fused kernel; needs a TPU), "xla" (portable einsum
    path), "auto" (pallas on TPU, xla elsewhere — what serving uses;
    the caller that must have the TPU checks ops/device.require()
    first), or "interpret" (the Pallas kernel through the interpreter:
    tests only, never picked by "auto").
    """

    def __init__(self, mode: str = "auto", host_cutover: int | None = None):
        if mode not in ("auto", "pallas", "xla", "interpret"):
            raise ValueError(f"unknown mode {mode!r}")
        on_tpu = device.on_tpu()
        if mode == "auto":
            mode = "pallas" if on_tpu else "xla"
        elif mode == "pallas" and not on_tpu:
            raise RuntimeError("mode='pallas' needs a TPU; the interpreter "
                               "is mode='interpret'")
        self._interpret = mode == "interpret"
        self.mode = "pallas" if self._interpret else mode
        if host_cutover is not None:
            self.HOST_CUTOVER_BYTES = host_cutover

    # -- device-array API (stays on device; used by batched/jit callers) ----

    def apply_matrix_device(self, matrix: np.ndarray, data: jax.Array) -> jax.Array:
        """data uint8 [B, k, L] on device -> [B, r, L] on device.

        Pads lanes to a whole number of tiles (zero bytes are a fixed
        point of the linear transform so the tail slices back out
        exactly). Eager and traced callers take the same path: the
        tile is a function of the shape alone.
        """
        bm_byte, bm_plane = _prep(matrix)
        traced = isinstance(data, jax.core.Tracer)
        if self.mode == "xla":
            if not traced:
                device.note_kernel("matrix", "xla")
            return _xla_apply(jnp.asarray(bm_byte), data)
        if not traced:
            device.note_kernel("matrix", "interpret" if self._interpret
                               else "pallas")
        b, k, l = data.shape
        tile, bb = _choose_tile(k, matrix.shape[0], l, b)
        pad = (-l) % tile
        padded = jnp.pad(data, ((0, 0), (0, 0), (0, pad))) if pad else data
        out = _pallas_apply(jnp.asarray(bm_plane), padded, tile=tile, bb=bb,
                            interpret=self._interpret)
        return out[..., :l] if pad else out

    # -- ECBackend protocol (numpy in / numpy out) --------------------------

    # Below this many input bytes a host->device->host round trip costs
    # more than the transform itself (and the batch cannot fill the
    # kernel's vector tiles): small PUT/GET/reconstruct calls run the
    # host GF core instead, keeping p50 latency of 1 MiB objects at
    # host-codec level while large batches ride the MXU.
    HOST_CUTOVER_BYTES = 8 << 20

    def apply_matrix(self, matrix: np.ndarray, shards: np.ndarray) -> np.ndarray:
        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        if shards.nbytes < self.HOST_CUTOVER_BYTES:
            # Same host core the pure-host codec uses (native C++ nibble
            # kernel when built) — small objects must not regress vs the
            # host backend.
            from minio_tpu.erasure.codec import _HOST
            return _HOST.apply_matrix(matrix, shards)
        out = self.apply_matrix_device(matrix, jnp.asarray(shards[None]))
        return np.asarray(jax.device_get(out))[0]


def make_encoder(matrix: np.ndarray, mode: str = "auto"):
    """Public jittable entry: fn(data uint8 [B, k, L]) -> uint8 [B, r, L].

    The GF matrix is baked in host-side (prep + padding handled); the
    returned closure is safe to wrap in jax.jit or call inside jitted
    code. This is the single dispatch point of the u8 transform: the
    byte framer (hh_device), make_mesh_matrix, __graft_entry__ and the
    sharded stripe steps all go through it.
    """
    backend = DeviceBackend(mode)
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    return lambda data: backend.apply_matrix_device(matrix, data)


def make_mesh_matrix(matrix: np.ndarray, mode: str = "auto", devices=None):
    """Batched GF(2^8) matrix application — the decode mirror of
    hh_device.make_mesh_framer's parity step: stacked u8 [B, k, L] ->
    u8 [B, r, L] numpy, with the batch dim ("stripes from MANY degraded
    GetObject / heal calls", coalesced by ops/batcher's reconstruct
    route) placed by `device.batch_placement(devices)`: one jitted step
    on one device, sharded over the chips on several.

    `matrix` is any (r x k) GF matrix: decode-matrix rows
    (gf256.decode_matrix gathered for the missing data shards — one
    compiled route per surviving-shard set, the common case being ONE
    set per dead drive) for degraded reads, parity rows for heal's
    re-derive. Same bytes on any chip count (gf256 bitplane transform,
    byte-identical to the host codec by the rs_device contract).
    """
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    backend = DeviceBackend(mode)
    impl = "interpret" if backend._interpret else backend.mode

    def matrix_apply(data):
        return backend.apply_matrix_device(matrix, data)

    jit_body, upload, ndev = device.batch_placement(devices)
    step = jit_body(matrix_apply)

    def run(stacked) -> np.ndarray:
        # the framer's round trip and its three lane stages
        from minio_tpu.ops.hh_device import _lane_round_trip
        stacked = np.ascontiguousarray(stacked, dtype=np.uint8)
        device.note_kernel("matrix", impl)
        out, = _lane_round_trip(lambda: (upload(stacked),),
                                lambda data: (step(data),))
        return out

    run.mesh_devices = ndev
    return run

"""The accelerator as this process sees it: one place for platform
detection, the compile cache, and the record of what the device did.

Everything that used to ask `jax.default_backend() == "tpu"` on its own
asks `info()` here, and the boot line, admin info and the
`minio_tpu_device_info` metric print the same answer — read from
`jax.devices()` in the process that owns the chip. Three rules live in
this module so no call site can get them wrong:

  * A chip belongs to ONE process. `probe_platform()` lets a parent
    that is about to fork learn the platform without importing JAX
    itself (a short-lived child asks and exits, releasing the chip).
  * A process that SERVES with a device backend (`require()`: the
    server's `--ec-backend tpu`, or `auto` whose probe found a TPU)
    never carries on without the device: the platform must be `tpu` —
    or `JAX_PLATFORMS` must name `cpu` explicitly, the tests' way to
    run the portable path, which the boot line then says — and every
    `except` that would absorb a device fault re-raises instead
    (`required()`).
  * Nothing that ran instead of the TPU kernel goes uncounted:
    `note_kernel()` records which implementation served each dispatch
    (`pallas` | `xla` | `interpret`), `note_mesh_blocks()` which chip
    of a mesh got how much of a batch and how much of that was
    padding, and `record_fault()` counts and logs, with its traceback,
    every exception a device call raised.

JAX is imported lazily: `erasure/codec.py` reaches `ops/gf256.py`
through this package, and the host codec, the pre-forked host-codec
workers and chip_smoke.py's parent must stay JAX-free.
"""

from __future__ import annotations

import contextlib
import functools
import os
import subprocess
import sys
import threading
import traceback
from typing import NamedTuple

from minio_tpu.utils import tracing

# <checkout>/.jax_cache — fixed, because the directory is part of the
# persistent cache's key: a path that moves (tempfile, pid, timestamp)
# never hits. Git-ignored.
_FIXED_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


class DeviceInfo(NamedTuple):
    platform: str        # jax.devices()[0].platform: "tpu", "cpu", ...
    device_kind: str     # jax.devices()[0].device_kind, e.g. "TPU v5 lite"
    devices: int         # len(jax.devices())
    mesh_devices: int    # chips the batched dispatch shards over


class DeviceUnavailable(RuntimeError):
    """This process was to serve on the TPU and did not get it."""


def compile_cache_dir(env=os.environ) -> str:
    """Where compiled executables persist: JAX_COMPILATION_CACHE_DIR
    when the environment places it, else the one fixed in-checkout
    path. JAX-free (chip_smoke.py's parent reads it too)."""
    return env.get("JAX_COMPILATION_CACHE_DIR", "") or _FIXED_CACHE_DIR


def explicit_cpu(env=os.environ) -> bool:
    """JAX_PLATFORMS names cpu first: the caller chose the portable
    path on purpose (the test suite does)."""
    return env.get("JAX_PLATFORMS", "").split(",")[0].strip().lower() \
        == "cpu"


@functools.lru_cache(maxsize=1)
def info() -> DeviceInfo:
    """Initialise JAX in THIS process (claiming the chip if there is
    one) and report what it found. The persistent compile cache is
    placed here, ahead of the first compile: every device path asks
    info() before it builds a kernel."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # Set: JAX reads the variable itself and no directory is named
        # in code. Unset: the fixed in-checkout path.
        jax.config.update("jax_compilation_cache_dir", _FIXED_CACHE_DIR)
    # Boot compiles ~120 tiny self-test kernels well under the default
    # one-second floor; without this only the big framers would hit.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    # The program's stages go onto the profiler's clock from here on
    # (utils/tracing.stage): whoever traces this process — the
    # benchmark's launcher, the admin's trace profile — finds them
    # beside the device's operations.
    tracing.set_annotator(jax.profiler.TraceAnnotation)
    return DeviceInfo(devs[0].platform, devs[0].device_kind, len(devs),
                      len(mesh_batch_devices(devs)))


def on_tpu() -> bool:
    return info().platform == "tpu"


def held() -> bool:
    """This process has initialised JAX through info() — it holds the
    device, if there is one. JAX-free: never initialises it."""
    return info.cache_info().currsize > 0


def mesh_batch_devices(devices=None) -> list:
    """The largest power-of-two prefix of the visible devices: padding
    buckets are powers of two (ops/batcher._BUCKETS), so a power-of-two
    mesh keeps every bucketed batch evenly divisible across chips with
    zero per-chip remainder shapes (one compile per bucket, not per
    (bucket, remainder) pair). MTPU_MESH_DEVICES caps the prefix (the
    tests' way to one device on the virtual eight; ROADMAP C17 asks
    whether it is a constant or a deployment setting)."""
    if devices is None:
        import jax
        devices = jax.devices()
    devs = list(devices)
    try:
        cap = int(os.environ.get("MTPU_MESH_DEVICES", "") or len(devs))
    except ValueError:
        cap = len(devs)
    devs = devs[:max(1, cap)]
    p = 1
    # Cap at the largest padding bucket (ops/batcher._BUCKETS[-1]): a
    # mesh wider than the biggest batch shape could never be fed a
    # divisible batch.
    while p * 2 <= len(devs) and p * 2 <= 256:
        p *= 2
    return devs[:p]


def batch_placement(devices=None) -> tuple:
    """Where a stacked batch runs: the one placement rule of every
    batched device operation (frame, de-frame, matrix apply). Returns
    `(jit_body, upload, ndev)` for `mesh_batch_devices(devices)`:
    `jit_body(body, static_argnames=())` is the jitted step of a body
    whose first argument carries the batch on its leading axis (the
    rest are replicated), `upload(x)` moves a host batch to where that
    step reads it.

    One device: `jax.jit(body)` on the default device and
    `jnp.asarray`, no mesh object and no donation. Several: the body
    under `shard_map` over `Mesh(devs, ("stripe",))`, batch axis
    `P("stripe")` in and out — each chip runs the body on its slice,
    no cross-chip traffic (stripes are independent) — jitted with the
    batch donated on a TPU (the staged host batch flows host -> HBM ->
    outputs without XLA's defensive copy; the CPU backend ignores
    donation with a compile warning, so it is declared only where it
    buys the copy), and one `jax.device_put(x, NamedSharding)` of a
    batch that divides by the chips (the batcher pads to power-of-two
    buckets). One compile per (padded batch size, body)."""
    import jax
    devs = mesh_batch_devices(devices)
    ndev = len(devs)
    if ndev == 1:
        import jax.numpy as jnp

        def jit_body(body, static_argnames=()):
            return jax.jit(body, static_argnames=static_argnames)

        return jit_body, jnp.asarray, 1
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devs), ("stripe",))
    sharding = NamedSharding(mesh, P("stripe"))
    donate = (0,) if on_tpu() else ()

    def jit_body(body, static_argnames=()):
        @functools.wraps(body)
        def sharded(batch, *replicated, **static):
            # check_vma off: the Pallas calls inside the bodies carry
            # no varying-manual-axes annotations.
            return jax.shard_map(
                functools.partial(body, **static), mesh=mesh,
                in_specs=(P("stripe"),) + (P(),) * len(replicated),
                out_specs=P("stripe"), check_vma=False)(batch, *replicated)
        return jax.jit(sharded, static_argnames=static_argnames,
                       donate_argnums=donate)

    def upload(batch):
        assert batch.shape[0] % ndev == 0, \
            f"batch {batch.shape[0]} not divisible by {ndev}-chip mesh " \
            f"(pad buckets)"
        return jax.device_put(batch, sharding)

    return jit_body, upload, ndev


def probe_platform(timeout: float = 180.0) -> str:
    """The platform JAX would come up on, learned WITHOUT importing JAX
    here: a short-lived child initialises it, prints the answer and
    exits (which frees the chip for whoever is meant to own it). For a
    parent that forks afterwards. "" when the child failed — its
    stderr is passed through so the reason is on the console."""
    if explicit_cpu():
        return "cpu"
    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].platform)"],
            stdout=subprocess.PIPE, timeout=timeout)
    except (OSError, subprocess.TimeoutExpired) as e:
        print(f"device probe failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return ""
    lines = out.stdout.decode(errors="replace").split()
    return lines[-1] if out.returncode == 0 and lines else ""


# -- a device backend serves here -------------------------------------------

_required = False


def require() -> DeviceInfo:
    """This process is about to serve with a device backend
    (`--ec-backend tpu`, or `auto` whose probe found a TPU). Raises
    DeviceUnavailable unless JAX came up on a TPU — or JAX_PLATFORMS
    names cpu explicitly, in which case the portable path serves and
    the returned info (and so the boot line) says cpu. From here on
    required() is true — "a device backend serves here" — and no
    device fault is absorbed into another codec."""
    global _required
    try:
        inf = info()
    except Exception as e:  # noqa: BLE001 - any init failure is the answer
        raise DeviceUnavailable(
            f"JAX failed to initialise: {type(e).__name__}: {e}") from e
    if inf.platform != "tpu" and not explicit_cpu():
        raise DeviceUnavailable(
            f"JAX came up on platform {inf.platform!r} "
            f"({inf.device_kind}), not a TPU; set JAX_PLATFORMS=cpu "
            f"explicitly to run the portable path on purpose")
    _required = True
    return inf


def required() -> bool:
    return _required


# -- what the device did -----------------------------------------------------

_mu = threading.Lock()
_kernel_calls: dict[tuple[str, str], int] = {}
_mesh_blocks: dict[tuple[int, str], int] = {}
_faults: dict[str, int] = {}
_last_fault = ""
_FAULT_LOG_MAX = 20       # tracebacks printed per process; all counted


def note_kernel(kernel: str, impl: str) -> None:
    """One host-level dispatch of `kernel` (frame|deframe|matrix|
    digest) ran as `impl` (pallas|xla|interpret)."""
    with _mu:
        key = (kernel, impl)
        _kernel_calls[key] = _kernel_calls.get(key, 0) + 1


_batch = threading.local()


@contextlib.contextmanager
def batch_of(real_blocks: int):
    """The batcher's word to the device function it calls inside, on
    this thread: of the batch handed over, the first `real_blocks` rows
    carry clients' data and the rest is bucket padding (the
    calibration probe's batch of zeros is all padding)."""
    _batch.real = real_blocks
    try:
        yield
    finally:
        _batch.real = None


def note_mesh_blocks(blocks: int, chips: int) -> None:
    """One dispatch of `blocks` rows cut evenly, in order, over `chips`
    chips of a mesh (`P("stripe")`): per chip, how many rows of its
    slice were real and how many padding. The real rows come first, so
    padding lands on the last chips. Without the batcher's word
    (`batch_of`) every row counts as real. One device is no mesh:
    nothing is counted, and the series stays absent."""
    if chips <= 1:
        return
    real = getattr(_batch, "real", None)
    real = blocks if real is None else min(real, blocks)
    per_chip = blocks // chips
    with _mu:
        for chip in range(chips):
            r = min(max(real - chip * per_chip, 0), per_chip)
            for kind, v in (("real", r), ("pad", per_chip - r)):
                _mesh_blocks[chip, kind] = _mesh_blocks.get(
                    (chip, kind), 0) + v


def record_fault(site: str, exc: BaseException) -> None:
    """A device call raised. Counted per site, remembered, and logged
    with its traceback (the first _FAULT_LOG_MAX per process in full —
    a kernel that fails every dispatch must not flood stderr)."""
    global _last_fault
    with _mu:
        _faults[site] = _faults.get(site, 0) + 1
        total = sum(_faults.values())
        _last_fault = f"{site}: {type(exc).__name__}: {exc}"[:500]
    if total <= _FAULT_LOG_MAX:
        print(f"device fault at {site}:\n" + "".join(
            traceback.format_exception(type(exc), exc,
                                       exc.__traceback__)),
              file=sys.stderr, flush=True)


def stats() -> dict:
    """Counters for metrics/admin info. JAX-free."""
    with _mu:
        return {"kernel_calls": {f"{k}/{i}": v for (k, i), v
                                 in sorted(_kernel_calls.items())},
                "mesh_blocks": {f"{c}/{kind}": v for (c, kind), v
                                in sorted(_mesh_blocks.items())},
                "faults": dict(_faults),
                "last_fault": _last_fault,
                "required": _required}


@functools.lru_cache(maxsize=1)
def _versions() -> dict:
    import jax
    import jaxlib
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = ""
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def report() -> dict:
    """Everything the boot line, admin info and chip_smoke.py say about
    the device, for a process that runs a device backend."""
    return {**info()._asdict(), **_versions(),
            "compile_cache_dir": compile_cache_dir(), **stats()}

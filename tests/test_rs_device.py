"""Device (XLA / Pallas-interpret) RS paths must match the host backend bit
for bit — and therefore the reference's golden digests."""

import numpy as np
import pytest

from minio_tpu.erasure.codec import Erasure, HostBackend
from minio_tpu.erasure.selftest import erasure_self_test
from minio_tpu.ops import device, gf256
from minio_tpu.ops.rs_device import DeviceBackend

CONFIGS = [(2, 2), (4, 2), (8, 4), (5, 3), (12, 4), (16, 4)]

# Off-TPU the Pallas kernel runs through the (slow) interpreter, asked
# for by name, so CI keeps a reduced sweep for it; the full sweep runs
# on the XLA path, which lowers the exact same bit-matrix math. On the
# chip (`JAX_PLATFORMS=tpu python -m pytest tests/test_rs_device.py`)
# the same tests run the compiled kernel in full.
_ON_TPU = device.on_tpu()


@pytest.fixture(scope="module",
                params=["xla", "pallas" if _ON_TPU else "interpret"])
def backend(request):
    # host_cutover=0: these tests exist to exercise the DEVICE kernels;
    # the production small-input host reroute would make them vacuous.
    return DeviceBackend(mode=request.param, host_cutover=0)


def _skip_slow_interpret(backend, heavy: bool):
    if heavy and backend.mode == "pallas" and not _ON_TPU:
        pytest.skip("pallas interpret mode: reduced sweep off-TPU")


@pytest.mark.parametrize("k,m", CONFIGS)
@pytest.mark.parametrize("length", [1, 77, 128, 1024, 5000])
def test_apply_matrix_matches_host(backend, k, m, length):
    _skip_slow_interpret(backend, heavy=(k, m) != (4, 2) or length not in (77, 1024))
    rng = np.random.default_rng(k * 1000 + m * 10 + length)
    shards = rng.integers(0, 256, size=(k, length), dtype=np.uint8)
    pm = gf256.parity_matrix(k, m)
    want = HostBackend().apply_matrix(pm, shards)
    got = backend.apply_matrix(pm, shards)
    np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("k,m", [(4, 2), (8, 4)])
def test_batched_apply(backend, k, m):
    _skip_slow_interpret(backend, heavy=(k, m) != (4, 2))
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    batch = rng.integers(0, 256, size=(3, k, 2000), dtype=np.uint8)
    pm = gf256.parity_matrix(k, m)
    got = np.asarray(backend.apply_matrix_device(pm, jnp.asarray(batch)))
    for b in range(3):
        want = HostBackend().apply_matrix(pm, batch[b])
        np.testing.assert_array_equal(want, got[b])


def test_device_backend_passes_reference_selftest(backend):
    # The reference's boot gate (cmd/erasure-coding.go:152-209) run with the
    # device backend: byte-identical golden xxhash64 digests.
    _skip_slow_interpret(backend, heavy=True)
    erasure_self_test(backend=backend)


@pytest.mark.parametrize("k,m", [(8, 4)])
def test_encode_reconstruct_roundtrip_device(backend, k, m):
    _skip_slow_interpret(backend, heavy=True)
    e = Erasure(k, m, 1 << 20, backend=backend)
    rng = np.random.default_rng(42)
    data = rng.integers(0, 256, size=100_000, dtype=np.uint8).tobytes()
    shards = e.encode_data(data)
    # Drop m shards (mixed data+parity) and reconstruct.
    shards[1] = np.zeros(0, dtype=np.uint8)
    shards[k + 1] = None
    lost2 = min(k - 1, 3)
    shards[lost2] = None
    e.decode_data_and_parity_blocks(shards)
    assert e.join(shards, len(data)) == data

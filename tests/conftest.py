"""Test env: JAX on a virtual 8-device CPU mesh unless told otherwise.

Multi-chip sharding tests run against this mesh. The suite is a CPU
suite: the tier-1 command sets JAX_PLATFORMS=cpu and a plain `pytest`
defaults to it. The TPU-gated kernel tests run on the chip by naming
it: `JAX_PLATFORMS=tpu python -m pytest tests/test_hh_device.py
tests/test_rs_device.py`.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

# The pytest process has JAX loaded, and the pre-forked worker
# front-end's os.fork() is unsafe after that: any in-process
# minio_tpu.server.main() call must take the single-process path.
# Worker-mode tests boot the fleet in a clean subprocess and override
# this explicitly (tests/test_io_engine.py).
os.environ.setdefault("MTPU_HTTP_WORKERS", "1")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long multi-process cluster tests")

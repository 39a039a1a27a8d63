"""Checks of the benchmark itself, runnable on the CPU:

    python -m pytest benchmark/tests -q            (quick ones)
    python -m pytest benchmark/tests -q -m slow    (boots the server)

They are not among the repo's tier-1 tests (`tests/`)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: boots the server on the CPU")

"""serve_traced.py with the program broken in one place, for the
control (`faults.py`, `deaf_deframer`) and the tests alone: the `get`
route hands every window the verdict "all frames verify", whatever the
de-framer found. The device still hashes every frame, so nothing else
of the run changes — which is what a later change would look like that
saves the hash, or the look at its answer, on the read path. Same
arguments as serve_traced.py.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import serve_traced  # noqa: E402


def main() -> int:
    sys.path.insert(0, os.getcwd())
    from minio_tpu.object import erasure_object   # imports no JAX
    heard = erasure_object._get_split

    def deaf(ok, off, c, member):
        verdict, payload = heard(ok, off, c, member)
        return np.ones_like(verdict), payload
    erasure_object._get_split = deaf
    return serve_traced.main()


if __name__ == "__main__":
    sys.exit(main())

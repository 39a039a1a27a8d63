"""A measuring run with no TPU exits non-zero and prints no result;
so does one in a directory that holds only the benchmark."""

import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
ARGS = ["--workload", "ec8p4-12d.put-64m", "--seed", "3000000019",
        "--seconds", "2", "--trace", "0"]


def test_cpu_named_first_is_no_accelerator():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), *ARGS],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=120)
    assert out.returncode != 0
    assert out.stdout == b""
    assert b"no accelerator" in out.stderr.lower() \
        or b"is no" in out.stderr.lower()


def test_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS], env=env,
        cwd=tmp_path, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        timeout=120)
    assert out.returncode != 0
    assert out.stdout == b""


def test_the_parent_and_the_generators_never_import_jax():
    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmark.run, benchmark.loadgen, benchmark.control, "
            "benchmark.faults; "
            "assert 'jax' not in sys.modules and "
            "not any(m.startswith('minio_tpu') for m in sys.modules)" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)

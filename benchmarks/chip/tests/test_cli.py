"""The command refuses to run without a TPU, and without the program's
sources next to it, and prints no result line either way."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import files

CELL = files.load_spec()["workloads"][0]["name"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload", CELL,
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    assert p.returncode != 0
    for ln in lines:
        try:
            assert not isinstance(json.loads(ln), dict)
        except json.JSONDecodeError:
            pass
    return p


def test_no_tpu_no_result():
    p = _run(files.ROOT)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(files.SPEC_FILE, tmp_path / "BENCHMARK.json")
    shutil.copytree(files.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert "sources" in p.stderr

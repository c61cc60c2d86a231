"""``bench/run.py`` refuses to measure without the chip it needs."""
import os
import shutil
import subprocess
import sys

import manifest


def run_cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sssp-kron18-mixed",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_nonzero_exit_and_no_result_line():
    p = run_cli(manifest.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_make_a_result(tmp_path):
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(manifest.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = run_cli(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout

"""Without a TPU, or without the program, the benchmark exits non-zero
and prints no result."""

import json
import os
import shutil
import subprocess
import sys

import tiny


def _run(cwd, root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "qwen3-0.6b.decode-batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_cpu_only_exits_nonzero_without_result():
    p = _run(tiny.ROOT, tiny.ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)

"""``bench.py`` refuses to run without a chip, and without the program."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
ROOT = HERE.parent.parent


def _run(cwd: Path, script: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script), "--workload", "olmo1b.chat_shared",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_chip():
    r = _run(ROOT, HERE / "bench.py")
    assert r.returncode == 1, r.stderr
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    r = _run(tmp_path, tmp_path / "benchmarks" / "chip" / "bench.py")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    json.loads((tmp_path / "BENCHMARK.json").read_text())

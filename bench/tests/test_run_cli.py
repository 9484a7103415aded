"""``bench/run.py`` refuses to measure without a chip."""
import json
import os
import shutil
import subprocess
import sys

from bench.lib import cells

CMD = [sys.executable, "bench/run.py", "--workload", "scnn_paper_saturated",
       "--seed", "3", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _passing_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return bool(json.loads(lines[-1]).get("correct"))
    except ValueError:
        return False


def test_no_chip_no_result():
    p = _run(cells.ROOT)
    assert p.returncode != 0
    assert not _passing_line(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(cells.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _passing_line(p.stdout)

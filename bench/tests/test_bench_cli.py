"""``bench/run.py`` refuses to measure without a chip, without the system
under test beside it, or with program selectors forced from outside."""
import json
import os
import shutil
import subprocess
import sys

from bench import spec

ARGS = ["--workload", "sift1m-flat.trickle", "--seed", "3", "--seconds",
        "1", "--trace", "0"]


def _run(root, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    e.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *ARGS], cwd=root, env=e, capture_output=True,
                          text=True, timeout=240)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_cpu_backend_exits_nonzero_without_a_result():
    p = _run(spec.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    _no_result(p)


def test_refuses_forced_selectors():
    p = _run(spec.ROOT, REPRO_BACKEND="ref")
    assert p.returncode != 0 and "REPRO_BACKEND" in p.stderr
    _no_result(p)


def test_bare_benchmark_files_exit_nonzero(tmp_path):
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0 and "src/repro" in p.stderr
    _no_result(p)

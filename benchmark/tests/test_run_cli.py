"""``run.py`` exits non-zero and prints no result without a TPU, and in
a directory that holds only BENCHMARK.json and the benchmark."""
import json
import os
import shutil
import subprocess
import sys

from benchmark import spec


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "small_msg.1chip",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in obj and "correct" not in obj


def test_no_tpu_no_result():
    proc = _run(spec.ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    tmp_path / "benchmark")
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    _no_result(_run(str(tmp_path)))

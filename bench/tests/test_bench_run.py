"""``bench/run.py`` refuses to measure without the chip it was given."""
import os
import subprocess
import sys

from bench.lib import manifest


def _run(cwd, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload",
         "batch-tiny", "--seed", "2147483659", "--seconds", "1", "--trace",
         "0", *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_exits_nonzero_without_a_tpu():
    p = _run(manifest.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no chip" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no program."""
    import shutil
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(manifest.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""

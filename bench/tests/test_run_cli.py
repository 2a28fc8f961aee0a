"""``bench/run.py`` measures nothing and prints no result without a TPU,
nor in a checkout that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

from bench.lib import harness

ROOT = harness.ROOT


def _run(cwd, argv, env_extra=None, code=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    cmd = [sys.executable, "bench/run.py"] + argv if code is None else \
        [sys.executable, "-c", code]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj), line


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT, ["--workload", "cnn_vgg16.b1", "--seed", "1",
                    "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert "nothing was measured" in p.stderr
    _no_result(p.stdout)


def test_unknown_workload_exits_nonzero():
    p = _run(ROOT, ["--workload", "no_such_cell", "--seed", "1",
                    "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    _no_result(p.stdout)


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    # past the look for a chip, the program under test is missing
    code = ("import sys; sys.path.insert(0, 'bench'); import run; "
            "sys.exit(run.main(['--workload', 'cnn_vgg16.b1', '--seed', "
            "'1', '--seconds', '1', '--trace', '0'], require_tpu=False))")
    p = _run(tmp_path, [], code=code)
    assert p.returncode != 0
    _no_result(p.stdout)

"""``run.py`` off the chip: no TPU, or no program beside the benchmark,
is a non-zero exit with no result line."""
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "qwen2-0.5b.decode-long", "--seed", "3000000007",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(p):
    last = p.stdout.strip().splitlines()[-1:] if p.stdout.strip() else []
    return not any(line.lstrip().startswith("{") for line in last)


def test_cpu_only_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in ("chipbench", "tests/chipbench"):
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p)

"""The benchmark runs only where it finds the chips a cell asks for, and
only with the program beside it."""
import os
import shutil
import subprocess
import sys

from bench import common, run


def test_refuses_without_tpu(capsys):
    rc = run.main(["--workload", "train-minitron4b-1chip", "--seed",
                   str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "no TPU" in out.err


def test_refuses_unknown_cell(capsys):
    rc = run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"])
    assert rc == 2 and capsys.readouterr().out == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)}
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "serve-dscoder33b-chat", "--seed", "5", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""

"""No result off the chip, and none without the program beside it."""
import shutil
import subprocess
import sys

from benchmarks.onchip import run, spec


def test_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "smollm360m.chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "TPU" in out.err


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.onchip.run", "--workload",
         "smollm360m.chat", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""

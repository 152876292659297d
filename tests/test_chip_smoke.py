"""chip_smoke.py off the chip: the CPU rehearsal, the refusal without a
TPU, and the compile-cache helper it shares with the launchers."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.common import compile_cache

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_rehearsal_prints_the_contract_line(capsys, monkeypatch, tmp_path):
    # the helper then sets nothing: the rehearsal writes no cache entries
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert chip_smoke.main(["--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert any("[mingru] greedy tokens" in ln for ln in lines)
    assert any("[gqa_paged] paged decode read" in ln for ln in lines)
    last = json.loads(lines[-1])
    dev = jax.devices()[0]
    assert last == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": 1}}


def test_refuses_a_non_tpu_platform(capsys, monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_cache_helper_honours_the_env_var(monkeypatch, tmp_path,
                                          restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_defaults_to_the_checkout(monkeypatch,
                                               restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path   # fixed, no temp

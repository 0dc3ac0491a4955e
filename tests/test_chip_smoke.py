"""Set-up shared by the chip entry points: ``chip_smoke.py`` refuses to
report success anywhere but on a TPU, and the launchers' compile cache
lands where ``JAX_COMPILATION_CACHE_DIR`` says or at one fixed path."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import runtime

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(script: pathlib.Path, cwd: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("alone", [False, True],
                         ids=["checkout", "script_alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    script = ROOT / "chip_smoke.py"
    if alone:            # a directory holding the script and nothing else
        script = pathlib.Path(shutil.copy(script, tmp_path))
    p = _run(script, script.parent)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "FAILED: no TPU" in p.stderr


def test_compile_cache_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert runtime.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads env
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert runtime.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()

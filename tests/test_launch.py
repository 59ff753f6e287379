"""Entry points: the compile-cache placement and the chip smoke run's
refusal to run anywhere but on a TPU."""
import importlib.util

import jax
import pytest

from repro.launch.serve import REPO_ROOT, use_compile_cache


def test_compile_cache_dir_from_env_or_fixed_in_checkout(monkeypatch, tmp_path):
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(REPO_ROOT / ".jax_cache")
        assert use_compile_cache() == use_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    ignored = (REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_chip_smoke_refuses_cpu(monkeypatch, capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO_ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.require_tpu()
    assert e.value.code not in (0, None)
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert '"ok": true' not in capsys.readouterr().out

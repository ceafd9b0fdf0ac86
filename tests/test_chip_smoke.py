"""CPU-side checks of the chip entry points: `chip_smoke.py` refuses a
non-TPU backend, and the compile-cache helper places JAX's persistent cache
where `repro.utils.compile_cache` documents."""
import importlib.util
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.utils.compile_cache import CACHE_ENV, configure_compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_chip_smoke_refuses_cpu_backend(argv, capsys):
    with pytest.raises(SystemExit) as exc:
        _chip_smoke().main(argv)
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert '"ok": true' not in capsys.readouterr().out


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                             restore_cache_dir):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    # what JAX itself configures when the variable is set at start-up
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    want = os.path.join(_ROOT, ".jax_cache")
    assert configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert configure_compile_cache() == want    # stable across calls

"""What the program decides from the platform it finds: Pallas interpret
mode, and where the entry points keep JAX's compile cache."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import interpret
from repro.launch.compile_cache import CHECKOUT, CompileCounter, enable_compile_cache


@pytest.mark.parametrize("backend,default", [("cpu", True), ("tpu", False)])
def test_pallas_interpret_follows_platform(monkeypatch, backend, default):
    monkeypatch.setattr(interpret.jax, "default_backend", lambda: backend)
    assert interpret.pallas_interpret() is default
    assert interpret.pallas_interpret(False) is False


def test_pallas_interpret_refused_on_tpu(monkeypatch):
    monkeypatch.setattr(interpret.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="not allowed on a TPU"):
        interpret.pallas_interpret(True)


def test_compile_cache_dir_env_else_checkout(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(CHECKOUT / ".jax_cache")
        assert enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert ".jax_cache/" in (CHECKOUT / ".gitignore").read_text().split()


def test_compile_counter_counts_each_shape_once():
    x7, x9 = jnp.ones(7), jnp.ones(9)      # built before counting
    counter = CompileCounter()
    try:
        f = jax.jit(lambda x: x * 3 + 1)
        f(x7).block_until_ready()
        f(x7).block_until_ready()
        assert counter.built == 1
        f(x9).block_until_ready()
        assert counter.built == 2
        assert "2 executables built" in counter.summary("somewhere")
    finally:
        counter.close()

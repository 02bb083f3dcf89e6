"""Process-wide JAX switches: the x64 helper, the compile-cache
placement, and the f32 matmul precision the thermal models require."""
import threading

import jax
import jax.numpy as jnp
import pytest

import repro.core  # noqa: F401 — sets the default matmul precision
from repro import runtime


def test_x64_is_thread_local():
    seen = {}

    def other():
        seen["other"] = jnp.asarray(1.0).dtype

    with runtime.x64():
        seen["inside"] = jnp.asarray(1.0).dtype
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=60)
    assert not t.is_alive()
    assert seen["inside"] == jnp.float64
    assert seen["other"] == jnp.float32      # the flag did not leak
    assert jnp.asarray(1.0).dtype == jnp.float32


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "/var/cache/jax-thermal"])
def test_compile_cache_placement(env, monkeypatch, cache_dir_restored):
    if env is None:
        monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
        want = str(runtime.REPO_ROOT / ".jax_cache")
    else:
        monkeypatch.setenv(runtime.CACHE_ENV, env)
        want = env
    assert runtime.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert (runtime.REPO_ROOT / "src" / "repro" / "runtime.py").is_file()


def test_core_requires_f32_matmuls():
    """Importing repro.core makes un-annotated matmuls full f32: a TPU's
    default precision rounds f32 operands to bf16."""
    assert jax.config.jax_default_matmul_precision == "highest"
    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    text = jax.jit(lambda a, b: a @ b).lower(x, x).as_text()
    assert "HIGHEST" in text

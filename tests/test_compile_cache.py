"""Where the persistent compilation cache goes, and what the kernel dispatch
follows."""
import types

import jax

from repro.kernels import flags
from repro.parallel import mesh_context
from repro.utils import compile_cache


def test_compile_cache_follows_env_then_checkout(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        jax.config.update("jax_compilation_cache_dir", prev)
        assert compile_cache.enable_compile_cache() == "/some/where"
        assert jax.config.jax_compilation_cache_dir == prev  # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CACHE_DIR)
        assert compile_cache.CACHE_DIR.name == ".jax_cache"
        assert (compile_cache.CACHE_DIR.parent / "pyproject.toml").exists()  # the checkout root
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_kernel_dispatch_follows_backend_not_mesh(monkeypatch):
    """On a TPU the kernels run, inside a multi-device mesh too: a caller
    that must not partition them (the sharded round) turns them off with
    ``force_pallas(False)`` itself."""
    monkeypatch.setattr(flags.jax, "default_backend", lambda: "tpu")
    assert flags.use_pallas()
    with mesh_context(types.SimpleNamespace(size=4)):
        assert flags.use_pallas()
        with flags.force_pallas(False):
            assert not flags.use_pallas()
    monkeypatch.setattr(flags.jax, "default_backend", lambda: "cpu")
    assert not flags.use_pallas()

"""The platform rule behind kernel defaults, and the compile-cache helper."""

from __future__ import annotations

import jax

from repro import compile_cache
from repro.configs import get_config
from repro.kernels import cpm_kernels as K
from repro.kernels import ops
from repro.serve import Engine


def _defaults():
    cfg = get_config("granite-8b").smoke()
    engine = Engine(cfg, params={}, max_len=16)
    return {"ops": ops._mode(None),
            "interpret": K.resolve_interpret(None),
            "backend": K.resolve_backend(None),
            "engine": engine.cpm_backend}


def test_defaults_follow_the_platform(monkeypatch):
    """Compiled Pallas when JAX reports a TPU, the jnp reference on the
    CPU; an explicit choice wins on either."""
    assert jax.default_backend() == "cpu"
    assert _defaults() == {"ops": "ref", "interpret": True,
                           "backend": "reference", "engine": "reference"}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _defaults() == {"ops": "pallas", "interpret": False,
                           "backend": "pallas", "engine": "pallas"}
    assert K.resolve_interpret(True) is True
    assert K.resolve_backend("reference") == "reference"
    with ops.use_impl("ref"):
        assert ops._mode(None) == "ref"
        assert ops._mode("interpret") == "interpret"
    monkeypatch.undo()
    assert _defaults()["ops"] == "ref"


def test_compile_cache_dir(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
    otherwise the cache goes to the checkout's fixed ``.jax_cache``."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
        assert (compile_cache.DEFAULT_DIR.parent / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.enable_compile_cache() == got   # fixed path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

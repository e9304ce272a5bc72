"""Where the program decides how it runs: the Pallas execution mode and
the persistent compilation cache."""

from __future__ import annotations

import pathlib

import jax
import pytest

from repro.configs.opto_vit import get_config
from repro.core.backend import ExecPolicy, interpret_default
from repro.launch import compile_cache

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def test_interpret_follows_the_platform():
    """Kernels are interpreted exactly where JAX runs on the CPU; an
    explicit value (what tests that steer it pass) always wins."""
    assert jax.default_backend() == "cpu"
    assert interpret_default() is True
    cfg = get_config("tiny", 96).with_(matmul_backend="photonic_pallas")
    assert ExecPolicy.from_cfg(cfg).interpret is True
    assert ExecPolicy().interpret is True
    assert ExecPolicy(interpret=False).interpret is False


def test_nothing_reads_a_config_interpret_flag():
    """The execution mode has one source; no config field shadows it."""
    hits = [str(p.relative_to(SRC)) for p in SRC.rglob("*.py")
            if "pallas_interpret" in p.read_text()]
    assert hits == []
    assert not hasattr(get_config("tiny", 96), "pallas_interpret")


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch,
                                                       cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = str(SRC.parent / ".jax_cache")
    assert compile_cache.enable_compile_cache() == expected
    assert jax.config.jax_compilation_cache_dir == expected
    # the same path on every call: a moving directory would never hit
    assert compile_cache.enable_compile_cache() == expected


def test_compile_cache_env_var_wins(monkeypatch, cache_dir_restored):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    # left to JAX's own reading of the variable: no directory set in code
    assert jax.config.jax_compilation_cache_dir is None

"""Where JAX's persistent compilation cache lands (repro.compile_cache), and
the benchmark suite's exit code."""
from __future__ import annotations

import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache as cc

_KNOBS = ("jax_compilation_cache_dir",
          "jax_persistent_cache_min_entry_size_bytes",
          "jax_persistent_cache_min_compile_time_secs",
          "jax_compilation_cache_include_metadata_in_key")


@pytest.fixture
def restore_cache_config():
    """Put jax's cache settings back, so later tests in this worker compile
    exactly as before."""
    prev = {k: getattr(jax.config, k) for k in _KNOBS}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("path", [None, "elsewhere"])
def test_env_var_wins(tmp_path, monkeypatch, restore_cache_config, path):
    env_dir = str(tmp_path / "env_cache")
    monkeypatch.setenv(cc.ENV_VAR, env_dir)
    arg = None if path is None else str(tmp_path / path)
    assert cc.default_compile_cache_dir() == env_dir
    assert cc.enable_compile_cache(arg) == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir


def test_default_is_fixed_path_in_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert cc.default_compile_cache_dir() == want
    assert cc.default_compile_cache_dir() == want  # the same on every call
    assert cc.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(root, ".gitignore")) as f:
        assert "/.jax_cache/" in f.read().split()


def test_explicit_path_without_env(tmp_path, monkeypatch, restore_cache_config):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    assert cc.enable_compile_cache(str(tmp_path)) == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_a_cached_executable_keeps_its_own_named_scopes(
        tmp_path, monkeypatch, restore_cache_config):
    """Two programs that differ only in a named scope: the second is not
    served the first's executable, whose metadata names the other scope."""
    import jax.numpy as jnp

    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    cc.enable_compile_cache(str(tmp_path))

    def scoped(name):
        def f(x):
            with jax.named_scope(name):
                return jnp.tanh(x) * 2
        return f

    x = jnp.ones(8)
    first = jax.jit(scoped("sde_forward")).lower(x).compile().as_text()
    second = jax.jit(scoped("sde_loss")).lower(x).compile().as_text()
    assert "sde_forward" in first
    assert "sde_loss" in second and "sde_forward" not in second
    assert len(os.listdir(tmp_path)) >= 2


def test_engine_config_routes_through_helper(tmp_path, monkeypatch,
                                             restore_cache_config):
    import jax.numpy as jnp

    from repro.core import SDETerm
    from repro.serving import SDESampleConfig, SDESampleEngine

    env_dir = str(tmp_path / "env_cache")
    monkeypatch.setenv(cc.ENV_VAR, env_dir)
    term = SDETerm(drift=lambda t, y, a: -y,
                   diffusion=lambda t, y, a: jnp.ones_like(y),
                   noise="diagonal")
    SDESampleEngine(term, jnp.ones(2), SDESampleConfig(
        slots=4, compile_cache_dir=str(tmp_path / "ignored")))
    assert jax.config.jax_compilation_cache_dir == env_dir


@pytest.mark.parametrize("failing", [None, "table2_vol"])
def test_benchmark_suite_exit_code(monkeypatch, failing):
    import benchmarks.run as suite
    from benchmarks import (bench_throughput, fig_convergence, table1_ou,
                            table2_vol, table3_kuramoto, table4_sphere,
                            table7_gbm)

    def boom():
        raise RuntimeError("injected")

    for mod in (bench_throughput, fig_convergence, table1_ou, table2_vol,
                table3_kuramoto, table4_sphere, table7_gbm):
        name = mod.__name__.split(".")[-1]
        monkeypatch.setattr(mod, "run", boom if name == failing else
                            (lambda: None))
    if failing is None:
        suite.main()
        return
    with pytest.raises(SystemExit) as exc:
        suite.main()
    assert exc.value.code == f"benchmarks failed: {failing}"

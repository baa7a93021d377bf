"""The benchmark's tests run float32 on the CPU, as the benchmark does on the
chip, whatever another conftest set for the rest of the suite.  A run of
the harness turns JAX's persistent compilation cache on; ``cpu_run`` points
it at a temporary directory and restores the process's settings after the
test, so that tests which follow in the same process see none of it."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

_SAVED = ("jax_enable_x64", "jax_compilation_cache_dir",
          "jax_persistent_cache_min_entry_size_bytes",
          "jax_persistent_cache_min_compile_time_secs",
          "jax_default_matmul_precision")


@pytest.fixture
def cpu_run(tmp_path, monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    old = {k: getattr(jax.config, k) for k in _SAVED}
    jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.fixture
def cpu_peaks(monkeypatch):
    """A peak table entry for the CPU, so a traced CPU run can be reduced."""
    from bench import common

    monkeypatch.setattr(common, "peaks",
                        lambda kind: {"bf16_flops_per_s": 1e12})

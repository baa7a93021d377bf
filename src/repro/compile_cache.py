"""One place that decides where JAX's persistent compilation cache lives.

A later process finds an executable only in the directory an earlier one
wrote it to, so a path that moves between runs (a fresh temporary
directory per process) never hits.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other — an
  explicit ``path`` argument is ignored.
* Otherwise: the caller's ``path`` if given, else ``<repo>/.jax_cache/``, a
  fixed directory inside the checkout (listed in ``.gitignore``).

Entries are keyed with the program's metadata (op names, named scopes,
source locations).  By default JAX strips it from the key, so a program
that differs from a cached one only in its named scopes loads the cached
executable, metadata and all, and a profile of it shows the other program's
names.  The price: an edit that moves source lines compiles once more.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = ["ENV_VAR", "default_compile_cache_dir", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache(path: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Compiled executables are written to (and reloaded from) the directory, so
    a fresh process warm-starts: the first dispatch of a known executable
    pays deserialization instead of XLA compilation.  The size/time floors
    are dropped so even small executables persist — serving executables are
    few (that is the point of bucketing) and re-compiling any of them stalls
    a tick.
    """
    directory = os.environ.get(ENV_VAR) or path or default_compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # jax latches "no cache" at the first compile it ever runs (imports
    # compile little helpers long before an engine exists), and config
    # updates alone do not re-initialize it — reset so the directory takes
    # effect for every compile from here on.
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    return directory

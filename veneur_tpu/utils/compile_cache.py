"""Where JAX's persistent compilation cache lives — the one rule every
entry point shares (cli/server.py, chip_smoke.py, the bench harness and
its children).

The directory is part of a cache entry's key, so it must be the same
path run after run: where ``JAX_COMPILATION_CACHE_DIR`` is set, the
outside placed the cache — JAX reads the variable itself and no code
here touches the directory setting; where it is unset, the cache is
``<checkout>/.xla_cache`` (git-ignored). Never a temp name, a pid or a
timestamp.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<checkout>/.xla_cache``: beside the package directory."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".xla_cache")


def configure(env=None) -> str:
    """Apply the rule and return the directory in effect.

    With `env` (a child process's environment mapping) only the mapping
    is filled in — the child's JAX reads the variable at start-up. With
    no argument this process is configured; call it before the first
    dispatch. JAX binds the variable when it is imported, so the unset
    case also updates the live config, and exports the variable so
    children inherit the same directory."""
    if env is not None:
        return env.setdefault(ENV_VAR, default_dir())
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    path = os.environ[ENV_VAR] = default_dir()
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    return path

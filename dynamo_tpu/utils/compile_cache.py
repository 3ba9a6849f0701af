"""Persistent XLA compilation cache, placed from outside.

The engine compiles one program per step variant (prefill buckets x row
counts, decode widths, sampling variants); a cold start pays for every
one of them. JAX's persistent cache keys an entry on the program AND on
the cache directory's path, so the directory must not move between
runs:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself at import; this
  module sets nothing.
- unset: one fixed directory inside the checkout, `<repo>/.jax_cache`
  (listed in `.gitignore`) — never a temp name, pid or timestamp. The
  CPU backend is left alone: its compiles are short, and XLA:CPU's
  ahead-of-time loader complains about every entry it reads back.

`JaxEngine.__init__` calls `configure()`, so every entry point that
builds an engine (run CLI, sdk workers, bench, chip_smoke) passes
through it.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def resolve_dir() -> str:
    """The cache directory this process uses: the environment's when
    set, else the fixed in-checkout path."""
    return os.environ.get(ENV_VAR) or os.path.join(_REPO_ROOT, ".jax_cache")


def configure() -> str:
    """Point JAX's persistent compilation cache at `resolve_dir()`.
    Idempotent; a no-op on the config when the environment already
    placed the cache."""
    import jax

    path = resolve_dir()
    if not os.environ.get(ENV_VAR) and jax.default_backend() != "cpu":
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
    return path

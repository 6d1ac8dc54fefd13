"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles for a device (chip_smoke.py,
bench.py, ``cmd.convert`` on the jax/fused backends, children they
start): if ``JAX_COMPILATION_CACHE_DIR`` is set in the environment, that
directory is the cache and nothing here overrides it; if not, the cache
is a fixed directory inside the checkout (git-ignored). The path is part
of the cache key, so it is never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def cache_dir() -> str:
    return os.environ.get(ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Point this process's JAX at :func:`cache_dir` (call before the
    first compile). JAX reads the environment variable itself; only the
    unset case needs telling."""
    path = cache_dir()
    if not os.environ.get(ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def child_env(env: dict | None = None) -> dict:
    """``env`` (default: this process's) with the cache placed for a
    child process by the same rule."""
    env = dict(os.environ if env is None else env)
    env[ENV] = env.get(ENV) or cache_dir()
    return env

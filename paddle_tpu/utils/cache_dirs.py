"""Where compiled programs are kept between processes.

Two caches, one rule: the location is decided from outside the program
or is a fixed path under the checkout, never ``/tmp``, ``mkdtemp``, a
pid or a timestamp (the path is part of JAX's cache key, so a directory
that moves never hits).

  * JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it, else ``<checkout>/.cache/jax``.
  * the persistent executable cache (``jit/persistent_cache.py``,
    ``FLAGS_executable_cache_dir``) where a tool defaults it:
    ``<checkout>/.cache/exec``.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def jax_compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(_CHECKOUT, ".cache", "jax")


def enable_jax_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on at
    :func:`jax_compile_cache_dir`; returns the directory."""
    import jax
    d = jax_compile_cache_dir()
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    return d


def executable_cache_dir(name: str) -> str:
    """A tool's default ``FLAGS_executable_cache_dir``: one fixed
    sub-directory per tool, so unrelated runs do not share entries."""
    return os.path.join(_CHECKOUT, ".cache", "exec", name)

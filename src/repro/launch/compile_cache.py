"""JAX's persistent compilation cache, placed from outside the program.

A chip run compiles every stage and kernel program; the cache lets the next
process that compiles the same program load it instead. Its directory is
part of each entry's key, so it must not move between runs:

  * `JAX_COMPILATION_CACHE_DIR` set -> JAX reads it itself; nothing else is
    set here.
  * unset -> `<checkout>/.jax_cache` (listed in .gitignore).

Call `use_compile_cache()` from a program's `main`, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)


__all__ = ["use_compile_cache", "CHECKOUT_CACHE"]

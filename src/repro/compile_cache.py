"""Where JAX keeps its persistent compilation cache.

A chip run compiles for minutes, and a second process or a second call of
the same program should find that work again.  JAX keys each entry by the
cache path too, so the path must not move: ``JAX_COMPILATION_CACHE_DIR``,
when set, is used as it is; otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` inside the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that path.  Call it at the start of an entry point."""
    path = os.environ.get(ENV)
    if path:
        return path                      # JAX reads the variable itself
    path = str(DEFAULT_DIR)
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    else:
        os.environ[ENV] = path           # read when jax is first imported
    return path

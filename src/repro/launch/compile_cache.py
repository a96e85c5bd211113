"""Where JAX's persistent compilation cache lives.

A process that starts with no compiled code spends its first minute or more
compiling the train step and the sync kernels. JAX's persistent cache saves
that on the next run only if the next run looks in the same directory, so
the directory is a fixed path: never one built from a temporary name, a
process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured by JAX itself and
    nothing is changed. Otherwise the cache goes to ``<checkout>/.jax_cache``.
    Call it from an entry point's ``main()`` before the first compile, never
    at import time.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)

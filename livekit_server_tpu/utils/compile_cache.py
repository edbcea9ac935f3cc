"""Where the persistent XLA compilation cache lives.

One rule for `serve`, `chip_smoke.py` and `bench.py`: where
JAX_COMPILATION_CACHE_DIR is set, JAX itself uses that directory and
this module sets nothing; otherwise the cache is one fixed directory
inside the checkout (the path is part of the cache's key, so a path made
from a hash, a pid or a temp name would never hit). Tests call none of
this and stay cache-free (tests/conftest.py says why).
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str | None:
    """Turn the persistent cache on for a TPU process; return its
    directory. On any other backend nothing is set and None returned:
    XLA:CPU entries written by clean runs have deserialized into
    miscompiled executables here (tests/conftest.py)."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)

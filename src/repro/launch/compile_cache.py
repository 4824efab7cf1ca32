"""JAX's persistent compilation cache, switched on by the entry points
(`chip_smoke.py`, `repro.launch.dmf_train`, `benchmarks.run`) and never on
library import, so tests and library users keep JAX's own setting."""
from __future__ import annotations

import os
import pathlib

import jax

# fixed and inside the checkout: the cache key includes the directory, so a
# path that moves between runs would never hit (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory. ``JAX_COMPILATION_CACHE_DIR``,
    when set, is already JAX's setting and is left alone; otherwise the
    cache goes to `DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

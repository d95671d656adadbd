"""Where JAX's persistent compilation cache lives.

A chip run starts with no compiled code, and the 125M train step plus the
engine's program families take minutes to compile from cold. The cache
directory is part of the cache key's surroundings: a directory that moves
(a temp name, a pid, a time) never hits. So it is placed once, here:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing — the caller's environment decides;
* unset: ``<checkout>/.jax_cache``, derived from this file's location
  (listed in ``.gitignore``).

Entry points that run on the chip (``chip_smoke.py``, ``bench.py``) call
:func:`place_compile_cache` first thing. Tests do not.
"""

from __future__ import annotations

import os
import pathlib

import jax

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def place_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

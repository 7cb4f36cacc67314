"""Persistent XLA compilation cache for the entry points.

`enable()` is called by the programs a user runs (`chip_smoke.py`,
`examples/*.py`, `launch/train.py`), never on import, so library code and
tests stay cache-free. With `JAX_COMPILATION_CACHE_DIR` set, JAX already
reads it and nothing is changed here; otherwise the cache lives at the
fixed path `<checkout>/.jax_cache` — fixed because the directory is part of
the cache key, so a path built from a temp name, pid or time never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: `<checkout>/.jax_cache` (this file is `<checkout>/src/repro/launch/...`).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

"""Per-process JAX settings: which processes may own the chip, and where
compiled programs are cached.

A TPU chip belongs to one process at a time.  The process that serves or
trains holds it; every helper process it starts (dispatcher workers, store
shards, benchmark clients) calls :func:`pin_to_cpu` before it first touches
a device, or it would try to open the same chip and fail or hang.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout: the path is part of the persistent cache's
# key, so a directory built from a tmp name, a pid or the time never hits
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def pin_to_cpu() -> None:
    """Keep this process, and the processes it starts, on the CPU backend.

    Call it before the process first touches a device: JAX picks its
    backends once, on first use."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins;
    otherwise the cache lives at :data:`COMPILE_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)

"""Persistent XLA compilation cache for the framework's compile chokepoints.

JAX's built-in persistent cache keys entries by serialized HLO + jaxlib
version + device topology, so a cache written on one toolchain or topology
never mis-hits on another. The directory is part of the key too: a cache
that moves never hits, so it is placed once and from outside the program.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module sets
  no directory, only the thresholds.
* unset: ``<repo>/.jax_cache`` (gitignored).

``ensure()`` is called where the framework compiles the programs that take
minutes — ``Plan.compile`` / ``Plan.train_step``, ``LLMEngine``'s step
functions, ``profiler/xmem.py::aot_compile`` and ``tools/pod_report.py`` —
so a second process on the same machine starts warm. Turn the cache off
with JAX's own switch (``JAX_ENABLE_COMPILATION_CACHE=false``).
"""
from __future__ import annotations

import os

__all__ = ["ensure", "cache_dir"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_done = False


def cache_dir() -> str:
    """The directory the persistent cache lives in."""
    return os.environ.get(_ENV) or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def ensure() -> str:
    """Turn the persistent cache on for this process (idempotent) and
    return its directory."""
    global _done
    if not _done:
        import jax
        if not os.environ.get(_ENV):
            jax.config.update("jax_compilation_cache_dir", cache_dir())
        # skip sub-2s compiles (the cache round trip costs more than it
        # saves); keep everything else regardless of size
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _done = True
    return cache_dir()

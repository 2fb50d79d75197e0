"""JAX's persistent compilation cache, placed from outside or in the checkout.

One helper, called by `chip_smoke.py` and every CLI under `repro.launch`
before its first compile:

* `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and this module
  sets no other directory.
* unset: the cache goes to `<checkout>/.jax_cache` — one fixed path (the
  path is part of the cache key, so a directory that moves never hits),
  listed in `.gitignore`.

Every compile is cached, however short (kernels compile in about a
second). `CacheHits` counts the hits JAX reports through `jax.monitoring`.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CacheHits:
    """Running count of persistent-cache hits in this process."""

    count = 0
    _listening = False

    @classmethod
    def _on_event(cls, event: str, **_) -> None:
        if event == _HIT_EVENT:
            cls.count += 1

    @classmethod
    def listen(cls) -> None:
        if not cls._listening:
            jax.monitoring.register_event_listener(cls._on_event)
            cls._listening = True


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    CacheHits.listen()
    return path


__all__ = ["CacheHits", "DEFAULT_DIR", "ENV_VAR", "enable_compile_cache"]

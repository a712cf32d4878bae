"""JAX's persistent compilation cache, placed from outside or in the checkout.

``enable_compile_cache()`` is called at the device entry points
(``chip_smoke.py`` and the first jit the tensorized residual builds). When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the cache lives at ``<checkout>/.jax_cache``, a
fixed path (the cache key includes nothing that moves between runs, so a
second run in the same checkout finds the first run's programs). The
thresholds are lowered so that every residual stage is cached, however
quickly it compiled. On the CPU backend the tests run on, the cache stays
as JAX's own settings leave it: it exists for the accelerator's programs.
"""
from __future__ import annotations

import os
import threading
from typing import Optional

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")

_LOCK = threading.Lock()
_DONE = False


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on (once per process) unless the backend
    is the CPU; returns the cache directory JAX uses, or ``None``."""
    global _DONE
    import jax
    with _LOCK:
        if not _DONE:
            _DONE = True
            if jax.default_backend() != "cpu":
                if not jax.config.jax_compilation_cache_dir:
                    jax.config.update("jax_compilation_cache_dir",
                                      CHECKOUT_CACHE_DIR)
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", 0)
                jax.config.update(
                    "jax_persistent_cache_min_entry_size_bytes", -1)
        return jax.config.jax_compilation_cache_dir or None

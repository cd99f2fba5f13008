"""JAX's persistent compilation cache for the entry points, and a count of
the executables a run built and of those the cache supplied.

``JAX_COMPILATION_CACHE_DIR``, where set, places the cache (JAX reads the
variable itself). Otherwise it goes to ``.jax_cache/`` at the root of the
checkout: a fixed path, because the path is part of what a later run must
find again. ``.gitignore`` lists it.
"""
from __future__ import annotations

import os
import pathlib

import jax
from jax import monitoring

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]

_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts, from construction to ``close()``, the executables built
    (``built``: each one compiled or read from the persistent cache) and
    the persistent-cache hits among them (``cache_hits``)."""

    def __init__(self) -> None:
        self.built = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == _BUILD_EVENT:
            self.built += 1

    def _on_event(self, event: str, **kwargs) -> None:
        if event == _HIT_EVENT:
            self.cache_hits += 1

    def close(self) -> None:
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def summary(self, cache_dir: str) -> str:
        return (
            f"compile cache {cache_dir}: {self.built} executables built, "
            f"{self.cache_hits} read from the cache "
            f"({'hit' if self.cache_hits else 'no hit'})"
        )

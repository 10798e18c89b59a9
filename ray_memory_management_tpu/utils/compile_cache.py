"""Where every process of the runtime keeps jax's persistent compile cache.

The chip path compiles in several short-lived processes (a leased worker is
retired when its lease ends), so what one compiled the next must find. The
rule: where ``JAX_COMPILATION_CACHE_DIR`` is set from outside, every process
uses it and nothing here names another directory; where it is not, the cache
is ONE fixed directory in the checkout (the path is part of jax's cache key,
so a temporary name, a pid or a time would never hit). jax itself reads the
variable when it is imported, so the rule is carried by the environment:
``package_env`` exports it to everything the runtime spawns, ``init`` adopts
it for the driver.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Dict, MutableMapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE_EVENT = "/jax/compilation_cache/cache_misses"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def default_dir() -> str:
    """The fixed in-checkout cache directory (git-ignored)."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_compile_cache")


def export(env: MutableMapping[str, str]) -> None:
    """Make ``env`` (a child's environment) carry the cache directory:
    untouched when already set, else the fixed default."""
    env.setdefault(ENV_VAR, default_dir())


def adopt() -> str:
    """Apply the rule to THIS process and return the directory. A jax
    imported before the variable existed read no directory; it is handed
    the same one its children will get. Never imports jax."""
    export(os.environ)
    path = os.environ[ENV_VAR]
    jax = sys.modules.get("jax")
    if jax is not None and jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts this process's XLA compile requests through jax.monitoring:
    how many programs were asked for, how many of those the persistent
    cache answered, how many it stored, and the seconds spent (a cache
    hit's retrieval included). Counting starts at construction."""

    def __init__(self):
        import jax.monitoring as monitoring

        self._lock = threading.Lock()
        self.programs = 0       # guarded-by: _lock
        self.cache_hits = 0     # guarded-by: _lock
        self.cache_writes = 0   # guarded-by: _lock
        self.seconds = 0.0      # guarded-by: _lock
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1
        elif event == _CACHE_WRITE_EVENT:
            with self._lock:
                self.cache_writes += 1

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.programs += 1
                self.seconds += duration

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"programs": self.programs,
                    "compiled": self.programs - self.cache_hits,
                    "cache_hits": self.cache_hits,
                    "cache_writes": self.cache_writes,
                    "seconds": self.seconds}

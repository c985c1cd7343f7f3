"""Compilation and device-memory watch.

``CompileClock`` counts XLA backend compiles proper. JAX records one
backend-compile event for every program it loads, whether the XLA
compiler ran or the persistent cache supplied the program, and one
cache-hit event for the latter; a compile is the difference. ``CacheLog``
records which programs the persistent compilation cache missed, and why
an entry was not written, from JAX's own log records. ``peak_bytes`` is
the device allocator's peak.
"""
from __future__ import annotations

import logging
import os
import re
from pathlib import Path
from typing import Any, List, Optional

import jax

_MISS = re.compile(r"PERSISTENT COMPILATION CACHE MISS for '([^']*)'")
_NOT_WRITTEN = re.compile(r"Not writing persistent cache entry for '([^']*)'"
                          r"(.*)")
_LOADED = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"

# a fixed path inside the checkout: the benchmark's persistent cache
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


class CompileClock:
    """Programs loaded, programs the persistent cache supplied, and the
    seconds spent loading them (compiling or reading the cache)."""

    def __init__(self):
        self.seconds = 0.0
        self.loaded = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    @property
    def count(self) -> int:
        """Backend compiles: programs loaded that the cache did not supply."""
        return self.loaded - self.hits

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        if event == _LOADED:
            self.seconds += duration
            self.loaded += 1

    def _on_event(self, event: str, **_: Any) -> None:
        if event == _HIT:
            self.hits += 1


class CacheLog(logging.Handler):
    """Persistent-cache misses and unwritten entries, by program name."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.misses: List[str] = []
        self.not_written: List[str] = []
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.addHandler(self)
        log.propagate = False

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        m = _MISS.search(msg)
        if m:
            self.misses.append(m.group(1))
        m = _NOT_WRITTEN.search(msg)
        if m:
            self.not_written.append(f"{m.group(1)}{m.group(2)}")


def use_persistent_cache(path: Optional[str] = None) -> str:
    """Keep JAX's persistent compilation cache in ``path`` (by default
    ``<checkout>/.jax_cache``, whatever the environment says) and write
    every program to it however short its compile and small its entry.
    The program's own cache helper is handed the same directory."""
    from repro.launch.compile_cache import use_compile_cache

    path = str(path or CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


_WATCH = {}


def watch() -> "tuple[CompileClock, CacheLog]":
    """The process's one compile clock and cache log (JAX's listeners
    cannot be removed, so each is registered once)."""
    if not _WATCH:
        _WATCH["clock"], _WATCH["cache"] = CompileClock(), CacheLog()
    return _WATCH["clock"], _WATCH["cache"]


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else int(peak)


def live_bytes() -> int:
    """Bytes held by live JAX arrays in this process."""
    return sum(a.nbytes for a in jax.live_arrays())

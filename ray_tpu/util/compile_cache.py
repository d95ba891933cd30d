"""Where compiled XLA programs are kept between processes.

A cold 1B train step plus the serve engine's prefill buckets and tick is
minutes of compilation; JAX's persistent compilation cache turns the
second process into a file read. The directory is part of the cache key,
so it must not move: if ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
and nothing here overrides it; otherwise the cache lives at ONE fixed,
git-ignored path inside the checkout. No other code sets a directory.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")

_lock = threading.Lock()
_counts = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _lock:
            _counts["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        with _lock:
            _counts["misses"] += 1


def ensure() -> str:
    """Place the persistent compilation cache and start counting its
    hits and misses. Call before the process's first compile; returns
    the directory in use. Idempotent."""
    global _listening
    import jax

    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if jax.config.jax_compilation_cache_dir != _DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR


def counts() -> Dict[str, int]:
    """Persistent-cache lookups by this process since :func:`ensure`:
    a hit skipped a compile, a miss compiled and wrote an entry."""
    with _lock:
        return dict(_counts)

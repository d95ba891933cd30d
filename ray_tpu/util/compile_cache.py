"""Where compiled XLA programs are kept between processes.

A cold 1B train step plus the serve engine's prefill buckets and tick is
minutes of compilation; JAX's persistent compilation cache turns the
second process into a file read. The directory is part of the cache key,
so it must not move: if ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
and nothing here overrides it; otherwise the cache lives at ONE fixed,
git-ignored path inside the checkout. No other code sets a directory.

What is cached must also be FOUND again: a program's key is made of its
lowered text, and :func:`ensure` keeps source positions out of that text
(see there), so an edit that moves lines re-keys nothing it did not
change.
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
    # A Mosaic kernel's body is serialized into the program WITH its
    # operations' locations, and is part of the cache key. JAX writes
    # file, line and column of up to this many caller frames into every
    # location (10 by default): with any, one line added above any
    # kernel's call re-keys every program that holds a kernel, in every
    # cell (PR 53: 25 of 210 programs, 80 s of set-up become 300).
    jax.config.update("jax_traceback_in_locations_limit", 0)
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

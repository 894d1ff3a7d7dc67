"""JAX's persistent compilation cache, kept at a fixed place.

``JAX_COMPILATION_CACHE_DIR``, when set, is where JAX keeps compiled
programs, and nothing here overrides it. Otherwise the cache lives at
``<repo>/.jax_cache``: a fixed path inside the checkout (the path is part
of what makes a later run find its entries), never a temporary one.
The serve and train drivers and ``chip_smoke.py`` call
:func:`enable_compile_cache` before compiling anything.
"""
from __future__ import annotations

import os
import pathlib
from typing import Dict

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts: Dict[str, int] = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_):
    key = _EVENTS.get(event)
    if key is not None:
        _counts[key] += 1


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def compile_cache_counts() -> Dict[str, int]:
    """Persistent-cache hits and misses seen since the cache was enabled."""
    return dict(_counts)

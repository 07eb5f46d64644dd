"""Persistent compilation cache placement for the entry points.

``enable()`` is called at the start of each launcher, benchmark and the
chip smoke script — never at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX already reads it and this module sets no other directory; otherwise
the cache lives at a fixed path inside the checkout (``.jax_cache/``, listed
in ``.gitignore``), so a second run finds the first run's programs.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_events = {"hits": 0, "misses": 0}
_listening = False


def _count(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _events["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _events["misses"] += 1


def enable() -> str:
    """Turn on the persistent cache; returns the directory in use."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_count)
        _listening = True
    return path


def stats() -> dict:
    """Persistent-cache hits and misses seen by this process so far."""
    return dict(_events)

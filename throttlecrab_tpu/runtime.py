"""Process-level JAX runtime: the persistent compile cache and the device.

Nothing here runs at import.  The entry points that own a process (the
server's ``main()``, ``bench.py``) call :func:`enable_compile_cache`
before their first compile; the tests never do, so they write no cache.
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Optional

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path (the path is part of the cache key), listed in .gitignore.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: Seconds spent compiling (or fetching from the persistent cache) and
#: the cache hits, summed over the process (GET /metrics).
_compile = {"seconds": 0.0, "cache_hits": 0}
_listening = False
_device: Optional[dict] = None


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        _compile["seconds"] += secs


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _compile["cache_hits"] += 1


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; returns its directory
    (None when the cache is switched off, e.g. by
    ``JAX_ENABLE_COMPILATION_CACHE=false``).

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it and
    no other directory is set here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.  Also starts counting compile seconds."""
    import jax

    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    if not jax.config.jax_enable_compilation_cache:
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # Every program, not only those over a second: a serving process
    # compiles dozens of small (K, B) launch shapes.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def compile_stats() -> dict:
    return dict(_compile)


def device_info() -> dict:
    """The backend this process computes on, as JAX reports it:
    ``{"platform", "kind", "count"}`` (the first device's platform and
    device_kind, and the number of devices)."""
    global _device
    if _device is None:
        import jax

        devices = jax.devices()
        _device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
    return _device


def health_suffix() -> str:
    """The device annotation GET /health carries after the state."""
    return "device=" + json.dumps(device_info(), separators=(",", ":"))


def parse_health(body: str) -> Optional[dict]:
    """The device a /health body reports, or None."""
    _, sep, rest = body.partition("device=")
    if not sep:
        return None
    return json.JSONDecoder().raw_decode(rest)[0]

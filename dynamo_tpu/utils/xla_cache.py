"""Persistent XLA compilation cache bootstrap for engine processes.

An engine restart otherwise re-pays every executable's compile; with the
cache, executables deserialize from disk. Every entry point that starts an
engine (run CLI, worker, prefill worker, SDK service worker, chip_smoke's
children) goes through this one helper, and no other site sets
a cache directory.
"""

from __future__ import annotations

import os
from pathlib import Path

from dynamo_tpu.utils.logging import get_logger

log = get_logger("utils.xla_cache")

#: where the cache goes when ``JAX_COMPILATION_CACHE_DIR`` is not set: one
#: fixed, git-ignored path inside the checkout. The path is part of what a
#: cache entry is found by, so it is never made from a temporary name, a pid
#: or the time — a directory that moves never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".xla_cache"

_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_stats: dict = {"dir": None, "hits": 0, "misses": 0}


def _count(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _stats[key] += 1


def cache_stats() -> dict:
    """{"dir", "hits", "misses"} of this process's persistent cache since
    ``enable_compilation_cache()``; dir is None where it was never enabled.
    (/ready of a colocated engine carries it; chip_smoke.py prints it.)"""
    return dict(_stats)


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already honours it and
    nothing is overridden here. Unset, the cache goes to
    ``DEFAULT_CACHE_DIR``. A directory that cannot be created or written is
    an error at start-up, not a warning: an engine that silently recompiles
    everything on every restart is a different deployment from the one asked
    for."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    from_env = bool(path)
    if not from_env:
        path = str(DEFAULT_CACHE_DIR)
    try:
        os.makedirs(path, exist_ok=True)
        probe = os.path.join(path, f".writable.{os.getpid()}")
        with open(probe, "w"):
            pass
        os.remove(probe)
    except OSError as e:
        raise RuntimeError(
            f"XLA compilation cache directory {path!r} is not usable: {e} "
            "(set JAX_COMPILATION_CACHE_DIR to a writable directory)"
        ) from e
    import jax

    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    if _stats["dir"] is None:
        jax.monitoring.register_event_listener(_count)
    _stats["dir"] = path
    log.info("XLA compilation cache: %s (%s)", path,
             "JAX_COMPILATION_CACHE_DIR" if from_env else "default, in the checkout")
    return path

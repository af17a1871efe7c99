"""Persistent XLA compilation cache placement — the ONE place a cache
directory is chosen.

Every process that compiles calls `ensure_compile_cache()` once, before its
first compile: the `dllama` CLI, replica workers, chip_smoke.py's children
and the test suite. The cache directory is part of the cache key,
so it must be identical in every process that should share compiles — a
replica worker, its respawns and the next boot of the same server all hit
what the first one compiled.

  * `JAX_COMPILATION_CACHE_DIR` set: nothing is done in code — JAX reads the
    variable itself, and whoever launched the process owns the placement.
  * unset: one fixed, git-ignored directory inside the checkout
    (`.jax_cache/`). No home directory, temp dir, pid or time in the path.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


# persistent-cache traffic of THIS process, fed by jax.monitoring (the
# compile ledger's /stats block reports it: a warm boot shows hits, a cold
# one misses) — plain ints, written only from the compiling thread
COUNTS = {"hits": 0, "misses": 0}
_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_listening = False


def _on_event(event: str, **_kw) -> None:
    field = _EVENTS.get(event)
    if field is not None:
        COUNTS[field] += 1


def ensure_compile_cache() -> str:
    """Place the cache (see module docstring) and start counting its hits
    and misses. Returns the directory the process will cache compiles in."""
    global _listening
    import jax

    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

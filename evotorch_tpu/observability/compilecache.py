"""Persistent XLA compilation cache wiring — one rule for where it lives.

jax can serialize compiled executables to disk and reload them in later
processes. The flagship generation takes about 25 s to compile for a TPU v5e
per eval contract, so every process after the first skips most of its
start-up tax when it finds the cache.

**The rule.** Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is
the cache and the code sets no other; where it is not, the cache is
``<checkout>/compile_cache`` (the test suite: ``compile_cache/tests`` under
it). Nothing else places the cache — no argument, no second variable — so
whoever runs the program (a driver that mounts a cache, a developer who
wants none of their entries in the checkout) decides from outside, and
``chip_smoke.py``, the bench scripts, the CLIs, the examples and
``conftest.py`` all agree.

:func:`enable_persistent_cache` applies the rule with thresholds lowered to
"cache everything" (the defaults skip entries that compiled in under a
second) and registers monitoring listeners so callers can report hit/miss
provenance (:func:`cache_stats`).

The default directory is gitignored: serialized executables are machine- and
jax-version-specific artifacts, not source.
"""

from __future__ import annotations

import contextlib
import os
from typing import Dict, Optional

import jax
from jax._src import monitoring

# Sibling of bench_curves/ at the repo root; gitignored (machine-local).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "compile_cache",
)

_COUNTS: Dict[str, int] = {"hits": 0, "misses": 0}
_LISTENER_INSTALLED = False
_ENABLED_DIR: Optional[str] = None

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _install_listener() -> None:
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return

    def _on_event(event: str, **kwargs) -> None:
        if event == _HIT_EVENT:
            _COUNTS["hits"] += 1
        elif event == _MISS_EVENT:
            _COUNTS["misses"] += 1

    monitoring.register_event_listener(_on_event)
    _LISTENER_INSTALLED = True


def enable_persistent_cache(subdir: str = "") -> str:
    """Enable jax's persistent compilation cache at the directory the rule
    names (module docstring): ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/compile_cache/<subdir>``. Returns the directory in use.

    Thresholds are dropped to zero so even fast-compiling programs are
    cached. Idempotent.
    """
    global _ENABLED_DIR
    from ..resilience.retry import retry_call

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.normpath(
        os.path.join(DEFAULT_CACHE_DIR, subdir)
    )
    # the cache dir often lives on shared/network storage: creating it
    # retries with bounded backoff (and is fault-injectable at site
    # "compilecache.io"); jax itself degrades to uncached compiles when
    # later entry reads/writes fail, so setup is the only hard IO edge
    retry_call(os.makedirs, path, exist_ok=True, site="compilecache.io")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # jax's own default for this option, like "all", writes a path under the
    # cache directory into the compile options, and the options are hashed
    # into every entry's key: a cache copied or mounted at another path then
    # never hits (shown on the CPU and on the v5e, CHANGES.md PR 21). The
    # XLA-internal caches the option names are GPU autotuning artifacts;
    # nothing here uses them.
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    _install_listener()
    _ENABLED_DIR = path
    return path


@contextlib.contextmanager
def past_persistent_cache():
    """Compile inside as if there were no persistent cache, and put it back
    afterwards. For what must see the program and not the cache's entry: an
    executable deserialized from the cache reports another peak memory than a
    fresh one, and it carries the metadata it was stored with: the cache key
    ignores ``jax.named_scope`` names, so after a scope was added or moved
    (or under a cache written by an older commit) the cached executable names
    its instructions' scopes as they were. The directory option alone is not
    enough: the cache singleton initialises once and keeps what it saw first,
    so the bypass flips the enable flag and resets the singleton. jax's
    in-process caches are the caller's to clear (``jax.clear_caches()``) where
    the same program was already compiled in this process."""
    from jax._src import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def cache_stats() -> Dict[str, object]:
    """Hit/miss counters since :func:`enable_persistent_cache` (this process)."""
    return {
        "enabled": _ENABLED_DIR is not None,
        "dir": _ENABLED_DIR,
        "hits": _COUNTS["hits"],
        "misses": _COUNTS["misses"],
    }

"""Persistent XLA executable cache, shared by tests, bench, and the
driver entry points.

The wave programs of the big actor models take tens of seconds to
compile; the persistent cache lets a warm run skip them. Where it
lives: ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
``.jax_cache/`` at the checkout root (gitignored).

On the **CPU backend the cache is refused**: beyond the loader's
"could lead to execution errors such as SIGILL" warning (XLA:CPU AOT
artifacts embed compile-time pseudo-features like
``+prefer-no-scatter`` that never appear in the host-feature list),
cache-deserialized CPU executables were observed to **mishandle
donated buffers**: the engines' donated visited-table/arena chain read
back with stale slots, zeros, and heap-pointer garbage while counts
stayed right — silent checkpoint corruption (reproduced on the round-5
engine as well, 2026-08-03). Every device engine donates by design.
"""

from __future__ import annotations

import os
import threading

__all__ = ["DEFAULT_CACHE_DIR", "enable_persistent_jit_cache",
           "WaveProgramCache", "shared_program_cache"]

#: compiles cheaper than this aren't worth the disk round-trip
_MIN_COMPILE_SECS = 0.5


class WaveProgramCache:
    """In-process cache of compiled wave programs, shared across engine
    INSTANCES — the job service's amortization layer (ROADMAP item 5:
    the Nth submission of a hot model skips compilation entirely).

    The persistent cache above amortizes compiles across *processes*
    via serialized XLA artifacts (and is refused on CPU — see the
    module doc); this one shares the live compiled callables within a
    process, which is safe on every backend: nothing is serialized, the
    second engine simply calls the same executable the first one built.
    Donation is per-call state, not per-program state, so two engines
    sharing a program each donate their own buffers.

    Keys must capture everything that affects the traced computation:
    the caller prefixes the engine's shape/knob key with a *model key*
    (the corpus registry name + canonical params) — two engines may
    share a program only when their device models are semantically
    identical, which is exactly what a registry key certifies. Ad-hoc
    models (no registry key) never reach this cache. The knobs that
    change the traced program ride in the key too (``pack_arena`` and
    symmetry): a packed-row program and a raw-row program are different
    executables even at identical shapes.

    ``get_or_build`` holds a per-key lock across the build, so N
    concurrent same-model jobs pay ONE compile and N-1 hits instead of
    racing N compiles into the same slot (the acceptance gate observes
    the second job's hit deterministically).

    The cache is bounded (``max_programs``, FIFO eviction): keys embed
    tenant-settable knobs (batch/table shapes; every capacity doubling
    adds an entry), so an unbounded dict would grow process memory for
    the service's lifetime. Eviction only drops the CACHE's reference
    — engines keep the executables they already fetched in their
    instance caches, so a running job never loses its programs.
    """

    def __init__(self, max_programs: int = 256):
        self._programs: dict = {}
        self._locks: dict = {}
        self._mu = threading.Lock()
        self._max = max(1, int(max_programs))
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_build(self, key, build):
        """Returns ``(program, hit)``; ``build()`` runs at most once per
        key across every thread."""
        with self._mu:
            prog = self._programs.get(key)
            if prog is not None:
                self.hits += 1
                return prog, True
            lock = self._locks.setdefault(key, threading.Lock())
        with lock:
            with self._mu:
                prog = self._programs.get(key)
                if prog is not None:
                    self.hits += 1
                    return prog, True
            prog = build()
            with self._mu:
                self._programs[key] = prog
                self.misses += 1
                while len(self._programs) > self._max:
                    oldest = next(iter(self._programs))
                    del self._programs[oldest]
                    self._locks.pop(oldest, None)
                    self.evictions += 1
        return prog, False

    def stats(self) -> dict:
        with self._mu:
            return {"programs": len(self._programs),
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "hit_ratio": round(
                        self.hits / max(1, self.hits + self.misses), 4)}


_SHARED_CACHE: WaveProgramCache | None = None
_SHARED_CACHE_MU = threading.Lock()


def shared_program_cache() -> WaveProgramCache:
    """The process-wide wave-program cache (lazily created); the job
    service hands this to every engine it spawns."""
    global _SHARED_CACHE
    with _SHARED_CACHE_MU:
        if _SHARED_CACHE is None:
            _SHARED_CACHE = WaveProgramCache()
        return _SHARED_CACHE


#: the fixed default cache directory: ``<checkout>/.jax_cache``
#: (gitignored). JAX's own cache key already covers the backend, the
#: device kind and the jax/jaxlib versions, so one directory serves
#: every machine; a path that moved would never hit.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_persistent_jit_cache(platform: str | None = None) -> None:
    """Turns JAX's persistent compilation cache on for this process.

    Call it before the process's first compile: JAX decides once per
    process whether the cache is in use. The device engines call it
    on construction, so ``spawn_tpu_bfs`` and ``check-tpu`` need no
    help from the caller.

    The directory is ``JAX_COMPILATION_CACHE_DIR`` when the
    environment sets it (JAX reads that variable itself, and nothing
    here replaces it), or one already set through ``jax.config``;
    otherwise :data:`DEFAULT_CACHE_DIR`.

    ``platform`` defaults to the initialized backend
    (``jax.default_backend()``). On ``cpu`` the cache is refused, and
    turned off if the environment had turned it on: cache-deserialized
    XLA:CPU executables corrupt donated buffers (module doc), and
    every device engine donates."""
    import jax

    if platform is None:
        platform = jax.default_backend()
    if platform == "cpu":
        if jax.config.jax_compilation_cache_dir:
            jax.config.update("jax_enable_compilation_cache", False)
        return
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_SECS)

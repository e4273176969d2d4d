"""Compiled-program cache for host-launched collectives.

The reference pays one MMIO round-trip per call; the TPU analog's fixed cost
is tracing+compiling an XLA program. To make host-driven per-op dispatch fast
(SURVEY.md §7 "hard parts"), every collective program is cached keyed on
``(op, communicator, shape, dtype, static params)`` — the same role the
firmware's cached communicator/arithcfg lookups play
(``ccl_offload_control.c:2330-2360``).

The cache is LRU-bounded (``ACCLConfig.program_cache_size``, generous by
default): a long-lived serving session resolving many distinct
(shape, dtype, algorithm) keys must not grow without limit, and an
eviction storm — the bound set far too low for the workload's working
set — must be *visible*, not a silent recompile tax. Hits, misses,
evictions and the live size export through ``accl_tpu.obs.metrics``
(``accl_program_cache_total{event}`` + the ``accl_program_cache_size``
gauge) beside the ``stats()`` fields that have always been there.

:meth:`ProgramCache.run` is the launch path's lookup-and-call: a miss
builds the program and makes its first call, where jit traces and
compiles, inside one ``accl.compile`` span.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Hashable, Tuple

from ..obs import metrics as _metrics
from ..obs import trace as _trace

_L_HIT = (("event", "hit"),)
_L_MISS = (("event", "miss"),)
_L_EVICT = (("event", "evict"),)


class ProgramCache:
    """Key -> jitted callable, LRU-bounded, with hit/miss/eviction
    counters for observability. ``maxsize <= 0`` disables the bound."""

    def __init__(self, maxsize: int = 0):
        self._cache: "OrderedDict[Hashable, Callable]" = OrderedDict()
        self.maxsize = int(maxsize)
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key: Hashable, builder: Callable[[], Callable]) -> Callable:
        fn = self._cache.get(key)
        if fn is None:
            return self._build(key, builder)
        return self._hit(key, fn)

    def run(self, key: Hashable, builder: Callable[[], Callable], *args):
        """Call the program for ``key`` on ``args``, building it on a
        miss; the build and the first call, which compiles, run inside
        the ``accl.compile`` span."""
        fn = self._cache.get(key)
        if fn is not None:
            return self._hit(key, fn)(*args)
        with _trace.span("accl.compile"):
            return self._build(key, builder)(*args)

    def _hit(self, key: Hashable, fn: Callable) -> Callable:
        self.hits += 1
        _metrics.inc("accl_program_cache_total", labels=_L_HIT)
        self._cache.move_to_end(key)
        _metrics.set_gauge("accl_program_cache_size", len(self._cache))
        return fn

    def _build(self, key: Hashable, builder: Callable[[], Callable]
               ) -> Callable:
        self.misses += 1
        _metrics.inc("accl_program_cache_total", labels=_L_MISS)
        fn = builder()
        self._cache[key] = fn
        self._evict()
        _metrics.set_gauge("accl_program_cache_size", len(self._cache))
        return fn

    def _evict(self) -> None:
        while self.maxsize > 0 and len(self._cache) > self.maxsize:
            self._cache.popitem(last=False)
            self.evictions += 1
            _metrics.inc("accl_program_cache_total", labels=_L_EVICT)

    def set_maxsize(self, maxsize: int) -> None:
        """Config write-through: apply a new LRU bound (shrinking evicts
        oldest-used programs immediately)."""
        self.maxsize = int(maxsize)
        self._evict()

    def clear(self) -> None:
        self._cache.clear()

    def __len__(self) -> int:
        return len(self._cache)

    def stats(self) -> Tuple[int, int, int]:
        return (len(self._cache), self.hits, self.misses)

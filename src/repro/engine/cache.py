"""Compiled-plan cache: jit an executor once per plan key, reuse it for every
later plan with the same shape/dtype/strategy/substrate signature
(DESIGN.md §1b).

The engine's plan -> compile -> execute pipeline looks executors up here.
A *miss* wraps the plan's executor in ``jax.jit`` (unless the plan opted out
with ``jit=False``) under the name ``<op>_<substrate>``, so the device trace
reads ``jit_spmv_local(...)`` for each program, and marks the entry pending; the runner times the
executor's first call (trace + XLA compile + first run on this signature)
and records it via :meth:`PlanCache.note_compiled`. A *hit* hands back the
already-warm executable, so the call skips tracing entirely and the run's
``RunReport`` carries ``cache_hit=True, compile_seconds=0.0`` — benchmarks
and the :class:`~repro.engine.service.EngineService` use this to separate
compile cost from steady-state throughput. Jitting here (rather than in
each kernel) is what makes the compile stage *compile*: before it, mesh
substrate plans executed ``shard_map`` op-by-op on every call, costing
seconds per request; the cached executable runs the same program fused.

Caching an executor closure is sound because :func:`~repro.engine.api.plan_key`
pins everything the closure captures: the op, the substrate fingerprint
(mesh identity / device window / interpret flag included), every strategy
axis, the op's static scalars, and the argument pytree signature. Only
array *values* vary across reuses — exactly what the executors are
polymorphic over.

**Placement pinning** (the executor pool, DESIGN.md §1d): entries remember
the pool slot that first compiled them (``CacheEntry.slot``). The service's
scheduler routes a plan-key group to its pinned slot so a compiled
executable keeps serving from the worker that owns it — a work-steal
*executes* a warm entry from another worker (the executable is shared
process memory) but never re-pins it, so the next group with that key still
routes home and the cache is not thrashed by migration.

The cache is thread-safe: the scheduler resolves plans while N executor
workers serve cache hits concurrently, so every entry-table access is taken
under one lock. Executor *calls* happen outside the lock — only the
bookkeeping is serialized.
"""
from __future__ import annotations

import collections
import dataclasses
import re
import threading
from typing import Any, Callable

import jax

from .api import ExecutionPlan


def _named(plan: ExecutionPlan) -> Callable[..., Any]:
    """The plan's executor under the name ``<op>_<substrate>``, which
    ``jax.jit`` gives its program. It closes over the executor only: the
    cache keeps it alive, and it must not pin the plan's input arrays."""
    executor = plan.executor

    def call(*args):
        return executor(*args)

    call.__name__ = call.__qualname__ = re.sub(r"\W", "_", f"{plan.op}_{plan.substrate}")
    return call


@dataclasses.dataclass
class CacheEntry:
    """One cached executor + its compile accounting."""

    executor: Callable[..., Any]
    compiled: bool = False  # first call completed (jax traced + compiled)
    compile_seconds: float = 0.0
    hits: int = 0
    slot: int | None = None  # executor-pool placement pin (None = unpinned)


@dataclasses.dataclass
class CompiledPlan:
    """A plan resolved through the cache, ready to execute.

    ``cache_hit`` is True iff an executor that already completed its first
    (compiling) call was reused — the run will be pure steady state.
    """

    plan: ExecutionPlan
    executor: Callable[..., Any]
    cache_hit: bool
    entry: CacheEntry | None

    def __call__(self) -> Any:
        return self.executor(*self.plan.args)


class PlanCache:
    """LRU cache of compiled executors keyed by ``ExecutionPlan.key``."""

    # placement pins for keys whose *entries* live under a different key
    # (a mesh group's base key aliases its slot-variant compiled key);
    # bounded separately from the entry table
    _PIN_ALIAS_MAX = 4096

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._entries: collections.OrderedDict[tuple, CacheEntry] = collections.OrderedDict()
        self._key_pins: collections.OrderedDict[tuple, int] = collections.OrderedDict()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.uncacheable = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __bool__(self) -> bool:
        return True  # an empty cache is still a cache, not a None stand-in

    def get(self, plan: ExecutionPlan, *, slot: "int | None" = None) -> CompiledPlan:
        """Resolve a plan's executor. Keyless plans bypass the cache (and
        stay eager — a jit wrapper with no reuse only adds tracing cost).
        ``slot`` tags the entry with the executor-pool slot on first
        resolution; later resolutions never move the pin."""
        with self._lock:
            if plan.key is None:
                self.uncacheable += 1
                return CompiledPlan(plan, plan.executor, cache_hit=False, entry=None)
            entry = self._entries.get(plan.key)
            if entry is not None:
                self._entries.move_to_end(plan.key)
                if slot is not None and entry.slot is None:
                    entry.slot = slot  # adopt: e.g. batch-compiled, pool-served
                if entry.compiled:
                    entry.hits += 1
                    self.hits += 1
                    return CompiledPlan(plan, entry.executor, cache_hit=True, entry=entry)
                # entry exists but its first call never ran: still a cold path
                self.misses += 1
                return CompiledPlan(plan, entry.executor, cache_hit=False, entry=entry)
            executor = jax.jit(_named(plan)) if plan.jit else plan.executor
            entry = CacheEntry(executor=executor, slot=slot)
            self._entries[plan.key] = entry
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
            self.misses += 1
            return CompiledPlan(plan, entry.executor, cache_hit=False, entry=entry)

    def note_compiled(self, compiled: CompiledPlan, seconds: float) -> None:
        """Record the timed first call of a miss (trace + compile + run)."""
        with self._lock:
            if compiled.entry is not None and not compiled.entry.compiled:
                compiled.entry.compiled = True
                compiled.entry.compile_seconds = seconds

    def is_warm(self, key: "tuple | None") -> bool:
        """True iff ``key`` resolves to an executor whose compiling call
        already completed — the pool scheduler's bypass test (warm groups go
        straight to their worker; only cold groups visit the compile stage)."""
        if key is None:
            return False
        with self._lock:
            entry = self._entries.get(key)
            return entry is not None and entry.compiled

    def pin_key(self, key: "tuple | None", slot: int) -> None:
        """Pin a *key* to a slot without requiring an entry under it. The
        pool's placement uses this for base plan keys whose compiled entry
        is stored under a slot-variant key (device windows change the
        fingerprint), so affinity survives the service's own pin table —
        e.g. across services sharing one cache. First pin wins."""
        if key is None:
            return
        with self._lock:
            if key not in self._key_pins:
                self._key_pins[key] = slot
                while len(self._key_pins) > self._PIN_ALIAS_MAX:
                    self._key_pins.popitem(last=False)

    def slot_of(self, key: "tuple | None") -> "int | None":
        """The executor-pool slot pinned at first compile (None = unpinned).
        Falls back to the :meth:`pin_key` alias table for keys whose entry
        lives under a variant key."""
        if key is None:
            return None
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and entry.slot is not None:
                return entry.slot
            return self._key_pins.get(key)

    def stats(self) -> dict[str, Any]:
        """Aggregate counters — the benchmark/CI cache health record."""
        with self._lock:
            lookups = self.hits + self.misses
            return {
                "entries": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
                "uncacheable": self.uncacheable,
                "hit_rate": self.hits / lookups if lookups else 0.0,
                "compile_seconds_total": sum(
                    e.compile_seconds for e in self._entries.values()
                ),
                "pinned": sum(
                    1 for e in self._entries.values() if e.slot is not None
                ),
            }

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._key_pins.clear()
            self.hits = self.misses = self.uncacheable = 0


_DEFAULT_CACHE = PlanCache()


def default_cache() -> PlanCache:
    """The process-wide cache ``engine.run`` uses when none is passed."""
    return _DEFAULT_CACHE

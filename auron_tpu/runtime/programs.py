"""Central program-cache registry.

Every jit-kernel builder in the engine used to memoize behind its own
module-level ``functools.lru_cache`` (~15 scattered sites: project/filter
kernels, sort, SMJ, hash-join, agg merge, shuffle split, window, explode,
bloom probe, SPMD exchange, ...). That shape had two costs:

- ``auron.max_live_programs`` (utils/compile_stats.maybe_clear) cleared
  jax's compiled caches but could not drop the builder memos, so the
  python-side kernel closures and their cache keys kept growing unbounded
  and no single place could answer "how many live programs does this
  process hold, and which compile site built them";
- per-site build/hit counts were invisible — the compile-budget numbers
  in PERF.md had to be reverse-engineered from raw backend-compile
  events.

This module replaces all of those with one registry: each compile site
declares a ``@program_cache("site.name")`` around its builder function and
gets LRU memoization (same semantics as the old ``lru_cache``) plus
central accounting. ``maybe_clear`` (utils/compile_stats) consults
``total_live()`` and calls ``clear_all()`` together with
``jax.clear_caches()``, so the documented ceiling now bounds every
compile site, builder memos included.

``snapshot()`` / ``delta()`` expose per-site and aggregate build/hit
counters — the per-query numbers ``tools/compile_report.py`` prints and
the per-task ``programs`` entry in ExecutionRuntime.finalize.
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import Callable, NamedTuple, Optional

_LOCK = threading.Lock()
_SITES: "OrderedDict[str, ProgramCache]" = OrderedDict()

#: per-QUERY build/hit attribution (query id from the lifecycle plane's
#: thread-local token). The cache itself is shared ACROSS concurrent
#: queries — a hit compiled by query A serves query B — so the process
#: totals can no longer attribute per query by delta; this ledger can.
#: Popped at query end (Session._end_query) so memory stays bounded;
#: read by explain(analyze=True)'s program-cache footer.
_QUERY_LOCK = threading.Lock()
_QUERY_COUNTS: dict[str, list] = {}


def _note_query(built: bool) -> None:
    from auron_tpu.runtime import lifecycle
    qid = lifecycle.current_query_id()
    if not qid:
        return
    with _QUERY_LOCK:
        ent = _QUERY_COUNTS.setdefault(qid, [0, 0])
        ent[0 if built else 1] += 1


def query_totals(qid: str) -> "ProgramSnapshot":
    """(builds, hits) attributed to ``qid`` so far."""
    with _QUERY_LOCK:
        ent = _QUERY_COUNTS.get(qid, (0, 0))
        return ProgramSnapshot(ent[0], ent[1])


def pop_query(qid: str) -> "ProgramSnapshot":
    """Remove and return ``qid``'s attribution (query teardown)."""
    with _QUERY_LOCK:
        ent = _QUERY_COUNTS.pop(qid, (0, 0))
        return ProgramSnapshot(ent[0], ent[1])


class ProgramSnapshot(NamedTuple):
    builds: int
    hits: int


class ProgramCache:
    """One compile site's builder memo: LRU-bounded, centrally counted.

    ``get_or_build`` returns ``(value, built)`` — ``built`` is True when
    the builder ran (a new program was constructed), letting call sites
    mirror build/hit counts into per-task metrics without racing on the
    monotonic totals.
    """

    def __init__(self, site: str, maxsize: int = 256):
        self.site = site
        self.maxsize = maxsize
        self._memo: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        #: monotonic totals (survive clear(): they describe history,
        #: not current residency)
        self.builds = 0
        self.hits = 0
        self.evictions = 0
        # offsets for lru_cache-compatible cache_info() (which resets
        # its counters on cache_clear; the monotonic totals above don't)
        self._builds_at_clear = 0
        self._hits_at_clear = 0

    def get_or_build(self, key, builder: Callable):
        # trace-semantic config values partition every cache key: a
        # builder's trace may read them (e.g. the map-key dedup policy),
        # so a changed value must build a FRESH function object — jax's
        # jit cache keys on function identity, making the re-trace real
        from auron_tpu import config as _cfg
        from auron_tpu.obs import profile as _profile
        key = (key, _cfg.trace_salt())
        value = None
        hit = False
        with self._lock:
            if key in self._memo:
                self._memo.move_to_end(key)
                self.hits += 1
                value = self._memo[key]
                hit = True
        from auron_tpu.obs import trace as _trace
        if hit:
            # per-site hit events make the compile economics visible on
            # the timeline; narrow auron.trace.events to drop them
            _trace.event("program", "program.hit", site=self.site)
            _note_query(built=False)
            # the memo holds the RAW program (stable identity for the
            # cache); the per-invocation host/device timing proxy wraps
            # only what leaves the registry (obs/profile.wrap_program —
            # a pass-through when profiling is off)
            return _profile.wrap_program(value, self.site), False
        from auron_tpu import errors as _errors
        from auron_tpu.runtime import faults as _faults
        _faults.maybe_fail("program.build", _errors.DeviceExecutionError)
        with _trace.layer_span("plan", "build", cat="program",
                               name="program.build", site=self.site):
            value = builder()   # build outside the lock: builders recurse
        with self._lock:
            if key in self._memo:   # raced with another thread: keep first
                self.hits += 1
                _note_query(built=False)
                return _profile.wrap_program(self._memo[key],
                                             self.site), False
            self._memo[key] = value
            self.builds += 1
            while len(self._memo) > self.maxsize:
                self._memo.popitem(last=False)
                self.evictions += 1
        _note_query(built=True)
        return _profile.wrap_program(value, self.site), True

    def live(self) -> int:
        with self._lock:
            return len(self._memo)

    def clear(self) -> None:
        with self._lock:
            self._memo.clear()
            self._builds_at_clear = self.builds
            self._hits_at_clear = self.hits

    def cache_info(self):
        """functools.lru_cache-compatible view (counters since the last
        clear), so converted sites stay drop-in for existing callers."""
        import functools
        with self._lock:
            return functools._CacheInfo(
                self.hits - self._hits_at_clear,
                self.builds - self._builds_at_clear,
                self.maxsize, len(self._memo))

    def stats(self) -> dict:
        with self._lock:
            return {"builds": self.builds, "hits": self.hits,
                    "live": len(self._memo), "evictions": self.evictions}


def register(cache: ProgramCache) -> ProgramCache:
    with _LOCK:
        assert cache.site not in _SITES, \
            f"duplicate program-cache site {cache.site!r}"
        _SITES[cache.site] = cache
    return cache


def site(name: str) -> Optional[ProgramCache]:
    with _LOCK:
        return _SITES.get(name)


def program_cache(site_name: str, maxsize: int = 256):
    """Decorator replacing ``functools.lru_cache`` on kernel builders.

    The wrapped builder keeps its call signature (positional, hashable
    args — the same contract ``lru_cache`` enforced) and gains a
    ``.cache`` attribute exposing the registered ProgramCache.
    """

    def deco(fn: Callable) -> Callable:
        cache = register(ProgramCache(site_name, maxsize))

        @functools.wraps(fn)
        def wrapper(*args):
            value, _built = cache.get_or_build(args, lambda: fn(*args))
            return value

        wrapper.cache = cache
        # lru_cache drop-in compat for existing call sites
        wrapper.cache_clear = cache.clear
        wrapper.cache_info = cache.cache_info
        return wrapper

    return deco


# ---------------------------------------------------------------------------
# aggregate views
# ---------------------------------------------------------------------------

def snapshot() -> dict:
    """{site: {builds, hits, live, evictions}} over every registered
    compile site."""
    with _LOCK:
        sites = list(_SITES.values())
    return {c.site: c.stats() for c in sites}


def totals() -> ProgramSnapshot:
    with _LOCK:
        sites = list(_SITES.values())
    b = sum(c.builds for c in sites)
    h = sum(c.hits for c in sites)
    return ProgramSnapshot(b, h)


def delta(since: ProgramSnapshot) -> ProgramSnapshot:
    now = totals()
    return ProgramSnapshot(now.builds - since.builds, now.hits - since.hits)


def total_live() -> int:
    """Programs currently held across every site's memo — what
    ``auron.max_live_programs`` bounds (utils/compile_stats.maybe_clear)."""
    with _LOCK:
        sites = list(_SITES.values())
    return sum(c.live() for c in sites)


def clear_all() -> None:
    """Drop every site's memo (the registry side of a compile-cache
    clear; jax.clear_caches() is the caller's half — see
    utils/compile_stats.maybe_clear)."""
    with _LOCK:
        sites = list(_SITES.values())
    for c in sites:
        c.clear()


# ---------------------------------------------------------------------------
# names a trace can read
# ---------------------------------------------------------------------------

def site_function_name(site_name: str) -> str:
    """The ``__name__`` a site's jitted function carries:
    ``ops.joins.probe_count`` -> ``auron_ops_joins_probe_count``. jax
    names the XLA module ``jit_<__name__>`` and the host dispatch event
    ``PjitFunction(<__name__>)``, and those two names are all a device
    trace keeps of a program (event stats read back empty), so every
    builder defines its traced function under this name."""
    return "auron_" + site_name.replace(".", "_")


def named(name: str):
    """Give a function the ``__name__`` jax will read — for module-level
    jits whose public name stays (kernels/grouped_agg.py). Apply UNDER
    the jit decorator: the dispatch event's name is fixed when
    ``jax.jit`` wraps the function."""

    def deco(fn: Callable) -> Callable:
        fn.__name__ = fn.__qualname__ = name
        return fn

    return deco


# ---------------------------------------------------------------------------
# donation-aware jit
# ---------------------------------------------------------------------------

def _aliased(args, argnums) -> bool:
    """True when an array object donated at ``argnums`` appears a second
    time anywhere in the call — among the donated leaves (columns of a
    scan-fed batch commonly share one all-valid mask) or in an argument
    that is not donated."""
    import jax
    donated = jax.tree_util.tree_leaves(
        [a for i, a in enumerate(args) if i in argnums])
    ids = {id(x) for x in donated}
    if len(ids) != len(donated):
        return True
    kept = jax.tree_util.tree_leaves(
        [a for i, a in enumerate(args) if i not in argnums])
    return any(id(x) in ids for x in kept)


def jit(fun=None, *, donate_argnums=(), **kwargs):
    """``jax.jit`` that applies ``donate_argnums`` only where donation is
    real and legal. The XLA CPU backend treats donation as advisory
    (every donated buffer is copied anyway and jax warns about it), so
    kernels that donate their dead inputs — the sort/gather kernels, the
    shuffle split — compile with donation on accelerators and without it
    on the CPU mesh. On an accelerator a donated buffer may appear ONCE
    in a call: a call whose donated leaves alias each other, or another
    argument, runs the non-donating twin instead (XLA rejects it
    otherwise — "Attempt to donate the same buffer twice", the first
    thing the shuffle split hit on a real chip)."""
    import jax

    def wrap(f):
        plain = jax.jit(f, **kwargs)
        if not donate_argnums or jax.default_backend() == "cpu":
            return plain
        # graft: donation-ok -- the donation-aware wrapper itself;
        # every caller annotates its own site
        donating = jax.jit(f, donate_argnums=donate_argnums, **kwargs)

        @functools.wraps(f)
        def call(*args, **kw):
            if _aliased(args, donate_argnums):
                return plain(*args, **kw)
            return donating(*args, **kw)

        return call

    if fun is None:
        return wrap
    return wrap(fun)

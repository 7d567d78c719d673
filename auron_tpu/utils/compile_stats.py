"""Process-wide XLA compile accounting (round-5 directive 7).

The reference pays plan-build per task but never kernel-compile per query
(DataFusion's physical operators are interpreted, planner.rs:121-856); on
this engine every jitted kernel is an XLA program, so compile latency is
a first-class perf axis — on a TPU a single program build costs from
tenths of a second to minutes. This module hooks ``jax.monitoring``'s
``backend_compile_duration`` event (fired on every real backend compile,
including shape-driven recompiles that python-level kernel caches cannot
see) and exposes cheap snapshots so the executor and the TPC-DS runner
can attribute compiles and compile-seconds per task / per query.

A healthy steady state compiles ~0 new programs: kernels are cached by
(exprs, schema, bucketed capacity), so re-running a query suite in one
process should be all cache hits — ``delta()`` makes that measurable.
"""

from __future__ import annotations

import threading
from typing import NamedTuple

_LOCK = threading.Lock()
_N = {"count": 0}
_S = {"seconds": 0.0}
_INSTALLED = False

_EVENT = "/jax/core/compile/backend_compile_duration"

#: jax's persistent-compilation-cache verdict events. The duration event
#: above fires for hits too (it wraps the lookup), so a warm process
#: shows the same program count with fewer seconds and these say why.
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}
_CACHE = {"hits": 0, "misses": 0}


class CompileSnapshot(NamedTuple):
    count: int
    seconds: float


def install() -> None:
    """Register the monitoring listener once per process (idempotent)."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return
        import jax.monitoring as mon

        from auron_tpu.obs import trace as _trace

        def _listen(name: str, dur: float, **_kw) -> None:
            if name == _EVENT:
                with _LOCK:
                    _N["count"] += 1
                    _S["seconds"] += dur
                    _SINCE_CLEAR["count"] += 1
                # the event fires on the thread that compiled: the task
                # bound to that thread owns it (the process-wide totals
                # above cannot say whose it was)
                _trace.on_compile(dur)

        def _listen_cache(name: str, **_kw) -> None:
            key = _CACHE_EVENTS.get(name)
            if key is not None:
                with _LOCK:
                    _CACHE[key] += 1

        mon.register_event_duration_secs_listener(_listen)
        mon.register_event_listener(_listen_cache)
        _INSTALLED = True


def snapshot() -> CompileSnapshot:
    install()
    with _LOCK:
        return CompileSnapshot(_N["count"], _S["seconds"])


def persistent_cache() -> dict:
    """{"hits": n, "misses": n}: programs this process restored from /
    had to compile past jax's persistent compilation cache."""
    install()
    with _LOCK:
        return dict(_CACHE)


def delta(since: CompileSnapshot) -> CompileSnapshot:
    now = snapshot()
    return CompileSnapshot(now.count - since.count,
                           now.seconds - since.seconds)


#: programs compiled since the last cache clear (distinct from the
#: monotonic totals above)
_SINCE_CLEAR = {"count": 0}

#: default ceiling on live compiled programs per process. The XLA CPU
#: backend's JIT has been observed to SEGFAULT inside backend_compile
#: after ~500-700 programs accumulate in one long-lived process (1-CPU
#: container, jax 0.8 era) — long before any visible memory pressure.
#: Clearing jax's compilation caches trades bounded recompiles for
#: survival; kernels rebuild lazily from the engine's own builder caches.
DEFAULT_MAX_LIVE_PROGRAMS = 400


#: process-lifetime count of cache clears (observability for the
#: suite runners' compile-budget note)
_CLEARS = {"count": 0}


def clears() -> int:
    with _LOCK:
        return _CLEARS["count"]


def maybe_clear(limit: int | None = None) -> bool:
    """Clear jax's compilation caches when more than ``limit`` programs
    were built since the last clear, OR when the central program-cache
    registry (runtime/programs.py) holds that many live builder entries —
    raw backend compiles miss programs restored from the persistent XLA
    cache, and the registry's python-side memos would otherwise pin
    kernel closures past the ceiling. Both halves clear together so the
    documented ``auron.max_live_programs`` semantics hold at every
    compile site. Returns True when a clear happened. Call between
    tasks / test modules — never mid-kernel."""
    install()   # counting must be live for the ceiling to mean anything
    if limit is None:
        # single binding through the typed config layer (session override
        # > AURON_CONF_MAX_LIVE_PROGRAMS env > default — the documented
        # precedence); a malformed value raises there, loudly
        from auron_tpu import config as cfg
        limit = cfg.get_config().get(cfg.MAX_LIVE_PROGRAMS)
    if limit <= 0:
        return False
    from auron_tpu.runtime import programs
    with _LOCK:
        due = _SINCE_CLEAR["count"] >= limit
        if due:
            _SINCE_CLEAR["count"] = 0
    if not due and programs.total_live() >= limit:
        due = True
        with _LOCK:
            _SINCE_CLEAR["count"] = 0
    if not due:
        return False
    import jax
    jax.clear_caches()
    programs.clear_all()
    with _LOCK:
        _CLEARS["count"] += 1
    return True

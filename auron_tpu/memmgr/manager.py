"""HBM budget arbitration.

Mirrors the reference's design (reference: auron-memmgr/src/lib.rs:303-423):
one manager per process, consumers update their usage after each growth
step, the manager answers Nothing or Spill based on fair share and a
global watermark. The reference's Wait arm (condvar, 10 s) exists because
many tasks share one pool concurrently; over-budget here resolves by
spilling the requester first when it holds at least its share (the
biggest consumer otherwise).

Concurrent-query fairness (the [serving] scheduler plane): every
consumer is tagged at registration with the query that created it (the
lifecycle plane's thread-local token), giving the manager a per-query
ledger. ``fair_share()`` divides the budget over LIVE QUERIES, not
consumers; the per-query quota (``auron.memmgr.query_quota_bytes``,
auto = budget / auron.sched.max_concurrent under concurrency) is
enforced against the requester's OWN ledger — and a quota breach spills
or sheds that query, never an innocent neighbor.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

logger = logging.getLogger("auron_tpu.memmgr")

#: don't bother spilling consumers below this (reference: MIN_TRIGGER_SIZE
#: 16MB, auron-memmgr/src/lib.rs:36)
MIN_TRIGGER_SIZE = 16 << 20

#: every live manager, weakly held — the process-wide consumer-leak
#: probe the tier-1 leak-audit fixture and the chaos battery read
import weakref as _weakref

_MANAGERS: "_weakref.WeakSet" = _weakref.WeakSet()


def live_consumer_count() -> int:
    """Registered consumers across every live MemManager (after a gc, a
    finished query must leave this at its pre-query value — consumers
    are weakly held, so anything still counted is either genuinely live
    or pinned by a leak)."""
    total = 0
    for m in list(_MANAGERS):
        with m._lock:
            total += len(m._used)
    return total


def aggregate_status() -> list[dict]:
    """Status snapshots of every live manager in the process — the ops
    plane's ``/healthz`` memmgr section and the bundle's memmgr.json
    (a scrape has no Session handle, so the weak registry is the
    discovery surface). Empty-ledger managers (no consumers, no spill
    history) are skipped: long-lived processes accumulate idle managers
    from finished tests/sessions and the operator surface should show
    pressure, not archaeology."""
    out = []
    for m in list(_MANAGERS):
        try:
            st = m.status()
        except Exception:   # pragma: no cover - status best-effort
            continue
        if st["num_consumers"] or st["num_spills"] or st["used"]:
            out.append(st)
    return out


class MemConsumer:
    """Spillable participant. Operators subclass / duck-type this."""

    #: display name for the status dump
    consumer_name: str = "consumer"

    #: may ``spill()`` be invoked from a thread OTHER than the one that
    #: registered (drives) this consumer? Under the concurrent runtime
    #: pressure can originate on any thread — a neighbor query's
    #: driver, this query's own prefetch worker — and pick any consumer
    #: as victim, but only consumers with internal locking
    #: (BufferedSpillConsumer's claim-under-lock protocol) survive a
    #: foreign-thread spill; the rest are spilled only from their OWN
    #: driving thread (victim pools filter on thread identity — the
    #: cross-query safety audit's finding)
    spill_thread_safe: bool = False

    def mem_used(self) -> int:
        raise NotImplementedError

    def spill(self) -> int:
        """Release device memory; returns bytes freed."""
        raise NotImplementedError

    def shrink(self) -> int:
        """OPTIONAL degradation hook (pressure ladder rung 1): release
        PART of the held memory — cheaper than a full spill — returning
        bytes freed. The default declines (0); consumers that buffer
        batch lists override (memmgr/consumer.BufferedSpillConsumer
        sheds its oldest half)."""
        return 0


class MemManager:
    def __init__(self, total_bytes: Optional[int] = None,
                 min_trigger: int = MIN_TRIGGER_SIZE,
                 spill_manager: Optional["object"] = None,
                 config=None):
        if total_bytes is None:
            total_bytes = self.default_budget()
        self.total = total_bytes
        self.min_trigger = min_trigger
        self.spill_manager = spill_manager
        #: knob source for the auto per-query quota divisor
        #: (auron.sched.max_concurrent): the owning Session binds its
        #: own config here so the quota divisor and the scheduler's
        #: admission clamp cannot desynchronize under per-Session
        #: overrides; None = process config
        self.config = config
        #: (config epoch, quota knob, max_concurrent) memo — per
        #: manager because the knob source is
        self._quota_cache: tuple = (-1, 0, 1)
        self._lock = threading.Lock()
        # weak keys: a consumer whose operator was dropped without an
        # explicit unregister (e.g. a memoized exchange buffer released
        # with its query) must not pin itself — or its accounted bytes —
        # in the manager for the process lifetime
        import weakref
        self._used: "weakref.WeakKeyDictionary[MemConsumer, int]" = \
            weakref.WeakKeyDictionary()
        #: per-query ledger: consumer → owning query id (tagged at
        #: registration from the lifecycle plane's thread-local token;
        #: "" is the anonymous bucket of direct collect() calls). The
        #: concurrent scheduler's fairness — per-query fair_share, the
        #: quota breach check, the over-quota-first force-spill — reads
        #: usage grouped by this tag.
        self._query_of: "weakref.WeakKeyDictionary[MemConsumer, str]" = \
            weakref.WeakKeyDictionary()
        #: consumer → registering (driving) thread id: the safety key
        #: for victim selection — spill() on a non-thread-safe consumer
        #: is only sound from the thread that drives it
        self._thread_of: "weakref.WeakKeyDictionary[MemConsumer, int]" = \
            weakref.WeakKeyDictionary()
        self.num_spills = 0
        self.spilled_bytes = 0
        #: degradation-ladder state: shrink rungs taken (drives the
        #: advised batch-rows hint scans consult) + per-rung counters
        self._shrink_level = 0
        #: consecutive comfortable grants (under half budget) since the
        #: last pressure event — the shrink-level decay hysteresis
        self._comfort_grants = 0
        self.pressure_counts = {"shrink": 0, "cache_evict": 0,
                                "force_spill": 0, "deny": 0, "shed": 0}
        _MANAGERS.add(self)

    @staticmethod
    def default_budget() -> int:
        """auron.memory.fraction of the device's HBM (the reference's
        spark.auron.memoryFraction × executor memory). The CPU platform
        reports no limit and budgets against a nominal 8 GB; an
        accelerator that reports none is an error, not an assumption."""
        import jax

        from auron_tpu import config as cfg
        fraction = cfg.get_config().get(cfg.MEMORY_FRACTION)
        dev = jax.devices()[0]
        limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
        if not limit:
            if dev.platform != "cpu":
                raise RuntimeError(
                    f"{dev.platform} device {dev.device_kind!r} reports "
                    "no memory_stats()['bytes_limit']; cannot size the "
                    "memory budget")
            limit = 8 << 30
        return int(limit * fraction)

    # -- registration -------------------------------------------------------

    def register_consumer(self, c: MemConsumer) -> None:
        from auron_tpu.runtime import lifecycle
        qid = lifecycle.current_query_id()
        with self._lock:
            self._used.setdefault(c, 0)
            self._query_of[c] = qid
            self._thread_of[c] = threading.get_ident()

    def unregister_consumer(self, c: MemConsumer) -> None:
        with self._lock:
            self._used.pop(c, None)
            self._query_of.pop(c, None)
            self._thread_of.pop(c, None)

    def _spill_eligible_locked(self, v: MemConsumer) -> bool:
        """May the CURRENT thread invoke ``v.spill()``? Yes when it is
        v's own driving (registering) thread, or when v advertises an
        internally-locked spill (``spill_thread_safe``). Query tags do
        NOT make a victim safe — this query's prefetch worker racing
        this query's agg consumer is just as unsynchronized as a
        neighbor's driver. Caller holds ``self._lock``."""
        return (self._thread_of.get(v) == threading.get_ident()
                or getattr(v, "spill_thread_safe", False))

    # -- accounting ---------------------------------------------------------

    @property
    def used_total(self) -> int:
        with self._lock:
            return sum(self._used.values())

    def _usage_by_query_locked(self) -> dict:
        """{query tag: accounted bytes} over registered consumers — the
        ONE definition every per-query view (live count, quota check,
        force-spill pool, status) derives from; "" is the anonymous tag
        of direct collect() calls. Caller holds ``self._lock``."""
        out: dict[str, int] = {}
        for c, u in self._used.items():
            tag = self._query_of.get(c, "")
            out[tag] = out.get(tag, 0) + u
        return out

    def _live_queries_locked(self) -> set:
        """Distinct query tags across registered consumers; the
        anonymous "" tag counts as one query (direct collect() calls).
        Caller holds ``self._lock``."""
        return set(self._usage_by_query_locked())

    def fair_share(self) -> int:
        """Budget divided over LIVE QUERIES (not consumers): the
        concurrent runtime's fairness unit — a query spawning many
        consumers must not multiply its claim on the budget. With one
        query live (the solo path) this is the whole budget."""
        with self._lock:
            n = max(len(self._live_queries_locked()), 1)
        return self.total // n

    def query_used(self, qid: str) -> int:
        """Bytes accounted to ``qid``'s registered consumers."""
        with self._lock:
            return self._query_used_locked(qid)

    def query_quota(self) -> int:
        """Public face of the effective per-query quota (0 = none) —
        the ops plane's /queries table prints usage against it."""
        return self._query_quota()

    def _query_used_locked(self, qid: str) -> int:
        return self._usage_by_query_locked().get(qid, 0)

    def update_mem_used(self, c: MemConsumer, used: int) -> str:
        """Record ``c``'s usage; returns 'nothing' or 'spilled'. May invoke
        c.spill() (or the largest consumer's) synchronously.

        Every accounting decision is observable on the same planes as
        compute (the PR 6 forensics contract): the post-decision status
        mirrors onto registry gauges (obs/registry.observe_memmgr), an
        under-budget grant drops a ``memory`` trace event, each spill
        opens a ``memmgr.spill`` span around the victim's spill, and an
        over-budget exit with no spillable candidate left records a
        ``memmgr.deny`` — so memory pressure lines up with the span
        timeline instead of hiding in log archaeology."""
        from auron_tpu.obs import trace
        from auron_tpu.runtime import faults
        observe = self._registry_enabled()
        with self._lock:
            self._used[c] = used
            qid = self._query_of.get(c, "")
            # ONE per-query walk serves every grant-path read (total,
            # the requester's query usage, live-query count) — the hot
            # path stays a single O(consumers) pass under the lock the
            # accounting already holds
            by_query = self._usage_by_query_locked()
            total_used = sum(by_query.values())
            q_used = by_query.get(qid, 0)
            n_live = len(by_query)
            # grant-path telemetry snapshot under the SAME lock — no
            # second acquisition, and the consumer copy only happens
            # when the registry will see it
            status = self._status_locked() if observe else None

        # the memmgr.deny chaos site: pretend the budget is exhausted so
        # the degradation ladder gets deterministic traffic
        forced = faults.fires("memmgr.deny", "deny")
        quota = self._query_quota(live=n_live)
        over_quota = bool(quota) and q_used > quota
        if total_used <= self.total and not over_quota and not forced:
            if self._shrink_level:
                # decay the shrink advice once pressure has demonstrably
                # subsided (16 consecutive grants under HALF budget) —
                # one pressure episode must not pin 8x-smaller scan
                # batches for the manager's lifetime
                if total_used <= self.total // 2:
                    self._comfort_grants += 1
                    if self._comfort_grants >= 16:
                        self._shrink_level -= 1
                        self._comfort_grants = 0
                else:
                    self._comfort_grants = 0
            trace.event("memory", "memmgr.grant",
                        consumer=getattr(c, "consumer_name", "?"),
                        used=used, total_used=total_used,
                        budget=self.total)
            if status is not None:
                self._observe(status)
            return "nothing"

        # Spill until under budget or out of candidates (the reference loops
        # to its watermark the same way; one victim's spill may free less
        # than the overshoot — e.g. a consumer refusing mid-merge).
        spilled_any = False
        exhausted = forced    # an injected deny skips straight to the ladder
        tried: set = set()
        while not exhausted:
            with self._lock:
                by_query = self._usage_by_query_locked()
                total_used = sum(by_query.values())
                q_used = by_query.get(qid, 0)
                q_consumers = [v for v in self._used
                               if self._query_of.get(v, "") == qid]
                n_queries = max(len(by_query), 1)
                c_used = self._used.get(c, 0)
            over_budget = total_used > self.total
            over_quota = bool(quota) and q_used > quota
            if not over_budget and not over_quota:
                break
            # requester-first when it holds at least its slice of its
            # query's fair share (per-query share split over the query's
            # consumers — reduces to total // num_consumers when one
            # query is live, the legacy heuristic)
            share = self.total // n_queries // max(len(q_consumers), 1)
            if (c not in tried and c_used >= max(share, 1)
                    and c_used >= self.min_trigger):
                victim = c
            else:
                with self._lock:
                    # a quota-only breach spills the OVER-QUOTA query's
                    # own consumers — a neighbor must not pay for this
                    # query's appetite; a global over-budget considers
                    # every consumer. Either way the victim must be
                    # spill-safe FROM THIS THREAD (its own driving
                    # thread, or an internally locked spill)
                    pool = (q_consumers if over_quota and not over_budget
                            else list(self._used))
                    candidates = [(self._used.get(v, 0), v) for v in pool
                                  if self._spill_eligible_locked(v)
                                  and self._used.get(v, 0)
                                  >= self.min_trigger and v not in tried]
                if not candidates:
                    exhausted = True
                    break
                _, victim = max(candidates, key=lambda t: t[0])
            tried.add(victim)

            with trace.span("memory", "memmgr.spill",
                            victim=getattr(victim, "consumer_name", "?"),
                            total_used=total_used,
                            budget=self.total) as sp:
                freed = victim.spill()
                sp.set(freed=freed)
            with self._lock:
                self._used[victim] = max(self._used.get(victim, 0) - freed, 0)
                if freed:
                    self.num_spills += 1
                    self.spilled_bytes += freed
            if freed:
                spilled_any = True
                logger.info("memmgr: spilled %s (%d bytes freed, %d/%d used)",
                            victim.consumer_name, freed,
                            max(total_used - freed, 0), self.total)
        if exhausted:
            # the spill loop ran dry still over budget — the old hard
            # "deny": now a policy (auron.memmgr.pressure_policy)
            if self._pressure_ladder(c, qid, quota, forced=forced):
                spilled_any = True
        if self._registry_enabled():
            self._observe(self.status())
        return "spilled" if spilled_any else "nothing"

    # -- memory-pressure degradation ladder (PR 8) --------------------------

    def _query_quota(self, live: Optional[int] = None) -> int:
        """Effective per-query quota (0 = none). The knob values are
        cached against the config epoch — update_mem_used runs per
        batch-add, so the common path costs one int compare plus
        arithmetic; the live-query count rides in from the accounting
        lock the caller already held (``live``), so no second lock
        acquisition happens on the hot path. An explicit positive
        ``auron.memmgr.query_quota_bytes`` wins; the default 0 is AUTO
        — budget / auron.sched.max_concurrent once MORE than one query
        is live on this manager (the per-query ledger makes the cap
        genuinely per-query), no quota while a single query runs;
        negative disables entirely. Knobs resolve from ``self.config``
        (the owning Session's — bound at Session init so the quota
        divisor and the scheduler's admission clamp read the SAME
        max_concurrent) falling back to the process config."""
        from auron_tpu import config as cfg
        epoch, knob, maxc = self._quota_cache
        if epoch != cfg.config_epoch():
            try:
                conf = (self.config if self.config is not None
                        else cfg.get_config())
                knob = int(conf.get(cfg.MEMMGR_QUERY_QUOTA_BYTES))
                maxc = max(int(conf.get(cfg.SCHED_MAX_CONCURRENT)), 1)
            except Exception:   # pragma: no cover - config resolvable
                knob, maxc = 0, 1
            self._quota_cache = (cfg.config_epoch(), knob, maxc)
        if knob > 0:
            return knob
        if knob < 0:
            return 0
        if live is None:
            with self._lock:
                live = len(self._live_queries_locked())
        return self.total // maxc if live > 1 else 0

    def advised_batch_rows(self, base: int) -> int:
        """Pressure-adapted scan granularity: every shrink rung taken
        halves the advised batch rows (floor ``base/8``, never below
        256), so the scans feeding a struggling query deliver smaller
        device batches instead of ramming full-capacity ones into a
        budget that just denied. Scans consult this per batch
        (io/parquet.ParquetScanOp)."""
        lvl = self._shrink_level
        if lvl <= 0:
            return base
        return max(base >> min(lvl, 3), min(base, 256))

    def _count_rung(self, rung: str) -> None:
        self.pressure_counts[rung] = self.pressure_counts.get(rung, 0) + 1
        if self._registry_enabled():
            try:
                from auron_tpu.obs import registry as obs_registry
                obs_registry.get_registry().counter(
                    "auron_memmgr_pressure_total", rung=rung).inc()
            except Exception:   # pragma: no cover - telemetry best-effort
                pass

    def _pressure_ladder(self, c: MemConsumer, qid: str, quota: int,
                         forced: bool = False) -> bool:
        """Walk the degradation rungs after the spill loop ran dry still
        over budget: (1) **shrink** — bump the advised-batch-rows hint
        and ask the REQUESTER to shrink (partial release, cheaper than a
        full spill); (2) **force-spill** — spill the largest consumer of
        the OVER-QUOTA query first (the query over its ledger pays for
        its own pressure before any neighbor), min_trigger waived; (3)
        **shed** — fail THIS query with the classified
        ``errors.MemoryExhausted`` (policy 'shed', or the requester's
        per-query quota breached), never the process — or, under the
        default 'degrade' policy, record a survivable deny. Returns True
        when any rung freed bytes. ``forced`` (the memmgr.deny chaos
        site) treats every rung as over budget so the whole ladder gets
        traffic."""
        from auron_tpu import config as cfg
        from auron_tpu.obs import trace
        policy = cfg.get_config().get(cfg.MEMMGR_PRESSURE_POLICY)
        cname = getattr(c, "consumer_name", "?")

        def over() -> tuple[bool, int]:
            with self._lock:
                total_used = sum(self._used.values())
                q_used = self._query_used_locked(qid)
            breach = total_used > self.total \
                or (bool(quota) and q_used > quota)
            return (forced or breach), total_used

        if policy == "legacy":
            _o, total_used = over()
            self._count_rung("deny")
            trace.event("memory", "memmgr.deny", consumer=cname,
                        total_used=total_used, budget=self.total)
            return False

        freed_any = False
        # rung 1: shrink — advise smaller scan batches from here on and
        # ask the requester for a partial release
        is_over, total_used = over()
        if is_over:
            self._shrink_level = min(self._shrink_level + 1, 3)
            self._comfort_grants = 0
            shrink_fn = getattr(c, "shrink", None)   # duck-typed consumers
            try:
                freed = int(shrink_fn() or 0) if shrink_fn else 0
            except Exception:   # pragma: no cover - consumer bug guard
                logger.exception("memmgr: %s.shrink() failed", cname)
                freed = 0
            if freed:
                freed_any = True
                with self._lock:
                    self._used[c] = max(self._used.get(c, 0) - freed, 0)
                    self.num_spills += 1
                    self.spilled_bytes += freed
            self._count_rung("shrink")
            trace.event("memory", "memmgr.pressure", rung="shrink",
                        consumer=cname, freed=freed,
                        advised_shift=self._shrink_level)

        # rung 1.5: cache_evict — drop warm-path cache entries (any
        # consumer marked pressure_evictable, i.e. pure DERIVED state
        # re-creatable at the cost of one query) before force-spilling
        # WORKING state. min_trigger is irrelevant here: small caches
        # that the main spill loop skipped still free real bytes
        is_over, total_used = over()
        if is_over:
            with self._lock:
                victims = [v for v, u in self._used.items()
                           if getattr(v, "pressure_evictable", False)
                           and u > 0 and self._spill_eligible_locked(v)]
            if victims:
                freed = 0
                for victim in victims:
                    with trace.span("memory", "memmgr.spill",
                                    victim=getattr(victim,
                                                   "consumer_name", "?"),
                                    total_used=total_used,
                                    budget=self.total,
                                    rung="cache_evict") as sp:
                        v_freed = victim.spill()
                        sp.set(freed=v_freed)
                    with self._lock:
                        self._used[victim] = max(
                            self._used.get(victim, 0) - v_freed, 0)
                        if v_freed:
                            self.num_spills += 1
                            self.spilled_bytes += v_freed
                    freed += v_freed
                if freed:
                    freed_any = True
                self._count_rung("cache_evict")
                trace.event("memory", "memmgr.pressure",
                            rung="cache_evict", consumer=cname,
                            freed=freed, victims=len(victims))

        # rung 2: force-spill the largest holder, min_trigger waived —
        # under real pressure many small consumers add up to the budget.
        # Victim pool: consumers of OVER-QUOTA queries first (the query
        # over its per-query ledger pays before any neighbor), every
        # consumer when no query is over quota
        is_over, total_used = over()
        if is_over:
            with self._lock:
                per_query = self._usage_by_query_locked()
                over_q = {q for q, u in per_query.items()
                          if quota and u > quota}
                # over-quota queries' consumers first; fall back to ALL
                # eligible consumers only when the GLOBAL budget is
                # breached (or the chaos deny forces the rung) — on a
                # quota-only breach spilling an innocent neighbor could
                # not lower the offender's ledger anyway ('never an
                # innocent neighbor'), so an empty offender pool lets
                # rung 3 decide instead
                pool = [(u, v) for v, u in self._used.items()
                        if self._query_of.get(v, "") in over_q
                        and self._spill_eligible_locked(v) and u > 0]
                if not pool and (forced
                                 or sum(per_query.values()) > self.total):
                    pool = [(u, v) for v, u in self._used.items()
                            if u > 0 and self._spill_eligible_locked(v)]
                candidates = pool
            freed = 0
            if candidates:
                _, victim = max(candidates, key=lambda t: t[0])
                with trace.span("memory", "memmgr.spill",
                                victim=getattr(victim, "consumer_name",
                                               "?"),
                                total_used=total_used, budget=self.total,
                                rung="force_spill") as sp:
                    freed = victim.spill()
                    sp.set(freed=freed)
                with self._lock:
                    self._used[victim] = max(
                        self._used.get(victim, 0) - freed, 0)
                    if freed:
                        self.num_spills += 1
                        self.spilled_bytes += freed
                if freed:
                    freed_any = True
            self._count_rung("force_spill")
            trace.event("memory", "memmgr.pressure", rung="force_spill",
                        consumer=cname, freed=freed)

        # rung 3: shed or survivable deny
        is_over, total_used = over()
        if is_over:
            with self._lock:
                q_used = self._query_used_locked(qid)
            if policy == "shed" or (quota and q_used > quota):
                self._count_rung("shed")
                trace.event("memory", "memmgr.shed", consumer=cname,
                            query=qid, query_used=q_used,
                            total_used=total_used, budget=self.total,
                            quota=quota)
                from auron_tpu import errors
                raise errors.MemoryExhausted(
                    f"memory pressure unresolved after the degradation "
                    f"ladder: {total_used} bytes used against budget "
                    f"{self.total}"
                    + (f" (query {qid or '<anon>'} used {q_used} against "
                       f"quota {quota})" if quota else "")
                    + f"; shedding the query (requester {cname})",
                    site="memmgr.deny")
            self._count_rung("deny")
            trace.event("memory", "memmgr.deny", consumer=cname,
                        total_used=total_used, budget=self.total)
        return freed_any

    @staticmethod
    def _registry_enabled() -> bool:
        try:
            from auron_tpu.obs import registry as obs_registry
            return obs_registry.enabled()
        except Exception:   # pragma: no cover
            return False

    def _observe(self, status: dict) -> None:
        """Mirror a status snapshot onto the process registry gauges
        (best-effort: telemetry must never fail an accounting update)."""
        try:
            from auron_tpu.obs import registry as obs_registry
            obs_registry.observe_memmgr(status)
        except Exception:   # pragma: no cover - observability best-effort
            logger.exception("memmgr gauge update failed")

    # -- status (reference dumps the consumer table on exit,
    #    auron-memmgr/src/lib.rs:143-163) ----------------------------------

    def status(self) -> dict:
        with self._lock:
            return self._status_locked()

    def _status_locked(self) -> dict:
        """Status snapshot; caller holds ``self._lock``."""
        queries = {tag or "<anon>": u
                   for tag, u in self._usage_by_query_locked().items()}
        n = max(len(queries), 1)
        return {
            "total": self.total,
            "used": sum(self._used.values()),
            "num_consumers": len(self._used),
            "num_queries": len(queries),
            # per LIVE QUERY, the concurrent runtime's fairness unit
            "fair_share": self.total // n,
            "num_spills": self.num_spills,
            "spilled_bytes": self.spilled_bytes,
            "consumers": {getattr(c, "consumer_name", "?"): u
                          for c, u in self._used.items()},
            "queries": queries,
        }

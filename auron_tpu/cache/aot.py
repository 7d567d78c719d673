"""AOT program plane: plan-signature inventory + startup warmer.

Productionizes the persistent XLA cache (``auron.xla_cache_dir``, bound
into jax at Session init) into an end-to-end cold-start story:

- **record** (``record_plan``): every completed top-level query whose
  plan reads only durable sources writes its plan bytes + a submission
  count under ``<xla_cache_dir>/aot_plans/<plan_fp>.{plan,json}``. The
  inventory is the mined "what does this deployment actually run".
- **warm** (``warm``): at Session init (``auron.cache.aot_top_n`` > 0)
  the top-N signatures by submission count — union of the aot_plans
  inventory and any resumable journals' recorded plans — are executed
  through the NORMAL planner/executor path. That drives every compile
  through the central program registry (per-site build/hit attribution
  stays correct) and the persistent XLA cache, and — when the result
  cache is enabled — leaves the warmed results ready to serve, so the
  process's first user query pays neither compile nor execution.

``warm`` runs OFF the construction path: Session init only spawns a
daemon thread, so warming overlaps the first user query's planning
instead of serializing ahead of it — ``last_stats()['overlapped_ms']``
is the wall the warmer ran concurrently. ``wait()`` joins the in-flight
warm (Session.close does, bounding the thread's lifetime to the session
that started it), and readers that need the FINAL summary call it
before ``last_stats``.

``warm`` NEVER raises (Session init must survive a corrupt inventory);
failures are collected in ``last_stats()['errors']`` and the perf_gate
cache arm fails loudly when the warmer errored silently.
"""

from __future__ import annotations

import base64
import json
import logging
import os
import threading
from typing import Optional

logger = logging.getLogger("auron.cache.aot")

_LOCK = threading.Lock()
_LAST: dict = {"warmed": 0, "skipped": 0, "errors": [],
               "overlapped_ms": 0.0}
#: the in-flight background warm, if any (one at a time: ``warm`` joins
#: the previous session's thread before starting its own)
_THREAD: Optional[threading.Thread] = None


def aot_dir(conf=None) -> str:
    """Inventory directory: ``aot_plans`` under ``auron.xla_cache_dir``.
    Empty string (= plane disarmed) when that knob is unset — the
    inventory is armed only by a deployment that names a directory,
    wherever the compile cache itself lives (utils/xla_cache.py)."""
    from auron_tpu import config as cfg
    if conf is None:
        conf = cfg.get_config()
    root = conf.get(cfg.XLA_CACHE_DIR)
    return os.path.join(root, "aot_plans") if root else ""


def record_plan(plan_bytes: bytes, catalog: Optional[dict],
                num_partitions: int = 1, conf=None) -> None:
    """Mine-side write: bump this plan's submission count in the
    inventory. Best-effort and silent — recording must never affect the
    query that triggered it."""
    try:
        d = aot_dir(conf)
        if not d:
            return
        from auron_tpu.cache import identity
        if not identity.cacheable(plan_bytes):
            return
        # durable sources only: a plan over in-memory tables cannot be
        # re-bound in a fresh process, so warming it would only error
        probe = identity.SourceProbe(plan_bytes, catalog)
        if any(not k.startswith("file:") for k in probe.fingerprints()):
            return
        fp = identity.plan_fingerprint(plan_bytes)
        os.makedirs(d, exist_ok=True)
        plan_path = os.path.join(d, fp + ".plan")
        if not os.path.exists(plan_path):
            tmp = plan_path + ".part"
            with open(tmp, "wb") as f:
                f.write(plan_bytes)
            os.replace(tmp, plan_path)
        meta_path = os.path.join(d, fp + ".json")
        meta = {"count": 0}
        try:
            with open(meta_path, encoding="utf-8") as f:
                meta.update(json.load(f))
        except (OSError, ValueError):
            pass
        meta["count"] = int(meta.get("count", 0)) + 1
        meta["num_partitions"] = int(num_partitions)
        tmp = meta_path + ".part"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(meta, f)
        os.replace(tmp, meta_path)
    except Exception:
        logger.debug("aot: record_plan failed", exc_info=True)


def _inventory(conf) -> dict:
    """fp -> (count, plan_bytes, num_partitions): the aot_plans
    inventory unioned with resumable journals' recorded plans (a
    crashed process's in-flight query is a strong warm candidate)."""
    out: dict = {}
    d = aot_dir(conf)
    if d and os.path.isdir(d):
        for name in sorted(os.listdir(d)):
            if not name.endswith(".plan"):
                continue
            fp = name[:-len(".plan")]
            try:
                with open(os.path.join(d, name), "rb") as f:
                    plan_bytes = f.read()
            except OSError:
                continue
            meta = {}
            try:
                with open(os.path.join(d, fp + ".json"),
                          encoding="utf-8") as f:
                    meta = json.load(f)
            except (OSError, ValueError):
                pass
            out[fp] = (int(meta.get("count", 1)), plan_bytes,
                       int(meta.get("num_partitions", 1)))
    from auron_tpu.runtime import journal as jrn
    jdir = jrn.journal_dir(conf)
    if jdir and os.path.isdir(jdir):
        for name in sorted(os.listdir(jdir)):
            path = os.path.join(jdir, name)
            header = jrn._peek_header(path)
            if not header or "plan_b64" not in header:
                continue
            try:
                plan_bytes = base64.b64decode(header["plan_b64"])
            except (ValueError, TypeError):
                continue
            fp = header.get("plan_fp", "")
            if fp and fp not in out:
                out[fp] = (1, plan_bytes,
                           int(header.get("num_partitions", 1)))
    return out


def warm(session) -> dict:
    """Start warming the top-N inventory plans through ``session``'s
    normal plan/execute path on a BACKGROUND daemon thread and return
    immediately — Session construction no longer blocks on the warm,
    which instead overlaps the first user query's planning. The final
    ``{"warmed", "skipped", "errors", "overlapped_ms"}`` summary lands
    in ``last_stats`` when the thread completes; ``wait()`` joins it.
    Never raises (a broken warmer must not fail construction)."""
    global _LAST, _THREAD
    import time
    # one warm at a time: a second Session arming the warmer while the
    # first is still warming would race the shared inventory/stats
    wait()
    stats: dict = {"warmed": 0, "skipped": 0, "errors": [],
                   "overlapped_ms": 0.0}
    top_n = 0
    try:
        from auron_tpu import config as cfg
        conf = session.config
        top_n = int(conf.get(cfg.CACHE_AOT_TOP_N))
    except Exception as e:   # Session init must survive a broken warmer
        stats["errors"].append(f"warm: {type(e).__name__}: {e}")
        logger.warning("aot: warm failed", exc_info=True)
    if top_n <= 0:
        with _LOCK:
            _LAST = dict(stats, errors=list(stats["errors"]))
        return stats

    def _run() -> None:
        global _LAST
        t0 = time.perf_counter()
        out: dict = {"warmed": 0, "skipped": 0, "errors": []}
        try:
            out = _warm_inner(session, conf, top_n)
        except Exception as e:   # same contract as the sync era
            out["errors"].append(f"warm: {type(e).__name__}: {e}")
            logger.warning("aot: warm failed", exc_info=True)
        out["overlapped_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        with _LOCK:
            _LAST = out

    th = threading.Thread(target=_run, name="auron-aot-warm", daemon=True)
    with _LOCK:
        _THREAD = th
    th.start()
    return stats


def wait(timeout: Optional[float] = None) -> bool:
    """Join the in-flight background warm; no-op when none is running.
    Returns True when no warm is left in flight (so ``last_stats`` is
    the FINAL summary), False on a timeout expiring first."""
    global _THREAD
    with _LOCK:
        th = _THREAD
    if th is None:
        return True
    th.join(timeout)
    if th.is_alive():
        return False
    with _LOCK:
        if _THREAD is th:
            _THREAD = None
    return True


def _warm_inner(session, conf, top_n: int) -> dict:
    from auron_tpu.cache import identity
    from auron_tpu.cache import result_cache as rcache
    from auron_tpu.ir.planner import plan_from_bytes
    from auron_tpu.obs import trace
    from auron_tpu.runtime import lifecycle, programs
    from auron_tpu.runtime.executor import collect as _collect

    stats: dict = {"warmed": 0, "skipped": 0, "errors": []}
    ranked = sorted(_inventory(conf).items(),
                    key=lambda kv: (-kv[1][0], kv[0]))[:top_n]
    for fp, (count, plan_bytes, num_partitions) in ranked:
        probe = identity.SourceProbe(plan_bytes, session.ctx.catalog)
        if probe.any_missing():
            # source vanished since it was recorded: not an error —
            # the inventory outlives datasets by design
            stats["skipped"] += 1
            continue
        token = lifecycle.CancelToken(query_id=f"aot-{fp[:12]}")
        try:
            with trace.span("cache", "aot.warm", plan_fp=fp,
                            count=count, partitions=num_partitions):
                op = plan_from_bytes(plan_bytes, session.ctx)
                table = _collect(op, num_partitions=num_partitions,
                                 mem_manager=session.mem_manager,
                                 config=conf, cancel_token=token)
            key = rcache.get_cache().result_key(
                plan_bytes, session.ctx.catalog)
            if key is not None:
                rcache.get_cache().put_result(key, table)
            stats["warmed"] += 1
        except Exception as e:
            stats["errors"].append(f"{fp}: {type(e).__name__}: {e}")
            logger.warning("aot: warming %s failed", fp, exc_info=True)
        finally:
            programs.pop_query(token.query_id)
    return stats


def last_stats() -> dict:
    """The most recent COMPLETED ``warm`` summary (perf_gate's
    silent-failure check and the ops endpoints read this). With a warm
    still in flight this is the previous summary — call ``wait()``
    first when the final figures are needed."""
    with _LOCK:
        return {"warmed": _LAST["warmed"], "skipped": _LAST["skipped"],
                "errors": list(_LAST["errors"]),
                "overlapped_ms": _LAST.get("overlapped_ms", 0.0)}

"""Cross-process serving boundary: the live host-attach protocol.

The reference's host engine drives the native runtime per task through
three JNI entry points — callNative (submit a TaskDefinition), nextBatch
(pull one Arrow batch through the FFI), finalizeNative (metrics +
teardown) — JniBridge.java:49-55 driven by
AuronCallNativeWrapper.java:78-190 over rt.rs:76-300. This module is the
same lifecycle WITHOUT a JVM: a length-prefixed framed protocol over a
TCP (or Unix) socket that any process — a Spark executor plugin, a test
client, another language — can speak.

Wire format (all integers little-endian):

    frame  := u8 kind | u32 len | payload[len]
    kinds  : 1 SUBMIT      client→server  TaskDefinition protobuf bytes
             2 BATCH       server→client  one Arrow IPC stream holding one
                                          RecordBatch (self-describing)
             3 DONE        server→client  JSON {metrics, schema_ipc b64,
                                          report?} — schema always present
                                          so empty results stay typed
             4 ERROR       server→client  utf-8 traceback; terminates task
             5 SHUTDOWN    client→server  stop serving (tests/admin)
             6 SUBMIT_PLAN client→server  JSON {plan: Spark plan.toJSON
                                          tree, path_rewrites?, partition_id?,
                                          num_partitions?, spark_version?} —
                                          the engine converts AND executes,
                                          the live-attach composition the
                                          reference does in
                                          AuronConverters.scala:209-310 +
                                          JniBridge.callNative
             7 ACK         client→server  consumed one BATCH (flow control)
             8 CANCEL      client→server  tear down the running task
             9 NEED_TABLES server→client  JSON [{table, exec, columns}] —
                                          unconvertible subtrees the host
                                          must execute (ConvertToNative
                                          boundary, AuronConvertStrategy)
            10 TABLE       client→server  u32 name_len | name | Arrow IPC
                                          stream with the subtree's rows
            11 RESUME      client→server  JSON {query_id} — continue a
                                          journaled query after a server
                                          restart (runtime/journal.py);
                                          streams BATCH/DONE like SUBMIT
                                          or answers a structured ERROR
                                          (first line "ResumeUnavailable
                                          reason=...").  Replays the
                                          journaled DRIVING SCOPE: a
                                          Session-journaled ("collect")
                                          query streams every partition
                                          0..N-1 — the dead driver's
                                          fan-out — while a serving-
                                          journaled ("task") one replays
                                          exactly its own partition_id

            14 TRACE       client→server  JSON {trace, parent, role,
                                          pid} — OPTIONAL prefix frame
                                          ahead of SUBMIT/SUBMIT_PLAN/
                                          RESUME carrying the sender's
                                          trace context
                                          (obs/trace.wire_context); the
                                          receiver adopts it so spans
                                          on both sides share one
                                          trace id. Sent only when
                                          auron.trace.{enabled,
                                          propagate} are on AND a trace
                                          is active — the wire is
                                          byte-identical otherwise, and
                                          a receiver with tracing off
                                          just skips the frame
            13 HELLO       client→server  empty payload — replica
                                          registration handshake: one
                                          DONE frame with JSON {pid,
                                          tag, host, port, ops_port,
                                          window, journal_dir} so a
                                          fleet router learns a
                                          replica's liveness identity
                                          (utils/liveness pid+epoch
                                          tag), its ops scrape port,
                                          and its journal dir without
                                          any side channel

CANCEL doubles as a FIRST frame carrying JSON {query_id}: cancel a live
query by id over a fresh connection (DONE {cancelled} on success, a
structured ERROR "UnknownQuery reason=unknown_query_id ..." when the id
is unknown or already finished).

A SUBMIT_PLAN whose JSON carries ``"router_tag": true`` (the fleet
router sets it; extra keys are ignored by older servers, so the client
wire contract is unchanged) receives one EARLY server→client ACK frame
with JSON {query_id, pid} before any BATCH: the router learns the
server-assigned query id (hence the journal stem ``<query_id>_<pid>``)
so it can CANCEL-by-id or RESUME the query on a survivor after this
replica dies mid-stream.

Flow control mirrors rt.rs's bound-1 sync channel, generalized to a
window: the server keeps at most ``window`` un-ACKed BATCH frames in
flight, so a slow host applies backpressure instead of unbounded socket
buffering. A CANCEL frame — or the client closing the socket — stops the
producer within one batch (reference: is_task_running checks,
rt.rs:208-238).

One SUBMIT/SUBMIT_PLAN per connection mirrors the per-task lifecycle of
the reference (each Spark task owns one native execution runtime).
"""

from __future__ import annotations

import base64
import io
import itertools
import json
import os
import queue
import socket
import socketserver
import struct
import threading
import traceback

import pyarrow as pa

from auron_tpu import errors

#: process-unique serving query ids: they key the process-global
#: per-query ledgers (program cache, memmgr), so handlers must not share
_SERVING_QUERY_SEQ = itertools.count(1)

KIND_SUBMIT = 1
KIND_BATCH = 2
KIND_DONE = 3
KIND_ERROR = 4
KIND_SHUTDOWN = 5
KIND_SUBMIT_PLAN = 6
KIND_ACK = 7
KIND_CANCEL = 8
KIND_NEED_TABLES = 9
KIND_TABLE = 10
#: first-frame RESUME: payload JSON {"query_id": ...} (or a bare utf-8
#: query id) — continue a journaled query after a server restart
#: (runtime/journal.py). The server streams the resumed result exactly
#: like a SUBMIT, or answers a STRUCTURED first-line ERROR naming why
#: not (ResumeUnavailable reason=no_journal|corrupt|
#: fingerprint_mismatch|journaling_disabled|ambiguous|missing_source).
KIND_RESUME = 11
#: first-frame STATS: answers one DONE frame with the ops plane's live
#: query table + admission counters + server stats as JSON — the
#: /queries endpoint over the EXISTING wire protocol, for clients
#: behind firewalls that cannot reach the HTTP port (AuronClient.stats)
KIND_STATS = 12
#: first-frame HELLO: the fleet router's registration handshake —
#: answers one DONE frame with this process's identity (pid + liveness
#: tag), serving address, ops port, and journal dir
KIND_HELLO = 13
#: OPTIONAL trace-context prefix frame ahead of SUBMIT/SUBMIT_PLAN/
#: RESUME (fleet-scope observability): JSON {trace, parent, role, pid}
#: from obs/trace.wire_context — the receiver adopts the trace id as
#: its query-span parent (obs/trace.wire_scope), so client, router and
#: replica exports stitch into ONE timeline. Never sent unless
#: auron.trace.enabled + auron.trace.propagate are on and a trace is
#: active.
KIND_TRACE = 14

#: max un-ACKed BATCH frames in flight (rt.rs uses a bound-1 channel; a
#: small window amortizes the network round trip without losing the
#: backpressure property)
DEFAULT_WINDOW = 4

_HDR = struct.Struct("<BI")


def write_frame(sock, kind: int, payload: bytes) -> None:
    sock.sendall(_HDR.pack(kind, len(payload)) + payload)


def _journal_error_frame(e) -> bytes:
    """ERROR payload for a JournalError verdict: ONE machine-parseable
    first line (``<Type> reason=<reason> query_id=<id>``) ahead of the
    human message — the single formatter every by-id control path uses
    (RESUME refusals, CANCEL-by-id unknowns), so the wire contract
    cannot drift between them."""
    return (f"{type(e).__name__} reason={e.reason or 'error'} "
            f"query_id={e.query_id or ''}\n{e}").encode()


def parse_shed(text: str):
    """``(reason, retry_after_s)`` parsed from a structured
    ``AdmissionRejected`` ERROR payload's first line, or None when the
    text is not a shed.  ONE parser for every consumer of the shed
    contract — the client's ``retry_sheds`` fallback and the fleet
    router's spill-over — so the wire format cannot drift between
    them.  ``retry_after_s`` is None when the server had no estimate
    (the literal ``None`` the f-string emits)."""
    first = text.splitlines()[0] if text else ""
    if not first.startswith("AdmissionRejected"):
        return None
    reason, retry = "unknown", None
    for tok in first.split()[1:]:
        key, _, val = tok.partition("=")
        if key == "reason":
            reason = val
        elif key == "retry_after_s":
            try:
                retry = float(val)   # graft: disable=GL001 -- parsing a wire-protocol token, host data
            except ValueError:
                retry = None
    return reason, retry


def read_frame(sock) -> tuple[int, bytes]:
    hdr = _read_exact(sock, _HDR.size)
    kind, ln = _HDR.unpack(hdr)
    return kind, _read_exact(sock, ln)


def _read_exact(sock, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed mid-frame")
        buf += chunk
    return buf


def _ipc_bytes(rb: pa.RecordBatch) -> bytes:
    out = io.BytesIO()
    with pa.ipc.new_stream(out, rb.schema) as w:
        w.write_batch(rb)
    return out.getvalue()


def _ipc_table(data: bytes) -> pa.Table:
    with pa.ipc.open_stream(io.BytesIO(data)) as r:
        return r.read_all()


def _ipc_batch(data: bytes) -> pa.RecordBatch:
    with pa.ipc.open_stream(io.BytesIO(data)) as r:
        return next(iter(r))


def _schema_ipc_b64(schema: pa.Schema) -> str:
    return base64.b64encode(schema.serialize().to_pybytes()).decode()


def _schema_from_b64(b64: str) -> pa.Schema:
    return pa.ipc.read_schema(pa.py_buffer(base64.b64decode(b64)))


class _TaskHandler(socketserver.BaseRequestHandler):
    def setup(self):
        # the handler's cancel registry IS a query CancelToken: the
        # CANCEL frame, a client disconnect, and a request deadline all
        # flip the SAME token the execution runtime polls — socket-level
        # and API-level cancel are one mechanism (runtime/lifecycle.py)
        from auron_tpu.runtime.lifecycle import CancelToken
        self._cancel = CancelToken(
            query_id=f"serving-{next(_SERVING_QUERY_SEQ)}")
        self._window = threading.Semaphore(
            getattr(self.server, "window", DEFAULT_WINDOW))
        self._tables: queue.Queue = queue.Queue()
        self._reader = None

    def handle(self):
        try:
            kind, payload = read_frame(self.request)
            self._wire_ctx = None
            if kind == KIND_TRACE:
                # optional trace-context prefix (fleet observability):
                # adopt it around the REAL first frame that follows; a
                # malformed payload degrades to no adoption, never an
                # error — telemetry must not fail a query
                try:
                    ctx = json.loads(payload.decode() or "{}")
                    if isinstance(ctx, dict):
                        self._wire_ctx = ctx
                except (ValueError, UnicodeDecodeError):
                    pass
                kind, payload = read_frame(self.request)
        except ConnectionError:
            return
        if kind == KIND_SHUTDOWN:
            self.server._shutdown_requested = True
            threading.Thread(target=self.server.shutdown,
                             daemon=True).start()
            return
        if kind == KIND_CANCEL:
            # first-frame CANCEL-BY-ID (a reconnecting/admin client
            # cancelling a query it no longer holds the socket for):
            # a live id cancels and DONEs; an unknown/expired id gets
            # the STRUCTURED verdict, never a generic traceback
            self._cancel_by_id(payload)
            return
        if kind == KIND_STATS:
            self._send_stats()
            return
        if kind == KIND_HELLO:
            self._send_hello()
            return
        if kind not in (KIND_SUBMIT, KIND_SUBMIT_PLAN, KIND_RESUME):
            write_frame(self.request, KIND_ERROR,
                        f"expected SUBMIT, got kind={kind}".encode())
            return
        # from here on, all socket READS belong to the control-reader
        # thread (ACK / CANCEL / TABLE / disconnect); the handler only
        # writes
        self.server.task_started()
        self._reader = threading.Thread(target=self._control_reader,
                                        daemon=True)
        self._reader.start()
        from auron_tpu import errors as _errors
        from auron_tpu.obs import trace as _trace
        self.server.register_query(self._cancel)
        try:
            # adopt the inbound wire trace context (no-op without one):
            # every span this handler thread records — the query scope,
            # task/operator spans — joins the SENDER's trace id
            with _trace.wire_scope(self._wire_ctx):
                if kind == KIND_SUBMIT:
                    self._run_task(payload)
                elif kind == KIND_RESUME:
                    self._run_resume(payload)
                else:
                    self._run_plan_task(payload)
        except _Cancelled:
            self.server.stats["cancelled"] += 1
        except _errors.JournalError as e:
            # resume verdicts carry a machine-readable reason on the
            # STRUCTURED first line (the AdmissionRejected precedent):
            # a reconnecting client learns WHY its query cannot be
            # continued without scraping a traceback
            self.server.stats["resume_refused"] += 1
            try:
                write_frame(self.request, KIND_ERROR,
                            _journal_error_frame(e))
            except OSError:
                pass
        except _errors.AdmissionRejected as e:
            # overload shed: a STRUCTURED first line (machine-parseable
            # reason + retry-after hint) ahead of the message, so a
            # client can back off without scraping a traceback
            self.server.stats["rejected"] += 1
            try:
                write_frame(self.request, KIND_ERROR,
                            (f"AdmissionRejected reason={e.reason} "
                             f"retry_after_s={e.retry_after_s}\n{e}")
                            .encode())
            except OSError:
                pass
        except Exception:
            try:
                write_frame(self.request, KIND_ERROR,
                            traceback.format_exc(limit=12).encode())
            except OSError:
                pass
        finally:
            self.server.unregister_query(self._cancel)
            # quiet completion, NOT a cancel: the token must release the
            # control reader without recording a cancel reason/event on
            # every successful request
            self._cancel.finish()
            try:
                # long-lived engine process: bound accumulated XLA
                # programs — but ONLY while no other handler thread is
                # mid-task (clear_caches during a concurrent trace would
                # race the very caches it prunes)
                self.server.task_done_maybe_trim()
            except Exception:   # graft: disable=GL004 -- post-request cache trim is opportunistic; the reply already shipped
                pass

    # -- control plane -----------------------------------------------------

    def _control_reader(self):
        """Reads client frames while the task runs: ACK releases window
        slots, CANCEL / disconnect stop the producer, TABLE feeds
        fallback-boundary rows."""
        try:
            while not self._cancel.is_set():
                kind, payload = read_frame(self.request)
                if kind == KIND_ACK:
                    self._window.release()
                elif kind == KIND_CANCEL:
                    return
                elif kind == KIND_TABLE:
                    (nlen,) = struct.unpack("<I", payload[:4])
                    name = payload[4:4 + nlen].decode()
                    self._tables.put((name, _ipc_table(payload[4 + nlen:])))
                else:
                    return   # protocol violation: treat as disconnect
        except Exception:   # graft: disable=GL004 -- reader teardown: dead peer/malformed frame ends the loop; the finally cancels the task
            pass   # malformed frame / peer went away: stop computing
        finally:
            # EVERY mid-task reader exit must cancel: a live handler
            # with a dead reader would otherwise spin on the window
            # semaphore forever. After the handler already finished
            # (token released quietly) there is nothing to cancel — a
            # post-DONE socket close must not record a spurious one.
            if not self._cancel.is_set():
                self._cancel.set()

    def _send_batch(self, rb: pa.RecordBatch) -> None:
        """Backpressured BATCH send; raises _Cancelled when the client
        cancelled or disconnected instead of writing into the void. A
        DEADLINE that expires while blocked on the window (slow or
        stopped consumer) raises the classified DeadlineExceeded so the
        client still gets the ERROR frame — the budget verdict must be
        visible even when the task itself never got to poll."""

        def stop():
            if self._cancel.reason == "deadline":
                self._cancel.raise_for_status()
            raise _Cancelled()

        from auron_tpu.obs import trace as _trace
        with _trace.layer_span("serve", "send"):
            while not self._window.acquire(timeout=0.1):
                if self._cancel.is_set():
                    stop()
            if self._cancel.is_set():
                stop()
            try:
                write_frame(self.request, KIND_BATCH, _ipc_bytes(rb))
                self.server.stats["batches_sent"] += 1
            except OSError:
                raise _Cancelled()

    @staticmethod
    def _parse_query_id(payload: bytes) -> str:
        """Query id from a by-id control frame: JSON ``{"query_id"}``
        or a bare utf-8 id.  ONE definition for both CANCEL-by-id and
        RESUME so the wire contract cannot drift between them."""
        try:
            req = json.loads(payload.decode() or "{}")
            return req.get("query_id", "") if isinstance(req, dict) \
                else str(req)
        except (ValueError, UnicodeDecodeError):
            return payload.decode("utf-8", "replace").strip()

    def _send_stats(self) -> None:
        """First-frame STATS: one DONE frame carrying the live query
        table (every scheduler in the process — the ops plane's
        /queries body), this server's admission stats and wire
        counters, and the ops endpoint's port when it is running — so
        a client that can reach the serving socket needs no second
        port to observe the process."""
        from auron_tpu.obs import ops_server as _ops
        from auron_tpu.runtime import scheduler as sched_mod
        body = {
            "queries": sched_mod.aggregate_query_table(),
            "admission": self.server.scheduler.stats(),
            "server": dict(self.server.stats),
        }
        try:
            from auron_tpu.cache import aot as _aot
            from auron_tpu.cache import result_cache as _rcache
            body["cache"] = _rcache.get_cache().stats()
            body["aot"] = _aot.last_stats()
        except Exception:   # graft: disable=GL004 -- stats tee is best-effort
            pass
        try:
            from auron_tpu.obs import ledger as _ledger
            body["cost_ledgers"] = _ledger.recent(16)
        except Exception:   # graft: disable=GL004 -- stats tee is best-effort
            pass
        ops = _ops.current()
        if ops is not None:
            body["ops_port"] = ops.port
        try:
            write_frame(self.request, KIND_DONE,
                        json.dumps(body, default=str).encode())
        except OSError:   # pragma: no cover - client went away
            pass

    def _send_hello(self) -> None:
        """First-frame HELLO: the fleet router's registration
        handshake. One DONE frame carrying this process's pid AND its
        liveness tag (host:pid:epoch — the router's provably-dead
        verdict needs the epoch, a recycled pid must not mask a death),
        the serving address, the ops scrape port, and the journal dir
        (empty when journaling is off) so the router knows whether
        failover can RESUME here or must re-execute."""
        from auron_tpu.runtime import journal as _jrn
        from auron_tpu.utils import liveness
        body = {
            "pid": os.getpid(),
            "tag": liveness.own_tag(),
            "host": self.server.address[0],
            "port": self.server.address[1],
            "window": getattr(self.server, "window", DEFAULT_WINDOW),
            "journal_dir": _jrn.journal_dir() or "",
            "ops_port": self.server.stats.get("ops_port"),
        }
        try:
            write_frame(self.request, KIND_DONE,
                        json.dumps(body).encode())
        except OSError:   # pragma: no cover - router went away
            pass

    def _cancel_by_id(self, payload: bytes) -> None:
        """First-frame CANCEL with a query-id payload: cancel another
        connection's live query on this server, or answer the
        structured ``UnknownQuery`` verdict."""
        qid = self._parse_query_id(payload)
        token = self.server.find_query(qid)
        if token is None:
            from auron_tpu import errors as _errors
            verdict = _errors.UnknownQuery(
                f"query {qid!r} is not live on this server (unknown "
                "id, or it already finished — cancel-after-DONE is a "
                "no-op)", query_id=qid, reason="unknown_query_id")
            try:
                write_frame(self.request, KIND_ERROR,
                            _journal_error_frame(verdict))
            except OSError:
                pass
            return
        token.cancel()
        try:
            write_frame(self.request, KIND_DONE,
                        json.dumps({"cancelled": qid}).encode())
        except OSError:
            pass

    # -- task execution ----------------------------------------------------

    def _run_task(self, task_bytes: bytes) -> None:
        from auron_tpu.ir.planner import PlannerContext
        self._execute(task_bytes, PlannerContext(), report=None)

    def _run_resume(self, payload: bytes) -> None:
        """RESUME: continue a journaled query after a server restart.
        The journal is loaded + validated (classified JournalError
        verdicts reach handle()'s structured ERROR frame), bound to
        this handler's token, and the journaled TaskDefinition replays
        through the normal execute path — satisfied exchanges skip
        their map sides, reducers fetch the journaled RSS files, and
        the client receives the continued stream exactly as a fresh
        SUBMIT would have delivered it."""
        from auron_tpu import config as cfg
        from auron_tpu import errors
        from auron_tpu.ir.planner import PlannerContext
        from auron_tpu.runtime import journal as jrn
        qid = self._parse_query_id(payload)
        conf = cfg.get_config()
        if not jrn.enabled(conf):
            raise errors.ResumeUnavailable(
                "journaling is disabled on this server "
                "(auron.journal.dir is empty)", query_id=qid,
                reason="journaling_disabled")
        jr = jrn.load_for_resume(jrn.journal_dir(conf), qid, {}, conf)
        # replay the journaled DRIVING SCOPE: a Session-journaled query
        # ("collect") streams every partition 0..N-1 — the driver that
        # owned the fan-out is dead, so the server takes its place; a
        # serving-journaled task ("task") replays exactly its own
        # partition_id (the host engine still owns the other tasks)
        parts = (list(range(jr.num_partitions))
                 if jr.scope == "collect" else None)
        try:
            # attach INSIDE the guard: a failed reopen (ENOSPC, the
            # file raced away) must release the open-stem/.claim too,
            # or the query is unresumable until this server restarts
            jrn.attach_resumed(self._cancel, jr)
            self._execute(jr.plan_bytes, PlannerContext(), report=None,
                          journal=jr, partitions=parts)
        except BaseException:
            # _execute suspends the journal only once INSIDE its slot;
            # an AdmissionRejected from the acquire (or any pre-slot
            # unwind) would otherwise leave the stem claimed 'open'
            # forever — suspend here too, idempotently
            jr.suspend()
            raise

    def _run_plan_task(self, payload: bytes) -> None:
        """SUBMIT_PLAN: convert a raw host plan server-side through the
        adaptor SPI (default: Spark plan.toJSON via SparkAdaptor), source
        any ConvertToNative boundaries from the client, execute."""
        from auron_tpu.integration.adaptor import SparkAdaptor, get_adaptor
        from auron_tpu.ir import pb
        from auron_tpu.ir.planner import PlannerContext
        req = json.loads(payload.decode())
        rewrites = req.get("path_rewrites") or {}
        # request-scoped deadline: arrives on the SUBMIT_PLAN frame so
        # the server enforces it even when the client vanishes
        timeout_s = req.get("timeout_s")
        if timeout_s:
            # graft: disable=GL001 -- a wire-protocol field, host data
            self._cancel.arm_deadline(float(timeout_s))
        if req.get("router_tag"):
            # fleet-router registration: echo the server-assigned query
            # id (and pid — together the journal stem) EARLY, before
            # any admission/planning work, so the router can CANCEL or
            # journal-RESUME this query even if the replica dies before
            # its first BATCH. Plain clients never set the key and the
            # server never volunteers the frame — the wire protocol is
            # unchanged for them.
            try:
                write_frame(self.request, KIND_ACK,
                            json.dumps({"query_id": self._cancel.query_id,
                                        "pid": os.getpid()}).encode())
            except OSError:
                raise _Cancelled()

        def rewrite(p):
            return rewrites.get(p) or rewrites.get(os.path.basename(p), p)

        name = req.get("adaptor", "spark")
        if name == "spark":
            adaptor = SparkAdaptor(req.get("spark_version", "3.5.0"))
        else:
            adaptor = get_adaptor(name)
        node, report = adaptor.convert_plan(req["plan"],
                                            path_rewrite=rewrite)

        catalog = {}
        if report.boundaries:
            need = [{"table": t, "exec": cls,
                     "columns": [a.name for a in attrs]}
                    for t, cls, attrs in report.boundaries]
            write_frame(self.request, KIND_NEED_TABLES,
                        json.dumps(need).encode())
            expected = {n["table"] for n in need}
            for _ in need:
                while True:
                    try:
                        name, tbl = self._tables.get(timeout=0.1)
                        break
                    except queue.Empty:
                        if self._cancel.is_set():
                            raise _Cancelled()
                # validate at receive time: a misnamed/duplicate TABLE
                # frame fails loudly here, not as an opaque missing-table
                # error mid-execution
                if name not in expected:
                    raise ValueError(
                        f"TABLE frame {name!r} does not match any "
                        f"requested boundary (outstanding: "
                        f"{sorted(expected)})")
                expected.discard(name)
                catalog[name] = tbl

        task_bytes = pb.TaskDefinition(
            plan=node,
            # graft: disable=GL001 -- a wire-protocol field, host data
            partition_id=int(req.get("partition_id", 0)),
            # graft: disable=GL001 -- a wire-protocol field, host data
            num_partitions=int(req.get("num_partitions", 1)),
        ).SerializeToString()
        self._execute(task_bytes, PlannerContext(catalog=catalog),
                      report={"converted": len(report.tags)
                              - len(report.never_converted),
                              "fallbacks": [
                                  {"exec": cls, "reason": reason}
                                  for cls, reason in
                                  report.never_converted],
                              "summary": report.summary()})

    def _execute(self, task_bytes: bytes, planner_ctx, report,
                 journal=None, partitions=None) -> None:
        """End-to-end observation wrapper around the execution body:
        every exit — DONE, shed, cancel, deadline, failure — lands on
        the ``auron_query_duration_seconds{outcome}`` histogram, and a
        classified failure writes its post-mortem bundle from THIS
        unwind (the serving half of the Session contract)."""
        import time as _time

        from auron_tpu.obs import bundle as _bundle
        from auron_tpu.obs import registry as _obs_registry

        def observe(exc) -> None:
            try:
                _obs_registry.observe_query(
                    _time.monotonic() - t0,
                    _obs_registry.classify_outcome(exc),
                    served_from=getattr(self._cancel, "served_from",
                                        None))
            except Exception:   # pragma: no cover  # graft: disable=GL004 -- per-query outcome telemetry is best-effort
                pass

        t0 = _time.monotonic()
        try:
            self._execute_inner(task_bytes, planner_ctx, report,
                                journal=journal, partitions=partitions)
        except BaseException as e:
            _bundle.maybe_write(e, token=self._cancel,
                                scheduler=self.server.scheduler)
            observe(e)
            raise
        else:
            observe(None)

    def _execute_inner(self, task_bytes: bytes, planner_ctx, report,
                       journal=None, partitions=None) -> None:
        """The task under its accumulator (obs/trace.py): the wait for
        a slot is ``auron:serve/queue``, outside the ledger's ``wall_s``;
        from the slot on it is ``auron:serve/task``, the root every
        other layer span of the task nests in."""
        from auron_tpu.obs import trace as _trace
        with _trace.task_scope(self._cancel.query_id) as acc:
            with _trace.layer_span("serve", "queue"):
                slot = self._admit()
            acc.start()
            with _trace.layer_span("serve", "task",
                                   query_id=self._cancel.query_id):
                self._execute_admitted(slot, acc, task_bytes, planner_ctx,
                                       report, journal, partitions)

    def _admit(self):
        from auron_tpu import errors
        from auron_tpu.ops.base import TaskCancelled
        from auron_tpu.runtime import lifecycle
        # admission control BEFORE any plan building: the server's
        # scheduler bounds concurrent executing tasks; past the bounded
        # queue (or a breached registry signal) this request is shed
        # with AdmissionRejected — mapped to a structured ERROR frame by
        # handle(). A CANCEL frame / client disconnect / deadline expiry
        # WHILE QUEUED dequeues here and tears down silently: no
        # runtime, no consumer or spill ledger entry ever exists.
        try:
            slot = self.server.scheduler.acquire(self._cancel)
        except errors.DeadlineExceeded:
            # ordering matters: DeadlineExceeded IS-A QueryCancelled,
            # and a deadline expiring WHILE QUEUED is just as much a
            # client-visible budget verdict as one mid-stream — it must
            # reach the ERROR frame, not vanish as a silent cancel
            lifecycle.observe_unwind(self._cancel, kind="deadline")
            raise
        except (TaskCancelled, errors.QueryCancelled):
            # queue-phase cancels feed the same cancel-latency
            # histogram as mid-execution ones — the acceptance gate
            # reads it as covering every cancel class
            lifecycle.observe_unwind(
                self._cancel, kind=self._cancel.reason or "cancel")
            raise _Cancelled()
        return slot

    def _execute_admitted(self, slot, acc, task_bytes: bytes, planner_ctx,
                          report, journal, partitions) -> None:
        # imported lazily so the server process controls jax platform
        # selection before anything initializes a backend
        from auron_tpu.columnar.arrow_bridge import (schema_to_arrow,
                                                     to_arrow)
        from auron_tpu.ir import pb
        from auron_tpu.ir.planner import plan_from_bytes
        from auron_tpu import errors
        from auron_tpu.obs import trace as _trace
        from auron_tpu.ops.base import TaskCancelled
        from auron_tpu.runtime import lifecycle
        from auron_tpu.runtime.executor import (ExecutionRuntime,
                                                TaskDefinition)
        self._cancel.slot = slot
        prev_bind = lifecycle.bind_token(self._cancel)
        import time as _time

        from auron_tpu.obs import ledger as _ledger
        ledger_on = _ledger.enabled()
        t_led = _time.monotonic()
        snaps: list = []
        rows_sent = batches_sent = 0
        jr = journal
        cache_key = None

        def _finish_ledger(outcome: str) -> dict:
            # the per-query accounting record (obs/ledger.py): stashed
            # on the token (the bundle writer reads it), retained in
            # the process ring (STATS frame / AuronClient.stats), and
            # — on success — ridden on the DONE frame
            led = _ledger.build(
                snaps, query_id=self._cancel.query_id, rows=rows_sent,
                batches=batches_sent, partitions=len(snaps),
                wall_s=_time.monotonic() - t_led,
                cache_hit=getattr(self._cancel, "served_from",
                                  None) == "cache",
                served_from=getattr(self._cancel, "served_from",
                                    None) or "",
                outcome=outcome, task=acc)
            self._cancel.cost_ledger = led
            _ledger.record(led)
            return led
        try:
            task = pb.TaskDefinition()
            with _trace.layer_span("plan", "decode"):
                task.ParseFromString(task_bytes)
            # warm-path lookup (auron_tpu/cache) BEFORE journal/plan
            # work — plain SUBMITs only (a RESUME or pre-adopted
            # journal means committed partial state exists and must be
            # driven to completion, not shadowed by a cached answer)
            from auron_tpu.cache import result_cache as _rcache
            cache = _rcache.get_cache()
            if journal is None and partitions is None:
                cache_key = cache.result_key(
                    task_bytes, planner_ctx.catalog, scope="task",
                    partition=task.partition_id)
            if cache_key is not None:
                hit = cache.get_result(cache_key)
                if hit is not None:
                    self._cancel.served_from = "cache"
                    self._cancel.tasks_total = 1
                    for rb in hit.to_batches():
                        if rb.num_rows:
                            self._send_batch(rb)
                            rows_sent += rb.num_rows
                            batches_sent += 1
                    self._cancel.tasks_done = 1
                    # the flag rides the first RESPONSE frame the
                    # protocol can carry it in: BATCH frames are raw
                    # Arrow IPC, so that is DONE (and for an empty
                    # result DONE literally IS the first frame)
                    done = {"metrics": {"cache_hit": True},
                            "cache_hit": True,
                            "schema_ipc": _schema_ipc_b64(hit.schema)}
                    if report is not None:
                        done["report"] = report
                    if ledger_on:
                        done["cost_ledger"] = _finish_ledger("ok")
                    write_frame(self.request, KIND_DONE,
                                json.dumps(done, default=str).encode())
                    return
            if jr is None:
                # journal this served task (when auron.journal.dir is
                # armed) so a server restart can RESUME it — the
                # reconnect contract; a None return degrades to the
                # pre-journal posture
                from auron_tpu.runtime import journal as jrn
                jr = jrn.begin(self._cancel, task_bytes,
                               task.num_partitions or 1,
                               planner_ctx.catalog, scope="task")
            with _trace.layer_span("plan", "decode"):
                op = plan_from_bytes(task_bytes, planner_ctx)
            # SUBMIT serves the host engine's one-task-per-partition
            # model (one runtime at task.partition_id); RESUME of a
            # collect-scoped journal passes the full partition list —
            # the dead driver's fan-out — streamed in partition order
            # so the reassembled stream is bit-identical to what the
            # driver would have collected
            parts = (partitions if partitions is not None
                     else [task.partition_id])
            # /queries task progress (the token is this handler's
            # CancelToken — one query per connection, so no nested
            # ownership question like the Session collect path)
            self._cancel.tasks_total = len(parts)
            self._cancel.tasks_done = 0
            cached_batches = [] if cache_key is not None else None
            # the handler's cancel TOKEN is the task's cancellation
            # registry: operators polling between child batches unwind
            # even MID-operator, not just between output batches
            try:
                for p in parts:
                    rt = ExecutionRuntime(
                        op, TaskDefinition(
                            partition_id=p,
                            num_partitions=task.num_partitions or 1,
                            stage_id=task.stage_id,
                            task_id=task.task_id),
                        cancel_token=self._cancel)
                    for batch in rt.batches():
                        with _trace.layer_span("convert", "to_arrow"):
                            rb = to_arrow(batch, op.schema())
                        if rb.num_rows:
                            self._send_batch(rb)
                            rows_sent += rb.num_rows
                            batches_sent += 1
                            if cached_batches is not None:
                                cached_batches.append(rb)
                    snaps.append(rt.finalize())
                    self._cancel.tasks_done += 1
            except errors.DeadlineExceeded:
                # a deadline is a CLIENT-VISIBLE verdict (ERROR frame
                # with the classified type), unlike a cancel (silent
                # teardown)
                lifecycle.observe_unwind(self._cancel, kind="deadline")
                raise
            except (TaskCancelled, errors.QueryCancelled):
                lifecycle.observe_unwind(
                    self._cancel, kind=self._cancel.reason or "cancel")
                raise _Cancelled()
            metrics = (snaps[0] if len(snaps) == 1
                       else {"num_partitions": len(snaps),
                             "per_partition": snaps})
        except BaseException:
            if ledger_on:
                try:
                    # partial ledger: whatever the finished partitions
                    # cost rides the token into the failure bundle
                    _finish_ledger("failed")
                except Exception:   # graft: disable=GL004 -- ledger assembly must never shadow the real failure
                    pass
            if jr is not None:
                # a failed/cancelled/died-mid-stream serving task keeps
                # its journal: the RESUME frame's inventory
                jr.suspend()
            raise
        finally:
            lifecycle.bind_token(prev_bind)
            slot.release()
            from auron_tpu.runtime import programs
            programs.pop_query(self._cancel.query_id)
        if jr is not None:
            jr.complete(write_report=True)
        if cache_key is not None:
            import pyarrow as _pa
            arrow_schema = schema_to_arrow(op.schema())
            cache.put_result(cache_key, _pa.Table.from_batches(
                cached_batches, schema=arrow_schema) if cached_batches
                else arrow_schema.empty_table())
        from auron_tpu.cache import aot as _aot
        _aot.record_plan(task_bytes, planner_ctx.catalog,
                         task.num_partitions or 1)
        done = {"metrics": metrics,
                "schema_ipc": _schema_ipc_b64(schema_to_arrow(op.schema()))}
        if report is not None:
            done["report"] = report
        if ledger_on:
            done["cost_ledger"] = _finish_ledger("ok")
        write_frame(self.request, KIND_DONE,
                    json.dumps(done, default=str).encode())


class _Cancelled(Exception):
    pass


class AuronServer(socketserver.ThreadingTCPServer):
    """Task-serving endpoint; one engine process serves many host tasks
    concurrently (threaded — batch compute holds the GIL only outside
    XLA execution)."""

    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 window: int = DEFAULT_WINDOW):
        super().__init__((host, port), _TaskHandler)
        self._shutdown_requested = False
        self.window = window
        self.stats = {"batches_sent": 0, "cancelled": 0, "rejected": 0,
                      "resume_refused": 0}
        self._active_lock = threading.Lock()
        self._active_tasks = 0
        #: live query tokens by id — the CANCEL-by-id frame's registry
        self._queries_lock = threading.Lock()
        self._live_queries: dict = {}
        # journal startup sweep: a restarted server reclaims its dead
        # predecessor's torn journals/unreferenced RSS run dirs while
        # KEEPING resumable ones — the RESUME frame's inventory
        from auron_tpu.runtime import journal as _jrn
        if _jrn.enabled():
            _jrn.sweep_orphans(_jrn.journal_dir())
        # the serving process's admission plane: handler threads are
        # cheap, EXECUTIONS are not — at most auron.sched.max_concurrent
        # tasks compute concurrently, auron.sched.queue_depth more wait,
        # the rest shed with a structured AdmissionRejected ERROR frame
        from auron_tpu.runtime.scheduler import QueryScheduler
        self.scheduler = QueryScheduler(name="serving")
        # ops plane (obs/ops_server.py): the serving process exposes
        # the same live telemetry endpoint Sessions do — refcounted, so
        # a Session in the same process shares it; the bound port rides
        # the stats dict (and the STATS frame) for discovery
        from auron_tpu.obs import ops_server as _ops_srv
        self._ops = _ops_srv.ensure_started()
        if self._ops is not None:
            self.stats["ops_port"] = self._ops.port

    def register_query(self, token) -> None:
        with self._queries_lock:
            self._live_queries[token.query_id] = token

    def unregister_query(self, token) -> None:
        with self._queries_lock:
            self._live_queries.pop(token.query_id, None)

    def find_query(self, query_id: str):
        """Live CancelToken behind ``query_id``, or None (the
        CANCEL-by-id lookup; expired ids return None by construction —
        tokens unregister when their handler finishes)."""
        with self._queries_lock:
            return self._live_queries.get(query_id)

    def task_started(self) -> None:
        with self._active_lock:
            self._active_tasks += 1

    def task_done_maybe_trim(self) -> None:
        """Decrement the active-task count; when it reaches zero, bound
        accumulated XLA programs (utils/compile_stats.maybe_clear). The
        quiescence check prevents clear_caches from racing another
        handler thread's in-flight trace/compile."""
        with self._active_lock:
            self._active_tasks -= 1
            quiescent = self._active_tasks == 0
        if quiescent:
            from auron_tpu.utils import compile_stats
            compile_stats.maybe_clear()

    def server_close(self) -> None:
        super().server_close()
        # drop the ops-endpoint acquisition (last release stops it)
        if getattr(self, "_ops", None) is not None:
            from auron_tpu.obs import ops_server as _ops_srv
            _ops_srv.release()
            self._ops = None

    @property
    def address(self) -> tuple[str, int]:
        return self.server_address

    def serve_background(self) -> threading.Thread:
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        return t


class AuronClient:
    """The host-engine side of the protocol: callNative is ``execute``'s
    SUBMIT, nextBatch is the BATCH stream, finalizeNative is DONE.

    Every socket operation is budgeted: connect attempts retry with
    jittered backoff inside ``timeout_s`` (default: the
    ``auron.client.timeout_s`` knob), and each frame read carries the
    same per-operation timeout — a dead or wedged server surfaces as a
    classified ``RemoteEngineError`` instead of hanging the caller
    forever. ``timeout_s<=0`` restores the legacy block-forever
    behavior."""

    def __init__(self, host: str, port: int,
                 timeout_s: "Optional[float]" = None,
                 connect_retries: int = 3):
        self.addr = (host, port)
        if timeout_s is None:
            from auron_tpu import config as cfg
            timeout_s = cfg.get_config().get(cfg.CLIENT_TIMEOUT_S)
        self.timeout_s = timeout_s if timeout_s and timeout_s > 0 else None
        self.connect_retries = max(0, int(connect_retries))   # graft: disable=GL001 -- constructor argument, host data

    def _connect(self):
        """Deadline-bounded connect with jittered reconnect: up to
        ``connect_retries`` extra attempts inside the ``timeout_s``
        budget (a replica restarting under a supervisor comes back
        within a beat — one refused SYN must not fail the query), then
        the classified ``RemoteEngineError``. The returned socket
        carries the same timeout for every subsequent read/write."""
        if self.timeout_s is None:
            return socket.create_connection(self.addr)
        import random
        import time as _time
        deadline = _time.monotonic() + self.timeout_s
        last = None
        for attempt in range(self.connect_retries + 1):
            remaining = deadline - _time.monotonic()
            if remaining <= 0:
                break
            try:
                return socket.create_connection(
                    self.addr, timeout=min(self.timeout_s, remaining))
            except OSError as e:
                last = e
                delay = min(0.05 * (2 ** attempt), 1.0)
                delay *= 0.5 + random.random() / 2   # full jitter
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    break
                _time.sleep(min(delay, remaining))
        raise errors.RemoteEngineError(
            f"cannot connect to engine at {self.addr[0]}:{self.addr[1]} "
            f"after {self.connect_retries + 1} attempts within the "
            f"{self.timeout_s}s budget (auron.client.timeout_s): {last}")

    def _timeout_error(self) -> errors.RemoteEngineError:
        return errors.RemoteEngineError(
            f"engine at {self.addr[0]}:{self.addr[1]} timed out "
            f"({self.timeout_s}s per-operation budget, "
            "auron.client.timeout_s) — server dead or wedged")

    def execute(self, task_bytes: bytes):
        """Submit one TaskDefinition; returns (pa.Table, metrics dict).
        Empty results return a typed empty table (schema rides DONE).
        Raises RuntimeError with the remote traceback on engine errors."""
        tbl, done = self._drive(KIND_SUBMIT, task_bytes, None)
        return tbl, self._metrics_from_done(done)

    @staticmethod
    def _metrics_from_done(done: dict) -> dict:
        """The metrics view of a DONE body. The per-query cost ledger
        rides DONE at top level (next to metrics — the router augments
        it there without touching engine metrics); surface it in the
        returned dict so callers see one flat observability record."""
        metrics = done.get("metrics", done)
        if "cost_ledger" in done and isinstance(metrics, dict) \
                and metrics is not done:
            metrics = dict(metrics, cost_ledger=done["cost_ledger"])
        return metrics

    def execute_plan(self, plan, path_rewrites=None, partition_id: int = 0,
                     num_partitions: int = 1, spark_version: str = "3.5.0",
                     fallback_provider=None,
                     timeout_s: "Optional[float]" = None,
                     retry_sheds: bool = False):
        """Live attach: submit a raw Spark ``plan.toJSON`` tree (parsed
        JSON list/dict). The engine converts it server-side; when the
        conversion hits unconvertible subtrees it asks back for their
        rows, sourced from ``fallback_provider(table, exec_class,
        columns) -> pa.Table`` (the role NativeHelper/ConvertToNativeExec
        plays host-side in the reference).

        Returns (pa.Table, done dict) where done carries metrics plus the
        conversion report (fallbacks + summary). ``timeout_s`` rides the
        frame as a SERVER-SIDE deadline: the engine's own CancelToken
        enforces it (errors.DeadlineExceeded on the ERROR frame), so the
        budget holds even if this client dies mid-stream.

        ``retry_sheds=True`` opts into honoring the server's
        ``AdmissionRejected retry_after_s=`` hint client-side: sleep
        the hinted interval (jittered, clamped to the remaining
        ``timeout_s``/client budget) and retry ONCE — the single-
        replica fallback of the fleet router's spill-over. Default off:
        a shed stays a structured error for callers that do their own
        backoff."""
        req = {"plan": plan, "partition_id": partition_id,
               "num_partitions": num_partitions,
               "spark_version": spark_version}
        if timeout_s:
            # graft: disable=GL001 -- a caller argument, host data
            req["timeout_s"] = float(timeout_s)
        if path_rewrites:
            req["path_rewrites"] = dict(path_rewrites)
        payload = json.dumps(req).encode()
        if not retry_sheds:
            return self._drive(KIND_SUBMIT_PLAN, payload,
                               fallback_provider)
        import random
        import time as _time
        budget = timeout_s or self.timeout_s
        deadline = (_time.monotonic() + budget) if budget else None
        try:
            return self._drive(KIND_SUBMIT_PLAN, payload,
                               fallback_provider)
        except errors.RemoteEngineError as e:
            shed = parse_shed(str(e).partition("engine error:\n")[2])
            if shed is None:
                raise
            hint = shed[1] if shed[1] is not None else 0.05
            delay = hint * (0.75 + random.random() / 2)   # jitter
            if deadline is not None:
                delay = min(delay, max(0.0,
                                       deadline - _time.monotonic()))
            _time.sleep(delay)
            return self._drive(KIND_SUBMIT_PLAN, payload,
                               fallback_provider)

    def _drive(self, kind: int, payload: bytes, fallback_provider):
        import contextlib

        from auron_tpu.obs import trace as _trace
        scopes = contextlib.ExitStack()
        wire_ctx = None
        if (kind in (KIND_SUBMIT, KIND_SUBMIT_PLAN, KIND_RESUME)
                and _trace.enabled()):
            # standalone client use (no enclosing Session scope): the
            # conversation becomes its own exported trace; inside a
            # scope it joins the active trace. The fleet.submit span is
            # the parent the remote side's spans hang under.
            if _trace.tracer().current_trace == 0:
                scopes.enter_context(_trace.query_scope("client.drive"))
            scopes.enter_context(_trace.span(
                "fleet", "fleet.submit", kind=kind,
                server=f"{self.addr[0]}:{self.addr[1]}"))
            wire_ctx = _trace.wire_context()
        batches, done = [], None
        with scopes:
            return self._drive_framed(kind, payload, fallback_provider,
                                      wire_ctx, batches)

    def _drive_framed(self, kind, payload, fallback_provider, wire_ctx,
                      batches):
        done = None
        try:
            with self._connect() as s:
                if wire_ctx is not None:
                    write_frame(s, KIND_TRACE,
                                json.dumps(wire_ctx).encode())
                write_frame(s, kind, payload)
                while True:
                    fkind, fpayload = read_frame(s)
                    if fkind == KIND_ERROR:
                        raise errors.RemoteEngineError(
                            "engine error:\n" + fpayload.decode())
                    if fkind == KIND_BATCH:
                        batches.append(_ipc_batch(fpayload))
                        try:
                            write_frame(s, KIND_ACK, b"")
                        except OSError:
                            # the engine sent what its window allowed,
                            # then DONE (or ERROR), and closed before
                            # this slow reader acknowledged anything:
                            # the ACK only reopens a window nobody
                            # waits at, and the frames it sent are still
                            # here to be read (a peer that is really
                            # gone fails the next read instead)
                            pass
                    elif fkind == KIND_NEED_TABLES:
                        need = json.loads(fpayload.decode())
                        if fallback_provider is None:
                            raise errors.RemoteEngineError(
                                "engine requested fallback tables "
                                f"{[n['table'] for n in need]} but no "
                                "fallback_provider was given")
                        for ent in need:
                            tbl = fallback_provider(ent["table"],
                                                    ent["exec"],
                                                    ent["columns"])
                            name = ent["table"].encode()
                            sink = io.BytesIO()
                            with pa.ipc.new_stream(sink, tbl.schema) as w:
                                w.write_table(tbl)
                            write_frame(s, KIND_TABLE,
                                        struct.pack("<I", len(name)) + name
                                        + sink.getvalue())
                    elif fkind == KIND_DONE:
                        done = json.loads(fpayload.decode())
                        break
        except TimeoutError as e:
            # socket timeout mid-conversation: the per-operation budget
            # expired with no frame — classify, never hang/raw-OSError
            raise self._timeout_error() from e
        if batches:
            tbl = pa.Table.from_batches(batches)
        elif done and done.get("schema_ipc"):
            tbl = _schema_from_b64(done["schema_ipc"]).empty_table()
        else:
            tbl = None
        return tbl, done

    def resume(self, query_id: str):
        """Continue a journaled query after a server restart (RESUME
        frame): returns (pa.Table, metrics) like ``execute``. A
        non-resumable id raises RuntimeError whose message LEADS with
        the server's structured verdict line
        (``ResumeUnavailable reason=...`` etc.)."""
        tbl, done = self._drive(
            KIND_RESUME, json.dumps({"query_id": query_id}).encode(),
            None)
        return tbl, self._metrics_from_done(done)

    def hello(self) -> dict:
        """Replica registration handshake (HELLO frame): the server's
        identity — {pid, tag, host, port, ops_port, window,
        journal_dir} — consumed by the fleet router at registration
        time (and usable by any supervisor for discovery)."""
        try:
            with self._connect() as s:
                write_frame(s, KIND_HELLO, b"")
                kind, payload = read_frame(s)
        except TimeoutError as e:
            raise self._timeout_error() from e
        if kind == KIND_ERROR:
            raise errors.RemoteEngineError(
                "engine error:\n" + payload.decode())
        return json.loads(payload.decode())

    def stats(self) -> dict:
        """The server's live observability over the wire (STATS frame):
        the /queries table + admission counters + server stats as one
        dict — for clients behind firewalls that cannot reach the ops
        HTTP port. The dict carries ``ops_port`` when the HTTP endpoint
        is also running."""
        try:
            with self._connect() as s:
                write_frame(s, KIND_STATS, b"")
                kind, payload = read_frame(s)
        except TimeoutError as e:
            raise self._timeout_error() from e
        if kind == KIND_ERROR:
            raise errors.RemoteEngineError(
                "engine error:\n" + payload.decode())
        return json.loads(payload.decode())

    def cancel_query(self, query_id: str) -> bool:
        """Cancel a live query BY ID over a fresh connection (the
        reconnect/admin path — no need to hold the original socket).
        True when a live query was cancelled; raises RuntimeError with
        the structured ``UnknownQuery reason=unknown_query_id`` first
        line when the id is unknown or already finished."""
        try:
            with self._connect() as s:
                write_frame(s, KIND_CANCEL,
                            json.dumps({"query_id": query_id}).encode())
                kind, payload = read_frame(s)
        except TimeoutError as e:
            raise self._timeout_error() from e
        if kind == KIND_ERROR:
            raise errors.RemoteEngineError(
                "engine error:\n" + payload.decode())
        return bool(json.loads(payload.decode()).get("cancelled"))

    def stream(self, task_bytes: bytes):
        """Yield (kind, payload) frames for one task submission, ACKing
        each BATCH (legacy-shaped helper used by tests)."""
        with self._connect() as s:
            write_frame(s, KIND_SUBMIT, task_bytes)
            while True:
                kind, payload = read_frame(s)
                if kind == KIND_ERROR:
                    raise errors.RemoteEngineError(
                        "engine error:\n" + payload.decode())
                if kind == KIND_BATCH:
                    try:
                        write_frame(s, KIND_ACK, b"")
                    except OSError:     # as in _drive_framed: a finished
                        pass            # engine closed before this ACK
                yield kind, payload
                if kind == KIND_DONE:
                    return

    def shutdown(self) -> None:
        with socket.create_connection(self.addr, timeout=10) as s:
            write_frame(s, KIND_SHUTDOWN, b"")


def serve_main(argv=None) -> int:
    """``python -m auron_tpu.runtime.serving --port N`` — run a serving
    engine process (prints the bound port for the parent to scrape)."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--window", type=int, default=DEFAULT_WINDOW)
    args = ap.parse_args(argv)
    # this process IS a replica: stamp every flight/trace export it
    # writes so stitched fleet telemetry stays attributable
    from auron_tpu.obs import flight_recorder as _flight
    _flight.set_role("replica")
    srv = AuronServer(args.host, args.port, window=args.window)
    print(f"AURON_SERVING {srv.address[0]}:{srv.address[1]}", flush=True)
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(serve_main())

"""Serving-fleet unit tests (the replicated-AuronServer plane).

Three layers, cheapest first:

- PURE routing decisions (``fleet/routing.py`` + ``fleet/snapshot.py``):
  least-loaded ordering, warm affinity, spill-over backoff clamping,
  the failover-action matrix, shed verdicts and scrape-shape tolerance
  — all from literal snapshots, no sockets.
- The ROUTER's failover state machine against FAKE replicas: scripted
  socket servers speaking the serving wire protocol (HELLO identity
  with a provably-dead liveness tag where a test needs a confirmable
  death, plus a fake ops endpoint the poll loop scrapes), so
  spill-over, death-confirmed re-execution, the fleet-saturated
  verdict and the idempotency guard's single-flight dedup are all
  asserted without booting a real engine.
- The CLIENT's budgets: connect-refused and wedged-server timeouts
  classify as ``RemoteEngineError`` (the ``auron.client.timeout_s``
  knob), and ``execute_plan(retry_sheds=True)`` honors a shed's
  ``retry_after_s`` hint exactly once.

The real-process half (SIGKILL, journal RESUME across process
boundaries) lives in tests/test_zz_fleet_battery.py — a fake cannot
die convincingly enough for the liveness plane.
"""

import json
import socket
import socketserver
import subprocess
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pyarrow as pa
import pytest

from auron_tpu import config as cfg
from auron_tpu import errors
from auron_tpu.fleet import routing
from auron_tpu.fleet.snapshot import (ReplicaSnapshot,
                                      snapshot_from_bodies, unreachable)
from auron_tpu.runtime import serving


def snap(name, running=0, queued=0, mem=0.0, status="ok", warm=(),
         stems=(), ok=True, at=100.0):
    return ReplicaSnapshot(
        name=name, host="127.0.0.1", port=1, ok=ok, status=status,
        running=running, queued=queued, mem_frac=mem,
        warm_fps=frozenset(warm), resume_stems=tuple(stems),
        scraped_at=at)


# ---------------------------------------------------------------------------
# pure routing decisions
# ---------------------------------------------------------------------------

class TestLoadScore:
    def test_occupancy_dominates(self):
        idle, busy = snap("b:1"), snap("a:1", running=2, queued=1)
        assert routing.load_score(idle) < routing.load_score(busy)

    def test_memory_breaks_occupancy_ties(self):
        lo, hi = snap("b:1", mem=0.1), snap("a:1", mem=0.9)
        assert routing.load_score(lo) < routing.load_score(hi)

    def test_degraded_sorts_after_ok(self):
        ok, deg = snap("b:1"), snap("a:1", status="degraded")
        assert routing.load_score(ok) < routing.load_score(deg)

    def test_name_gives_a_total_order(self):
        a, b = snap("a:1"), snap("b:1")
        assert routing.load_score(a) != routing.load_score(b)
        assert sorted([b, a], key=routing.load_score)[0] is a


class TestUsable:
    def test_filters_unreachable_and_stale(self):
        fresh = snap("a:1", at=100.0)
        stale = snap("b:1", at=90.0)
        down = unreachable("c:1", "127.0.0.1", 1, 100.0)
        out = routing.usable([fresh, stale, down], now=100.5,
                             staleness_s=2.0)
        assert out == [fresh]

    def test_degraded_stays_usable(self):
        deg = snap("a:1", status="degraded", at=100.0)
        assert routing.usable([deg], now=100.1, staleness_s=2.0) == [deg]


class TestRouteOrder:
    def test_least_loaded_without_affinity(self):
        a, b = snap("a:1", running=3), snap("b:1")
        order = routing.route_order([a, b], affinity=False, now=100.1)
        assert [s.name for s in order] == ["b:1", "a:1"]

    def test_warm_replica_ranks_ahead_of_idler_cold_one(self):
        warm_busy = snap("a:1", running=2, warm=("fp9",))
        cold_idle = snap("b:1")
        order = routing.route_order([warm_busy, cold_idle],
                                    plan_fp="fp9", now=100.1)
        assert [s.name for s in order] == ["a:1", "b:1"]

    def test_sticky_counts_as_warm(self):
        a, b = snap("a:1", running=2), snap("b:1")
        order = routing.route_order([a, b], plan_fp="fp9",
                                    sticky="a:1", now=100.1)
        assert order[0].name == "a:1"

    def test_affinity_off_ignores_warm_inventory(self):
        warm_busy = snap("a:1", running=2, warm=("fp9",))
        cold_idle = snap("b:1")
        order = routing.route_order([warm_busy, cold_idle],
                                    plan_fp="fp9", affinity=False,
                                    now=100.1)
        assert order[0].name == "b:1"

    def test_load_spreads_inside_the_warm_group(self):
        w1 = snap("a:1", running=2, warm=("fp9",))
        w2 = snap("b:1", warm=("fp9",))
        order = routing.route_order([w1, w2], plan_fp="fp9", now=100.1)
        assert [s.name for s in order] == ["b:1", "a:1"]


class TestResumeTarget:
    def test_prefers_a_survivor_seeing_the_stem(self):
        busy_with_stem = snap("a:1", running=3, stems=("q7_11",))
        idle = snap("b:1")
        got = routing.resume_target([busy_with_stem, idle], "q7_11",
                                    now=100.1, staleness_s=2.0)
        assert got.name == "a:1"

    def test_falls_back_to_least_loaded(self):
        a, b = snap("a:1", running=3), snap("b:1")
        got = routing.resume_target([a, b], "q7_11", now=100.1,
                                    staleness_s=2.0)
        assert got.name == "b:1"

    def test_none_when_no_usable_survivor(self):
        down = unreachable("a:1", "127.0.0.1", 1, 100.0)
        assert routing.resume_target([down], "q7_11", now=100.1,
                                     staleness_s=2.0) is None


class TestSpilloverDelay:
    def test_hint_anchors_the_delay_with_full_jitter(self):
        lo = routing.spillover_delay(1.0, 0, 0.0, None)
        hi = routing.spillover_delay(1.0, 0, 0.999, None)
        assert lo == pytest.approx(0.5)
        assert 0.5 < hi < 1.0

    def test_exponential_from_floor_without_a_hint(self):
        d0 = routing.spillover_delay(None, 0, 0.0, None)
        d3 = routing.spillover_delay(None, 3, 0.0, None)
        assert d3 == pytest.approx(d0 * 8)

    def test_cap_clamps_a_huge_hint(self):
        assert routing.spillover_delay(60.0, 0, 0.999, None) <= 2.0

    def test_deadline_clamps_and_never_negative(self):
        assert routing.spillover_delay(1.0, 0, 0.5, 0.1) == \
            pytest.approx(0.1)
        assert routing.spillover_delay(1.0, 0, 0.5, -3.0) == 0.0


class TestFailoverAction:
    def test_disabled_is_an_error(self):
        assert routing.failover_action(
            query_id="q", pid=1, journal_shared=True,
            failover_enabled=False, survivors=2) == "error"

    def test_no_survivors_is_an_error(self):
        assert routing.failover_action(
            query_id="q", pid=1, journal_shared=True,
            failover_enabled=True, survivors=0) == "error"

    def test_known_journal_identity_resumes(self):
        assert routing.failover_action(
            query_id="q", pid=1, journal_shared=True,
            failover_enabled=True, survivors=1) == "resume"

    @pytest.mark.parametrize("qid,pid,shared", [
        (None, 1, True), ("q", None, True), ("q", 1, False)])
    def test_missing_identity_reexecutes(self, qid, pid, shared):
        assert routing.failover_action(
            query_id=qid, pid=pid, journal_shared=shared,
            failover_enabled=True, survivors=1) == "reexecute"


class TestShedVerdict:
    def test_largest_hint_wins(self):
        reason, hint = routing.shed_verdict(
            [("queue_full", 0.5), ("queue_full", 2.0),
             ("queue_full", None)])
        assert reason == "fleet_saturated"
        assert hint == 2.0

    def test_no_hints_is_none(self):
        assert routing.shed_verdict([("q", None)]) == \
            ("fleet_saturated", None)


class TestParseShed:
    def test_structured_shed_parses(self):
        got = serving.parse_shed(
            "AdmissionRejected reason=queue_full retry_after_s=1.5\n"
            "the queue is full")
        assert got == ("queue_full", 1.5)

    def test_literal_none_hint_is_none(self):
        got = serving.parse_shed(
            "AdmissionRejected reason=queue_full retry_after_s=None\nx")
        assert got == ("queue_full", None)

    def test_non_shed_text_is_none(self):
        assert serving.parse_shed("ReplicaUnavailable reason=dead\nx") \
            is None
        assert serving.parse_shed("") is None


class TestSnapshotFromBodies:
    def test_full_bodies(self):
        health = {"status": "degraded",
                  "memmgr": [{"used": 30, "total": 100},
                             {"used": 90, "total": 100}]}
        queries = {
            "queries": [{"state": "running"}, {"state": "running"},
                        {"state": "queued"}, {"state": "done"}],
            "admission": {"default": {"admitted": 7, "rejected": 3}},
            "warm_plan_fps": ["fp1", "fp2"],
            "resume_inventory": [
                {"stem": "q1_9", "owner_alive": False,
                 "claimed": False},
                {"stem": "q2_9", "owner_alive": True,
                 "claimed": False},
                {"stem": "q3_9", "owner_alive": False,
                 "claimed": True}]}
        s = snapshot_from_bodies("a:1", "127.0.0.1", 1, health,
                                 queries, 50.0)
        assert (s.running, s.queued, s.occupancy) == (2, 1, 3)
        assert (s.admitted, s.rejected) == (7, 3)
        assert s.mem_frac == pytest.approx(0.9)
        assert s.status == "degraded"
        assert s.warm_fps == frozenset(("fp1", "fp2"))
        # only unclaimed dead-owner stems are resume inventory
        assert s.resume_stems == ("q1_9",)

    def test_empty_bodies_degrade_to_neutral(self):
        s = snapshot_from_bodies("a:1", "127.0.0.1", 1, {}, {}, 50.0)
        assert s.ok and s.status == "ok"
        assert s.occupancy == 0 and s.mem_frac == 0.0
        assert s.warm_fps == frozenset() and s.resume_stems == ()


# ---------------------------------------------------------------------------
# fake replicas: scripted wire-protocol servers + fake ops endpoints
# ---------------------------------------------------------------------------

class TestReplicaEnvironment:
    """spawn_replica / FleetHarness build each child's environment from
    the parent's — platform selection included — plus what the caller
    gives THAT replica: how a supervisor hands each replica its chip."""

    @staticmethod
    def _capture_popen(monkeypatch, stdout_line, stderr_text=b""):
        from auron_tpu.fleet import replica
        envs = []

        class FakeProc:
            pid = 4242
            returncode = 3

            def __init__(self, args, env=None, stderr=None, **kw):
                envs.append(env)
                if stderr_text:
                    stderr.write(stderr_text)
                import io
                self.stdout = io.StringIO(stdout_line)

            def poll(self):
                return self.returncode if not stdout_line else None

            def wait(self, timeout=None):
                return self.returncode

            def terminate(self):
                pass

            def kill(self):
                pass

        monkeypatch.setattr(replica.subprocess, "Popen", FakeProc)
        return replica, envs

    def test_child_inherits_platform_and_takes_its_own_env(
            self, monkeypatch, tmp_path):
        replica, envs = self._capture_popen(
            monkeypatch, "AURON_SERVING 127.0.0.1:7\n")
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        rep = replica.spawn_replica(
            str(tmp_path), env_extra={"TPU_VISIBLE_CHIPS": 1})
        assert (rep.host, rep.port) == ("127.0.0.1", 7)
        env = envs[0]
        assert env["JAX_PLATFORMS"] == "tpu"      # inherited, never pinned
        assert env["TPU_VISIBLE_CHIPS"] == "1"
        assert env["AURON_CONF_JOURNAL_DIR"] == str(tmp_path)
        monkeypatch.delenv("JAX_PLATFORMS")
        replica.spawn_replica(str(tmp_path))
        assert "JAX_PLATFORMS" not in envs[1]
        rep.stop()

    def test_harness_gives_each_replica_its_own_environment(
            self, monkeypatch, tmp_path):
        replica, envs = self._capture_popen(
            monkeypatch, "AURON_SERVING 127.0.0.1:7\n")
        from auron_tpu.fleet import router as router_mod

        class FakeRouter:
            def __init__(self, addrs, config=None):
                pass

            def start(self):
                return self

            def close(self):
                pass

        monkeypatch.setattr(router_mod, "FleetRouter", FakeRouter)
        with replica.FleetHarness(
                2, journal_dir=str(tmp_path), env_extra={"SHARED": "x"},
                replica_env=[{"TPU_VISIBLE_CHIPS": "0"},
                             {"TPU_VISIBLE_CHIPS": "1"}]):
            pass
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
        assert [e["SHARED"] for e in envs] == ["x", "x"]
        with pytest.raises(ValueError, match="replica_env"):
            replica.FleetHarness(2, replica_env=[{}])

    def test_tpu_chip_env_gives_each_replica_its_own_chip_and_port(self):
        from auron_tpu.fleet import tpu_chip_env
        a, b = tpu_chip_env(0), tpu_chip_env(3)
        assert (a["TPU_VISIBLE_CHIPS"], b["TPU_VISIBLE_CHIPS"]) == ("0", "3")
        assert a["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert a["TPU_MESH_CONTROLLER_PORT"] != b["TPU_MESH_CONTROLLER_PORT"]
        assert "JAX_PLATFORMS" not in a       # placement, not platform

    def test_boot_failure_says_why(self, monkeypatch, tmp_path):
        """A replica that dies before announcing itself (say, its chip
        is held by another process) surfaces its own stderr."""
        replica, _envs = self._capture_popen(
            monkeypatch, "", b"RuntimeError: TPU already in use\n")
        with pytest.raises(errors.ReplicaUnavailable,
                           match="TPU already in use"):
            replica.spawn_replica(str(tmp_path), boot_timeout_s=5)


def _dead_tag():
    """A liveness tag whose owner is PROVABLY dead: a reaped child's
    pid.  The router's ``_mark_dead`` confirmation must accept it."""
    p = subprocess.Popen(["/bin/true"])
    p.wait()
    return f"{socket.gethostname()}:{p.pid}:1"


class _OpsHandler(BaseHTTPRequestHandler):
    def do_GET(self):
        body = self.server.bodies.get(self.path, {})
        data = json.dumps(body).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *a):   # silence test output
        pass


class FakeReplica:
    """One scripted wire-protocol server + its fake ops endpoint.

    ``behavior(replica, sock, kind, payload)`` runs for every query
    frame (SUBMIT / SUBMIT_PLAN / RESUME); HELLO answers with the
    configured identity (tag defaults to a provably-DEAD owner so a
    scripted death is confirmable by the router's liveness check).
    ``occupancy`` shapes the fake /queries body — the routing knob."""

    def __init__(self, behavior, tag=None, occupancy=0,
                 journal_dir=""):
        self.behavior = behavior
        self.tag = tag if tag is not None else _dead_tag()
        self.journal_dir = journal_dir
        self.submits = []
        self.lock = threading.Lock()

        self.ops = ThreadingHTTPServer(("127.0.0.1", 0), _OpsHandler)
        self.ops.bodies = {
            "/healthz": {"status": "ok", "memmgr": []},
            "/queries": {
                "queries": [{"state": "running"}] * occupancy,
                "admission": {}, "warm_plan_fps": [],
                "resume_inventory": []}}
        threading.Thread(target=self.ops.serve_forever,
                         daemon=True).start()

        rep = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    kind, payload = serving.read_frame(self.request)
                except (OSError, ConnectionError):
                    return
                if kind == serving.KIND_HELLO:
                    serving.write_frame(
                        self.request, serving.KIND_DONE,
                        json.dumps({
                            "pid": 0, "tag": rep.tag,
                            "host": rep.host, "port": rep.port,
                            "window": 4,
                            "journal_dir": rep.journal_dir,
                            "ops_port": rep.ops_port}).encode())
                    return
                with rep.lock:
                    rep.submits.append((kind, payload))
                try:
                    rep.behavior(rep, self.request, kind, payload)
                except (OSError, ConnectionError):
                    pass

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self.server = Server(("127.0.0.1", 0), Handler)
        self.host, self.port = self.server.server_address
        self.ops_port = self.ops.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    @property
    def addr(self):
        return (self.host, self.port)

    def close(self):
        self.server.shutdown()
        self.server.server_close()
        self.ops.shutdown()
        self.ops.server_close()


def serve_rows(n=4, delay_s=0.0):
    """Behavior: one BATCH (awaiting the ACK) then DONE."""
    def behavior(rep, sock, kind, payload):
        if delay_s:
            time.sleep(delay_s)
        rb = pa.record_batch({"x": pa.array(list(range(n)))})
        serving.write_frame(sock, serving.KIND_BATCH,
                            serving._ipc_bytes(rb))
        serving.read_frame(sock)   # the ACK
        serving.write_frame(sock, serving.KIND_DONE,
                            json.dumps({"metrics": {"rows": n}}).encode())
    return behavior


def shed_always(retry_after_s=0.01):
    def behavior(rep, sock, kind, payload):
        serving.write_frame(
            sock, serving.KIND_ERROR,
            (f"AdmissionRejected reason=queue_full "
             f"retry_after_s={retry_after_s}\nfull").encode())
    return behavior


def die_on_event(event, hold_s=5.0):
    """Behavior: hold the conversation open until ``event`` fires (or
    ``hold_s``), then drop the connection — a death mid-query."""
    def behavior(rep, sock, kind, payload):
        event.wait(hold_s)
        # returning closes the socket with no DONE: the router sees a
        # broken conversation and consults the liveness tag
    return behavior


@pytest.fixture
def fleet_of_fakes():
    made = []

    def build(*replicas):
        from auron_tpu.fleet.router import FleetRouter
        made.extend(replicas)
        router = FleetRouter([r.addr for r in replicas]).start()
        made.append(router)
        return router

    yield build
    for m in reversed(made):
        m.close()


def _client(router, **kw):
    host, port = router.address
    kw.setdefault("timeout_s", 30)
    return serving.AuronClient(host, port, **kw)


TASK = b"fleet-test-task-payload"


class TestRouterAgainstFakes:
    def test_routes_to_least_loaded_and_replays_batches(
            self, fleet_of_fakes):
        idle = FakeReplica(serve_rows(5))
        busy = FakeReplica(shed_always(), occupancy=4)
        router = fleet_of_fakes(idle, busy)
        tbl, _ = _client(router).execute(TASK)
        assert tbl.num_rows == 5
        assert router.stats_dict()["router"]["routed"] == 1
        assert not busy.submits   # never touched the loaded one

    def test_spillover_retries_a_shed_at_the_next_replica(
            self, fleet_of_fakes):
        shedder = FakeReplica(shed_always())
        server = FakeReplica(serve_rows(3), occupancy=2)
        router = fleet_of_fakes(shedder, server)
        tbl, _ = _client(router).execute(TASK)
        assert tbl.num_rows == 3
        r = router.stats_dict()["router"]
        assert r["spillovers"] >= 1
        assert r["fleet_sheds"] == 0
        assert shedder.submits and server.submits

    def test_fleet_wide_shed_is_a_structured_verdict(
            self, fleet_of_fakes):
        a = FakeReplica(shed_always(0.01))
        b = FakeReplica(shed_always(0.02))
        router = fleet_of_fakes(a, b)
        with pytest.raises(errors.RemoteEngineError) as ei:
            _client(router).execute(TASK)
        msg = str(ei.value)
        assert "AdmissionRejected" in msg
        assert "fleet_saturated" in msg
        assert router.stats_dict()["router"]["fleet_sheds"] == 1

    def test_confirmed_death_reexecutes_on_the_survivor(
            self, fleet_of_fakes):
        died = threading.Event()
        victim = FakeReplica(die_on_event(died, hold_s=0.2))
        survivor = FakeReplica(serve_rows(7), occupancy=2)
        router = fleet_of_fakes(victim, survivor)
        died.set()
        tbl, _ = _client(router).execute(TASK)
        assert tbl.num_rows == 7
        r = router.stats_dict()["router"]
        assert r["replica_deaths"] == 1
        assert r["failovers_reexecute"] == 1
        assert r["failovers_resume"] == 0

    def test_idempotency_guard_dedups_concurrent_reexecution(
            self, fleet_of_fakes):
        """Two clients in flight on the same dying replica with the
        SAME task: failover must re-execute it ONCE on the survivor
        and replay the shared result to the second caller."""
        died = threading.Event()
        victim = FakeReplica(die_on_event(died))
        survivor = FakeReplica(serve_rows(4, delay_s=0.5), occupancy=2)
        router = fleet_of_fakes(victim, survivor)

        results, errs = [], []

        def drive():
            try:
                tbl, _ = _client(router).execute(TASK)
                results.append(tbl)
            except Exception as e:   # noqa: BLE001 — asserted below
                errs.append(e)

        threads = [threading.Thread(target=drive) for _ in range(2)]
        for t in threads:
            t.start()
        # both conversations must be parked on the victim before it
        # dies; its submit log is the rendezvous
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with victim.lock:
                if len(victim.submits) >= 2:
                    break
            time.sleep(0.01)
        died.set()
        for t in threads:
            t.join(timeout=30)
        assert not errs, errs
        assert [t.num_rows for t in results] == [4, 4]
        assert len(survivor.submits) == 1, (
            "the idempotency guard must single-flight the re-execution")
        r = router.stats_dict()["router"]
        assert r["guard_shared"] == 1
        assert r["replica_deaths"] == 1

    def test_shutdown_frame_reaches_every_replica(self, fleet_of_fakes):
        seen = []

        def record_shutdown(rep, sock, kind, payload):
            seen.append(kind)

        a = FakeReplica(record_shutdown)
        b = FakeReplica(record_shutdown)
        router = fleet_of_fakes(a, b)
        _client(router).shutdown()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and len(seen) < 2:
            time.sleep(0.01)
        assert seen == [serving.KIND_SHUTDOWN] * 2


# ---------------------------------------------------------------------------
# client budgets (auron.client.timeout_s) + retry_sheds
# ---------------------------------------------------------------------------

class TestClientBudgets:
    def test_connect_refused_classifies_within_budget(self):
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()   # nothing listens here now
        client = serving.AuronClient("127.0.0.1", port, timeout_s=0.5,
                                     connect_retries=1)
        t0 = time.monotonic()
        with pytest.raises(errors.RemoteEngineError) as ei:
            client.hello()
        assert "cannot connect" in str(ei.value)
        assert time.monotonic() - t0 < 5.0

    def test_wedged_server_classifies_as_timeout(self):
        wedge = socket.socket()
        wedge.bind(("127.0.0.1", 0))
        wedge.listen(1)
        try:
            client = serving.AuronClient(
                "127.0.0.1", wedge.getsockname()[1], timeout_s=0.3)
            with pytest.raises(errors.RemoteEngineError) as ei:
                client.execute(TASK)
            assert "timed out" in str(ei.value)
        finally:
            wedge.close()

    def test_timeout_defaults_from_the_config_knob(self):
        conf = cfg.get_config()
        conf.set(cfg.CLIENT_TIMEOUT_S, 7.5)
        try:
            assert serving.AuronClient("127.0.0.1", 1).timeout_s == 7.5
        finally:
            conf.unset(cfg.CLIENT_TIMEOUT_S)

    def test_nonpositive_timeout_restores_block_forever(self):
        assert serving.AuronClient("127.0.0.1", 1,
                                   timeout_s=0).timeout_s is None


class TestRetrySheds:
    def _shed_once_replica(self):
        state = {"count": 0}

        def behavior(rep, sock, kind, payload):
            with rep.lock:
                state["count"] += 1
                first = state["count"] == 1
            if first:
                serving.write_frame(
                    sock, serving.KIND_ERROR,
                    b"AdmissionRejected reason=queue_full "
                    b"retry_after_s=0.01\nfull")
            else:
                serving.write_frame(
                    sock, serving.KIND_DONE,
                    json.dumps({"metrics": {}}).encode())
        return FakeReplica(behavior)

    def test_retry_sheds_honors_the_hint_once(self):
        rep = self._shed_once_replica()
        try:
            client = serving.AuronClient(*rep.addr, timeout_s=10)
            tbl, done = client.execute_plan([], retry_sheds=True)
            assert done == {"metrics": {}}
            assert len(rep.submits) == 2
        finally:
            rep.close()

    def test_default_surfaces_the_shed_unretried(self):
        rep = self._shed_once_replica()
        try:
            client = serving.AuronClient(*rep.addr, timeout_s=10)
            with pytest.raises(errors.RemoteEngineError) as ei:
                client.execute_plan([])
            assert "AdmissionRejected" in str(ei.value)
            assert len(rep.submits) == 1
        finally:
            rep.close()

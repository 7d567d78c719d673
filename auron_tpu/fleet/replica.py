"""Subprocess replica harness: real AuronServer processes for the
fleet tooling.

Everything that exercises cross-process failover — ``tools/
load_report.py --fleet``, the perf-gate fleet arm, the chaos
``fleet_failover`` scenario, tests/test_zz_fleet_battery.py — boots
replicas through this ONE harness, because the property under test
(a SIGKILLed engine's journal claim becomes winnable by a survivor)
only exists across real process boundaries: an in-process "kill"
leaves the claim owner's pid alive and the liveness plane would
correctly refuse the steal.

Each replica is ``python -m auron_tpu.runtime.serving --port 0`` with
its knobs injected through the ``AURON_CONF_*`` environment mapping
(ops endpoint on, shared journal dir; the platform is whatever the
parent's environment selects) and discovered through the
``AURON_SERVING host:port`` stdout line — the same contract the serving
CLI prints for any supervisor.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import time


class ReplicaProc:
    """One spawned AuronServer subprocess (host, port, Popen)."""

    def __init__(self, proc: subprocess.Popen, host: str, port: int,
                 stderr_file=None):
        self.proc = proc
        self.host = host
        self.port = port
        self.name = f"{host}:{port}"
        #: the child's stderr (an anonymous temp file, gone on close)
        self._stderr_file = stderr_file

    @property
    def pid(self) -> int:
        return self.proc.pid

    def alive(self) -> bool:
        return self.proc.poll() is None

    def kill(self) -> None:
        """SIGKILL — no shutdown courtesy, the failover test surface."""
        if self.alive():
            self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=30)

    def stop(self) -> None:
        if self.alive():
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
        if self._stderr_file is not None:
            self._stderr_file.close()
            self._stderr_file = None


def _stderr_tail(f, limit: int = 2000) -> str:
    f.seek(0, os.SEEK_END)
    f.seek(max(0, f.tell() - limit))
    return f.read().decode(errors="replace").strip()


def tpu_chip_env(index: int) -> dict:
    """The environment that confines one process to chip ``index`` of a
    TPU host — what a supervisor passes as ``replica_env[index]``: one
    visible chip, a 1x1x1 process topology, and a mesh-controller port
    of its own so sibling runtimes do not collide. (Two replicas booted
    this way on a 4-chip v5e host each saw ``1 x TPU v5 lite``.)"""
    port = 8476 + index
    return {"TPU_VISIBLE_CHIPS": str(index),
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
            "TPU_MESH_CONTROLLER_PORT": str(port)}


def spawn_replica(journal_dir: str, *, window: int = 4,
                  env_extra: dict | None = None,
                  boot_timeout_s: float = 60.0) -> ReplicaProc:
    """Boot one serving subprocess and wait for its bound address.

    The child inherits the parent's environment — platform selection
    included — plus ``env_extra``, which is THIS replica's own (a
    supervisor gives each replica its chip there: ``TPU_VISIBLE_CHIPS``
    and the process-bounds variables on a TPU VM). A chip belongs to
    one process, so a parent that has initialized a jax backend on the
    chip cannot spawn a replica onto it. The ops endpoint is enabled on
    an ephemeral port (the router scrapes it; HELLO reveals the port)
    and ``journal_dir`` is the SHARED journal directory every replica
    of the fleet writes — failover's resume path exists only because
    the survivors see the dead owner's stems there.
    """
    env = dict(os.environ)
    env.update({
        "AURON_CONF_OPS_ENABLED": "1",
        "AURON_CONF_OPS_PORT": "0",
        "AURON_CONF_JOURNAL_DIR": journal_dir,
    })
    if env_extra:
        env.update({k: str(v) for k, v in env_extra.items()})
    # stderr goes to an anonymous file, not a pipe nobody drains: a boot
    # failure (say, a chip another process holds) then says why
    stderr_file = tempfile.TemporaryFile()
    proc = subprocess.Popen(
        [sys.executable, "-m", "auron_tpu.runtime.serving",
         "--port", "0", "--window", str(window)],
        stdout=subprocess.PIPE, stderr=stderr_file,
        env=env, text=True)
    deadline = time.monotonic() + boot_timeout_s
    line = ""
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                break
            time.sleep(0.05)
            continue
        if line.startswith("AURON_SERVING "):
            break
    if not line.startswith("AURON_SERVING "):
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        tail = _stderr_tail(stderr_file)
        stderr_file.close()
        from auron_tpu import errors
        raise errors.ReplicaUnavailable(
            f"replica (rc={proc.returncode}) never announced its "
            f"address; stderr tail:\n{tail}", reason="boot")
    host, _, port = line.split()[1].rpartition(":")
    return ReplicaProc(proc, host, int(port), stderr_file)


class FleetHarness:
    """N subprocess replicas + an in-process FleetRouter, as a context
    manager.  The router runs inside the caller's process (its decision
    counters and failover latencies are directly inspectable via
    ``router.stats_dict()``); the replicas are real processes so
    SIGKILL is a real death. ``env_extra`` reaches every replica;
    ``replica_env[i]`` only replica ``i`` — where a supervisor pins
    each replica to its own chip. The caller's process must not hold
    that chip itself (see ``spawn_replica``)."""

    def __init__(self, n: int | None = None, *,
                 journal_dir: str | None = None,
                 window: int = 4, env_extra: dict | None = None,
                 replica_env: "list[dict] | None" = None,
                 config=None):
        if n is None:
            from auron_tpu import config as cfg
            n = int((config or cfg.get_config()).get(cfg.FLEET_REPLICAS))
        if replica_env is not None and len(replica_env) != n:
            raise ValueError(
                f"replica_env has {len(replica_env)} entries for "
                f"{n} replicas")
        self.n = n
        self._own_journal = journal_dir is None
        self.journal_dir = journal_dir or tempfile.mkdtemp(
            prefix="auron_fleet_journal_")
        self.window = window
        self.env_extra = env_extra
        self.replica_env = replica_env
        self._config = config
        self.replicas: list = []
        self.router = None

    def __enter__(self) -> "FleetHarness":
        from auron_tpu.fleet.router import FleetRouter
        try:
            for i in range(self.n):
                env = dict(self.env_extra or {})
                if self.replica_env is not None:
                    env.update(self.replica_env[i])
                self.replicas.append(spawn_replica(
                    self.journal_dir, window=self.window,
                    env_extra=env))
            self.router = FleetRouter(
                [(r.host, r.port) for r in self.replicas],
                config=self._config).start()
        except BaseException:
            self.__exit__(None, None, None)
            raise
        return self

    def __exit__(self, *exc) -> None:
        if self.router is not None:
            try:
                self.router.close()
            except Exception:   # graft: disable=GL004 -- teardown must reach every replica even if the router is wedged
                pass
            self.router = None
        for rep in self.replicas:
            rep.stop()
        self.replicas = []

    @property
    def address(self) -> tuple:
        return self.router.address

    def client(self, **kw):
        """An AuronClient pointed at the ROUTER — the fleet looks like
        one server."""
        from auron_tpu.runtime import serving
        host, port = self.router.address
        return serving.AuronClient(host, port, **kw)

    def kill_replica(self, index: int) -> ReplicaProc:
        """SIGKILL replica ``index`` (failover drill)."""
        rep = self.replicas[index]
        rep.kill()
        return rep

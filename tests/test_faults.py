"""Unit tests for the robustness plane's building blocks: the seeded
fault-injection plane (runtime/faults.py), the durable-tier checksum
module (utils/checksum.py) and what is left of the watchdog
(runtime/watchdog.py: the stall plane only). The end-to-end contract — bit-identical or
classified, never leaks — lives in test_zz_chaos_battery.py; these pin
the deterministic mechanics the battery relies on."""

import pytest

from auron_tpu import config as cfg
from auron_tpu import errors
from auron_tpu.runtime import faults, watchdog
from auron_tpu.utils import checksum as cks


@pytest.fixture(autouse=True)
def _disarm():
    """Every test starts and ends with no fault plan armed."""
    conf = cfg.get_config()
    conf.unset(cfg.FAULTS_PLAN)
    conf.unset(cfg.FAULTS_SEED)
    faults.reset()
    yield
    conf.unset(cfg.FAULTS_PLAN)
    conf.unset(cfg.FAULTS_SEED)
    faults.reset()


# -- plan grammar -----------------------------------------------------------

def test_parse_plan_grammar():
    rules = faults.parse_plan(
        "rss.fetch:corrupt@0.05; spill.read:io_error@0.1 ;device.compute:fatal")
    assert [(r.site, r.kind, r.prob) for r in rules] == [
        ("rss.fetch", "corrupt", 0.05),
        ("spill.read", "io_error", 0.1),
        ("device.compute", "fatal", 1.0),   # @prob defaults to 1.0
    ]
    assert faults.parse_plan("") == []


@pytest.mark.parametrize("bad", [
    "nosuch.site:io_error",          # unknown site
    "rss.fetch:meteor",              # unknown kind
    "rss.fetch:corrupt@1.5",         # probability out of range
    "rss.fetch",                     # malformed (no kind)
])
def test_parse_plan_rejects_typos_loudly(bad):
    with pytest.raises(ValueError):
        faults.parse_plan(bad)


# -- deterministic injection ------------------------------------------------

def _sequence(plan, seed, site, n=64, exc=errors.TransientError):
    """The injected/clean outcome sequence of ``n`` site checks."""
    conf = cfg.get_config()
    conf.set(cfg.FAULTS_PLAN, plan)
    conf.set(cfg.FAULTS_SEED, seed)
    faults.reset()
    out = []
    for _ in range(n):
        try:
            faults.maybe_fail(site, exc)
            out.append(False)
        except errors.AuronError:
            out.append(True)
    conf.unset(cfg.FAULTS_PLAN)
    faults.reset()
    return out


def test_same_seed_replays_exactly():
    a = _sequence("rss.fetch:io_error@0.3", seed=7, site="rss.fetch")
    b = _sequence("rss.fetch:io_error@0.3", seed=7, site="rss.fetch")
    assert a == b
    assert any(a) and not all(a)      # prob 0.3 over 64 events: mixed


def test_different_seed_differs():
    a = _sequence("rss.fetch:io_error@0.3", seed=7, site="rss.fetch")
    b = _sequence("rss.fetch:io_error@0.3", seed=8, site="rss.fetch")
    assert a != b


def test_unarmed_site_never_fires():
    assert not any(_sequence("rss.fetch:io_error@1.0", seed=1,
                             site="spill.read"))


def test_io_error_raises_call_sites_class():
    conf = cfg.get_config()
    conf.set(cfg.FAULTS_PLAN, "spill.write:io_error@1.0")
    faults.reset()
    with pytest.raises(errors.SpillIOError) as ei:
        faults.maybe_fail("spill.write", errors.SpillIOError)
    assert ei.value.transient
    assert ei.value.site == "spill.write"


def test_fatal_is_deterministic_class():
    conf = cfg.get_config()
    conf.set(cfg.FAULTS_PLAN, "device.compute:fatal@1.0")
    faults.reset()
    with pytest.raises(errors.InjectedFatalError) as ei:
        faults.maybe_fail("device.compute", errors.DeviceExecutionError)
    assert not ei.value.transient


def test_maybe_corrupt_flips_exactly_one_byte_deterministically():
    conf = cfg.get_config()
    conf.set(cfg.FAULTS_PLAN, "rss.write:corrupt@1.0")
    conf.set(cfg.FAULTS_SEED, 3)
    faults.reset()
    data = bytes(range(256))
    a = faults.maybe_corrupt("rss.write", data)
    faults.reset()
    b = faults.maybe_corrupt("rss.write", data)
    assert a == b != data
    assert sum(x != y for x, y in zip(a, data)) == 1
    # unarmed: payload passes through untouched, same object
    conf.unset(cfg.FAULTS_PLAN)
    faults.reset()
    assert faults.maybe_corrupt("rss.write", data) is data


def test_snapshot_counts_injections():
    conf = cfg.get_config()
    conf.set(cfg.FAULTS_PLAN, "rss.fetch:io_error@1.0")
    faults.reset()
    base = faults.totals()
    for _ in range(3):
        with pytest.raises(errors.AuronError):
            faults.maybe_fail("rss.fetch", errors.RssUnavailableError)
    assert faults.snapshot() == {"rss.fetch": {"io_error": 3}}
    assert faults.totals() - base == 3
    # totals are monotonic across plane resets (per-task delta source)
    faults.reset()
    assert faults.totals() - base == 3


# -- checksum module --------------------------------------------------------

def test_checksum_roundtrip_and_detection():
    algo = cks.preferred_algo()
    data = b"the quick brown fox" * 100
    crc = cks.compute(data, algo)
    assert cks.verify(data, crc, algo)
    flipped = bytearray(data)
    flipped[7] ^= 0x01
    assert not cks.verify(bytes(flipped), crc, algo)


def test_checksum_algo_none_disables_verification():
    assert cks.compute(b"anything", cks.ALGO_NONE) == 0
    assert cks.verify(b"anything", 0xDEAD, cks.ALGO_NONE)


def test_unknown_algo_rejected_not_misread():
    with pytest.raises(cks.UnsupportedChecksum):
        cks.compute(b"x", 42)


# -- no backend watchdog, no swallowed device errors -------------------------

def test_watchdog_module_holds_only_the_stall_watchdog():
    """The init/compile probes and the CPU fallback are gone: a process
    runs on the platform jax gives it, and nothing can flip it."""
    for gone in ("ensure_backend", "first_compile_probe",
                 "run_probe_ladder", "ProbeReport", "_fallback_to_cpu",
                 "_subprocess_init_probe", "_drop_noncpu_backends",
                 "totals"):
        assert not hasattr(watchdog, gone), gone
    assert set(watchdog.stats()) == {"stalls", "mesh_rounds_forgiven"}
    for kept in ("register_heartbeat", "TaskHeartbeat", "StallReport",
                 "MeshRoundGuard", "stall_totals"):
        assert hasattr(watchdog, kept), kept


def test_backend_init_site_and_probe_knobs_are_gone():
    assert "backend.init" not in faults.SITES
    with pytest.raises(ValueError):
        faults.parse_plan("backend.init:hang@1.0")
    keys = {o.key for o in cfg.options()}
    assert "auron.watchdog.stall_timeout_s" in keys
    assert not {"auron.watchdog.init_timeout_s",
                "auron.watchdog.compile_timeout_s"} & keys
    assert not hasattr(errors, "BackendInitError")


class _DeadLeaf:
    """Array-like whose wait raises — a device error surfacing at the
    sync point."""

    def block_until_ready(self):
        raise RuntimeError("device halted")


def test_profile_block_propagates_device_errors():
    from auron_tpu.obs import profile
    with pytest.raises(RuntimeError, match="device halted"):
        profile._block([_DeadLeaf()])


def test_a_profiled_program_call_leaves_its_outputs_in_flight():
    """The program wrapper times the dispatch and hands the outputs
    back unwaited: a leaf whose wait raises passes through the call and
    the call books no device time — the error belongs to the sync point
    above (``device_fence``), where it surfaces."""
    from auron_tpu.obs import profile
    leaf = _DeadLeaf()
    prog = profile.ProfiledProgram(lambda x: {"out": x}, "test.faults.site")
    frame = profile.push_frame()
    assert frame is not None
    try:
        assert prog(leaf)["out"] is leaf
        assert (frame.calls, frame.device) == (1, 0)
        assert frame.dispatch > 0
        with pytest.raises(RuntimeError, match="device halted"):
            profile.device_fence({"out": leaf})
    finally:
        profile._stack().remove(frame)


def test_default_budget_needs_the_accelerator_to_report_its_memory(
        monkeypatch):
    """The nominal 8 GB is the CPU platform's only; an accelerator that
    reports no bytes_limit is an error, never an assumption."""
    import jax

    from auron_tpu.memmgr.manager import MemManager

    class Dev:
        def __init__(self, platform, stats):
            self.platform, self._stats = platform, stats
            self.device_kind = "fake"

        def memory_stats(self):
            return self._stats

    fraction = cfg.get_config().get(cfg.MEMORY_FRACTION)
    monkeypatch.setattr(jax, "devices", lambda: [Dev("cpu", None)])
    assert MemManager.default_budget() == int((8 << 30) * fraction)
    monkeypatch.setattr(
        jax, "devices", lambda: [Dev("tpu", {"bytes_limit": 16 << 30})])
    assert MemManager.default_budget() == int((16 << 30) * fraction)
    monkeypatch.setattr(jax, "devices", lambda: [Dev("tpu", {})])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        MemManager.default_budget()

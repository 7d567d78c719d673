"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the reference runs its native tests
without a JVM the same way — the 'fake backend' pattern, reference:
auron-memmgr/src/spill.rs:78-87): multi-chip sharding logic is exercised with
xla_force_host_platform_device_count; the chip is checked separately by
chip_smoke.py.

Env vars must be set before jax initializes, hence this happens at conftest
import time, before any test module imports auron_tpu.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite runs on the CPU mesh
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full-sweep batteries excluded from the tier-1 window "
        "(tier-1 runs -m 'not slow')")


@pytest.fixture(autouse=True)
def _spill_file_leak_check():
    """Tier-1 resource-leak audit, per-TEST half (PR 8): fail any test
    that leaves spill files in the system temp dir behind. A glob costs
    ~a millisecond; the gc pass (spill refs pinned by collected
    generators) runs only when the cheap check trips."""
    import glob as _glob
    import tempfile

    pattern = os.path.join(tempfile.gettempdir(), "auron-spill-*")
    files_before = set(_glob.glob(pattern))
    yield
    leaked = set(_glob.glob(pattern)) - files_before
    if leaked:
        import gc
        gc.collect()
        leaked = set(_glob.glob(pattern)) - files_before
    if leaked:
        for p in leaked:   # clean up so ONE leak fails ONE test
            try:
                os.unlink(p)
            except OSError:
                pass
        pytest.fail("lifecycle leak audit: leaked spill files: "
                    f"{sorted(leaked)}", pytrace=False)


@pytest.fixture(autouse=True, scope="module")
def _journal_leak_check():
    """Tier-1 leak audit, journal half (ISSUE 13): no test module may
    grow the set of ``*.journal`` files across the journal dirs this
    process touched (runtime/journal tracks them), nor leave a journal
    registered OPEN. Tests that crash/suspend journals mid-module must
    consume them (resume/reuse/GC) before the module ends — a journal
    surviving its test module is the in-process equivalent of a leaked
    spill file."""
    try:
        from auron_tpu.runtime import journal as _jrn
    except Exception:
        yield
        return

    def _journal_files():
        import glob as _glob
        found = []
        for d in _jrn.seen_dirs():
            found.extend(_glob.glob(os.path.join(d, "*.journal")))
        return set(found)

    before = _journal_files()
    open_before = _jrn.open_journal_count()
    yield
    leaked = _journal_files() - before
    still_open = _jrn.open_journal_count()
    if leaked:
        for p in leaked:   # clean up so ONE leak fails ONE module
            try:
                os.unlink(p)
            except OSError:
                pass
        pytest.fail("lifecycle leak audit: leaked query journals: "
                    f"{sorted(leaked)}", pytrace=False)
    if still_open > open_before:
        pytest.fail(
            f"lifecycle leak audit: open journal count grew "
            f"{open_before} -> {still_open} over this module",
            pytrace=False)


@pytest.fixture(autouse=True, scope="module")
def _memmgr_consumer_leak_check():
    """Per-MODULE half of the leak audit: no test module may grow the
    set of live registered memmgr consumers. Module-scoped because the
    verdict needs a full gc (consumers are weakly held — 'pinned leak'
    vs 'not collected yet'), and a per-test gc would tax the whole
    tier-1 window ~100 ms per test."""
    try:
        from auron_tpu.memmgr import manager as _mgr
    except Exception:
        yield
        return
    before = _mgr.live_consumer_count()
    yield
    consumers = _mgr.live_consumer_count()
    if consumers > before:
        import gc
        gc.collect()
        consumers = _mgr.live_consumer_count()
    if consumers > before:
        pytest.fail(
            f"lifecycle leak audit: live memmgr consumers grew "
            f"{before} -> {consumers} over this module", pytrace=False)


@pytest.fixture(autouse=True, scope="module")
def _bound_live_programs():
    """Bound accumulated XLA programs across the suite: the CPU backend's
    JIT segfaults after several hundred programs pile up in one process
    (see utils/compile_stats.DEFAULT_MAX_LIVE_PROGRAMS). Clearing between
    modules keeps single-process full-suite runs alive; CI's sharded
    workers never get close."""
    yield
    from auron_tpu.utils import compile_stats
    compile_stats.maybe_clear()


def spin_until(predicate, timeout_s=30.0, what="condition"):
    """Poll ``predicate`` until true or fail after ``timeout_s``
    (monotonic clock) — the shared wait helper of the concurrency
    tests (test_scheduler / test_serving), one definition so clock
    source and failure shape cannot drift between modules."""
    import time as _time
    end = _time.monotonic() + timeout_s
    while _time.monotonic() < end:
        if predicate():
            return
        _time.sleep(0.005)
    pytest.fail(f"timed out waiting for {what}", pytrace=False)

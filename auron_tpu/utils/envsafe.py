"""Child-process environments for the CPU test harness.

``cpu_child_env`` is the one place outside the test suite that pins a
platform: tests and the virtual-mesh dryrun spawn children that must run
on an N-device virtual CPU mesh whatever the parent runs on.
``require_shareable_device`` is the other half of one-process-per-chip:
a harness whose engine parent spawns engine children says so instead of
hanging a child on a chip its parent holds.
"""

from __future__ import annotations

import os


def watchdogged_child_code(body: str, parent_timeout_s: int,
                           margin_s: int = 30) -> tuple[str, int]:
    """Wrap python ``-c`` code with a faulthandler watchdog.

    The watchdog thread fires even when the main thread is stuck inside
    native code, printing every stack to stderr and hard-exiting — so a
    hang becomes a fast diagnosable failure instead of an opaque
    parent-side SIGKILL. Returns ``(code,
    watchdog_s)`` where the watchdog fires ``margin_s`` BEFORE the
    parent's ``parent_timeout_s`` so the stack dump always wins the race
    against the parent's kill.
    """
    watchdog_s = max(parent_timeout_s - margin_s, 5)
    code = (
        "import faulthandler\n"
        f"faulthandler.dump_traceback_later({watchdog_s}, exit=True)\n"
        f"{body}\n"
        "faulthandler.cancel_dump_traceback_later()\n"
    )
    return code, watchdog_s


def cpu_asked_for_by_name() -> bool:
    """True when the environment pins ``JAX_PLATFORMS=cpu`` explicitly —
    the only way the chip-or-fail entry points (bench.py, the multichip
    dryrun) accept a CPU. jax landing on the CPU because it found
    nothing better does not count."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require_shareable_device(what: str) -> None:
    """Raise unless this process runs on the CPU platform. For harnesses
    that run engine children BESIDE an engine parent (the crash sweep,
    in-process fleet drills): an accelerator belongs to one process."""
    import jax
    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            f"{what} runs engine child processes beside this one, which "
            f"already holds the {platform} device; a chip belongs to one "
            "process — run it under JAX_PLATFORMS=cpu")


def cpu_child_env(n_devices: int | None = None) -> dict:
    """A copy of os.environ pinned to the CPU platform, with the virtual
    device count forced to ``n_devices`` when given."""
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    if n_devices is not None:
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    if flags:
        env["XLA_FLAGS"] = " ".join(flags)
    else:
        env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env

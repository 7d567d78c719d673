"""Driver-contract tests for __graft_entry__.

The driver compile-checks ``entry()`` single-chip and runs
``dryrun_multichip(n)``: on the first n visible devices — real chips on
an accelerator host — and on a virtual CPU mesh only under an explicit
``JAX_PLATFORMS=cpu``, re-exec'd with the device count forced when the
calling process holds too few. The output line says which it was.
"""

import subprocess
import sys

import jax
import pytest

import __graft_entry__ as graft

_REPO = graft.__file__.rsplit("/", 1)[0]


def test_entry_jits_and_runs():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    keys, valid, sums, counts, avg = out
    assert keys.shape == valid.shape == sums.shape == counts.shape == avg.shape


def test_dryrun_multichip_in_process(capsys):
    # conftest forces an 8-device CPU platform, so this exercises the
    # in-process path — and the line names the devices it ran on
    graft.dryrun_multichip(8)
    out = capsys.readouterr().out
    assert "dryrun_multichip ok: n_devices=8 devices=cpu " \
           "(virtual cpu mesh)" in out


def test_dryrun_multichip_subprocess_fallback(capfd):
    # More devices than this process exposes, under the suite's explicit
    # JAX_PLATFORMS=cpu -> re-exec with a forced virtual mesh.
    assert len(jax.devices()) < 16
    graft.dryrun_multichip(16)
    assert "dryrun_multichip ok: n_devices=16 devices=cpu" \
        in capfd.readouterr().out


def _run_dryrun_under(env_extra):
    env = {"PATH": "/usr/bin:/bin", "HOME": "/root"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=600)


def test_dryrun_multichip_explicit_cpu_env():
    """A bare driver that asks for the CPU by name: one device visible,
    so the dryrun re-execs itself onto an 8-device virtual mesh."""
    proc = _run_dryrun_under({"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip ok: n_devices=8 devices=cpu" in proc.stdout


def test_dryrun_multichip_without_chips_or_explicit_cpu_fails():
    """No platform variables and no chips: jax lands on ONE cpu device.
    The dryrun must say so and fail — never quietly stand a virtual mesh
    in for the slice it was asked to prove."""
    proc = _run_dryrun_under({})
    assert proc.returncode != 0
    assert "dryrun_multichip ok" not in proc.stdout
    assert "only under an explicit JAX_PLATFORMS=cpu" in proc.stderr


def test_cpu_child_env_pins_platform_and_device_count(monkeypatch):
    from auron_tpu.utils.envsafe import cpu_child_env
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count=2 --xla_dump_to=/x")
    env = cpu_child_env(n_devices=8)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == \
        "--xla_dump_to=/x --xla_force_host_platform_device_count=8"
    # without a count the forced-device flag is dropped, the rest kept
    assert cpu_child_env()["XLA_FLAGS"] == "--xla_dump_to=/x"


def test_engine_parent_with_engine_children_needs_a_shareable_device(
        monkeypatch):
    """One process per chip: the harnesses that run engine children
    beside an engine parent refuse an accelerator with a reason instead
    of hanging a child on a chip the parent holds."""
    from auron_tpu.utils.envsafe import require_shareable_device
    require_shareable_device("the crash sweep")      # cpu: fine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="crash sweep.*one process"):
        require_shareable_device("the crash sweep")

"""Driver-contract tests for bench.py and chip_smoke.py.

bench.py measures in ONE process on the device jax gives it and prints
one JSON line stamped with that device. Chip or fail: without a TPU it
prints an ``error`` record and exits non-zero — the only CPU run it
accepts is an explicit ``JAX_PLATFORMS=cpu`` (this suite), whose record
says ``platform: cpu``. chip_smoke.py never accepts a CPU at all.
"""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMALL = {"AURON_BENCH_CAPACITY": "16384", "AURON_BENCH_ITERS": "2"}


def _run(script, extra_env, timeout=560, cwd=_REPO):
    env = {"PATH": "/usr/bin:/bin", "HOME": "/root"}
    env.update(_SMALL)
    env.update(extra_env)
    return subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True,
                          timeout=timeout, cwd=cwd)


def _run_bench(extra_env, timeout=560):
    return _run(os.path.join(_REPO, "bench.py"), extra_env, timeout)


def _parse_single_json_line(stdout: str) -> dict:
    lines = [l for l in stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected ONE json line, got: {lines}"
    return json.loads(lines[0])


def test_bench_emits_measurement_on_explicit_cpu():
    proc = _run_bench({"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = _parse_single_json_line(proc.stdout)
    assert rec["metric"] == "q01_pipeline_rows_per_sec_per_chip"
    assert rec["value"] > 0
    assert rec["unit"] == "rows/s"
    assert rec["vs_baseline"] > 0
    assert rec["platform"] == "cpu"
    assert rec["device_kind"] and rec["device_count"] == 1
    # one process measured everything: no section was skipped by a
    # swallowed error, and a single device skips the mesh sweep in words
    assert not [k for k in rec if k.endswith("_error")], rec
    assert rec["mesh"] == {"skipped": "1 device visible"}
    # the cache sits where the one helper says — inside the checkout
    assert rec["xla_cache"]["dir"] == os.path.join(_REPO, ".jax_cache")


def test_bench_without_tpu_is_an_error_not_a_cpu_rate():
    """No platform variables, no chip: jax quietly lands on the CPU —
    bench.py must refuse to measure there."""
    proc = _run_bench({})
    assert proc.returncode != 0
    rec = _parse_single_json_line(proc.stdout)
    assert rec["metric"] == "q01_pipeline_rows_per_sec_per_chip"
    assert "no TPU" in rec["error"]
    assert "value" not in rec and "platform" not in rec


def test_bench_error_record_is_parseable(tmp_path):
    """When the measurement cannot run (a dependency unimportable), the
    output is one JSON line with an ``error`` key and a non-zero exit.

    pyarrow is shadowed rather than auron_tpu because the repo dir sits
    ahead of PYTHONPATH in sys.path; PYTHONPATH still precedes
    site-packages."""
    broken = tmp_path / "broken"
    broken.mkdir()
    (broken / "pyarrow").mkdir()
    (broken / "pyarrow" / "__init__.py").write_text(
        "raise RuntimeError('deliberately broken for the error-record test')")
    proc = _run_bench({"JAX_PLATFORMS": "cpu",
                       "PYTHONPATH": str(broken)})
    assert proc.returncode != 0
    rec = _parse_single_json_line(proc.stdout)
    assert rec["metric"] == "q01_pipeline_rows_per_sec_per_chip"
    assert "deliberately broken" in rec["error"]


def test_bench_failed_section_fails_the_run():
    """A section that raises is recorded AND turns the exit code: it can
    no longer hide behind the sections that worked."""
    import bench
    result: dict = {"value": 1.0}
    failed: list = []

    def boom():
        raise ValueError("mosaic refused")

    bench._section(result, failed, "pallas_agg", boom)
    bench._section(result, failed, "fine", lambda: result.update(ok=1))
    assert failed == ["pallas_agg"]
    assert result["pallas_agg_error"] == "ValueError: mosaic refused"
    assert result["ok"] == 1 and "fine_error" not in result


def _smoke_result_lines(stdout: str) -> list:
    return [l for l in stdout.splitlines() if l.lstrip().startswith("{")]


def test_chip_smoke_refuses_cpu():
    smoke = os.path.join(_REPO, "chip_smoke.py")
    for env in ({}, {"JAX_PLATFORMS": "cpu"}):
        proc = _run(smoke, env, timeout=120)
        assert proc.returncode != 0, proc.stdout[-2000:]
        assert "no TPU" in proc.stdout + proc.stderr
        assert not _smoke_result_lines(proc.stdout), proc.stdout[-2000:]


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program beside it proves nothing."""
    import shutil
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    proc = _run(str(tmp_path / "chip_smoke.py"), {"JAX_PLATFORMS": "cpu"},
                timeout=120, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert not _smoke_result_lines(proc.stdout)

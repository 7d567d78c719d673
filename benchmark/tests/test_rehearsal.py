"""The one command, end to end, on the CPU at scale 0.02 under an
explicit JAX_PLATFORMS=cpu: one device for a one-chip cell, four virtual
devices for the mesh cell. The result line has the contract's keys and
says `cpu`; a run that finds fewer devices than the cell needs, or no
program beside the benchmark, exits non-zero and prints no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


#: BENCHMARK.json as it will read once the 4-chip cell is proven on the
#: chip: the same file with `tpcds_sf1_mesh4.star_join_serial`, its
#: configuration and its exchange metrics added
MESH_JSON = os.path.join(BENCH, "tests", "BENCHMARK.mesh4.json")
MESH = "tpcds_sf1_mesh4.star_join_serial"


def _run(workload, devices, trace, cwd=ROOT, extra=()):
    if workload == MESH:
        extra = (*extra, "--benchmark-json", MESH_JSON)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
           "--workload", workload, "--seed", "2147483999", "--seconds", "4",
           "--trace", str(trace), "--scale", "0.02", *extra]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=900)


def _result(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,devices", [
    ("tpcds_sf1.star_join", 1),
    ("tpcds_sf1_smallfiles.star_join", 1),
    (MESH, 4)])
def test_end_to_end_line(workload, devices):
    from harness.cell import Cell
    proc = _run(workload, devices, trace=0)
    res = _result(proc)
    assert proc.stderr.strip().splitlines()[-1].startswith(
        "compared shape_errors: ")
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 4
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == devices
    assert "memory_peak_bytes" in res["device"]
    cell = Cell(workload, MESH_JSON if workload == MESH else None)
    assert set(res["metrics"]) == set(cell.end_to_end())
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["rehearsal_scale"] == 0.02
    # each number compared beside its limit: last in the line, and the
    # last lines of stderr
    assert list(res)[-1] == "compared"
    for name in ("exact_mismatches", "max_double_rel", "shape_errors"):
        assert res["compared"][name]["value"] <= res["compared"][name]["limit"]
    assert res["compared"]["answers"] == res["attempted"]


def test_traced_line_has_the_layer_metrics():
    from harness.cell import Cell
    res = _result(_run(MESH, 4, trace=1))
    assert res["correct"] is True
    want = set(Cell(MESH, MESH_JSON).per_layer())
    # a reader that finds nothing to read leaves its metric out: the CPU
    # has no device plane for the all-to-all's time
    assert want - {"exchange.all_to_all_ms"} <= set(res["metrics"]) <= want
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_too_few_devices_fails_without_a_result():
    proc = _run(MESH, 1, trace=0)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_alone_in_a_directory_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run("tpcds_sf1.star_join", 1, trace=0, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout

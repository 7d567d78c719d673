"""The readers of the version-2 cost ledger (PR 25): each returns a
number from a hand-written version-2 DONE frame and None from a
version-1 frame — the parent commit's, which the driver runs them
against — and the CPU rehearsal prints all fifteen in both cells."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

#: metric -> what the hand-written frames below must give
NEW = {
    "serve.queue_wait_ms": 2.0,
    "serve.cpu_share": 0.25,
    "serve.unattributed_ms": 100.0,
    "serve.send_ms": 30.0,
    "plan.decode_ms": 40.0,
    "compile.task_ms": 500.0,
    "scan.decode_ms": 300.0,
    "scan.encode_ms": 2000.0,
    "scan.h2d_ms": 700.0,
    "scan.h2d_transfers": 103,
    "convert.to_arrow_ms": 20.0,
    "ops.host_ms": 2600.0,
    "ops.device_wait_ms": 200.0,
    "ops.program_calls": 11,
    "ops.readbacks": 4,
}

V1 = {"version": 1, "wall_s": 4.0, "device_s": 6.0,
      "host_s": {"dispatch": 0.03, "convert": 1.5, "serde": 0.0,
                 "iter": 0.0, "other": 0.2},
      "mesh_bytes": 0,
      "compile": {"xla_compiles": 0, "seconds": 0.0, "program_builds": 0,
                  "program_hits": 11}}


def v2(compile_s: float) -> dict:
    return dict(
        V1, version=2, queue_s=0.002, cpu_s=1.0,
        layers_s={"plan": 0.04, "compile": compile_s, "scan_wait": 1.0,
                  "op_host": 2.6, "op_device_wait": 0.2, "exchange": 0.0,
                  "to_arrow": 0.02, "send": 0.03,
                  "other": 0.1},
        ops_s={"hash_join": {"host_s": 2.6, "device_wait_s": 0.2,
                             "batches": 10}},
        scan_worker_s={"decode": 0.3, "encode": 2.0, "h2d": 0.7},
        counts={"program_calls": 11, "program_calls_by_site": {},
                "readbacks": 4, "d2h_bytes": 1000, "h2d_transfers": 103,
                "h2d_bytes": 1_000_000, "layer_spans": 71},
        compile=dict(V1["compile"], task_xla_compiles=3,
                     task_seconds=compile_s))


def ctx(ledgers):
    return {"tasks": [{"plan": "q3", "t_submit": 0.0, "t_done": 4.1,
                       "done": {"cost_ledger": led}} for led in ledgers]}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_gained_exactly_these_entries(bench):
    # by name, wherever later PRs appended theirs
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(by_name)
    for name in NEW:
        assert "workloads" not in by_name[name]   # cells added later too


@pytest.mark.parametrize("name", list(NEW))
def test_reader_reads_a_version_2_frame(name):
    from harness import cell
    read = cell.load_module("layer_metrics", name).read
    # three tasks, one of which carries all the compile seconds: the
    # median of its field would hide it, the mean does not
    got = read(ctx([v2(0.0), v2(1.5), v2(0.0)]))
    assert got == pytest.approx(NEW[name])


@pytest.mark.parametrize("name", list(NEW))
def test_reader_gives_none_for_a_version_1_frame(name):
    from harness import cell
    read = cell.load_module("layer_metrics", name).read
    assert read(ctx([V1, V1])) is None
    assert read(ctx([])) is None
    assert read({"tasks": [{"done": {}}]}) is None


@pytest.mark.parametrize("workload", ["tpcds_sf1.star_join",
                                      "tpcds_sf1_smallfiles.star_join"])
def test_rehearsal_prints_all_fifteen(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    cmd = [sys.executable, os.path.join(BENCH, "run.py"),
           "--workload", workload, "--seed", "2147484001", "--seconds", "4",
           "--trace", "1", "--scale", "0.02"]
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    for name in NEW:
        assert name in res["metrics"], name
        assert res["metrics"][name]["value"] is not None
    assert 0 < res["metrics"]["serve.cpu_share"]["value"]
    assert res["metrics"]["ops.program_calls"]["value"] > 0
    assert res["metrics"]["scan.h2d_transfers"]["value"] > 0

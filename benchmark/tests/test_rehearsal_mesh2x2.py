"""The 4-chip cell `tpcds_sf1_mesh2x2.star_join_serial`, taken from
BENCHMARK.json itself: rehearsed on four virtual CPU devices at scale
0.02 with `--trace 1`, and its four new readers against hand-written
frames and traces — a number where there is something to read, None
(never an exception) where the program or the trace lacks it, as the
parent commit and a CPU trace do."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

CELL = "tpcds_sf1_mesh2x2.star_join_serial"
SHIPPED = ("exchange.all_to_all_ms", "exchange.mesh_bytes")
NEW = ("exchange.host_ms", "exchange.rounds", "exchange.ici_share",
       "device.busy_min_share")
SERIAL_P50 = "serve.task_p50_ms.serial"
#: what a CPU trace has nothing for: no device plane, so no all-to-all
#: time and no busy seconds by chip
NEED_DEVICE_PLANE = {"exchange.all_to_all_ms", "exchange.ici_share",
                     "device.busy_min_share"}

A2A = "%all-to-all.3 = (f32[4,8]) all-to-all(%p), replica_groups={{0,1,2,3}}"


def module(name):
    from harness import cell
    return cell.load_module("layer_metrics", name)


def reader(name):
    return module(name).read


def frame(counts=None, exchange_s=0.074):
    """A version-2 DONE frame of a mesh stage; ``counts=None`` is the
    parent's, from before the mesh counts."""
    ledger = {"version": 2, "wall_s": 2.6, "mesh_bytes": 1225,
              "layers_s": {"op_host": 2.2, "exchange": exchange_s},
              "counts": {"program_calls": 56, "readbacks": 34}}
    ledger["counts"].update(counts or {})
    return ledger


def ctx(ledgers, trace=None, count=4, kind="TPU v5 lite"):
    return {"tasks": [{"plan": "q3", "t_submit": 10.0 + 3 * i,
                       "t_done": 12.6 + 3 * i,
                       "done": {"cost_ledger": led}}
                      for i, led in enumerate(ledgers)],
            "trace": trace, "trace_slice": {"start": 12.0, "stop": 17.0},
            "device": {"count": count, "kind": kind, "platform": "tpu"}}


def tpu_trace(busy=(0.04, 0.004, 0.004, 0.004), a2a_s=15e-6):
    return {"window_s": 5.0, "busy_s": sum(busy) / 4, "devices": len(busy),
            "busy_s_by_device": {f"/device:TPU:{i}": b
                                 for i, b in enumerate(busy)},
            "op_s": {A2A: a2a_s, "%fusion.1 = fusion(...)": 0.01}}


CPU_TRACE = {"window_s": 5.0, "busy_s": 0.0, "devices": 0,
             "busy_s_by_device": {}, "op_s": {}}
MESH_COUNTS = {"mesh_rounds": 1, "mesh_escalations": 0, "mesh_bytes": 1200,
               "mesh_slot_bytes": 204800}


def test_benchmark_json_lists_the_cell_and_its_six_metrics():
    from harness.cell import Cell
    cell = Cell(CELL)
    assert cell.chips == 4 and cell.config["name"] == "tpcds_sf1_mesh2x2"
    assert cell.traffic["clients"] == 1
    assert cell.config["engine"] == {"auron.mesh.enabled": True,
                                     "auron.mesh.devices": 4,
                                     "auron.max_live_programs": 0}
    # one client: latency is rows over the rate, so the rate judges the
    # cell and the median stays as a per-layer reading
    assert set(cell.end_to_end()) == {"fact_rows_per_s", "setup_s"}
    layer = cell.per_layer()
    assert layer[SERIAL_P50]["workloads"] == [CELL]
    assert layer[SERIAL_P50]["moves"] == "fact_rows_per_s"
    assert layer[SERIAL_P50]["layer"] == "entry"
    for name in SHIPPED + NEW:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "fact_rows_per_s"
    # no one-chip cell reads them
    for other in ("tpcds_sf1.star_join", "tpcds_sf1_smallfiles.star_join"):
        assert not {*SHIPPED, *NEW, SERIAL_P50} & set(Cell(other).per_layer())
        assert "task_p50_ms" in Cell(other).end_to_end()


def test_the_configuration_differs_from_the_shipped_one_only_as_said():
    from harness.cell import BENCH_DIR, load_json
    old = load_json(os.path.join(BENCH_DIR, "configs", "tpcds_sf1_mesh4.json"))
    new = load_json(os.path.join(BENCH_DIR, "configs",
                                 "tpcds_sf1_mesh2x2.json"))
    differ = {k for k in set(old) | set(new) if old.get(k) != new.get(k)}
    assert differ == {"name", "source", "engine", "warmup_groups", "assumed"}
    assert new["guarantees"] == old["guarantees"] and len(old["guarantees"]) == 4
    assert set(new["assumed"]) - set(old["assumed"]) == {
        "max_live_programs", "warmup_groups"}


@pytest.mark.parametrize("name,want", [
    ("exchange.host_ms", 74.0),
    ("exchange.rounds", 1),
    # two of the three tasks end in the slice: 2400 B live, a chip sends
    # 1/4 of it and keeps 1/4 of that: 450 B over 15 us x 200 GB/s
    ("exchange.ici_share", 450 / (15e-6 * 200e9)),
    ("device.busy_min_share", 0.004 / 5.0)])
def test_reader_reads_a_mesh_stage(name, want):
    got = reader(name)(ctx([frame(MESH_COUNTS)] * 3, tpu_trace()))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_none_or_a_number_where_something_is_missing(name):
    read = reader(name)
    # the parent's frames (no mesh counts) under a TPU trace
    parent = read(ctx([frame()] * 3, tpu_trace()))
    assert (parent is None) == (name in ("exchange.rounds",
                                         "exchange.ici_share"))
    # a CPU trace: no device plane
    cpu = read(ctx([frame(MESH_COUNTS)] * 3, CPU_TRACE, kind="cpu"))
    assert (cpu is None) == (name in NEED_DEVICE_PLANE)
    # no trace at all, no task at all, a frame with no ledger
    for c in (ctx([frame(MESH_COUNTS)], None), ctx([], tpu_trace()),
              ctx([None], tpu_trace())):
        got = read(c)
        assert got is None or isinstance(got, (int, float))


def test_the_serial_median_is_the_median_of_the_clients_latencies():
    read = reader(SERIAL_P50)
    tasks = [{"ok": True, "t_submit": 0.0, "t_done": 1.4},
             {"ok": True, "t_submit": 1.4, "t_done": 3.1},
             {"ok": True, "t_submit": 3.1, "t_done": 4.6},
             {"ok": True, "t_submit": 4.6, "t_done": 6.2},
             {"ok": False, "t_submit": 6.2, "t_done": 6.3}]
    # 1400, 1700, 1500, 1600 ms: the failed task gives no latency
    assert read({"tasks": tasks}) == pytest.approx(1550.0)
    assert read({"tasks": tasks[:3]}) == pytest.approx(1500.0)
    assert read({"tasks": []}) is None
    assert read({"tasks": tasks[4:]}) is None


def test_a_chip_without_a_plane_counts_as_idle():
    got = reader("device.busy_min_share")(
        ctx([frame()], tpu_trace(busy=(0.04,))))
    assert got == 0.0


def test_ici_share_cannot_count_more_than_the_wire_carried():
    # every byte of a stage leaving its chip at the peak is a share of 1
    from harness.peaks import peaks_for
    peak = peaks_for("TPU v5 lite")["ici_bits_per_s"] / 8
    mod = module("exchange.ici_share")
    assert mod.sent_bytes_a_chip(1600, 4) == 300
    assert mod.sent_bytes_a_chip(1600, 1) == 0
    secs = 300 / peak
    got = reader("exchange.ici_share")(
        ctx([frame(dict(MESH_COUNTS, mesh_bytes=1600))],
            tpu_trace(a2a_s=secs)))
    assert got == pytest.approx(1.0)


def test_the_cell_rehearsed_traced_on_four_virtual_devices():
    from harness.cell import Cell
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "4",
         "--trace", "1", "--scale", "0.02"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 4
    want = set(Cell(CELL).per_layer())
    assert want - NEED_DEVICE_PLANE <= set(res["metrics"]) <= want
    assert res["metrics"]["exchange.rounds"]["value"] >= 1
    assert res["metrics"]["exchange.mesh_bytes"]["value"] > 0
    assert res["metrics"]["exchange.host_ms"]["value"] > 0
    assert res["metrics"][SERIAL_P50]["value"] > 0

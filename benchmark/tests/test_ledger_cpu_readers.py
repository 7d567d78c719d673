"""The readers of the ledger's CPU self times and row-count reads (PR 40):
each returns its number from hand-written version-2 DONE frames that
carry ``layers_cpu_s``, ``scan_worker_cpu_s`` and ``counts.row_syncs``,
and None from a frame without them — the parent commit's, which the
driver runs them against."""

import json
import os

import pytest

from conftest import ROOT

#: metric -> what the three hand-written frames below must give
NEW = {
    "ops.host_cpu_ms": 110.0,
    "ops.host_offcpu_ms": 50.0,
    "serve.offcpu_ms": 75.0,
    "scan.encode_offcpu_ms": 8.0,
    "ops.row_syncs": 12,
}

#: a frame of the parent commit: version 2, none of the new fields
PARENT = {
    "version": 2, "wall_s": 0.4, "cpu_s": 0.2, "queue_s": 0.0,
    "layers_s": {"plan": 0.01, "compile": 0.0, "scan_wait": 0.05,
                 "op_host": 0.16, "op_device_wait": 0.1, "exchange": 0.05,
                 "to_arrow": 0.01, "send": 0.01, "other": 0.01},
    "exchange_s": {"materialize": 0.02, "map_write": 0.0,
                   "broadcast_collect": 0.0, "gang_wait": 0.03,
                   "mesh_stack": 0.0, "mesh_round": 0.0},
    "ops_s": {"hash_join": {"host_s": 0.16, "device_wait_s": 0.1,
                            "batches": 10}},
    "scan_worker_s": {"decode": 0.02, "encode": 0.03, "h2d": 0.02},
    "counts": {"program_calls": 18, "readbacks": 9, "layer_spans": 71},
}


def change(op_host_cpu: float, compile_in_plan: bool = False) -> dict:
    """The same task on the change. Undeclared waiting: plan 10 − 6,
    op_host 160 − ``op_host_cpu``, exchange (50 − 30 of gang_wait) − 5,
    to_arrow 10 − 8, send 10 − 6 ms. With a compile in ``plan`` the
    span's CPU exceeds what is left of its wall: the floor is 0."""
    led = json.loads(json.dumps(PARENT))
    led["layers_cpu_s"] = {"plan": 0.006, "scan_wait": 0.001,
                           "op_host": op_host_cpu, "op_device_wait": 0.002,
                           "exchange": 0.005, "to_arrow": 0.008,
                           "send": 0.006}
    if compile_in_plan:
        led["layers_s"]["plan"] = 0.002
        led["layers_s"]["compile"] = 0.008
    led["ops_s"]["hash_join"]["cpu_s"] = op_host_cpu
    led["scan_worker_cpu_s"] = {"decode": 0.015, "encode": 0.022,
                                "h2d": 0.004}
    led["counts"]["row_syncs"] = 12
    return led


def ctx(ledgers):
    return {"tasks": [{"plan": "q3", "t_submit": 0.0, "t_done": 0.41,
                       "done": {"cost_ledger": led}} for led in ledgers]}


def reader(name):
    from harness import cell
    return cell.load_module("layer_metrics", name).read


def test_benchmark_json_lists_them_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(by_name)
    for name in NEW:
        entry = by_name[name]
        assert "workloads" not in entry           # cells added later too
        assert entry["moves"] == "fact_rows_per_s"
        assert entry["better"] == "lower"
        assert entry["source"] in ("program_span", "program_counter")


@pytest.mark.parametrize("name", list(NEW))
def test_reader_reads_the_new_fields(name):
    # the CPU readers give means (the chip host's CPU clock ticks at
    # 10 ms): op_host 160 ms of which 90 / 100 / 140 are CPU
    frames = [change(0.09), change(0.10), change(0.14)]
    assert reader(name)(ctx(frames)) == pytest.approx(NEW[name])


def test_off_cpu_is_never_below_zero():
    # a span whose compile left its wall but not its CPU
    frames = [change(0.17, compile_in_plan=True)] * 3
    assert reader("ops.host_offcpu_ms")(ctx(frames)) == 0.0
    # plan and op_host floor at 0; exchange 15 + to_arrow 2 + send 4
    assert reader("serve.offcpu_ms")(ctx(frames)) == pytest.approx(21.0)


@pytest.mark.parametrize("name", list(NEW))
def test_reader_gives_none_for_the_parents_frame(name):
    read = reader(name)
    assert read(ctx([PARENT, PARENT])) is None
    assert read(ctx([])) is None
    assert read({"tasks": [{"done": {}}]}) is None

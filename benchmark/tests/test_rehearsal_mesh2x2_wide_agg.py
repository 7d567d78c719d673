"""The 4-chip cell `tpcds_sf1_mesh2x2.wide_agg` (PR 38), taken from
BENCHMARK.json itself: TPC-DS q65 in its two forms and their check plans
as 4-partition stages from four closed-loop clients, under its own
configuration `tpcds_sf1_mesh2x2_q65`. Rehearsed traced on
four virtual CPU devices at scale 0.02 (counts and correctness only);
its four readers on hand-written frames — a number where there is
something to read, None (never an exception) where the program lacks it,
as the parent commit's frames do; and the control at this cell's stage
size: the reference in the program's place with the (store, item) sums
carried in float32 has to read `correct` false through `compare`."""

import json
import os
import subprocess
import sys

import pytest

import test_wide_agg_cells as one_chip
from conftest import ROOT
from test_wide_agg_cells import sf1  # noqa: F401  (the SF1 tables, once)

CELL = "tpcds_sf1_mesh2x2.wide_agg"
SERIAL = "tpcds_sf1_mesh2x2.star_join_serial"
NEW = ("exchange.gang_wait_ms", "exchange.read_ms", "exchange.read_batches",
       "exchange.slot_fill")
#: the readers whose source the parent's frames already hold
ON_THE_PARENT = {"exchange.read_ms", "exchange.slot_fill"}


def read(name, tasks):
    from harness.cell import load_module
    return load_module("layer_metrics", name).read({"tasks": tasks})


def frame(gang_wait_s=None, read_s=(1.2, 0.3), counts=None):
    """A version-2 DONE frame of a mesh stage. ``gang_wait_s=None`` and no
    ``mesh_read_batches`` among ``counts`` is the parent's frame."""
    ledger = {"version": 2, "wall_s": 4.0,
              "layers_s": {"op_host": 2.5, "exchange": 1.1},
              "ops_s": {}, "counts": dict(counts or {})}
    if gang_wait_s is not None:
        ledger["exchange_s"] = {
            "materialize": 0.05, "map_write": 0.0, "broadcast_collect": 0.0,
            "gang_wait": gang_wait_s, "mesh_stack": 0.02,
            "mesh_round": round(1.03 - gang_wait_s, 6)}
    if read_s is not None:
        ledger["ops_s"]["shuffle_exchange_read"] = {
            "host_s": read_s[0], "device_wait_s": read_s[1], "batches": 48}
    return {"plan": "q65", "group": 0, "rows": 262144, "t_submit": 0.0,
            "t_done": 4.0, "done": {"cost_ledger": ledger}}


def test_benchmark_json_lists_the_cell_and_its_four_metrics():
    from harness.cell import Cell
    cell = Cell(CELL)
    assert cell.chips == 4 and cell.config["name"] == "tpcds_sf1_mesh2x2_q65"
    assert cell.plans == ["q65", "q65m", "q65sa", "q65sam"]
    assert cell.traffic["clients"] == 4
    assert cell.traffic["task_timeout_s"] == 180
    assert cell.config["require_counters"] == {"exchange_route_all_to_all": 1}
    assert len(cell.config["guarantees"]) == 4
    assert set(cell.end_to_end()) == {"fact_rows_per_s", "setup_s"}
    layer = cell.per_layer()
    for name in NEW:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "fact_rows_per_s"
        assert layer[name]["layer"] == "exchange"
    # its own configuration (the q65 deployment): the star-join mesh
    # cell's splits, stage shape, warm-up, cuts and guarantees, with the
    # executor's four task slots written out; the readers are not shared
    old = Cell(SERIAL).config
    differ = {k for k in set(old) | set(cell.config)
              if old.get(k) != cell.config.get(k)}
    assert differ == {"name", "source", "deployment", "engine", "assumed"}
    assert cell.config["engine"] == dict(
        old["engine"], **{"auron.sched.max_concurrent": 4})
    assert cell.traffic["clients"] == \
        cell.config["engine"]["auron.sched.max_concurrent"]
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config["name"])
    assert entry == cell.bench["configs"][-1]
    assert entry["source"] == cell.config["source"]
    assert entry["reduced"] == list(cell.config["reduced"])
    assert not set(NEW) & set(Cell(SERIAL).per_layer())
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert 2 * len(four) <= len(bench["workloads"])


def test_the_readers_read_a_mesh_stage():
    check = frame(0.4, (0.6, 0.1), {
        "mesh_rounds": 1, "mesh_bytes": 500_000,
        "mesh_slot_bytes": 15_000_000, "mesh_read_batches": 16,
        "mesh_home_bytes": 400_000})
    q65 = frame(0.9, (2.0, 0.4), {
        "mesh_rounds": 3, "mesh_bytes": 1_000_000,
        "mesh_slot_bytes": 45_000_000, "mesh_read_batches": 48,
        "mesh_home_bytes": 800_000})
    late = frame(2.0, (2.2, 0.6), dict(q65["done"]["cost_ledger"]["counts"]))
    tasks = [check, q65, late]
    # medians a task of the spans, a MEAN of the count (the plans of the
    # mix differ), one ratio of sums for the fill
    assert read("exchange.gang_wait_ms", tasks) == pytest.approx(900.0)
    assert read("exchange.read_ms", tasks) == pytest.approx(2400.0)
    assert read("exchange.read_batches", tasks) == pytest.approx(112 / 3)
    assert read("exchange.slot_fill", tasks) == pytest.approx(2.5 / 105)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_none_or_a_number_where_something_is_missing(name):
    # the parent's frames: no exchange_s split, no read-side counts; the
    # read operator's span and the two byte counts it has had since PR 28
    parent = [frame(None, (1.2, 0.3), {"mesh_rounds": 3, "mesh_bytes": 9_000,
                                       "mesh_slot_bytes": 900_000})] * 3
    got = read(name, parent)
    assert (got is not None) == (name in ON_THE_PARENT), got
    if name == "exchange.read_ms":
        assert got == pytest.approx(1500.0)
    if name == "exchange.slot_fill":
        assert got == pytest.approx(0.01)
    # a one-chip frame: the counts are there and read 0, no slot to fill
    single = [frame(0.0, None, {"mesh_bytes": 0, "mesh_slot_bytes": 0,
                                "mesh_read_batches": 0})]
    got = read(name, single)
    assert got == {"exchange.gang_wait_ms": 0.0, "exchange.read_ms": None,
                   "exchange.read_batches": 0.0,
                   "exchange.slot_fill": None}[name]
    # no task at all, a task with no frame, a frame with no ledger, a
    # version-1 ledger, fields of the wrong kind
    for tasks in ([], [{"done": None}], [{"done": {}}],
                  [{"done": {"cost_ledger": {"version": 1}}}],
                  [{"done": {"cost_ledger": {
                      "exchange_s": "?", "ops_s": {"shuffle_exchange_read": 3},
                      "counts": {"mesh_bytes": None,
                                 "mesh_slot_bytes": True}}}}]):
        assert read(name, tasks) is None


def test_the_cell_rehearsed_traced_on_four_virtual_devices(tmp_path):
    from harness.cell import Cell
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    tasks_out = str(tmp_path / "tasks.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "8",
         "--trace", "1", "--scale", "0.02", "--tasks-out", tasks_out],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 4
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 4
    assert res["compared"]["exact_mismatches"]["value"] == 0
    assert res["compared"]["max_double_rel"]["value"] <= 1e-7
    want = set(Cell(CELL).per_layer())
    assert set(res["metrics"]) == want and set(NEW) <= want
    got = {k: res["metrics"][k]["value"] for k in NEW}
    assert got["exchange.gang_wait_ms"] > 0 and got["exchange.read_ms"] > 0
    # 16 slices an exchange at most: one a (partition, source), one round
    assert 0 < got["exchange.read_batches"] <= 48
    assert 0 < got["exchange.slot_fill"] < 1
    # task for task: three exchanges on the all_to_all in a q65 / q65m
    # stage, one in a check stage, none demoted; the split sums to the layer
    import run
    with open(tasks_out) as f:
        frames = json.load(f)
    assert len(frames) >= 16 + 4        # the warm-up's 16 and the window's
    for t in frames:
        led = t["done"]["cost_ledger"]
        n = 3 if t["plan"] in ("q65", "q65m") else 1
        assert t["ok"] and run.leaf_sum(
            t["done"], "exchange_route_all_to_all") == n
        assert led["counts"]["mesh_rounds"] == n
        assert 0 < led["counts"]["mesh_read_batches"] <= 16 * n
        assert led["counts"]["mesh_home_bytes"] > 0
        assert not run.nonzero_leaves(t["done"], run.FAULT_NEEDLES)
        assert sum(led["exchange_s"].values()) == pytest.approx(
            led["layers_s"]["exchange"], abs=1e-5)


def test_float32_sums_turn_correct_false_at_this_cells_stage_size(sf1):
    """The control, at the cell's own size: a stage is 4 splits of 65,536
    rows. The check plans' answers are the wide sums after they crossed
    the chips; with every sum carried in float32 each check stage comes
    out wrong, by the double limit (q65sa) or by whole cents (q65sam),
    and the run's `correct` is false; carried in double, the reference
    against itself passes. The one-chip cells' control, over this cell's
    split groups (CPU, SF1: q65sa `max_double_rel` 6.8e-7 - 9.1e-7,
    q65sam 20 - 23 differing cells a stage)."""
    from harness import cell as cellmod
    cell = cellmod.Cell(CELL)
    n_rows, split = cell.config["rows"]["store_sales"], cell.config["split_rows"]
    groups = cellmod.split_groups(
        [(None, lo, min(split, n_rows - lo))
         for lo in range(0, n_rows, split)], cell.config["splits_per_task"])
    assert len(groups) == 11 and groups[0][3] == 262_144 \
        and groups[-1][3] == 258_560
    one_chip.test_float32_sums_turn_correct_false(CELL, sf1)

"""The 4-chip cell `tpcds_sf1_mesh2x2.price_bands` (PR 48), taken from
BENCHMARK.json itself: TPC-DS q28 at its published text as 4-partition
stages from four closed-loop clients, under its own configuration
`tpcds_sf1_mesh2x2_q28`. Rehearsed traced on four virtual CPU devices at
scale 0.02 (counts and correctness only): `correct`, the four readers
PR 48 adds present in the line, every stage's six hash exchanges on the
`all_to_all` route and its six gathers on the host route. And the
controls at this cell's stage size (SF1, 4 splits of 65,536 rows, CPU):
PR 44's two — the average summed and divided in float32, the distinct
count over float16 prices — and the one this deployment adds: the
distinct count taken a partition and summed, which is what a stage
without the exchange would answer. Each has to read `correct` false
through `compare`; the exact reference against itself passes."""

import json
import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

from conftest import ROOT

CELL = "tpcds_sf1_mesh2x2.price_bands"
ONE_CHIP = "tpcds_sf1_q28.price_bands"
NEW = ("exchange.read_rows_per_batch", "exchange.home_bytes",
       "exchange.path_share", "exchange.combine_ratio")
BANDS = range(1, 7)


def test_benchmark_json_lists_the_cell_and_its_four_metrics():
    from harness.cell import Cell
    cell = Cell(CELL)
    assert cell.chips == 4 and cell.config["name"] == "tpcds_sf1_mesh2x2_q28"
    assert cell.plans == ["q28"] == Cell(ONE_CHIP).plans
    assert cell.traffic == Cell(ONE_CHIP).traffic    # the file that was there
    assert cell.traffic["clients"] == 4 == \
        cell.config["engine"]["auron.sched.max_concurrent"]
    assert cell.traffic["task_timeout_s"] == 180
    assert cell.config["require_counters"] == {
        "exchange_route_all_to_all": 6, "agg_distinct_groups": 1,
        "agg_groups": 1}
    assert len(cell.config["guarantees"]) == 7
    assert set(cell.end_to_end()) == {"fact_rows_per_s", "setup_s"}
    layer = cell.per_layer()
    for name in NEW:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "fact_rows_per_s"
        assert layer[name]["layer"] == "exchange"
        assert name not in Cell(ONE_CHIP).per_layer()
    # the readers that list no cell read here by themselves; those that
    # list their cells by name stay as they are (M9 (ii))
    assert {"ops.program_calls", "agg.program_calls", "ops.batch_shrinks",
            "ops.row_syncs", "serve.cpu_share"} <= set(layer)
    assert not {"exchange.read_ms", "exchange.read_batches",
                "exchange.slot_fill", "exchange.gang_wait_ms",
                "agg.regroup_ms", "agg.distinct_groups"} & set(layer)
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config["name"])
    assert entry["source"] == cell.config["source"]
    assert entry["reduced"] == list(cell.config["reduced"]) == ["split_rows"]
    four = [w for w in cell.bench["workloads"] if w["chips"] == 4]
    assert 2 * len(four) <= len(cell.bench["workloads"])


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
    assert res["compared"]["max_double_rel"]["value"] == 0.0
    want = set(Cell(CELL).per_layer())
    assert set(res["metrics"]) == want and set(NEW) <= want
    got = {k: res["metrics"][k]["value"] for k in NEW}
    # a slice holds a handful of rows at this scale, ~190 at SF1
    assert got["exchange.read_rows_per_batch"] >= 1
    assert got["exchange.home_bytes"] > 0
    assert 0 < got["exchange.path_share"] < 1
    # the key is the price itself: the combine merges next to nothing
    assert 0.9 < got["exchange.combine_ratio"] <= 1
    assert res["metrics"]["ops.batch_shrinks"]["value"] == 0
    # stage for stage: six hash exchanges on the all_to_all and six
    # gathers on the host route, none demoted, one round an exchange
    import run
    with open(tasks_out) as f:
        frames = json.load(f)
    assert len(frames) >= 4 + 4         # the warm-up's 4 and the window's
    for t in frames:
        counts = t["done"]["cost_ledger"]["counts"]
        assert t["ok"] and t["plan"] == "q28"
        assert run.leaf_sum(t["done"], "exchange_route_all_to_all") == 6
        assert run.leaf_sum(t["done"], "exchange_route_device_buffer") == 6
        assert run.leaf_sum(t["done"], "split_folded") == 6
        assert counts["mesh_rounds"] == counts["mesh_gang_acquires"] == 6
        assert 0 < counts["mesh_read_batches"] <= 96
        assert counts["mesh_read_rows"] >= counts["mesh_read_batches"]
        assert counts["agg_hash_batches"] == counts["mesh_read_batches"]
        assert counts["agg_sort_fallbacks"] == 0
        assert not run.nonzero_leaves(t["done"], run.FAULT_NEEDLES)


@pytest.fixture(scope="module")
def sf1():
    from harness import datagen
    return datagen.generate(2_147_483_777, 1.0, ("store_sales",))


def _with_counts(table, counts: dict):
    for name, value in counts.items():
        table = table.set_column(table.column_names.index(name), name,
                                 pa.array([value], pa.int64()))
    return table


def test_the_controls_turn_correct_false_at_this_cells_stage_size(sf1):
    """Every stage of one seed: the 11 groups of 4 splits of 65,536 rows
    (the last split shorter). CPU, SF1; the differing cells counted."""
    from harness import cell as cellmod, compare
    cell = cellmod.Cell(CELL)
    n_rows = cell.config["rows"]["store_sales"]
    split = cell.config["split_rows"]
    groups = cellmod.split_groups(
        [(None, lo, min(split, n_rows - lo))
         for lo in range(0, n_rows, split)], cell.config["splits_per_task"])
    assert len(groups) == 11 and groups[0][3] == 262_144 \
        and groups[-1][3] == 258_560
    oracle = cellmod.load_module("plans", "q28").oracle
    fact = sf1["store_sales"]
    assert fact.num_rows == n_rows
    averaged_cells, repeats, delivered, passed = 0, [], [], []

    def verdict(answer, exact):
        return compare.decide([compare.compare_tables(answer, exact)])

    for _g, _files, lo, rows in groups:
        view = {"store_sales": fact.slice(lo, rows)}
        exact = oracle(view)
        row = exact.to_pylist()[0]
        assert exact.num_rows == 1 and exact.num_columns == 18
        assert verdict(oracle(view), exact)["correct"] is True
        # PR 44's controls, at this stage's size
        averaged = verdict(oracle(view, float32_average=True), exact)
        assert averaged["correct"] is False
        assert averaged["exact_mismatches"] >= 3
        averaged_cells += averaged["exact_mismatches"]
        assert verdict(oracle(view, distinct_dtype=np.float32),
                       exact)["correct"] is True
        halved = verdict(oracle(view, distinct_dtype=np.float16), exact)
        assert halved["correct"] is False
        assert halved["exact_mismatches"] == 6
        # this deployment's control: each partition counts its own split's
        # prices and the stage sums the four counts
        local = [oracle({"store_sales": fact.slice(lo + s, min(split,
                                                               rows - s))})
                 .to_pylist()[0] for s in range(0, rows, split)]
        assert len(local) == 4
        summed = {f"B{i}_CNTD": sum(p[f"B{i}_CNTD"] for p in local)
                  for i in BANDS}
        for i in BANDS:
            # ~790 rows a band and partition pass the filter
            assert 2_500 < row[f"B{i}_CNT"] < 3_900
            assert sum(p[f"B{i}_CNT"] for p in local) == row[f"B{i}_CNT"]
            # every band of every stage holds a price in two partitions
            assert summed[f"B{i}_CNTD"] > row[f"B{i}_CNTD"]
        no_exchange = verdict(_with_counts(exact, summed), exact)
        assert no_exchange["correct"] is False
        assert no_exchange["exact_mismatches"] == 6
        repeats.append(sum(summed[f"B{i}_CNTD"] - row[f"B{i}_CNTD"]
                           for i in BANDS))
        delivered.append(sum(summed.values()))
        passed.append(sum(row[f"B{i}_CNT"] for i in BANDS))
    # nearly every one of the 11 x 6 averages misses Spark's digits
    assert averaged_cells >= 11 * 5
    # what a stage's six exchanges deliver (mesh_read_rows) and what its
    # combines merge: the key is the price, so one row in twenty (the
    # same price twice in one partition's 65,536 rows)
    assert 17_000 < min(delivered) and max(delivered) < 19_000
    assert all(0.93 < d / p < 0.97 for d, p in zip(delivered, passed))
    # one price in seven of a stage sits in more than one partition:
    # what the exchange is for
    assert all(0.10 < r / (d - r) < 0.20 for r, d in zip(repeats, delivered))

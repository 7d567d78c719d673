"""The cell of PR 44: `tpcds_sf1_q28.price_bands`.

TPC-DS q28 at its published text (`plans/q28.py`), four closed-loop
clients. Rehearsed through the one command on the CPU at scale 0.02
(counts and correctness only); its two readers on hand-written frames;
the configuration held to `tpcds_sf1_fullsplit`'s numbers but for the
split it cut; and **the control at SF1**: against the exact reference,
the reference with the average summed and divided in float32 — the
precision below the decimal(7,2) the configuration states — reads
`correct` false in every task of one seed (22 splits of 131,072 rows:
five or six of a task's six averages differ, of its 18 cells), and
passes when exact. The DISTINCT count is not moved by float32, which
still tells two prices under $1,310.72 a cent apart (list prices stay
under $250): taken over float16 prices all six counts of a task differ.
Both are counted here, so that the limit of the comparison (exact: 0
differing cells) is shown to stand under the control.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT

CELL = "tpcds_sf1_q28.price_bands"
NEW = ("agg.distinct_groups", "agg.regroup_ms")


def _run(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", CELL, "--seed", "2147483999", "--seconds", "6",
           "--trace", str(trace), "--scale", "0.02"]
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_price_bands_cell_rehearses():
    from harness.cell import Cell
    cell = Cell(CELL)
    assert cell.plans == ["q28"] and cell.chips == 1
    assert cell.traffic["clients"] == 4 and cell.traffic["loop"] == "closed"
    assert set(cell.end_to_end()) == {"fact_rows_per_s", "setup_s"}
    layer = cell.per_layer()
    assert set(NEW) <= set(layer)
    # the older aggregation readers list their cells by name
    assert not {"agg.hash_batches", "window.rows",
                "exprs.decimal_div_rows"} & set(layer)
    assert cell.config["require_counters"] == {"agg_distinct_groups": 1,
                                               "agg_groups": 1}
    res = _run(trace=0)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 8 and res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"fact_rows_per_s", "setup_s"}
    assert res["compared"]["exact_mismatches"]["value"] == 0
    assert res["compared"]["max_double_rel"]["value"] == 0.0
    traced = _run(trace=1)
    assert traced["correct"] is True and traced["failed"] == 0
    got = {k: traced["metrics"][k]["value"] for k in NEW}
    assert all(v > 0 for v in got.values()), got
    assert traced["metrics"]["compile.xla_in_window"]["value"] == 0


def test_the_configuration_is_fullsplits_numbers_but_for_the_split():
    """ISSUE 44's stated alternative: `tpcds_sf1`'s split, because a
    window at 720,000 rows completed 16 tasks (my chip runs, PR 44)."""
    from harness.cell import load_json
    base = load_json(os.path.join(ROOT, "benchmark", "configs",
                                  "tpcds_sf1_fullsplit.json"))
    small = load_json(os.path.join(ROOT, "benchmark", "configs",
                                   "tpcds_sf1.json"))
    mine = load_json(os.path.join(ROOT, "benchmark", "configs",
                                  "tpcds_sf1_q28.json"))
    same = ("benchmark", "scale_factor", "chips", "splits_per_task",
            "scan_partitions", "warmup_groups", "engine")
    assert all(mine[k] == base[k] for k in same)
    assert mine["split_rows"] == small["split_rows"] == 131_072
    assert list(mine["reduced"]) == ["split_rows"]
    assert mine["rows"] == {"store_sales": base["rows"]["store_sales"]}
    assert mine["guarantees"][:4] == base["guarantees"]
    assert len(mine["guarantees"]) == 5 and mine["architecture"] is None
    assert "720,000" in mine["split_size"] and "16 tasks" in mine["split_size"]
    assert len(mine["source"]) <= 200 and mine["source"] != base["source"]
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == mine["name"])
    assert entry["source"] == mine["source"]
    assert entry["reduced"] == ["split_rows"]
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == mine["name"]] == [CELL]
    assert len(bench["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 3


def _frame(ops, counts):
    return {"done": {"cost_ledger": {"wall_s": 1.0, "ops_s": ops,
                                     "counts": counts}}}


def test_the_new_readers_on_hand_written_frames():
    from harness.cell import load_module
    tasks = [
        _frame({"agg": {"host_s": 0.300, "device_wait_s": 0.900}},
               {"agg_distinct_groups": 39_000}),
        _frame({"agg": {"host_s": 0.200, "device_wait_s": 0.700}},
               {"agg_distinct_groups": 39_400}),
        _frame({"agg": {"host_s": 0.400}}, {"agg_distinct_groups": 38_900}),
        # a plan with an aggregation and no DISTINCT is not a regroup
        _frame({"agg": {"host_s": 9.0, "device_wait_s": 9.0}},
               {"agg_distinct_groups": 0}),
    ]
    ctx = {"tasks": tasks}
    read = {n: load_module("layer_metrics", n).read for n in NEW}
    assert read["agg.distinct_groups"](ctx) == pytest.approx(29_325.0)
    assert read["agg.regroup_ms"](ctx) == pytest.approx(900.0)
    # a program from before the counter and the spans: nothing, no raise
    old = {"tasks": [{"done": {"cost_ledger": {"counts": {}, "ops_s": {}}}},
                     _frame({"agg": {"host_s": 1.0}}, {}), {"done": None}]}
    for name in NEW:
        assert read[name](old) is None


@pytest.fixture(scope="module")
def sf1():
    from harness import datagen
    return datagen.generate(2_147_483_777, 1.0, ("store_sales",))


def test_the_controls_turn_correct_false_at_sf1(sf1):
    """The control at the cell's own size: every task of one seed (the
    22 splits of 131,072 rows, the last one shorter), the differing cells
    counted."""
    from harness import compare
    from harness.cell import load_module
    oracle = load_module("plans", "q28").oracle
    fact = sf1["store_sales"]
    averaged_cells = 0
    for lo in range(0, fact.num_rows, 131_072):
        view = {"store_sales": fact.slice(lo, 131_072)}
        exact = oracle(view)
        row = exact.to_pylist()[0]
        assert exact.num_rows == 1 and exact.num_columns == 18
        for i in range(1, 7):
            # ~1.2 % of a split a band, most of them a price of their own
            assert 1_300 < row[f"B{i}_CNT"] < 1_900
            assert 1_000 < row[f"B{i}_CNTD"] <= row[f"B{i}_CNT"]
        assert compare.decide([compare.compare_tables(oracle(view), exact)]
                              )["correct"] is True
        averaged = compare.decide([compare.compare_tables(
            oracle(view, float32_average=True), exact)])
        assert averaged["correct"] is False
        assert averaged["exact_mismatches"] >= 3
        averaged_cells += averaged["exact_mismatches"]
        # float32 resolves a cent under $1,310.72: the counts stand
        assert compare.decide([compare.compare_tables(
            oracle(view, distinct_dtype=np.float32), exact)]
        )["correct"] is True
        halved = compare.decide([compare.compare_tables(
            oracle(view, distinct_dtype=np.float16), exact)])
        assert halved["correct"] is False
        assert halved["exact_mismatches"] == 6
    # nearly every one of the 22 x 6 averages misses Spark's digits
    assert averaged_cells >= 22 * 5

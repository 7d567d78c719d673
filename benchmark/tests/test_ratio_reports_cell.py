"""The two cells of PR 42.

`tpcds_sf1_fullsplit.ratio_reports`: TPC-DS q36, q53, q59 and q98 at
their published text. Rehearsed through the one command on the CPU at
scale 0.02 (counts and correctness only), its four readers on
hand-written frames, and **the control at SF1**: the reference with every
division carried in double and rounded to the result scale must read
`correct` false against the exact reference in every q36, q59 and q98
task (decimal cells compare exactly in `harness/compare.py`), and pass
when exact. q53's answer carries no ratio (its division is in the
filter), so its control is the required counter: a task without
`decimal_div_rows` fails.

`tpcds_sf1_mesh2x2.star_join`: entries only (configuration and traffic
were there); its reader `exchange.gang_wait_share` on hand-written
frames. The cell's rehearsal on four virtual devices is
`test_rehearsal_mesh2x2.py`'s, with another traffic file.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

CELL = "tpcds_sf1_fullsplit.ratio_reports"
MESH_CELL = "tpcds_sf1_mesh2x2.star_join"
NEW = ("window.host_ms", "window.rows", "expand.rows_out",
       "exprs.decimal_div_rows")


def _run(workload, trace, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", "2147483999", "--seconds", "6",
           "--trace", str(trace), "--scale", "0.02"]
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_ratio_reports_cell_rehearses():
    from harness.cell import Cell
    cell = Cell(CELL)
    assert cell.plans == ["q36", "q53", "q59", "q98"] and cell.chips == 1
    assert set(cell.end_to_end()) == {"fact_rows_per_s", "setup_s"}
    assert set(NEW) <= set(cell.per_layer())
    assert "exchange.gang_wait_share" not in cell.per_layer()
    assert cell.config["require_counters"] == {"decimal_div_rows": 1,
                                               "agg_groups": 1}
    res = _run(CELL, trace=0)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 8 and res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"fact_rows_per_s", "setup_s"}
    assert res["compared"]["exact_mismatches"]["value"] == 0
    assert res["compared"]["max_double_rel"]["value"] == 0.0
    traced = _run(CELL, trace=1)
    assert traced["correct"] is True and traced["failed"] == 0
    got = {k: traced["metrics"][k]["value"] for k in NEW}
    assert all(v > 0 for v in got.values()), got
    assert traced["metrics"]["compile.xla_in_window"]["value"] == 0


def test_the_configuration_differs_from_fullsplit_only_as_said():
    from harness.cell import load_json
    base = load_json(os.path.join(ROOT, "benchmark", "configs",
                                  "tpcds_sf1_fullsplit.json"))
    mine = load_json(os.path.join(ROOT, "benchmark", "configs",
                                  "tpcds_sf1_fullsplit_reports.json"))
    same = ("benchmark", "scale_factor", "chips", "rows", "date_dim_rows",
            "split_rows", "splits_per_task", "scan_partitions",
            "warmup_groups", "engine", "key_skew", "reduced")
    assert all(mine[k] == base[k] for k in same)
    assert mine["guarantees"][:4] == base["guarantees"]
    assert len(mine["guarantees"]) == 5 and mine["architecture"] is None
    assert len(mine["source"]) <= 200 and mine["source"] != base["source"]
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == mine["name"])
    assert entry["source"] == mine["source"]
    assert entry["reduced"] == list(mine["reduced"])


def _frames(ops, counts, exchange=None, wall=1.0):
    return {"done": {"cost_ledger": {
        "wall_s": wall, "ops_s": ops, "counts": counts,
        "exchange_s": exchange or {}}}}


def test_the_new_readers_on_hand_written_frames():
    from harness.cell import load_module
    tasks = [
        _frames({"window": {"host_s": 0.004, "device_wait_s": 0.006}},
                {"window_rows": 171, "expand_rows_out": 432_000,
                 "decimal_div_rows": 171}),
        _frames({"window": {"host_s": 0.002, "device_wait_s": 0.001}},
                {"window_rows": 40, "expand_rows_out": 0,
                 "decimal_div_rows": 30}),
        _frames({"agg": {"host_s": 0.1, "device_wait_s": 0.5}},
                {"window_rows": 0, "expand_rows_out": 0,
                 "decimal_div_rows": 700}),
        _frames({"window": {"host_s": 0.003, "device_wait_s": 0.003}},
                {"window_rows": 523, "expand_rows_out": 0,
                 "decimal_div_rows": 523}),
    ]
    ctx = {"tasks": tasks}
    want = {"window.host_ms": 6.0, "window.rows": 183.5,
            "expand.rows_out": 108_000.0, "exprs.decimal_div_rows": 356.0}
    for name, value in want.items():
        assert load_module("layer_metrics", name).read(ctx) \
            == pytest.approx(value), name
    # a program from before the spans and counters: nothing, no raise
    old = {"tasks": [{"done": {"cost_ledger": {"counts": {}, "ops_s": {}}}},
                     {"done": None}]}
    for name in NEW + ("exchange.gang_wait_share",):
        assert load_module("layer_metrics", name).read(old) is None


def test_the_mesh_star_join_cell_and_its_reader():
    from harness.cell import Cell, load_module
    cell = Cell(MESH_CELL)
    assert cell.plans == ["q3", "q42", "q52", "q55"] and cell.chips == 4
    assert cell.traffic["clients"] == 4
    assert cell.config["require_counters"] == {
        "exchange_route_all_to_all": 1}
    assert set(cell.end_to_end()) == {"fact_rows_per_s", "setup_s"}
    layer = cell.per_layer()
    assert "exchange.gang_wait_share" in layer
    assert not set(NEW) & set(layer)
    ctx = {"tasks": [
        _frames({}, {}, {"gang_wait": 0.5, "mesh_round": 0.1}, wall=1.0),
        _frames({}, {}, {"gang_wait": 0.1, "mesh_round": 0.1}, wall=0.5)]}
    assert load_module("layer_metrics", "exchange.gang_wait_share") \
        .read(ctx) == pytest.approx(0.4)


@pytest.fixture(scope="module")
def sf1():
    from harness import datagen
    return datagen.generate(2_147_483_777, 1.0)


@pytest.mark.parametrize("plan", ("q36", "q59", "q98"))
def test_double_division_turns_correct_false_at_sf1(plan, sf1):
    """The control, at the cell's own size: every task of the plan (the
    four splits of 720,000 rows)."""
    from harness import compare
    from harness.cell import load_module
    oracle = load_module("plans", plan).oracle
    for group in range(4):
        view = dict(sf1)
        view["store_sales"] = sf1["store_sales"].slice(group * 720_000,
                                                       720_000)
        exact = oracle(view)
        assert exact.num_rows >= 10
        control = compare.compare_tables(
            oracle(view, double_division=True), exact)
        verdict = compare.decide([control])
        assert verdict["correct"] is False
        assert verdict["exact_mismatches"] >= exact.num_rows // 2
        assert compare.decide([compare.compare_tables(oracle(view), exact)]
                              )["correct"] is True


def test_every_q53_task_answers_ten_rows_at_sf1(sf1):
    from harness.cell import load_module
    oracle = load_module("plans", "q53").oracle
    for group in range(4):
        view = dict(sf1)
        view["store_sales"] = sf1["store_sales"].slice(group * 720_000,
                                                       720_000)
        assert oracle(view).num_rows >= 10

"""The two wide-aggregation cells (PR 34): `tpcds_sf1_fullsplit.wide_agg`
and `tpcds_sf1.wide_agg`, TPC-DS q65 in its two forms, each with its
check plan. Rehearsed through the one command on the CPU at scale 0.05
(counts and correctness only), their five `agg.*` readers on
hand-written frames, the new configuration held to differing from
`tpcds_sf1.json` only as said, and the two ways `correct` and `failed`
have to be able to come out wrong here: the control (the reference in
the program's place with the (store, item) sums carried in float32, at
SF1, through `compare`) and a DONE frame that says the hash-table
aggregation was demoted to the sort path."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT

CELLS = ("tpcds_sf1.wide_agg", "tpcds_sf1_fullsplit.wide_agg")
#: the readers this PR adds, with what the hand-written frames below give
AGG = {"agg.host_ms": 450.0, "agg.hash_batches": 11.0,
       "agg.sort_batches": 12.0, "agg.state_grows": 7.0,
       "agg.sort_fallbacks": 1}


def _run(workload, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", "2147483999", "--seconds", "4",
           "--trace", str(trace), "--scale", "0.05"]
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_a_wide_agg_cell_rehearses(workload):
    from harness.cell import Cell
    from test_contract import IN_EVERY_CELL
    cell = Cell(workload)
    assert cell.plans == ["q65", "q65m", "q65sa", "q65sam"]
    assert cell.chips == 1
    assert set(cell.end_to_end()) == {"fact_rows_per_s", "setup_s"}
    assert set(cell.per_layer()) == IN_EVERY_CELL | set(AGG)
    assert len(IN_EVERY_CELL) == 21 and len(AGG) == 5
    res = _run(workload, trace=0)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 8 and res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"fact_rows_per_s", "setup_s"}
    assert res["compared"]["exact_mismatches"]["value"] == 0
    assert res["compared"]["max_double_rel"]["value"] <= 1e-7
    traced = _run(workload, trace=1)
    assert traced["correct"] is True and traced["failed"] == 0
    assert set(traced["metrics"]) == IN_EVERY_CELL | set(AGG)
    got = {k: traced["metrics"][k]["value"] for k in AGG}
    # the plans in equal shares: the decimal sums take the hash table,
    # the double sums the sort path (and q65m's 12-store average too)
    assert got["agg.hash_batches"] > 0 and got["agg.sort_batches"] > 0
    assert got["agg.sort_fallbacks"] == 0 and got["agg.host_ms"] > 0


@pytest.fixture(scope="module")
def sf1():
    from harness import datagen
    seed = 2_147_483_777
    return seed, datagen.generate(seed, 1.0)


@pytest.mark.parametrize("workload", CELLS)
def test_float32_sums_turn_correct_false(workload, sf1):
    """The control of these cells, at their own size. q65 and q65m answer
    with sums of one or two sales, which float32 carries to 6e-8: their
    answers pass whatever the accumulator (so `control_full_size.py`'s
    float32 money cannot fail on them). The check plans answer with the
    aggregate's many-addend groups: with every sum carried in float32
    each of their tasks comes out wrong, by the double limit (q65sa) or
    by whole cents (q65sam), and the run's `correct` is false."""
    from harness import cell as cellmod
    from harness import compare
    seed, arrow = sf1
    cell = cellmod.Cell(workload)
    plans = {p: cellmod.load_module("plans", p) for p in cell.plans}
    split = cell.config["split_rows"]
    n_rows = arrow["store_sales"].num_rows
    groups = cellmod.split_groups(
        [(None, lo, min(split, n_rows - lo))
         for lo in range(0, n_rows, split)], cell.config["splits_per_task"])
    sound, control = [], {p: [] for p in cell.plans}
    for plan, g in cellmod.task_order(cell.plans, len(groups), seed)[:16]:
        _g, _files, lo, rows = groups[g]
        view = dict(arrow, store_sales=arrow["store_sales"].slice(lo, rows))
        want = plans[plan].oracle(view)
        sound.append(compare.compare_tables(plans[plan].oracle(view), want))
        low = plans[plan].oracle(view, carry=np.float32) \
            if plan in ("q65sa", "q65sam") \
            else compare.float32_answer(want, want)
        control[plan].append(compare.compare_tables(low, want))
    assert compare.decide(sound)["correct"]
    verdict = compare.decide([r for rs in control.values() for r in rs])
    assert verdict["correct"] is False and verdict["tasks_wrong"] == 8
    assert all(compare.answer_ok(r) for p in ("q65", "q65m")
               for r in control[p]), "q65's own answer cannot tell"
    for r in control["q65sa"]:
        assert r["max_double_rel"] > 3 * compare.DOUBLE_REL_TOL, r
        assert r["exact_mismatches"] == 0 and not r["error"]
    for r in control["q65sam"]:
        assert r["exact_mismatches"] >= 3 and not r["error"], r


def test_a_demoted_aggregation_fails_the_task():
    """The fourth guarantee of `tpcds_sf1_fullsplit` is held by the rule
    `run.py` has for every cell: the program counts a hash-table overflow
    that latched the sort path as `agg_demoted_to_sort` too, and a DONE
    frame with a nonzero "demot" leaf is a failed task."""
    import run

    class Client:
        def __init__(self, fallbacks):
            self.counts = {"agg_groups": 9, "agg_sort_fallbacks": fallbacks,
                           "agg_demoted_to_sort": fallbacks}

        def execute(self, blob):
            return None, {"cost_ledger": {"outcome": "ok", "version": 2,
                                          "counts": self.counts}}

    ok = run.run_task(Client(0), b"", require={"agg_groups": 1})
    assert ok["ok"] and not ok["error"]
    bad = run.run_task(Client(1), b"", require={"agg_groups": 1})
    assert not bad["ok"] and "agg_demoted_to_sort" in bad["error"]
    from auron_tpu.obs import trace
    assert "agg_demoted_to_sort" in trace.COUNT_KEYS


def _frame(counts=None, agg_host_s=None):
    ledger = {"version": 2, "wall_s": 1.0, "counts": counts or {},
              "ops_s": {} if agg_host_s is None else
              {"agg": {"host_s": agg_host_s, "device_wait_s": 0.0,
                       "batches": 3}}}
    return {"plan": "q65", "group": 0, "rows": 1, "t_submit": 0.0,
            "t_done": 1.0, "done": {"cost_ledger": ledger}}


def _read(name, tasks):
    from harness.cell import load_module
    return load_module("layer_metrics", name).read({"tasks": tasks})


def test_the_agg_readers_on_hand_written_frames():
    q65 = _frame({"agg_hash_batches": 0, "agg_sort_batches": 23,
                  "agg_groups": 44_190, "agg_state_grows": 6,
                  "agg_sort_fallbacks": 0}, agg_host_s=0.5)
    q65m = _frame({"agg_hash_batches": 22, "agg_sort_batches": 1,
                   "agg_groups": 44_190, "agg_state_grows": 8,
                   "agg_sort_fallbacks": 1}, agg_host_s=0.4)
    tasks = [q65, q65m]
    # a MEAN a task where the plans differ, the SUM for the fall-backs,
    # the median for the span
    for name, want in AGG.items():
        assert _read(name, tasks) == pytest.approx(want), name
    # a frame from before the counters (the parent's) gives nothing, and
    # the metric is left out of the line; the span it has had since PR 25
    old = [_frame({"program_calls": 99}, agg_host_s=0.4)]
    for name in AGG:
        got = _read(name, old)
        assert (got == pytest.approx(400.0)) if name == "agg.host_ms" \
            else got is None, name
    assert _read("agg.host_ms", [_frame()]) is None
    assert _read("agg.sort_fallbacks", [{"done": None}]) is None


def test_the_new_configuration_differs_only_as_said():
    def load(name):
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            return json.load(f)
    base, new = load("tpcds_sf1"), load("tpcds_sf1_fullsplit")
    differ = {k for k in set(base) | set(new) if base.get(k) != new.get(k)}
    assert differ == {"name", "source", "split_rows", "warmup_groups",
                      "require_counters", "guarantees", "reduced", "assumed"}
    assert new["split_rows"] == 720_000 and new["warmup_groups"] == 4
    assert new["rows"]["store_sales"] == 4 * new["split_rows"]
    assert new["require_counters"] == {"agg_groups": 1}
    assert new["engine"] == {} and new["chips"] == 1
    assert new["guarantees"][:3] == base["guarantees"]
    assert "agg_sort_fallbacks" in new["guarantees"][3]
    assert "agg_demoted_to_sort" in new["guarantees"][3]
    assert set(new["reduced"]) == {"date_dim_rows"}
    assert set(base["assumed"]) < set(new["assumed"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == new["name"])
    assert entry["source"] == new["source"] and len(new["source"]) <= 200
    assert entry["reduced"] == ["date_dim_rows"]
    for metric in bench["per_layer"]:
        if metric["name"] in AGG:
            assert sorted(metric["workloads"]) == sorted(CELLS)
            assert metric["layer"] == "operators"
            assert metric["moves"] == "fact_rows_per_s"

"""The four ratio reports of the benchmark (PR 42; tier-1, on the CPU:
answers, types and counts, no timings).

``benchmark/plans/{q36,q53,q59,q98}.py`` — TPC-DS q36, q53, q59 and q98
at their published text — run as served tasks over one ``store_sales``
split of three scan batches, each answer against the plan's own oracle
(Python's ``decimal`` for every division, average and window). Held here:
the answers, digit for digit; the ratio columns' Arrow types, Spark's;
the double-division control failing where the answer carries a ratio;
q59's week-by-store aggregate planned once (``subplan_reuse_hits`` 1);
and the four counts this PR adds to the DONE frame, against what the
oracle's own rows say they must be.
"""

import os
import sys

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from auron_tpu.frontend import Session

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
PLANS = ("q36", "q53", "q59", "q98")
SPLIT_ROWS, BATCH_ROWS = 36_000, 12_288
#: the ratio columns of each answer and Spark's type of them
RATIOS = {
    "q36": {"gross_margin": pa.decimal128(37, 20)},
    "q53": {"avg_quarterly_sales": pa.decimal128(21, 6),
            "sum_sales": pa.decimal128(17, 2)},
    "q59": {f"r_{d}": pa.decimal128(37, 20)
            for d in ("sun", "mon", "tue", "wed", "thu", "fri", "sat")},
    "q98": {"revenueratio": pa.decimal128(38, 17),
            "itemrevenue": pa.decimal128(17, 2)},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server in this process, the four plans over one split (scale
    0.05: 900 items, 12 stores, 144,000 fact rows of which the split is
    the first 36,000)."""
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    from harness import cell, compare, datagen

    from auron_tpu.runtime.serving import AuronClient, AuronServer
    root = str(tmp_path_factory.mktemp("tpcds"))
    arrow = datagen.generate(seed=2_147_483_659, scale=0.05)
    split = arrow["store_sales"].slice(0, SPLIT_ROWS)
    path = os.path.join(root, "store_sales_0000.parquet")
    pq.write_table(split, path, row_group_size=BATCH_ROWS)
    dims = {name: datagen.write_whole(root, name, arrow[name])
            for name in arrow if name != "store_sales"}
    task_rows = dict(arrow, store_sales=split)
    session = Session()
    modules = {p: cell.load_module("plans", p) for p in PLANS}
    tasks = {p: m.build(session, dims, [path], 1).task_bytes(0)
             for p, m in modules.items()}
    server = AuronServer()
    server.serve_background()
    host, port = server.address
    answers = {}

    def run(plan):
        if plan not in answers:
            table, metrics = AuronClient(
                host, port, timeout_s=600).execute(tasks[plan])
            answers[plan] = (table, metrics["cost_ledger"])
        return answers[plan]

    run.oracle = lambda plan, **kw: modules[plan].oracle(task_rows, **kw)
    run.compare = compare
    yield run
    server.shutdown()
    server.server_close()
    session.close()


@pytest.mark.parametrize("plan", PLANS)
def test_a_report_answers_as_its_oracle_digit_for_digit(plan, served):
    table, ledger = served(plan)
    want = served.oracle(plan)
    res = served.compare.compare_tables(table, want)
    assert served.compare.answer_ok(res), res["error"] or res["examples"]
    assert res["exact_mismatches"] == 0 and res["max_double_rel"] == 0.0
    assert table.num_rows == want.num_rows >= 10
    assert ledger["outcome"] == "ok"


@pytest.mark.parametrize("plan", PLANS)
def test_the_ratio_columns_are_sparks_types(plan, served):
    table, _ledger = served(plan)
    for name, typ in RATIOS[plan].items():
        assert table.schema.field(name).type == typ, name
    # no answer column is a double: money stays decimal scan to answer
    assert not any(pa.types.is_floating(f.type) for f in table.schema)


@pytest.mark.parametrize("plan", ("q36", "q59", "q98"))
def test_a_division_carried_in_double_fails_the_comparison(plan, served):
    """The control: the reference with every division in double, rounded
    to the result scale, against the exact reference."""
    exact = served.oracle(plan)
    control = served.oracle(plan, double_division=True)
    res = served.compare.compare_tables(control, exact)
    assert not served.compare.answer_ok(res)
    assert res["exact_mismatches"] >= exact.num_rows // 2
    # and the engine's answer stands on the exact side
    table, _ledger = served(plan)
    assert not served.compare.answer_ok(
        served.compare.compare_tables(table, control))


def test_q59_plans_its_week_by_store_aggregate_once(served):
    counts = {p: served(p)[1]["counts"] for p in PLANS}
    assert counts["q59"]["subplan_reuse_hits"] == 1
    assert [counts[p]["subplan_reuse_hits"]
            for p in ("q36", "q53", "q98")] == [0, 0, 0]


def test_the_done_frame_counts_window_expand_and_division(served):
    counts = {p: served(p)[1]["counts"] for p in PLANS}
    answers = {p: served(p)[0] for p in PLANS}
    # q36: every joined row leaves the expand three times, the margin is
    # divided once a rollup row, the rank sees every rollup row in its
    # (level, parent) partitions: 10 categories + the two upper levels
    q36 = counts["q36"]
    assert q36["expand_rows_out"] > 0 and q36["expand_rows_out"] % 3 == 0
    assert q36["window_rows"] == q36["decimal_div_rows"] == q36["agg_groups"]
    assert q36["window_rows"] >= answers["q36"].num_rows
    parents = len({r["i_category"] for r in answers["q36"].to_pylist()
                   if r["lochierarchy"] == 0})
    assert parents <= q36["window_partitions"] <= 12
    # q53: the average's window sees every (manufacturer, quarter) group,
    # a partition a manufacturer; the filter's division counts its
    # survivors, which are the answer (under the limit)
    q53 = counts["q53"]
    assert q53["window_rows"] == q53["agg_groups"]
    assert 1 <= q53["window_partitions"] <= q53["window_rows"]
    assert q53["decimal_div_rows"] == answers["q53"].num_rows
    # q59: no window, no expand; seven ratios a row of the hundred
    q59 = counts["q59"]
    assert (q59["window_rows"], q59["window_partitions"],
            q59["expand_rows_out"]) == (0, 0, 0)
    assert q59["decimal_div_rows"] == 7 * answers["q59"].num_rows
    # q98: one partition a class, one division a group, all of them back
    q98 = counts["q98"]
    assert q98["window_rows"] == q98["decimal_div_rows"] \
        == answers["q98"].num_rows
    assert q98["window_partitions"] == len(
        set(answers["q98"]["i_class"].to_pylist()))
    for plan in ("q53", "q59", "q98"):
        assert counts[plan]["expand_rows_out"] == 0

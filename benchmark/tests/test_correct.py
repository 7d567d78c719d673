"""`correct` has to be able to come out false.

1. The control: the reference in the program's place with money carried
   in float32 (the precision below the decimal(7,2) the configurations
   state) turns `correct` false, at a size a test run can hold, while the
   sound reference passes. `control_full_size.py` is the same at SF1.
2. The timed path broken underneath the harness: an answer altered where
   it arrives turns a whole run's `correct` false; the same run left
   alone says true.
"""

import argparse
import os
import sys

import pyarrow as pa
import pytest

from conftest import BENCH

sys.path.insert(0, os.path.join(BENCH, "tests"))


@pytest.fixture(scope="module")
def sf1():
    # the cells' own size: five seconds to make, and under it a small
    # file's sums stay below $131,072, where float32 still holds a cent
    from harness import datagen
    seed = 2_147_483_777
    return seed, datagen.generate(seed, 1.0)


@pytest.mark.parametrize("workload", ["tpcds_sf1.star_join",
                                      "tpcds_sf1_smallfiles.star_join"])
def test_float32_money_control_fails(workload, sf1):
    from control_full_size import control_verdict
    from harness import cell
    seed, arrow = sf1
    v = control_verdict(cell.Cell(workload), arrow, 1.0, seed, n_tasks=24)
    assert v["sound"]["correct"] and v["sound"]["exact_mismatches"] == 0
    assert not v["control"]["correct"]
    assert v["control"]["exact_mismatches"] >= 3


def _args(**kw):
    base = dict(workload="tpcds_sf1_smallfiles.star_join", seed=2_147_483_700,
                seconds=4.0, trace=0, scale=0.02, benchmark_json=None,
                keep_trace=None, tasks_out=None)
    base.update(kw)
    return argparse.Namespace(**base)


def _one_cent_more(client):
    """An AuronClient whose answers come back with one cent added to the
    first sum of every non-empty table."""
    inner = client.execute

    def execute(blob):
        table, done = inner(blob)
        if table is not None and table.num_rows:
            i = table.column_names.index("sum_agg")
            vals = table.column(i).to_pylist()
            vals[0] = vals[0] + type(vals[0])("0.01")
            table = table.set_column(
                i, "sum_agg", pa.array(vals, table.schema.field(i).type))
        return table, done

    client.execute = execute
    return client


def test_a_wrong_answer_turns_correct_false():
    import run
    bad = run.run(_args(), client_wrapper=_one_cent_more)
    assert bad["attempted"] > 0 and bad["failed"] == 0
    assert bad["correct"] is False
    good = run.run(_args())
    assert good["correct"] is True and good["attempted"] > 0


def test_comparison_rules():
    import decimal
    from harness import compare
    d = decimal.Decimal
    want = pa.table({"k": ["a", "b"], "m": pa.array([d("1.10"), d("2.20")],
                                                    pa.decimal128(17, 2)),
                     "x": [1.0, 2.0]})
    same = pa.table({"k": ["b", "a"], "m": pa.array([d("2.20"), d("1.10")],
                                                    pa.decimal128(38, 2)),
                     "x": [2.0 * (1 + 1e-9), 1.0]})
    assert compare.answer_ok(compare.compare_tables(same, want))
    cent = same.set_column(1, "m", pa.array([d("2.21"), d("1.10")],
                                            pa.decimal128(38, 2)))
    assert compare.compare_tables(cent, want)["exact_mismatches"] == 1
    drift = same.set_column(2, "x", pa.array([2.0 * (1 + 1e-6), 1.0]))
    assert not compare.answer_ok(compare.compare_tables(drift, want))
    assert compare.compare_tables(want.slice(0, 1), want)["error"]
    assert not compare.decide([])["correct"]

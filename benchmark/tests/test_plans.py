"""Every plan of the library against its own oracle, through the
engine's DataFrame path on the CPU, over one scan partition and over
four (the mesh cell's stage shape). A plan added later is found by its
file and checked with no edit here."""

import glob
import os

import pytest

from conftest import BENCH

PLANS = sorted(os.path.basename(p)[:-3]
               for p in glob.glob(os.path.join(BENCH, "plans", "q*.py")))


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    from harness import datagen
    root = str(tmp_path_factory.mktemp("tpcds"))
    arrow = datagen.generate(seed=2_147_483_659, scale=0.02)
    splits = datagen.write_splits(root, "store_sales",
                                  arrow["store_sales"], 16_384)
    dims = {name: datagen.write_whole(root, name, arrow[name])
            for name in arrow if name != "store_sales"}
    return arrow, [p for p, _lo, _n in splits], dims


@pytest.fixture(scope="module")
def session():
    from auron_tpu.frontend.session import Session
    s = Session()
    yield s
    s.close()


@pytest.mark.parametrize("partitions", [1, 4])
@pytest.mark.parametrize("plan", PLANS)
def test_plan_matches_its_oracle(plan, partitions, data, session):
    from harness import cell, compare
    arrow, files, dims = data
    mod = cell.load_module("plans", plan)
    assert mod.TABLES[0] == "store_sales"
    df = mod.build(session, dims, files, partitions)
    assert df.num_partitions == 1, "a task is one TaskDefinition"
    got = df.collect()
    res = compare.compare_tables(got, mod.oracle(arrow))
    assert compare.answer_ok(res), res
    assert got.num_rows > 0, "an empty answer proves nothing"


def test_library_has_the_cells_plans_and_q65():
    assert {"q3", "q42", "q52", "q55", "q65"} <= set(PLANS)

"""TPC-DS q65, the wide aggregation, on both general-aggregation paths
(PR 34; tier-1, on the CPU: counts and answers, no timings).

The benchmark's two plans (``benchmark/plans/q65.py``: money cast to
double, a float sum, so the sort path; ``q65m.py``: money kept decimal,
so the hash table) run as served tasks over ONE ``store_sales`` split of
ten scan batches (its rows in store order, so that every batch brings
new groups), with ``auron.agg.initial_capacity`` at 64 so that the
(store x item) state grows several times while the batches fold into it.
Held here:

(a) the answers equal their oracles: integers, strings and decimal
    money exact, doubles to 1e-7 — and the (store, item) sums carried
    through float32 FAIL that comparison in the answers of the check
    plans ``q65sa`` / ``q65sam`` (the mix's other two: the same first
    aggregate, answered by its hundred best-selling pairs), where q65's
    own answer, sums of one or two sales, cannot tell;
(b) the dispatch rule: a decimal sum takes the hash table, a float sum
    the sort path (``kernels/dispatch.select_hash_agg`` under ``auto``);
(c) the five counters of the DONE frame's ``cost_ledger.counts``, exact:
    batches by path, groups against the oracle's group counts (the plan's
    shared subtree runs ONCE, both parents read the one result:
    ``subplan_reuse_hits`` 1, and 0 in the check plans, which have no
    duplicate), growths against the programs that ran, no fall-back;
(d) a hash table that cannot place its keys latches the sort path
    mid-stream (``agg_sort_fallbacks`` 1, and ``agg_demoted_to_sort`` 1:
    the name under which a client's fault rule fails the task) and the
    answer is still right;
(e) the scans read 4 + 2 + 2 + 3 = 11 columns.
"""

import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from auron_tpu import config as cfg
from auron_tpu.frontend import Session

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
PLANS = ("q65", "q65m")
#: the mix's check plans: q65's first aggregate, answered directly
CHECKS = ("q65sa", "q65sam")
#: rows of the task's split and of one scan batch (a parquet row group)
SPLIT_ROWS, BATCH_ROWS = 40_960, 4_096
BATCHES = SPLIT_ROWS // BATCH_ROWS
INITIAL_CAPACITY = 64
#: runs of the sa subtree a task: both of its parents (sb, and the join
#: of sa to sb) read one result since PR 37 (ir/reuse.py)
SA_RUNS = 1


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server in this process and the two plans over one split of
    ``BATCHES`` row groups (scale 0.02: 360 items, 12 stores)."""
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    from harness import cell, datagen

    from auron_tpu.runtime.serving import AuronClient, AuronServer
    root = str(tmp_path_factory.mktemp("tpcds"))
    arrow = datagen.generate(seed=2_147_483_659, scale=0.02)
    split = arrow["store_sales"].slice(0, SPLIT_ROWS)
    split = split.take(pc.sort_indices(split, [("ss_store_sk",
                                                "ascending")]))
    path = os.path.join(root, "store_sales_0000.parquet")
    pq.write_table(split, path, row_group_size=BATCH_ROWS)
    dims = {name: datagen.write_whole(root, name, arrow[name])
            for name in arrow if name != "store_sales"}
    task_rows = dict(arrow, store_sales=split)
    conf = cfg.get_config()
    before = conf.get(cfg.AGG_INITIAL_CAPACITY)
    conf.set(cfg.AGG_INITIAL_CAPACITY, INITIAL_CAPACITY)
    session = Session()
    modules = {p: cell.load_module("plans", p) for p in PLANS + CHECKS}
    tasks = {p: m.build(session, dims, [path], 1).task_bytes(0)
             for p, m in modules.items()}
    server = AuronServer()
    server.serve_background()
    host, port = server.address
    answers = {}

    def run(plan, fresh=False):
        if fresh or plan not in answers:
            table, metrics = AuronClient(
                host, port, timeout_s=600).execute(tasks[plan])
            answers[plan] = (table, metrics["cost_ledger"])
        return answers[plan]

    run.oracle = lambda plan, **kw: modules[plan].oracle(task_rows, **kw)
    run.rows = task_rows
    yield run
    server.shutdown()
    server.server_close()
    session.close()
    conf.set(cfg.AGG_INITIAL_CAPACITY, before)


def _year_2000(rows: dict) -> pa.Table:
    """The task's fact rows that pass q65's date filter, in file order."""
    dd = rows["date_dim"]
    keep = dd.filter(pc.and_(pc.greater_equal(dd["d_month_seq"], 24),
                             pc.less_equal(dd["d_month_seq"], 35)))
    ss = rows["store_sales"]
    return ss.filter(pc.is_in(ss["ss_sold_date_sk"],
                              value_set=keep["d_date_sk"]))


def _group_counts(rows: dict) -> tuple:
    """((store, item) groups, stores) of the task, from pyarrow alone."""
    ss = _year_2000(rows)
    pairs = ss.group_by(["ss_store_sk", "ss_item_sk"]).aggregate([])
    return pairs.num_rows, len(pc.unique(pairs["ss_store_sk"]))


def _sorted_state_grows(rows: dict) -> int:
    """Growths of the sort path's state over one run of the (store, item)
    aggregation: a merge that finds more groups than the state's
    power-of-two capacity holds runs again at the next bucket."""
    ss = rows["store_sales"]
    dates = set(_year_2000(rows)["ss_sold_date_sk"].to_pylist())
    seen, cap, grows = set(), None, 0
    for lo in range(0, ss.num_rows, BATCH_ROWS):
        part = ss.slice(lo, BATCH_ROWS).to_pydict()
        seen.update((s, i) for d, s, i in zip(
            part["ss_sold_date_sk"], part["ss_store_sk"], part["ss_item_sk"])
            if d in dates)
        need = max(INITIAL_CAPACITY, 1 << (len(seen) - 1).bit_length())
        if cap is not None and need > cap:
            grows += 1
        cap = need if cap is None else max(cap, need)
    return grows


# -- (a) the answers ---------------------------------------------------------

@pytest.mark.parametrize("plan", PLANS)
def test_a_wide_agg_task_answers_as_its_oracle(plan, served):
    from harness import compare
    table, _led = served(plan)
    want = served.oracle(plan)
    res = compare.compare_tables(table, want)
    assert compare.answer_ok(res), res
    assert res["exact_mismatches"] == 0 and res["max_double_rel"] <= 1e-7
    assert table.num_rows == 100, "the limit is met: the answer is wide"
    money = table.schema.field("revenue").type
    if plan == "q65m":
        assert pa.types.is_decimal(money) and money.scale == 2
    else:
        assert pa.types.is_float64(money)


@pytest.mark.parametrize("plan", CHECKS)
def test_a_float32_sum_fails_in_the_check_plans_answer(plan, served):
    """q65 returns the pairs UNDER a tenth of their store's average: one
    or two sales each, which float32 still carries to 6e-8. What a
    narrower accumulator, a lost addend or a group dropped in a growth
    would change is in the aggregate under it, so the mix sends that
    aggregate as a plan of its own, answered by its many-addend groups:
    the engine's answer passes, the oracle with its sums carried in
    float32 does not (a double beyond 1e-7, a decimal by whole cents)."""
    from harness import compare
    table, led = served(plan)
    want = served.oracle(plan)
    sound = compare.compare_tables(table, want)
    assert compare.answer_ok(sound), sound
    assert sound["max_double_rel"] < 1e-12
    assert table.num_rows == 100 and min(table["sales"].to_pylist()) >= 8
    same = compare.compare_tables(served.oracle(plan, carry=np.float64), want)
    assert compare.answer_ok(same) and same["max_double_rel"] < 1e-12
    low = compare.compare_tables(served.oracle(plan, carry=np.float32), want)
    assert not low["error"] and not compare.answer_ok(low)
    counts = led["counts"]
    if plan == "q65sa":
        assert low["max_double_rel"] > 2 * compare.DOUBLE_REL_TOL, low
        assert counts["agg_sort_batches"] == BATCHES
        assert counts["agg_hash_batches"] == 0
    else:
        assert low["exact_mismatches"] >= 3, low
        assert counts["agg_hash_batches"] == BATCHES
        assert counts["agg_sort_batches"] == 0
    assert counts["agg_groups"] == _group_counts(served.rows)[0]
    assert counts["agg_sort_fallbacks"] == 0


# -- (b) which path takes which sum -----------------------------------------

def test_a_decimal_sum_takes_the_hash_table_a_float_sum_the_sort_path(
        served):
    assert cfg.get_config().get(cfg.HASHTABLE_BACKEND) == "auto"
    double = served("q65")[1]["counts"]
    money = served("q65m")[1]["counts"]
    assert double["agg_sort_batches"] > 0 and double["agg_hash_batches"] == 0
    assert money["agg_hash_batches"] > 0
    assert "hashtable.agg_step" not in double["program_calls_by_site"]
    # a batch whose insert overflowed its probe rounds ran its program
    # again after the table grew, and counts once
    assert money["program_calls_by_site"]["hashtable.agg_step"] >= \
        money["agg_hash_batches"]


# -- (c) the five counters, exact -------------------------------------------

def test_the_done_frame_counts_the_aggregation(served):
    pairs, stores = _group_counts(served.rows)
    assert pairs > 16 * INITIAL_CAPACITY, "the state must grow mid-stream"
    double = served("q65")[1]["counts"]
    money = served("q65m")[1]["counts"]
    sites_d = double["program_calls_by_site"]
    sites_m = money["program_calls_by_site"]
    # every scan batch reaches the (store, item) aggregation, which runs
    # once; the 12-store average folds the one batch of its output and
    # sums doubles in both plans
    assert double["agg_sort_batches"] == SA_RUNS * BATCHES + 1
    assert double["agg_hash_batches"] == 0
    assert money["agg_hash_batches"] == SA_RUNS * BATCHES
    assert money["agg_sort_batches"] == 1
    for counts in (double, money):
        assert counts["agg_groups"] == SA_RUNS * pairs + stores
        assert counts["agg_sort_fallbacks"] == 0
    # the sort path: a growth is a merge run again at the next bucket
    grows = _sorted_state_grows(served.rows)
    assert grows >= 3
    assert double["agg_state_grows"] == SA_RUNS * grows
    merges = double["agg_sort_batches"] - (SA_RUNS + 1)
    assert sites_d["ops.agg.state_merge"] == merges + double["agg_state_grows"]
    assert sites_d["ops.agg.batch_reduce"] == double["agg_sort_batches"]
    # the hash table: a growth is one re-bucketing program a doubling —
    # but for a table's FIRST step where it overflows (PR 51: it committed
    # nothing, so nothing is re-bucketed; the step runs again, fresh, at
    # the doubled capacity): this table's does at 64 and at 128 slots,
    # the only steps that run again
    again = sites_m["hashtable.agg_step"] - money["agg_hash_batches"]
    assert money["agg_state_grows"] - sites_m["hashtable.agg_grow"] \
        == again == 2
    assert money["agg_state_grows"] >= SA_RUNS * 3
    assert money["agg_state_grows"] % SA_RUNS == 0
    cap = INITIAL_CAPACITY << (money["agg_state_grows"] // SA_RUNS)
    assert pairs <= cap <= 4 * (1 << (pairs - 1).bit_length())


def test_a_task_without_an_aggregation_counts_none(served):
    from auron_tpu.obs import trace
    for key in ("agg_hash_batches", "agg_sort_batches", "agg_groups",
                "agg_state_grows", "agg_sort_fallbacks",
                "agg_demoted_to_sort"):
        assert key in trace.COUNT_KEYS
        assert trace.TaskAccumulator().sealed(0.0)["counts"][key] == 0


# -- (d) the overflow fall-back ---------------------------------------------

def test_an_overflowing_hash_table_latches_the_sort_path(served,
                                                        monkeypatch):
    """The first table's second batch overflows at every capacity: the
    operator salvages the table as a sorted state, pushes the failed
    batch and the rest of its stream through the sort path, and says so
    once. (The aggregation runs once a task: no second run keeps a
    table.)"""
    from auron_tpu.hashtable import HashTableOverflow
    from auron_tpu.hashtable import agg as htagg
    from harness import compare
    real = htagg.HashAggState.update
    first = []

    def update(self, *batch):
        if not first:
            first.append(self)
        if self is first[0] and self.rows_seen:
            raise HashTableOverflow("forced by the test")
        return real(self, *batch)

    monkeypatch.setattr(htagg.HashAggState, "update", update)
    table, led = served("q65m", fresh=True)
    monkeypatch.undo()
    served("q65m", fresh=True)      # the cached answer is the sound one
    counts = led["counts"]
    assert counts["agg_sort_fallbacks"] == 1
    assert counts["agg_demoted_to_sort"] == 1
    assert counts["agg_hash_batches"] == 1, "the batch before the overflow"
    assert counts["agg_sort_batches"] == (BATCHES - 1) + 1
    pairs, stores = _group_counts(served.rows)
    assert counts["agg_groups"] == SA_RUNS * pairs + stores
    res = compare.compare_tables(table, served.oracle("q65m"))
    assert compare.answer_ok(res), res


# -- (e) the scans' width ----------------------------------------------------

@pytest.mark.parametrize("plan", PLANS)
def test_a_wide_agg_task_reads_eleven_columns(plan, served):
    counts = served(plan)[1]["counts"]
    # fact 4 + date_dim 2 (the subtree both sides of the self-join hang
    # from runs once), store 2, item 3; of 20 + 9, 7, 16
    assert counts["scan_columns_read"] == SA_RUNS * (4 + 2) + 2 + 3
    assert counts["scan_columns_pruned"] == SA_RUNS * (16 + 7) + 5 + 13
    assert counts["subplan_reuse_hits"] == 1


@pytest.mark.parametrize("plan", CHECKS)
def test_a_check_plan_has_no_subplan_to_share(plan, served):
    counts = served(plan)[1]["counts"]
    assert counts["subplan_reuse_hits"] == 0
    assert counts["scan_columns_read"] == 4 + 2


# -- (f) the sort on a double key, where a double has no bits ---------------

def test_a_double_sort_key_orders_without_its_bits():
    """The TPU carries a double as two float32 and refuses a bitcast of
    one (q65's sort on ``revenue`` did not compile there): its order word
    is the pair. On doubles that such a pair holds it orders, and tells
    apart, exactly as the IEEE word does."""
    import jax.numpy as jnp
    from auron_tpu.ops import sort
    rng = np.random.default_rng(7)
    hi = rng.normal(0.0, 1e5, 4096).astype(np.float32)
    lo = (hi * rng.uniform(-2.0 ** -25, 2.0 ** -25, hi.size)) \
        .astype(np.float32)
    x = np.concatenate([
        hi.astype(np.float64) + lo.astype(np.float64),
        [0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan, 1.5, 1.5, -3.25,
         131072.01, 131072.02, 3e38, -3e38, 1e-30]])
    split = np.asarray(sort.f64_split_order_word(jnp.asarray(x)))
    ieee = np.asarray(sort.f64_order_word(jnp.asarray(x)))
    assert (np.argsort(split, kind="stable")
            == np.argsort(ieee, kind="stable")).all()
    assert len(set(split.tolist())) == len(set(ieee.tolist()))
    # -0.0 is 0.0 and every NaN is one (greatest) key, as Spark orders
    by = dict(zip(map(repr, x.tolist()), split.tolist()))
    assert by["0.0"] == by["-0.0"] and by["nan"] == max(split)

"""The deployment `tpcds_sf1_mesh2x2_q28` (PR 48; tier-1, on the CPU:
answers, plan shapes and counts, no timings).

TPC-DS q28 at its published text (``benchmark/plans/q28.py``) as ONE
4-partition stage over four input splits on four of the virtual CPU
devices, through ``AuronServer`` / ``AuronClient`` under the ``engine``
settings of ``benchmark/configs/tpcds_sf1_mesh2x2_q28.json``. Each band's
single-DISTINCT aggregate is Spark's four aggregates with a hash exchange
on ``ss_list_price`` — a decimal(7,2) — between the first and the second
and a gather to one partition between the third and the fourth. Held here:

- the configuration file is ``tpcds_sf1_mesh2x2_q65.json`` in every number
  but ``warmup_groups`` (11: ISSUE 48's stated alternative) and differs in
  the keys PR 48 names, and its ``source`` is the ``BENCHMARK.json``
  entry's;
- the served stage answers its oracle in all 18 cells; its six hash
  exchanges cross the mesh and its six gathers do not; one round an
  exchange at one scan batch a partition; the groups that leave the
  second aggregates are the answer's distinct counts; and
  ``counts.mesh_read_rows`` is the rows the six exchanges delivered — the
  distinct prices of every (band, partition), which the map-side combine
  left of the rows that passed the band's filter — handed to the second
  aggregates as ONE batch a (band, partition): 24 table steps on 96
  merged slices (PR 49);
- **the control this deployment adds**: the distinct count taken per split
  and summed — what a stage without the exchange would answer — differs
  from the exact reference in every band that holds a price in two splits,
  and the comparison that decides ``correct`` calls it incorrect;
- the four readers PR 48 adds, on hand-written frames: a number where
  there is something to read, None (never an exception) where the frame
  lacks it, as the parent commit's frames lack ``mesh_read_rows``.
"""

import json
import os
import sys

import jax
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from auron_tpu import config as cfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(ROOT, "benchmark")
NAME, SIBLING = "tpcds_sf1_mesh2x2_q28", "tpcds_sf1_mesh2x2_q65"
CELL = "tpcds_sf1_mesh2x2.price_bands"
#: one scan batch a partition: a split is one row group under the
#: 65,536-row batch capacity
SPLIT_ROWS = 16_384
BANDS = range(1, 7)
READERS = ("exchange.read_rows_per_batch", "exchange.home_bytes",
           "exchange.path_share", "exchange.combine_ratio")
#: the keys in which the file may differ from its sibling's (PR 48)
MAY_DIFFER = {"name", "source", "deployment", "rows", "date_dim_rows",
              "schema", "key_skew", "require_counters", "guarantees",
              "reduced", "assumed",
              # ISSUE 48's stated alternative, taken: a window of the
              # chip runs held a compile with 3 groups warmed
              "warmup_groups"}

needs_4 = pytest.mark.skipif(len(jax.devices()) < 4,
                             reason="needs 4 virtual devices")


def _bench():
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    from harness import cell, compare, datagen
    return cell, compare, datagen


def _config(name):
    with open(os.path.join(_BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _leaf_sum(tree, key) -> float:
    if isinstance(tree, dict):
        return sum(v if k == key and isinstance(v, (int, float))
                   else _leaf_sum(v, key) for k, v in tree.items())
    if isinstance(tree, list):
        return sum(_leaf_sum(v, key) for v in tree)
    return 0


# -- (a) the configuration file ----------------------------------------------

@pytest.mark.parametrize("key", sorted(
    set(_config(SIBLING)) | set(_config(NAME))))
def test_the_file_is_its_sibling_but_for_the_keys_named(key):
    new, old = _config(NAME), _config(SIBLING)
    if key not in MAY_DIFFER:
        assert new[key] == old[key], key
        return
    assert new.get(key) != old.get(key), key
    want = {
        "name": NAME,
        "rows": {"store_sales": 2_880_000},
        "date_dim_rows": None,      # the plan reads no dimension table
        "warmup_groups": 11,        # every pair the window can draw
        "require_counters": {"exchange_route_all_to_all": 6,
                             "agg_distinct_groups": 1, "agg_groups": 1},
    }
    if key in want:
        assert new.get(key) == want[key]
    if key == "reduced":
        assert list(new[key]) == ["split_rows"]
    if key == "guarantees":
        one_chip = _config("tpcds_sf1_q28")[key]
        # the one-chip q28 file's five (its fifth names that file's own
        # control), the mesh file's fourth, and one of its own
        assert new[key][:4] == one_chip[:4]
        assert new[key][4].split("(benchmark/tests")[0] == \
            one_chip[4].split("(benchmark/tests")[0]
        assert "all_to_all route across 4 devices" in new[key][5]
        assert "all_to_all route across 4 devices" in old[key][3]
        assert "counted once" in new[key][6] and len(new[key]) == 7
    if key in ("schema", "key_skew"):
        assert new[key] == _config("tpcds_sf1_q28")[key]


def test_the_numbers_are_the_siblings_and_the_source_is_the_entrys():
    new, old = _config(NAME), _config(SIBLING)
    for key in ("chips", "split_rows", "splits_per_task", "scan_partitions",
                "scale_factor", "engine"):
        assert new[key] == old[key], key
    # every (plan, group) pair is warmed: ten full groups and the short one
    groups = -(-new["rows"]["store_sales"]
               // (new["split_rows"] * new["splits_per_task"]))
    assert new["warmup_groups"] == groups == 11 and old["warmup_groups"] == 3
    assert new["engine"] == {"auron.mesh.enabled": True,
                             "auron.mesh.devices": 4,
                             "auron.max_live_programs": 0,
                             "auron.sched.max_concurrent": 4}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["source"] == new["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == list(new["reduced"]) == ["split_rows"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "price_bands", 4)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    # at most half of the cells may take four chips (with this one: 4 of
    # 10; ISSUE 48 counted 5)
    assert 2 * len(four) <= len(bench["workloads"])
    for name in READERS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        assert (metric["layer"], metric["moves"]) == \
            ("exchange", "fact_rows_per_s")


# -- (b), (c) the served stage -----------------------------------------------

class _Bag:
    pass


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """Four splits of one scan batch each, their exact answer, and each
    split's own answer (what a partition would count with no exchange)."""
    cell, compare, datagen = _bench()
    root = str(tmp_path_factory.mktemp("tpcds"))
    arrow = datagen.generate(seed=2_147_548_003, scale=0.05,
                             tables=("store_sales",))
    fact = arrow["store_sales"].slice(0, 4 * SPLIT_ROWS)
    d = _Bag()
    d.compare, d.q28 = compare, cell.load_module("plans", "q28")
    splits, d.paths = [], []
    for i in range(4):
        splits.append(fact.slice(i * SPLIT_ROWS, SPLIT_ROWS))
        d.paths.append(os.path.join(root, f"store_sales_{i:04d}.parquet"))
        pq.write_table(splits[-1], d.paths[-1], row_group_size=SPLIT_ROWS)
    d.exact_table = d.q28.oracle({"store_sales": fact})
    d.exact = d.exact_table.to_pylist()[0]
    d.local = [d.q28.oracle({"store_sales": split}).to_pylist()[0]
               for split in splits]
    return d


@pytest.fixture(scope="module")
def stage(data):
    """One server in this process under the file's ``engine``; q28 over
    the four splits in four partitions, served once."""
    from auron_tpu.frontend import Session
    from auron_tpu.parallel import mesh
    from auron_tpu.runtime.serving import AuronClient, AuronServer
    conf = cfg.get_config()
    engine = _config(NAME)["engine"]
    for key, value in engine.items():
        conf.set(key, value)
    session = Session()
    server = AuronServer()
    server.serve_background()
    s = _Bag()
    try:
        s.table, s.done = AuronClient(*server.address, timeout_s=900) \
            .execute(data.q28.build(session, {}, data.paths, 4)
                     .task_bytes(0))
    finally:
        server.shutdown()
        server.server_close()
        session.close()
        for key in engine:
            conf.unset(key)
        mesh.reset_plane()
    s.counts = s.done["cost_ledger"]["counts"]
    return s


@needs_4
def test_the_stage_answers_its_oracle_in_all_18_cells(stage, data):
    res = data.compare.compare_tables(stage.table, data.exact_table)
    assert data.compare.answer_ok(res) and res["cells"] == 18, res
    assert stage.table.num_rows == 1
    assert stage.done["cost_ledger"]["outcome"] == "ok"
    for i in BANDS:
        assert stage.table.schema.field(f"B{i}_LP").type == \
            pa.decimal128(11, 6)


@needs_4
@pytest.mark.parametrize("leaf, want", [
    ("exchange_route_all_to_all", 6), ("exchange_route_device_buffer", 6),
    ("exchange_route_demoted", 0), ("split_folded", 6),
    ("mesh_stragglers", 0)])
def test_the_stages_exchanges_take_these_routes(stage, leaf, want):
    """Each band's hash exchange on the decimal price crosses the mesh
    with its select -> filter chain and the first aggregate's combine
    folded into the sharded stage program; its gather to one partition
    stays on the host route."""
    assert _leaf_sum(stage.done, leaf) == want


@needs_4
def test_the_stage_counts_its_rounds_its_groups_and_no_fallback(stage):
    counts, row = stage.counts, stage.table.to_pylist()[0]
    # one scan batch a partition: one round an exchange, the door once each
    assert counts["mesh_rounds"] == counts["mesh_gang_acquires"] == 6
    assert counts["mesh_escalations"] == 0
    # the partitions of a band's second aggregate hold disjoint prices
    assert counts["agg_distinct_groups"] == \
        sum(row[f"B{i}_CNTD"] for i in BANDS)
    assert counts["agg_sort_fallbacks"] == counts["agg_demoted_to_sort"] == 0
    assert counts["cross_join_rows"] == 5
    assert counts["mesh_home_bytes"] > 0
    # the folded filter never passes FusedStageOp.execute: it is not cut
    assert counts["batch_shrinks"] == 0
    sites = counts["program_calls_by_site"]
    assert sites["parallel.mesh_exchange.stage"] == 6
    # one table a (band, partition), stepped ONCE: the reduce side hands
    # the second aggregate one batch a partition, its four sources'
    # slices merged (PR 49; 96 steps on 96 slices before), and a
    # table's set-up rides that step: no launch of its own
    assert "hashtable.agg_init" not in sites
    assert sites["hashtable.agg_step"] == counts["agg_hash_batches"] \
        == counts["mesh_read_batches"] == 24
    assert counts["mesh_read_slices"] == 96
    # one launch of the read a partition and exchange, and one a gather:
    # the four maps' one-row batches reach a band's last aggregate as
    # one batch of four rows, so it reduces once and merges no state
    assert sites["parallel.exchange.read_cut"] == 24 + 6
    assert sites["ops.agg.batch_reduce"] == 24 + 6
    assert "ops.agg.state_merge" not in sites
    # no row count is read on the reduce side: a batch's is the host sum
    # of its slices' counts. Nor does an aggregate's emit read one: it
    # leaves with the host's count of its groups, which is also what the
    # cross joins read of their build sides. Left: the scan's 24, the
    # cross joins' outputs 5, the limit's 2
    assert counts["row_syncs"] == 31
    # the 24 steps' (n_new, overflow) and what is not the aggregation's;
    # the 30 keyless reduces over counted batches read nothing
    assert counts["readbacks"] == 55
    assert counts["program_calls"] == 173


@needs_4
def test_mesh_read_rows_is_what_the_six_exchanges_delivered(stage, data):
    """A band's exchange delivers, from each map partition, that
    partition's distinct prices (the combine merged the rows that passed
    the filter by price): the sum over bands and partitions is the live
    rows of the slices the reducers were handed."""
    delivered = sum(part[f"B{i}_CNTD"] for part in data.local for i in BANDS)
    passed = sum(part[f"B{i}_CNT"] for part in data.local for i in BANDS)
    counts = stage.counts
    assert counts["mesh_read_rows"] == delivered
    # one slice a (band, partition, source), none of them empty, merged
    # into one batch a (band, partition)
    assert counts["mesh_read_slices"] == 6 * 4 * 4
    assert counts["mesh_read_batches"] == 6 * 4
    assert counts["mesh_read_rows"] >= counts["mesh_read_slices"]
    # the combine's own figures: the rows in are the filter's survivors,
    # the rows out what crossed — the key is the price, so it merges
    # next to nothing
    # (a frame's tree may hold an exchange's leaf more than once: both
    # sums then count it as often, which a ratio of them cancels)
    rows_in = _leaf_sum(stage.done, "combine_rows_in")
    rows_out = _leaf_sum(stage.done, "combine_rows_out")
    assert rows_in >= passed and rows_in % passed == 0
    assert rows_out * passed == rows_in * delivered
    assert 0.9 < delivered / passed <= 1.0


def _with_counts(table, counts: dict):
    for name, value in counts.items():
        table = table.set_column(table.column_names.index(name), name,
                                 pa.array([value], pa.int64()))
    return table


@pytest.mark.parametrize("band", BANDS)
def test_a_partition_local_distinct_count_is_a_different_result(data, band):
    """The control: without the exchange every partition counts its own
    prices and the stage sums them. A price that two splits hold is then
    counted more than once — the sum is over the exact count — while the
    count (a sum of counts) is still right."""
    name = f"B{band}_CNTD"
    summed = sum(part[name] for part in data.local)
    assert sum(part[f"B{band}_CNT"] for part in data.local) \
        == data.exact[f"B{band}_CNT"]
    # this size leaves no band without a price in two splits
    assert summed > data.exact[name]
    res = data.compare.compare_tables(
        _with_counts(data.exact_table, {name: summed}), data.exact_table)
    assert res["exact_mismatches"] == 1 and not data.compare.answer_ok(res)
    assert data.compare.decide([res])["correct"] is False


def test_the_summed_answer_is_incorrect_and_the_exact_one_is_not(data):
    summed = {f"B{i}_CNTD": sum(p[f"B{i}_CNTD"] for p in data.local)
              for i in BANDS}
    res = data.compare.compare_tables(
        _with_counts(data.exact_table, summed), data.exact_table)
    assert res["exact_mismatches"] == 6 and res["cells"] == 18
    assert data.compare.decide([res])["correct"] is False
    same = data.compare.compare_tables(data.exact_table, data.exact_table)
    assert data.compare.decide([same])["correct"] is True


# -- (d) the four readers ----------------------------------------------------

def _read(name, tasks):
    cell, _compare, _datagen = _bench()
    return cell.load_module("layer_metrics", name).read({"tasks": tasks})


def _frame(rows=None, batches=96, home=80_000_000, wall=4.0, exchange=0.8,
           read=(0.5, 0.1), combine=(1963, 1954)):
    """A version-2 DONE frame of a q28 mesh stage; ``rows=None`` is the
    parent's frame, which has no ``mesh_read_rows``."""
    counts = {"mesh_rounds": 6, "mesh_read_batches": batches,
              "mesh_home_bytes": home}
    if rows is not None:
        counts["mesh_read_rows"] = rows
    ledger = {"version": 2, "wall_s": wall,
              "layers_s": {"op_host": 2.0, "exchange": exchange},
              "ops_s": {}, "counts": counts}
    if read is not None:
        ledger["ops_s"]["shuffle_exchange_read"] = {
            "host_s": read[0], "device_wait_s": read[1], "batches": 120}
    done = {"cost_ledger": ledger}
    if combine is not None:
        # one leaf an exchange, somewhere in the frame's operator tree
        done["shuffle_exchange"] = {"children": [
            {"combine_rows_in": combine[0] // 2,
             "combine_rows_out": combine[1] // 2},
            {"combine_rows_in": combine[0] - combine[0] // 2,
             "combine_rows_out": combine[1] - combine[1] // 2}]}
    return {"plan": "q28", "group": 0, "rows": 262_144, "t_submit": 0.0,
            "t_done": wall, "done": done}


@pytest.mark.parametrize("name, want", [
    ("exchange.read_rows_per_batch", (18_000 + 20_400) / (96 + 96)),
    ("exchange.home_bytes", 85_000_000.0),
    ("exchange.path_share", (0.8 + 0.6 + 1.2 + 0.9) / (4.0 + 6.0)),
    ("exchange.combine_ratio", (1954 + 3000) / (1963 + 3100)),
])
def test_a_reader_reads_a_window_of_stages(name, want):
    tasks = [_frame(rows=18_000),
             _frame(rows=20_400, home=90_000_000, wall=6.0, exchange=1.2,
                    read=(0.7, 0.2), combine=(3100, 3000))]
    assert _read(name, tasks) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_none_or_a_number_where_something_is_missing(name):
    # the parent's frames: everything but the new count
    got = _read(name, [_frame(rows=None)] * 3)
    if name == "exchange.read_rows_per_batch":
        assert got is None
    else:
        assert got == pytest.approx({
            "exchange.home_bytes": 80_000_000.0,
            "exchange.path_share": (0.8 + 0.6) / 4.0,
            "exchange.combine_ratio": 1954 / 1963}[name])
    # a one-chip frame: the counts read 0, no exchange was read or combined
    single = [_frame(rows=0, batches=0, home=0, exchange=0.0, read=None,
                     combine=None)]
    assert _read(name, single) == {
        "exchange.read_rows_per_batch": None, "exchange.home_bytes": 0.0,
        "exchange.path_share": 0.0, "exchange.combine_ratio": None}[name]
    # no task, no frame, no ledger, a version-1 ledger, fields of the
    # wrong kind: None, never an exception
    for tasks in ([], [{"done": None}], [{"done": {}}],
                  [{"done": {"cost_ledger": {"version": 1}}}],
                  [{"done": {"cost_ledger": {
                      "wall_s": "?", "layers_s": 3, "ops_s": [],
                      "counts": {"mesh_read_rows": None,
                                 "mesh_read_batches": True,
                                 "mesh_home_bytes": "many"}},
                      "combine_rows_in": "?", "combine_rows_out": None}}]):
        assert _read(name, tasks) is None

"""The served 4-device mesh path against the plain reference (PR 28).

TPC-DS as 4-partition stages over a 4-device mesh at a small scale, on 4
of the 8 virtual CPU devices: each of the eight plans the benchmark
serves — the star joins (``it/tpcds_queries.py``'s q3 / q42 / q52 / q55)
and the wide aggregation (its q65 / q65m, and their check plans q65sa /
q65sam, built here from the same helpers: q65's first aggregate answered
by its hundred best-selling pairs) — as ONE task, a 4-partition stage
over 4 input splits, through ``AuronServer`` / ``AuronClient`` with
``auron.mesh.enabled``.

- every answer equals the plan's own Acero oracle over exactly the
  task's rows (integers, strings and decimal money exact, doubles to
  1e-7) and the mesh-off answer bit for bit;
- the DONE frame of a mesh stage carries the exchange layer's three
  spans inside ``layers_s.exchange`` (``gang_wait``, ``mesh_stack``,
  ``mesh_round``), ``layers_s`` still summing to ``wall_s`` and
  ``exchange_s`` to ``layers_s.exchange``, the ``counts`` of the mesh
  route and of its read side, and the recorded ``all_to_all`` route:
  three hash exchanges in a q65 / q65m stage (the (store, item)
  aggregation is planned twice across its exchange, and the per-store
  average above it), one in every other;
- every exchange a stage leaves on ``device_buffer`` has a reason that is
  not a hash exchange's;
- a mesh partition's slices of a round are cut by ONE call of the read
  program and every delivered batch of the host route by one: the site's
  count in the frame is those two, and the slices delivered are what the
  eager read delivered (16 / 44 a check / q65 stage);
- a one-chip task's frame has none of them non-zero;
- the stage's global sort runs as programs (PR 41): every map batch of
  the range exchange and of the gather is split by ONE call of the split
  program (the range bounds its operand) and sampled by one, a reducer's
  batches are concatenated by one, and the stage reads fewer row counts
  than PR 40's tree did; a one-partition task calls none of the three;
- the gang door closes round a ROUND's sharded launch only (PR 43): four
  stages' map sides are in flight at once and none of them ever pulls
  with the door in its hand, a stage takes the door once a round
  (``counts.mesh_gang_acquires``), two exchanges of several rounds
  alternate at it with one of them between stack and fence at a time,
  parking there is not a round's latency, and a cancel between two
  rounds finds it open.
"""

import threading

import time

import pytest

import jax

from auron_tpu import config as cfg
from auron_tpu.obs import trace

STAR = ("q3", "q42", "q52", "q55")
WIDE = ("q65", "q65m", "q65sa", "q65sam")
PLANS = STAR + WIDE
#: hash exchanges of a stage that cross the mesh
MESH_EXCHANGES = dict.fromkeys(STAR + ("q65sa", "q65sam"), 1) \
    | dict.fromkeys(("q65", "q65m"), 3)
MESH_COUNTS = ("mesh_rounds", "mesh_escalations", "mesh_bytes",
               "mesh_slot_bytes", "mesh_gang_acquires", "mesh_read_batches",
               "mesh_read_rows", "mesh_home_bytes", "mesh_read_slices")
MESH_SPAN_KEYS = ("gang_wait", "mesh_stack", "mesh_round")
#: the program the reduce side reads an exchange buffer with (PR 39)
READ_CUT = "parallel.exchange.read_cut"
#: non-empty (partition, source, round) slices a wide stage's reducers
#: read (merged into one batch a partition since PR 49): 16 an ``sa``
#: exchange, 12 the per-store average's (12 stores)
WIDE_SLICES = dict.fromkeys(("q65sa", "q65sam"), 16) \
    | dict.fromkeys(("q65", "q65m"), 44)
MESH_SPANS = ("exchange.gang_wait", "exchange.mesh_stack",
              "exchange.mesh_round")
#: the programs of a stage's global sort (PR 41): the map side's split
#: of an exchange left on ``device_buffer``, the range sample, and the
#: concatenation of what a reducer collected
SPLIT = "parallel.exchange.fused_split"
RANGE_SAMPLE = "parallel.partitioning.range_sample"
CONCAT = "ops.sort.concat"
#: ``counts.row_syncs`` of each stage on PR 40's tree, this file's data
#: (the eager split read a batch's row count twice, the range sample
#: once more, the sort's concatenation once a collected batch)
ROW_SYNCS_PR40 = {"q3": 80, "q42": 95, "q52": 90, "q55": 55, "q65": 276,
                  "q65m": 244, "q65sa": 122, "q65sam": 106}
SCALE = 0.2                  # 100,000 fact rows in the task: every plan answers
SPLITS_PER_TASK = 4          # of the generator's 8 ``store_sales`` files

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 virtual devices")


def _best_sellers(decimal_money: bool):
    """The check plan of q65 (double money) / q65m (decimal): the same
    ``sa`` subtree answered by its hundred best-selling (store, item)
    pairs, ranked by the exact count of sales and the keys."""
    import pyarrow as pa
    import pyarrow.compute as pc

    from auron_tpu.columnar.schema import DataType
    from auron_tpu.frontend.dataframe import col, functions as F
    from auron_tpu.it.tpcds_queries import _join_dim, _oj, _rd, _topn

    def run(s, t):
        ss = _rd(s, t, "store_sales").select(
            "ss_sold_date_sk", "ss_item_sk", "ss_store_sk", "ss_sales_price")
        dd = _rd(s, t, "date_dim").filter(
            (col("d_month_seq") >= 24) & (col("d_month_seq") <= 35)) \
            .select("d_date_sk")
        price = col("ss_sales_price")
        if not decimal_money:
            price = price.cast(DataType.FLOAT64)
        return (_join_dim(ss, dd, "ss_sold_date_sk", "d_date_sk")
                .group_by("ss_store_sk", "ss_item_sk")
                .agg(F.sum(price).alias("revenue"),
                     F.count(col("ss_sales_price")).alias("sales"))
                .sort(col("sales").desc(), col("ss_store_sk").asc(),
                      col("ss_item_sk").asc())
                .limit(100).collect())

    def oracle(a):
        seq = a["date_dim"]["d_month_seq"]
        dd = a["date_dim"].filter(pc.and_(
            pc.greater_equal(seq, 24), pc.less_equal(seq, 35))) \
            .select(["d_date_sk"])
        ssj = _oj(a["store_sales"], dd, ["ss_sold_date_sk"], ["d_date_sk"])
        money = pa.decimal128(17, 2) if decimal_money else pa.float64()
        if not decimal_money:
            ssj = ssj.set_column(
                ssj.column_names.index("ss_sales_price"), "ss_sales_price",
                ssj["ss_sales_price"].cast(money))
        sa = ssj.group_by(["ss_store_sk", "ss_item_sk"], use_threads=False) \
            .aggregate([("ss_sales_price", "sum"),
                        ("ss_sales_price", "count")]) \
            .rename_columns(["ss_store_sk", "ss_item_sk", "revenue",
                             "sales"])
        sa = sa.set_column(2, "revenue", sa["revenue"].cast(money))
        return _topn(sa, [("sales", "descending"),
                          ("ss_store_sk", "ascending"),
                          ("ss_item_sk", "ascending")])

    return run, oracle


def _leaf_sum(tree, key) -> float:
    """Sum of the numeric leaves called ``key`` anywhere in a DONE frame
    (the benchmark's ``require_counters`` rule)."""
    if isinstance(tree, dict):
        return sum(v if k == key and isinstance(v, (int, float))
                   else _leaf_sum(v, key) for k, v in tree.items())
    if isinstance(tree, list):
        return sum(_leaf_sum(v, key) for v in tree)
    return 0


@pytest.fixture(scope="module")
def stage(tmp_path_factory):
    """One server in this process; ``run(plan, mesh)`` sends the plan's
    task and returns (answer, DONE frame). Mesh-on and mesh-off share the
    task bytes: the route is the serving process's setting, not the
    plan's."""
    from auron_tpu.frontend.session import Session
    from auron_tpu.it import tpcds
    from auron_tpu.it.tpcds_queries import QUERIES, Query
    from auron_tpu.parallel import mesh
    from auron_tpu.runtime.serving import AuronClient, AuronServer

    class PlanOnly(Session):
        """``Query.run`` ends in ``collect()``: hand back the task's
        bytes instead of running it here."""

        def execute(self, df, timeout_s=None):
            return df.task_bytes(0)

    tables = tpcds.generate(str(tmp_path_factory.mktemp("tpcds_mesh")),
                            scale=SCALE)
    # the task's rows: 4 splits, one a scan partition (the queries read
    # ``store_sales`` as 4 partitions)
    tables["store_sales"] = tables["store_sales"][:SPLITS_PER_TASK]
    queries = {q.name: q for q in QUERIES if q.name in PLANS}
    queries["q65sa"] = Query("q65sa", "q65's check", *_best_sellers(False))
    queries["q65sam"] = Query("q65sam", "q65m's check", *_best_sellers(True))
    session = PlanOnly()
    tasks = {p: queries[p].run(session, tables) for p in PLANS}
    arrow = tpcds.load_arrow({name: tables[name] for name in
                              ("store_sales", "date_dim", "item", "store")})
    server = AuronServer()
    server.serve_background()
    host, port = server.address
    conf = cfg.get_config()

    def run(plan, on):
        if on:
            conf.set(cfg.MESH_ENABLED, True)
            conf.set(cfg.MESH_DEVICES, 4)
        try:
            return AuronClient(host, port, timeout_s=600).execute(
                tasks[plan])
        finally:
            conf.unset(cfg.MESH_ENABLED)
            conf.unset(cfg.MESH_DEVICES)

    run.oracle = lambda plan: queries[plan].oracle(arrow)
    run.client = lambda: AuronClient(host, port, timeout_s=600)
    run.tasks = tasks
    yield run
    server.shutdown()
    server.server_close()
    session.close()
    mesh.reset_plane()


@pytest.fixture(scope="module")
def answers(stage):
    """Each plan once on the mesh and once with the mesh off."""
    return {plan: {"mesh": stage(plan, True), "single": stage(plan, False)}
            for plan in PLANS}


@pytest.mark.parametrize("plan", PLANS)
def test_mesh_answer_equals_the_oracle_and_the_single_device_answer(
        plan, stage, answers):
    from auron_tpu.it.comparator import QueryResultComparator
    table, _done = answers[plan]["mesh"]
    single, _ = answers[plan]["single"]
    assert table.num_rows > 0
    # money is decimal(7,2) summed as decimal: compared exactly; q65's
    # and q65sa's double sums crossed the chips as partial sums
    res = QueryResultComparator(double_rel_tol=1e-7).compare(
        plan, table, stage.oracle(plan))
    assert res.ok, res.report()
    assert table.equals(single), \
        f"{plan}: the mesh stage's answer differs from mesh-off " \
        "(values or order)"


@pytest.mark.parametrize("plan", PLANS)
def test_mesh_stage_frame_has_the_exchange_layer_and_its_counts(
        plan, answers):
    _table, done = answers[plan]["mesh"]
    led = done["cost_ledger"]
    assert led["version"] == 2 and led["outcome"] == "ok"
    layers = led["layers_s"]
    assert sum(layers.values()) == pytest.approx(led["wall_s"], abs=1e-5)
    assert layers["exchange"] > 0
    assert layers["other"] > -1e-4
    # the layer's self time by span: the door, the stack and the round
    # are in it, and nothing of the layer is outside the split
    split = led["exchange_s"]
    assert sum(split.values()) == pytest.approx(layers["exchange"], abs=1e-5)
    assert all(split[k] > 0 for k in MESH_SPAN_KEYS + ("materialize",))
    counts = led["counts"]
    assert counts["mesh_rounds"] == MESH_EXCHANGES[plan]
    # every reducer partition reads at most one slice a source and round,
    # merged into one batch a partition and exchange (one round each)
    assert 0 < counts["mesh_read_batches"] <= 4 * counts["mesh_rounds"]
    assert counts["mesh_read_batches"] <= counts["mesh_read_slices"] \
        <= 16 * counts["mesh_rounds"]
    # a slice handed on holds at least a row
    assert counts["mesh_read_rows"] >= counts["mesh_read_slices"]
    # three of the four partitions live on another chip than the home one
    assert counts["mesh_home_bytes"] > 0
    assert counts["mesh_escalations"] >= 0
    assert 0 < counts["mesh_bytes"] <= counts["mesh_slot_bytes"]
    # one number under two names: the version-1 key and the new count
    assert counts["mesh_bytes"] == led["mesh_bytes"]
    assert _leaf_sum(done, "exchange_route_all_to_all") \
        == MESH_EXCHANGES[plan]
    assert _leaf_sum(done, "exchange_route_demoted") == 0
    # the stage's program calls are counted like every other program's
    assert counts["program_calls"] > 0 and counts["readbacks"] > 0


@pytest.mark.parametrize("plan", PLANS)
def test_mesh_stage_reads_its_exchanges_with_the_cut_program(plan, answers):
    """One call a batch: one a mesh partition that was brought anything
    — its sources' slices merged into the one batch (PR 49) — and one a
    batch the host route delivers (the range and the single exchange of
    the stage's tail)."""
    _table, done = answers[plan]["mesh"]
    counts = done["cost_ledger"]["counts"]
    batches, slices = counts["mesh_read_batches"], counts["mesh_read_slices"]
    delivered = done["shuffle_exchange_read"]["output_batches"]
    mesh_cuts = counts["program_calls_by_site"][READ_CUT] \
        - (delivered - batches)
    partitions = 4 * counts["mesh_rounds"]      # one round an exchange
    assert mesh_cuts == batches <= partitions
    assert batches <= slices <= 4 * batches
    if plan in WIDE:
        assert slices == WIDE_SLICES[plan]
        # thousands of (store, item) groups fill every partition of an
        # ``sa`` exchange; the 12 stores of the third may leave one empty
        assert batches >= partitions - (plan in ("q65", "q65m"))
    # the route without a mesh reads every batch with one call
    _table, single = answers[plan]["single"]
    assert single["cost_ledger"]["counts"]["program_calls_by_site"][
        READ_CUT] == single["shuffle_exchange_read"]["output_batches"]


@pytest.mark.parametrize("plan", PLANS)
def test_mesh_stage_runs_its_global_sort_as_programs(plan, answers):
    """The stage's tail — range exchange 4 → 4, a sort a partition, the
    gather 4 → 1 — by site: one split call a map batch of the two
    exchanges (and no ``sort_by_pid`` with eager partition ids), one
    sample call a map batch of the range exchange, one sort a partition
    and the limit's — no concatenation before it — and fewer row counts
    read than before."""
    _table, done = answers[plan]["mesh"]
    counts = done["cost_ledger"]["counts"]
    sites = counts["program_calls_by_site"]
    sampled = sites[RANGE_SAMPLE]
    assert 1 <= sampled <= 4
    # the range exchange's map batches, then the gather's (a partition
    # the bounds left empty sends it nothing)
    assert sampled + 1 <= sites[SPLIT] <= sampled + 4
    assert "parallel.exchange.sort_by_pid" not in sites
    assert sites["ops.sort.sort"] >= 2
    # a partition's maps' rows reach its sort as ONE batch (PR 49):
    # nothing is left to concatenate first (q65 / q65m sort more than
    # their tail: their joins' inputs)
    assert plan in ("q65", "q65m") or CONCAT not in sites
    if plan in WIDE:
        # hundreds of rows from four maps in every partition
        assert sites[SPLIT] == 8
        assert sites["ops.sort.sort"] >= 4
    assert counts["row_syncs"] < ROW_SYNCS_PR40[plan]
    # the sample's readback is the one it always was: no readback added
    assert counts["readbacks"] > 0


def test_one_partition_task_calls_no_split_program(stage, tmp_path_factory):
    """A one-chip cell's task — the benchmark's q3 and q65 over ONE
    split, one partition — runs no exchange: no split, no range sample
    and no read-cut call in its frame."""
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import cell, datagen

    from auron_tpu.frontend.session import Session
    root = str(tmp_path_factory.mktemp("tpcds_one_chip"))
    arrow = datagen.generate(seed=2_147_483_659, scale=0.02)
    splits = datagen.write_splits(root, "store_sales",
                                  arrow["store_sales"], 16_384)
    dims = {name: datagen.write_whole(root, name, arrow[name])
            for name in arrow if name != "store_sales"}
    session = Session()
    try:
        for plan in ("q3", "q65"):
            task = cell.load_module("plans", plan).build(
                session, dims, [splits[0][0]], 1).task_bytes(0)
            table, done = stage.client().execute(task)
            sites = done["cost_ledger"]["counts"]["program_calls_by_site"]
            assert table.num_rows > 0 and sites["ops.sort.sort"] >= 1
            assert not {SPLIT, RANGE_SAMPLE, READ_CUT} & set(sites), sites
    finally:
        session.close()


@pytest.mark.parametrize("plan", STAR)
def test_mesh_stage_moves_the_columns_its_plan_reads(plan, stage, answers):
    """The planner's required-columns pass (``ir/pruning.py``) narrows
    the scans before the stage's input spec is taken from them: all four
    shards stack the same three fact columns."""
    table, done = answers[plan]["mesh"]
    counts = done["cost_ledger"]["counts"]
    # data + validity a column (chars + lens + validity the one string),
    # and the row count a batch: 4 fact batches of 3 columns, date_dim's
    # 3 columns, item's 4
    assert counts["h2d_transfers"] == SPLITS_PER_TASK * 7 + 7 + 10
    assert counts["scan_columns_read"] == SPLITS_PER_TASK * 3 + 3 + 4
    assert counts["scan_columns_pruned"] > counts["scan_columns_read"]
    assert _leaf_sum(done, "exchange_route_all_to_all") >= 1
    assert table.num_rows == stage.oracle(plan).num_rows


@pytest.mark.parametrize("plan", PLANS)
def test_one_chip_frame_has_no_mesh_count(plan, answers):
    _table, done = answers[plan]["single"]
    led = done["cost_ledger"]
    assert all(led["counts"][k] == 0 for k in MESH_COUNTS)
    assert led["mesh_bytes"] == 0
    assert _leaf_sum(done, "exchange_route_all_to_all") == 0
    layers = led["layers_s"]
    assert sum(layers.values()) == pytest.approx(led["wall_s"], abs=1e-5)
    split = led["exchange_s"]
    assert sum(split.values()) == pytest.approx(layers["exchange"], abs=1e-5)
    assert all(split[k] == 0 for k in MESH_SPAN_KEYS)


def _recorded_spans(run):
    """``run()`` with ``auron.trace.enabled`` and the spans of ITS trace.

    The tracer is the process's: whatever thread opens a span while the
    setting is on records it when it closes, be that after this
    function's ``trace.reset()`` — an earlier stage's root closes on
    its server thread after the DONE frame has gone out, and a thread
    another test file left behind in this worker closes its spans when
    it pleases. Read whole, the tracer handed a later test such
    strangers' spans (and let a stale ``serve.task`` end its wait for
    the root). So the run gets a trace of its own — the client joins
    the scope opened here and the server's handler thread adopts its id
    off the wire — and only that trace's spans are the run's."""
    conf = cfg.get_config()
    conf.set(cfg.TRACE_ENABLED, True)
    try:
        with trace.query_scope("test_mesh_deployment") as scope:
            result = run()
        # the root span closes on the server thread after the DONE frame
        deadline = time.monotonic() + 30.0
        while True:
            spans = trace.tracer().spans(scope.trace_id)
            if any(s.name == "serve.task" for s in spans) \
                    or time.monotonic() > deadline:
                return result, spans
            time.sleep(0.01)
    finally:
        conf.unset(cfg.TRACE_ENABLED)
        trace.reset()


def test_recorded_spans_are_the_runs_own(stage, answers):
    """A stranger's span — recorded under another trace while the run
    is in flight, as a thread left over from an earlier stage records
    its own — is not among the run's, and the run's root is."""
    stop = threading.Event()

    def stranger():
        with trace.query_scope("stranger"):
            while not stop.is_set():
                with trace.layer_span("exchange", "mesh_round"):
                    time.sleep(0.001)

    th = threading.Thread(target=stranger, daemon=True)

    def run():
        th.start()
        try:
            return stage("q3", False)
        finally:
            stop.set()
            th.join(timeout=30)

    _result, spans = _recorded_spans(run)
    assert not th.is_alive()
    names = {s.name for s in spans}
    assert "serve.task" in names and "op.hash_join" in names
    assert len({s.trace_id for s in spans}) == 1
    assert not names & set(MESH_SPANS)


def test_the_three_spans_lie_inside_the_exchange_layer(stage, answers):
    """With tracing on the spans are recorded: each of the three is a
    child of ``shuffle.materialize`` (or of another of its children), and
    their time is part of ``layers_s.exchange``."""
    (_table, done), spans = _recorded_spans(lambda: stage("q3", True))
    by_id = {s.span_id: s for s in spans}
    mine = [s for s in spans if s.name in MESH_SPANS]
    assert {s.name for s in mine} == set(MESH_SPANS)

    def under_materialize(s):
        while s.parent_id in by_id:
            s = by_id[s.parent_id]
            if s.name == "shuffle.materialize":
                return True
        return False

    assert all(under_materialize(s) for s in mine)
    led = done["cost_ledger"]
    rounds = [s for s in mine if s.name == "exchange.mesh_round"]
    assert len(rounds) == led["counts"]["mesh_rounds"] \
        + led["counts"]["mesh_escalations"]
    # the fence is the round's own readback, inside its span
    readbacks = [s for s in spans if s.name == "op.readback"
                 and by_id.get(s.parent_id) in rounds]
    assert len(readbacks) == len(rounds)
    # their time is the exchange layer's, but for the compiles that fell
    # inside them (the process's program guard may have cleared the
    # stage's programs since they were built): those are layers_s.compile
    inside_s = sum(s.dur_ns for s in mine) * 1e-9
    layers = led["layers_s"]
    assert 0 < inside_s <= layers["exchange"] + layers["compile"] + 1e-4


@pytest.mark.parametrize("plan", WIDE)
def test_every_hash_exchange_of_a_wide_stage_takes_the_mesh(plan, stage,
                                                            answers):
    """The recorded decisions of one stage, exchange by exchange: each
    ``HashPartitioning`` exchange of two or more outputs is on
    ``all_to_all``, and what stays on ``device_buffer`` says why in
    ``exchange_route``'s own words — another partitioning: the global
    sort's range exchange and the gather into the one partition the
    limit reads."""
    (_table, done), spans = _recorded_spans(lambda: stage(plan, True))
    routes = [s.attrs for s in spans if s.name == "exchange.route"]
    assert len(routes) == _leaf_sum(done, "exchange_route_all_to_all") \
        + _leaf_sum(done, "exchange_route_device_buffer")
    for r in routes:
        hashed = "HashPartitioning" in r["op"]
        if r["route"] == "all_to_all":
            assert hashed and r["partitions"] == 4 and r["reason"] == "mesh"
        else:
            assert r["route"] == "device_buffer"
            assert r["reason"] == "single_output" and r["partitions"] == 1 \
                or r["reason"].startswith("partitioning_") and not hashed
    on_mesh = [r for r in routes if r["route"] == "all_to_all"]
    assert len(on_mesh) == MESH_EXCHANGES[plan]
    assert sorted(r["reason"] for r in routes if r not in on_mesh) == [
        "partitioning_RangePartitioning", "partitioning_SinglePartitioning"]


def _meet_in_the_map_side(monkeypatch, parties, timeout_s=120.0):
    """Hook every pull of a mesh exchange's map side (the
    ``shuffle.map`` checkpoint that follows it): note whether the thread
    that pulled held the gang door, and make each thread's FIRST pull
    wait for ``parties`` threads to be at theirs. The barrier breaks —
    and every waiter raises — unless that many map sides are in flight
    at once."""
    from auron_tpu.ops.base import ExecContext
    from auron_tpu.parallel import mesh
    barrier = threading.Barrier(parties, timeout=timeout_s)
    met, pulls = set(), []
    checkpoint = ExecContext.checkpoint

    def hooked(self, site=""):
        if site == "shuffle.map":
            me = threading.current_thread()
            pulls.append(mesh.current_plane()._holder_thread is me)
            if me not in met:
                met.add(me)
                barrier.wait()
        return checkpoint(self, site)

    monkeypatch.setattr(ExecContext, "checkpoint", hooked)
    return met, pulls


def test_four_stages_at_the_gang_door_wait_and_answer_right(stage,
                                                            monkeypatch):
    """As many clients as task slots, each with a wide stage: the door
    closes round a round's sharded launch only, so the four map sides
    meet — each at its first pull, where a stage that held the door
    through its map side would keep the other three parked behind it
    until the barrier broke — and no pull of any exchange of the four
    stages, nested ones included, is made by the thread that holds the
    door. Every answer is the oracle's, on the mesh route."""
    from auron_tpu.it.comparator import QueryResultComparator
    from auron_tpu.parallel import mesh
    conf = cfg.get_config()
    conf.set(cfg.MESH_ENABLED, True)
    conf.set(cfg.MESH_DEVICES, 4)
    got, errors = {}, []
    met, pulls = _meet_in_the_map_side(monkeypatch, len(WIDE))

    def client(plan):
        try:
            got[plan] = stage.client().execute(stage.tasks[plan])
        except Exception as e:      # seen below, on the test's thread
            errors.append((plan, e))

    try:
        threads = [threading.Thread(target=client, args=(p,)) for p in WIDE]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = mesh.current_plane().stats()
    finally:
        conf.unset(cfg.MESH_ENABLED)
        conf.unset(cfg.MESH_DEVICES)
    assert not errors, errors
    assert len(met) == len(WIDE)
    # four map batches an exchange and round, each pulled door open
    assert len(pulls) >= 4 * sum(MESH_EXCHANGES[p] for p in WIDE)
    assert not any(pulls)
    assert stats["gang_holder"] is None and stats["gang_queued"] == 0
    for plan in WIDE:
        table, done = got[plan]
        res = QueryResultComparator(double_rel_tol=1e-7).compare(
            plan, table, stage.oracle(plan))
        assert res.ok, res.report()
        led = done["cost_ledger"]
        assert _leaf_sum(done, "exchange_route_all_to_all") \
            == MESH_EXCHANGES[plan]
        assert _leaf_sum(done, "exchange_route_demoted") == 0
        assert led["counts"]["mesh_gang_acquires"] \
            == led["counts"]["mesh_rounds"] == MESH_EXCHANGES[plan]
        split = led["exchange_s"]
        assert sum(split.values()) == pytest.approx(
            led["layers_s"]["exchange"], abs=1e-5)


@pytest.mark.parametrize("plan", PLANS)
def test_a_stage_takes_the_gang_door_once_a_round(plan, answers):
    """``counts.mesh_gang_acquires`` beside ``counts.mesh_rounds``: one
    door a round in a star-join stage, a check stage and a q65 stage —
    whose three exchanges nest, the per-store average's map side reading
    ``sa`` across ``sa``'s own exchange — and none off the mesh."""
    _table, done = answers[plan]["mesh"]
    counts = done["cost_ledger"]["counts"]
    assert counts["mesh_gang_acquires"] == counts["mesh_rounds"] \
        == MESH_EXCHANGES[plan]
    _table, single = answers[plan]["single"]
    assert single["cost_ledger"]["counts"]["mesh_gang_acquires"] == 0


# ---------------------------------------------------------------------------
# the door's scope on an exchange of several rounds (no cell has one)
# ---------------------------------------------------------------------------

ROUNDS = 4


def _rounds_exchange(seed, n_out=4):
    """A hash exchange 2 -> 4 whose maps hold four batches each: four
    all-to-all rounds on the 4-device mesh."""
    import numpy as np
    import pyarrow as pa

    from auron_tpu.columnar.arrow_bridge import schema_from_arrow
    from auron_tpu.exprs import ir
    from auron_tpu.io.parquet import MemoryScanOp
    from auron_tpu.parallel.exchange import ShuffleExchangeOp
    from auron_tpu.parallel.partitioning import HashPartitioning
    rng = np.random.default_rng(seed)
    n = 2 * ROUNDS * 250
    rb = pa.record_batch({
        "k": pa.array(rng.integers(0, 37, n), pa.int64()),
        "v": pa.array(list(range(n)), pa.int64()),
    })
    parts = [[rb.slice(o, 250) for o in range(m * n // 2,
                                              (m + 1) * n // 2, 250)]
             for m in range(2)]
    scan = MemoryScanOp(parts, schema_from_arrow(rb.schema), capacity=256)
    return ShuffleExchangeOp(scan, HashPartitioning((ir.ColumnRef(0),),
                                                    n_out),
                             input_partitions=2)


def _drain(ex, ctx):
    """Every partition of ``ex`` read in order, as one Arrow table."""
    import pyarrow as pa

    from auron_tpu.columnar.arrow_bridge import schema_to_arrow, to_arrow
    batches = [b for p in range(4) for b in ex.execute(p, ctx)]
    return pa.Table.from_batches(
        [to_arrow(b, ex.schema()) for b in batches if int(b.num_rows)],
        schema=schema_to_arrow(ex.schema()))


@pytest.fixture(scope="module")
def serial_rounds():
    """The exchange's answer off the mesh, by seed (asked for before
    ``mesh4`` turns the mesh on: the setting builds the plane anew)."""
    from auron_tpu.ops.base import ExecContext
    return {seed: _drain(_rounds_exchange(seed), ExecContext())
            for seed in (17, 18)}


@pytest.fixture()
def mesh4(serial_rounds):
    from auron_tpu.parallel import mesh
    conf = cfg.get_config()
    conf.set(cfg.MESH_ENABLED, True)
    conf.set(cfg.MESH_DEVICES, 4)
    try:
        yield mesh.current_plane()
    finally:
        conf.unset(cfg.MESH_ENABLED)
        conf.unset(cfg.MESH_DEVICES)


def test_two_exchanges_of_four_rounds_alternate_at_the_door(
        serial_rounds, mesh4, monkeypatch):
    """Two stages, four rounds each, on two threads: the first waits
    BETWEEN its first and its second round — in its map side, door open
    — until the second has completed a round, so the rounds alternate
    (a door held across the map side would keep the second parked and
    the first waiting for it). Between a round's stack and the fence of
    its last launch there is never another stage's: ``max_active`` 1.
    Both answers are the serial ones."""
    from auron_tpu.ops.base import ExecContext
    from auron_tpu.parallel import exchange, mesh
    plane = mesh4
    lock = threading.Lock()
    events = []                       # (thread name, "stack" | "fence")
    completed = []                    # thread name, a completed round each
    b_started, b_round_done = threading.Event(), threading.Event()
    pulls = {"a": 0}

    stack, run_round = mesh.stack_global_batch, exchange._run_mesh_round
    add_round = exchange._MeshExchangeBuffer.add_round
    checkpoint = ExecContext.checkpoint

    def note(kind):
        with lock:
            events.append((threading.current_thread().name, kind))

    def stacked(*a, **k):
        assert plane._holder_thread is threading.current_thread()
        note("stack")
        return stack(*a, **k)

    def fenced(*a, **k):
        try:
            return run_round(*a, **k)
        finally:
            assert plane._holder_thread is threading.current_thread()
            note("fence")

    def added(self, *a, **k):
        me = threading.current_thread().name
        assert plane._holder_thread is not threading.current_thread()
        with lock:
            completed.append(me)
        if me == "b":
            b_round_done.set()
        elif completed.count("a") == 1:
            b_started.set()           # a's first round is in: b may start
        return add_round(self, *a, **k)

    def hooked(self, site=""):
        if site == "shuffle.map" \
                and threading.current_thread().name == "a":
            pulls["a"] += 1
            if pulls["a"] == 3:       # the first pull of a's second round
                assert plane._holder_thread is not threading.current_thread()
                assert b_round_done.wait(120), \
                    "b completed no round while a was between two of its own"
        return checkpoint(self, site)

    monkeypatch.setattr(mesh, "stack_global_batch", stacked)
    monkeypatch.setattr(exchange, "_run_mesh_round", fenced)
    monkeypatch.setattr(exchange._MeshExchangeBuffer, "add_round", added)
    monkeypatch.setattr(ExecContext, "checkpoint", hooked)
    got, errors = {}, []
    acquired = plane.gang_acquired

    def run(name, seed, gate):
        try:
            assert gate is None or gate.wait(120)
            got[name] = _drain(_rounds_exchange(seed), ExecContext())
        except BaseException as e:      # seen below, on the test's thread
            errors.append((name, e))
            b_started.set()
            b_round_done.set()

    threads = [threading.Thread(target=run, name="a", args=("a", 17, None)),
               threading.Thread(target=run, name="b",
                                args=("b", 18, b_started))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert not errors, errors
    assert got["a"].equals(serial_rounds[17])
    assert got["b"].equals(serial_rounds[18])
    assert completed.count("a") == completed.count("b") == ROUNDS
    # a's first, then one of b's before a's second
    assert completed[0] == "a" and completed[1] == "b", completed
    # one door a round (a quota re-run stays inside its round's)
    assert plane.gang_acquired - acquired == 2 * ROUNDS
    assert plane.gang_holder() is None
    # a round's interval: its stack .. the last fence that thread made
    # before it stacked again; never two of them open at once
    intervals = []                    # [thread, first event, last event]
    for i, (who, kind) in enumerate(events):
        if kind == "stack":
            intervals.append([who, i, i])
        else:
            next(iv for iv in reversed(intervals) if iv[0] == who)[2] = i
    max_active = max(sum(a <= i <= b for _who, a, b in intervals)
                     for i in range(len(events)))
    assert max_active == 1, events
    assert sum(k == "stack" for _w, k in events) == 2 * ROUNDS


def test_parking_at_the_door_is_not_a_rounds_latency(serial_rounds, mesh4):
    """The guard's clock starts AFTER the door: an exchange that parks
    there for far over ten times a round's p50 — and over the straggler
    factor's four — books no straggler and, with
    ``auron.mesh.demote_on_straggler`` on, stays on the mesh."""
    from auron_tpu.ops.base import ExecContext
    from auron_tpu.runtime.lifecycle import CancelToken
    plane = mesh4
    for _ in range(2):                # arm the window: 4 rounds and more
        assert _drain(_rounds_exchange(17), ExecContext()) \
            .equals(serial_rounds[17])
    p50 = plane.round_stats.p50()
    assert p50 is not None and p50 > 0
    stragglers, demotions = plane.stragglers, dict(plane.demotions)
    conf = cfg.get_config()
    conf.set(cfg.MESH_DEMOTE_ON_STRAGGLER, True)
    got, errors = {}, []
    ctx = ExecContext()

    def run():
        try:
            got["t"] = _drain(_rounds_exchange(18), ctx)
        except BaseException as e:      # seen below, on the test's thread
            errors.append(e)

    t = threading.Thread(target=run)
    try:
        with plane.gang(CancelToken("holder")):
            t.start()
            deadline = time.monotonic() + 60
            while plane.stats()["gang_queued"] < 1:
                assert time.monotonic() < deadline and t.is_alive(), errors
                time.sleep(0.002)
            time.sleep(max(0.3, 50 * p50))
        t.join(120)
    finally:
        conf.unset(cfg.MESH_DEMOTE_ON_STRAGGLER)
    assert not errors, errors
    assert got["t"].equals(serial_rounds[18])
    m = ctx.metrics["shuffle_exchange"]
    assert m.counter("mesh_stragglers").value == 0
    assert m.counter("exchange_route_all_to_all").value == 1
    assert m.counter("exchange_route_demoted").value == 0
    assert m.counter("mesh_rounds").value == ROUNDS
    assert plane.stragglers == stragglers
    assert plane.demotions == demotions
    assert plane.gang_contended >= 1 and plane.gang_holder() is None


def test_a_cancel_between_two_rounds_finds_the_door_open(mesh4, monkeypatch):
    """The cancel battery's contract at the new seam: a cancel that lands
    in the map side between the first round and the second — door open —
    unwinds with the token's classified error, nobody holding the door or
    queued at it, the completed round's buffer unregistered."""
    import gc
    import tempfile

    from auron_tpu import errors
    from auron_tpu.memmgr.manager import MemManager
    from auron_tpu.memmgr.spill import SpillManager
    from auron_tpu.ops.base import ExecContext
    from auron_tpu.parallel import exchange
    from auron_tpu.runtime.lifecycle import CancelToken
    plane = mesh4
    token = CancelToken("between-rounds")
    checkpoint = ExecContext.checkpoint
    add_round = exchange._MeshExchangeBuffer.add_round
    seen = {"pulls": 0, "rounds": 0, "holder": "unset", "consumers": None}

    def added(self, *a, **k):
        seen["rounds"] += 1
        return add_round(self, *a, **k)

    def hooked(self, site=""):
        if site == "shuffle.map":
            seen["pulls"] += 1
            if seen["pulls"] == 3:    # the first pull of the second round
                seen["holder"] = plane.gang_holder()
                seen["consumers"] = len(mm.status()["consumers"])
                token.cancel("test: between two rounds")
        return checkpoint(self, site)

    monkeypatch.setattr(exchange._MeshExchangeBuffer, "add_round", added)
    monkeypatch.setattr(ExecContext, "checkpoint", hooked)
    acquired = plane.gang_acquired
    with tempfile.TemporaryDirectory() as d:
        mm = MemManager(total_bytes=1 << 24, min_trigger=0,
                        spill_manager=SpillManager(
                            host_budget_bytes=1 << 20, spill_dir=d))
        ctx = ExecContext(mem_manager=mm, cancel_event=token)
        with pytest.raises(errors.QueryCancelled):
            _drain(_rounds_exchange(17), ctx)
        # one round completed and its buffer was the manager's; the
        # cancel found the door open
        assert seen["rounds"] == 1 and seen["holder"] is None
        assert seen["consumers"] >= 1
        assert plane.gang_acquired - acquired == 1
        assert plane.gang_holder() is None
        assert plane.stats()["gang_queued"] == 0
        gc.collect()
        assert not mm.status()["consumers"]
        assert mm.spill_manager.live_disk_files() == 0


def test_one_chip_task_opens_none_of_the_three_spans(stage, answers):
    _result, spans = _recorded_spans(lambda: stage("q3", False))
    assert not {s.name for s in spans} & set(MESH_SPANS)


@pytest.mark.parametrize("hasher", ["murmur3_string", "xxhash64_string"])
def test_string_hash_with_a_constant_seed_inside_a_sharded_stage(hasher):
    """ROADMAP D10's cause: the stage program hashes a string key FIRST
    (q43's partition key, q62's combine group key), so a constant seed is
    the carry of the hasher's loop — it must enter the loop varying over
    the mesh axis like the shard's strings it is mixed with. Same hashes
    as outside the mesh."""
    import numpy as np
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from auron_tpu.ops import hashing
    fn = getattr(hashing, hasher)
    rng = np.random.default_rng(7)
    n, width = 4 * 64, 40
    lens = rng.integers(0, width + 1, n).astype(np.int32)
    chars = np.where(np.arange(width)[None, :] < lens[:, None],
                     rng.integers(0, 256, (n, width)), 0).astype(np.uint8)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    sharded = jax.jit(shard_map(lambda c, ln: fn(c, ln, 42), mesh=mesh,
                                in_specs=(P("data"), P("data")),
                                out_specs=P("data")))
    np.testing.assert_array_equal(
        np.asarray(sharded(jnp.asarray(chars), jnp.asarray(lens))),
        np.asarray(fn(jnp.asarray(chars), jnp.asarray(lens), 42)))

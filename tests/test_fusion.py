"""Whole-stage fusion (ISSUE 2): planner pass, fragment semantics, the
central program-cache registry, and the compile-count budget for a
canonical fused pipeline. The heavyweight fused-vs-unfused TPC-DS
differential battery lives in test_zz_fusion_battery.py (late in the
collection order so the time-boxed tier-1 window is not displaced)."""

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu import config as cfg
from auron_tpu.frontend import Session, col, functions as F
from auron_tpu.runtime import programs


@pytest.fixture
def fusion_on():
    conf = cfg.get_config()
    conf.set("auron.fusion.enabled", True)
    yield conf
    conf.unset("auron.fusion.enabled")


@pytest.fixture
def fusion_off():
    conf = cfg.get_config()
    conf.set("auron.fusion.enabled", False)
    yield conf
    conf.unset("auron.fusion.enabled")


def _session(n=5000, seed=0):
    rng = np.random.default_rng(seed)
    s = Session()
    s.register("t", pa.table({
        "k": pa.array(rng.integers(0, 10, n), pa.int64()),
        "v": pa.array(rng.normal(size=n), pa.float64()),
        "s": pa.array([f"x{i % 7}" for i in range(n)]),
    }))
    return s


def _walk(op):
    yield op
    for c in op.children:
        yield from _walk(c)


# ---------------------------------------------------------------------------
# planner pass
# ---------------------------------------------------------------------------

def test_planner_fuses_row_local_chain(fusion_on):
    from auron_tpu.ops.fused import FusedStageOp
    s = _session()
    df = (s.table("t").filter(col("v") > 0.0)
          .with_column("w", col("v") * 2.0).limit(100))
    op = s.plan_physical(df)
    stages = [o for o in _walk(op) if isinstance(o, FusedStageOp)]
    assert len(stages) == 1
    names = [type(m).__name__ for m in stages[0].members]
    assert names == ["FilterOp", "ProjectOp", "LimitOp"]


def test_fusion_disabled_leaves_operators_alone(fusion_off):
    from auron_tpu.ops.fused import FusedStageOp
    s = _session()
    df = s.table("t").filter(col("v") > 0.0).with_column("w", col("v") * 2.0)
    op = s.plan_physical(df)
    assert not [o for o in _walk(op) if isinstance(o, FusedStageOp)]


def test_planner_never_fuses_across_stage_breakers(fusion_on):
    """Agg cores, joins, exchanges and sorts are stage breakers: they
    never appear inside a FusedStageOp, and chains stop at them."""
    from auron_tpu.ops.agg import AggOp
    from auron_tpu.ops.fused import FusedStageOp
    from auron_tpu.ops.joins import HashJoinOp
    from auron_tpu.ops.sort import SortOp
    from auron_tpu.parallel.exchange import ShuffleExchangeOp
    s = _session()
    t = s.table("t")
    df = (t.filter(col("v") > 0.0)
          .repartition(4, col("k"))
          .join(t.group_by("k").agg(F.count_star().alias("n")), on="k")
          .with_column("w", col("v") + 1.0)
          .group_by("k").agg(F.sum(col("w")).alias("sw"))
          .sort(col("k").asc())
          .limit(5))
    op = s.plan_physical(df)
    breakers = (AggOp, HashJoinOp, SortOp, ShuffleExchangeOp)
    fusable_names = {"FilterOp", "ProjectOp", "FilterProjectOp",
                     "ExpandOp", "LimitOp", "RenameColumnsOp"}
    saw_stage = saw_breaker = False
    for o in _walk(op):
        if isinstance(o, FusedStageOp):
            saw_stage = True
            for m in o.members:
                assert not isinstance(m, breakers), \
                    f"stage breaker {m!r} fused into a stage"
                assert type(m).__name__ in fusable_names, repr(m)
        if isinstance(o, breakers):
            saw_breaker = True
    assert saw_stage and saw_breaker
    assert df.collect().num_rows == 5


def test_preagg_projection_pushed_below_agg(fusion_on):
    """group/agg expressions over arbitrary exprs become ColumnRefs over
    a projection that joins the fused chain below the agg."""
    from auron_tpu.exprs import ir
    from auron_tpu.ops.agg import AggOp
    from auron_tpu.ops.fused import FusedStageOp
    s = _session()
    df = (s.table("t").filter(col("v") < 1.0)
          .group_by((col("k") % 3).alias("g"))
          .agg(F.sum(col("v") * 2.0).alias("sv")))
    op = s.plan_physical(df)
    aggs = [o for o in _walk(op) if isinstance(o, AggOp)]
    assert aggs
    agg = aggs[0]
    assert all(isinstance(e, ir.ColumnRef) for e in agg.group_exprs)
    assert all(a.arg is None or isinstance(a.arg, ir.ColumnRef)
               for a in agg.aggs)
    assert isinstance(agg.children[0], FusedStageOp)


# ---------------------------------------------------------------------------
# execution semantics (fused == unfused, streaming state)
# ---------------------------------------------------------------------------

def _collect_both(build):
    conf = cfg.get_config()
    try:
        conf.set("auron.fusion.enabled", False)
        off = build().collect()
        conf.set("auron.fusion.enabled", True)
        on = build().collect()
    finally:
        conf.unset("auron.fusion.enabled")
    return off, on


def test_fused_chain_bit_identical():
    def build():
        s = _session()
        return (s.table("t").filter(col("v") > 0.0)
                .with_column("w", col("v") * 3.5 + 1.0)
                .select("k", "w"))
    off, on = _collect_both(build)
    assert on.equals(off)


def test_fused_limit_across_batches():
    """A fused limit truncates across batch boundaries exactly like the
    host-side LimitOp (carry threads the remaining budget on device)."""
    def build():
        s = Session(batch_capacity=64)   # force many small batches
        s.register("u", pa.table({"i": pa.array(range(1000), pa.int64())}))
        return (s.table("u").filter(col("i") >= 10)
                .with_column("j", col("i") * 2).limit(137))
    off, on = _collect_both(build)
    assert on.equals(off)
    assert on.num_rows == 137


def test_fused_shuffle_split_bit_identical():
    """The exchange's fused split (chain + partition ids + sort-by-pid in
    one program) produces the same buckets as the classic path."""
    def build():
        s = _session(seed=3)
        return (s.table("t").filter(col("v") > -0.5)
                .repartition(4, col("k"))
                .with_column("w", col("v") + 1.0))
    off, on = _collect_both(build)
    assert on.equals(off)


def test_expand_fragment_matches_operator():
    """ExpandOp fused into a chain emits the same per-projection batches
    (grouping-sets lowering) as the standalone operator."""
    import pyarrow as _pa

    from auron_tpu.columnar.arrow_bridge import schema_from_arrow
    from auron_tpu.exprs import ir
    from auron_tpu.io.parquet import MemoryScanOp
    from auron_tpu.ops.expand import ExpandOp
    from auron_tpu.ops.fused import FusedStageOp
    from auron_tpu.ops.project import ProjectOp
    from auron_tpu.runtime.executor import collect

    from auron_tpu.columnar.schema import DataType

    rb = _pa.record_batch({"a": _pa.array([1, 2, 3], _pa.int64()),
                           "b": _pa.array([10.0, 20.0, 30.0])})
    scan = MemoryScanOp([[rb]], schema_from_arrow(rb.schema), capacity=8)
    projections = [
        [ir.ColumnRef(0), ir.ColumnRef(1)],
        [ir.ColumnRef(0), ir.Literal(None, DataType.FLOAT64)],
    ]
    expand = ExpandOp(scan, projections, ["a", "b"])
    proj = ProjectOp(expand, [ir.ColumnRef(0), ir.ColumnRef(1)], ["a", "b"])
    plain = collect(proj)
    fused = collect(FusedStageOp([expand, proj]))
    assert fused.equals(plain)


# ---------------------------------------------------------------------------
# central program-cache registry
# ---------------------------------------------------------------------------

def test_registry_counts_builds_and_hits(fusion_on):
    s = _session(seed=11)
    df = (s.table("t").filter(col("v") > 0.25)
          .with_column("w", col("v") * 0.125))
    p0 = programs.totals()
    df.collect()
    d1 = programs.delta(p0)
    assert d1.builds >= 1
    df2 = (_session(seed=12).table("t").filter(col("v") > 0.25)
           .with_column("w", col("v") * 0.125))
    p1 = programs.totals()
    df2.collect()
    d2 = programs.delta(p1)
    assert d2.builds == 0, \
        f"identical fused plan rebuilt {d2.builds} programs"
    assert d2.hits >= 1


def test_max_live_programs_bounds_registry():
    """auron.max_live_programs now bounds every compile site: once the
    registry holds >= limit live programs, maybe_clear drops the builder
    memos together with jax's compiled caches."""
    from auron_tpu.utils import compile_stats
    _session(seed=21).table("t").filter(col("v") > 0.5).collect()
    assert programs.total_live() >= 1
    assert compile_stats.maybe_clear(limit=1) is True
    assert programs.total_live() == 0


def test_task_metrics_carry_program_attribution(fusion_on):
    from auron_tpu.runtime.executor import ExecutionRuntime, TaskDefinition
    s = _session(seed=31)
    df = s.table("t").filter(col("v") > 0.0)
    op = s.plan_physical(df)
    rt = ExecutionRuntime(op, TaskDefinition())
    for _ in rt.batches():
        pass
    m = rt.finalize()
    assert "program_builds" in m and "program_hits" in m
    assert m["program_builds"] + m["program_hits"] >= 1


# ---------------------------------------------------------------------------
# compile-count budget (regression gate for the fusion win)
# ---------------------------------------------------------------------------

def test_q01_pipeline_compile_budget(fusion_on):
    """The canonical q01-shaped pipeline (filter → project → grouped agg
    → sort) must stay within a pinned program-build budget when fused —
    a silent fusion regression re-explodes compile counts and fails
    here first. Unique literals make the measurement cold even in a
    warm suite process."""
    s = _session(n=4000, seed=41)
    df = (s.table("t")
          .filter(col("v") > 0.1234567)          # unique → cold kernels
          .with_column("w", col("v") * 1.000321)
          .group_by("k").agg(F.sum(col("w")).alias("sw"),
                             F.count_star().alias("n"))
          .sort(col("k").asc()))
    p0 = programs.totals()
    out = df.collect()
    d = programs.delta(p0)
    assert out.num_rows == 10
    # measured: 4 builds (fused stage, agg batch-reduce, agg state-merge
    # at a second bucket, sort); headroom for capacity re-bucketing only
    assert d.builds <= 6, \
        f"fused q01 pipeline built {d.builds} programs (budget 6)"


# ---------------------------------------------------------------------------
# Fusion 2.0: map-side combine + cost-based plan selection
# ---------------------------------------------------------------------------

def _grouped_session(n=20000, keys=50, seed=0):
    """Dup-heavy grouped-agg shape: tiny key domain vs row count, the
    case map-side combine exists for."""
    rng = np.random.default_rng(seed)
    s = Session()
    s.register("g", pa.table({
        "k": pa.array(rng.integers(0, keys, n), pa.int64()),
        "v": pa.array(rng.integers(0, 1000, n), pa.int64()),
        "f": pa.array(rng.normal(size=n), pa.float64()),
    }))
    return s


def test_combine_eligibility_vocabulary(fusion_on):
    """combine_fold_reason: exact kinds (int sum/count) fold; a float
    sum refuses — segment-reducing in a different order than the
    reducer would reassociate float adds, and the fold's contract is
    bit-identity, not approximate equality."""
    from auron_tpu.ops.agg import AggOp
    s = _grouped_session()
    df = (s.table("g").repartition(4)
          .group_by("k").agg(F.sum(col("v")).alias("sv"),
                             F.count(col("v")).alias("n")))
    partials = [o for o in _walk(s.plan_physical(df))
                if isinstance(o, AggOp) and o.mode == "partial"]
    assert partials and partials[0].combine_fold_reason() is None
    df_f = (s.table("g").repartition(4)
            .group_by("k").agg(F.sum(col("f")).alias("sf")))
    partials = [o for o in _walk(s.plan_physical(df_f))
                if isinstance(o, AggOp) and o.mode == "partial"]
    assert partials
    assert partials[0].combine_fold_reason() == "float_sum_inexact"


def test_planner_stamps_combine_mode(fusion_on):
    """The fold walk stamps the exchange: combine on an eligible site,
    and no fold at all — with the explain reason — on an ineligible
    float sum."""
    from auron_tpu.parallel.exchange import ShuffleExchangeOp
    s = _grouped_session()
    df = (s.table("g").repartition(4)
          .group_by("k").agg(F.sum(col("v")).alias("sv")))

    def exchange_of(frame):
        ex = [o for o in _walk(s.plan_physical(frame))
              if isinstance(o, ShuffleExchangeOp)]
        assert ex
        return ex[0]

    assert exchange_of(df).combine_mode == "combine"
    df_f = (s.table("g").repartition(4)
            .group_by("k").agg(F.sum(col("f")).alias("sf")))
    ex = exchange_of(df_f)
    assert ex.combine_mode is None
    assert ex.combine_why == "float_sum_inexact"


def test_combine_bit_identical_and_fewer_shuffle_rows():
    """The fold's whole contract in one run: the combined plan and the
    unfused one (``auron.fusion.enabled`` off: the partial aggregate
    runs as its own operator) return byte-identical tables (values AND
    order), while the combined run books its rows-in/rows-out counters
    honestly — fewer rows cross the exchange than entered the fold."""
    from auron_tpu.ops.base import ExecContext
    conf = cfg.get_config()

    def run(fused: bool):
        conf.set("auron.fusion.enabled", fused)
        try:
            s = _grouped_session(seed=3)
            df = (s.table("g").repartition(4)
                  .group_by("k").agg(F.sum(col("v")).alias("sv"),
                                     F.count(col("v")).alias("n")))
            op = s.plan_physical(df)
            ctx = ExecContext()
            rows = []
            for p in range(df.num_partitions):
                for b in op.execute(p, ctx):
                    n = int(b.num_rows)
                    rows.extend(zip(
                        np.asarray(b.columns[0].data[:n]).tolist(),
                        np.asarray(b.columns[1].data[:n]).tolist(),
                        np.asarray(b.columns[2].data[:n]).tolist()))
            m = ctx.metrics["shuffle_exchange"]
            return (rows, m.counter("shuffle_bytes_live").value,
                    m.counter("combine_rows_in").value,
                    m.counter("combine_rows_out").value)
        finally:
            conf.unset("auron.fusion.enabled")

    rows_on, bytes_on, in_on, out_on = run(True)
    rows_off, bytes_off, in_off, out_off = run(False)
    assert rows_on == rows_off          # bit-identical, order included
    assert bytes_on > 0 and bytes_off > 0
    assert in_on > out_on > 0           # the fold merged groups...
    assert in_off == out_off == 0       # ...the unfused plan has no fold


def test_probe_into_consumer_fold_counted_and_bit_identical():
    """An inner join under a fused consumer chain runs gather + chain
    as ONE program (probe_consumer_folded counts it) and returns the
    same table as the unfused plan (``auron.fusion.enabled`` off)."""
    from auron_tpu.ops.base import ExecContext
    from auron_tpu.ops.fused import FusedStageOp
    from auron_tpu.ops.joins import HashJoinOp
    conf = cfg.get_config()
    rng = np.random.default_rng(9)
    n = 8000
    left = pa.table({
        "k": pa.array(rng.integers(0, 500, n), pa.int64()),
        "v": pa.array(rng.integers(0, 1000, n), pa.int64()),
    })
    right = pa.table({
        "k": pa.array(rng.integers(0, 500, 600), pa.int64()),
        "w": pa.array(rng.integers(0, 9, 600), pa.int64()),
    })

    def run(fused: bool):
        conf.set("auron.fusion.enabled", fused)
        try:
            s = Session()
            s.register("l", left)
            s.register("r", right)
            df = (s.table("l").join(s.table("r"), on="k")
                  .filter(col("v") > 100)
                  .with_column("z", col("v") + col("w")))
            op = s.plan_physical(df)
            stages = [o for o in _walk(op) if isinstance(o, FusedStageOp)
                      and isinstance(o.input, HashJoinOp)]
            assert bool(stages) == fused, \
                "consumer chain did not fuse over the join"
            ctx = ExecContext()
            rows = []
            for p in range(df.num_partitions):
                for b in op.execute(p, ctx):
                    m = int(b.num_rows)
                    rows.extend(zip(*(np.asarray(c.data[:m]).tolist()
                                      for c in b.columns)))
            folded = ctx.metrics["fused_stage"].counter(
                "probe_consumer_folded").value if fused else 0
            return sorted(rows), folded
        finally:
            conf.unset("auron.fusion.enabled")

    rows_folded, n_folded = run(True)
    assert n_folded >= 1
    rows_unfused, _ = run(False)
    assert rows_folded == rows_unfused


def test_combined_exchange_program_reused_across_runs(fusion_on):
    """Compile budget for the fold: the SAME dup-heavy grouped agg run
    twice builds its combined split program once — the combine stage
    rides the split-program cache key, it must not defeat it."""
    from auron_tpu.ops.base import ExecContext
    s = _grouped_session(seed=17)
    df = (s.table("g").repartition(4)
          .group_by("k").agg(F.sum(col("v")).alias("sv")))

    def run():
        op = s.plan_physical(df)
        ctx = ExecContext()
        for p in range(df.num_partitions):
            for _ in op.execute(p, ctx):
                pass

    run()
    p0 = programs.totals()
    run()
    d = programs.delta(p0)
    assert d.builds == 0, \
        f"second identical combined run rebuilt {d.builds} program(s)"
    assert d.hits >= 1


# ---------------------------------------------------------------------------
# a plan is a function of its bytes (ISSUE 47): what a task's operator
# tree depends on is its bytes, the configuration and the platform —
# nothing the process ran before
# ---------------------------------------------------------------------------

def _describe(op) -> str:
    """The operator tree as a planner decision record: every node's
    repr, each exchange's combine stamp and, per fused stage, whether it
    folds into the join beneath it."""
    from auron_tpu.ops.fused import FusedStageOp
    from auron_tpu.parallel.exchange import ShuffleExchangeOp
    lines = []

    def walk(o, depth):
        line = "  " * depth + repr(o)
        if isinstance(o, ShuffleExchangeOp):
            line += f" combine={o.combine_mode!r} why={o.combine_why!r}"
        if isinstance(o, FusedStageOp):
            line += f" consumer_fold={o._consumer_fold(None) is not None}"
        lines.append(line)
        for c in o.children:
            walk(c, depth + 1)

    walk(op, 0)
    return "\n".join(lines)


def _drain(op, n_partitions=1):
    from auron_tpu.ops.base import ExecContext
    ctx = ExecContext()
    for p in range(n_partitions):
        for _ in op.execute(p, ctx):
            pass


def _starved_probe(s, tmp_path=None):
    """An inner join whose probe finds a handful of candidates a batch,
    under a fused consumer chain (over parquet files when a server, which
    sees no session's tables, is to run it)."""
    rng = np.random.default_rng(5)
    tables = {"l": pa.table({
        "k": pa.array(rng.integers(0, 100_000, 8000), pa.int64()),
        "v": pa.array(rng.integers(0, 1000, 8000), pa.int64())}),
        "r": pa.table({
            "k": pa.array(np.arange(40), pa.int64()),
            "w": pa.array(np.arange(40), pa.int64())})}
    if tmp_path is None:
        for name, table in tables.items():
            s.register(name, table)
        left, right = s.table("l"), s.table("r")
    else:
        import pyarrow.parquet as pq
        for name, table in tables.items():
            pq.write_table(table, str(tmp_path / f"{name}.parquet"))
        left = s.read_parquet(str(tmp_path / "l.parquet"))
        right = s.read_parquet(str(tmp_path / "r.parquet"))
    return (left.join(right, on="k")
            .filter(col("v") >= 0).with_column("z", col("v") + col("w")))


def _high_cardinality_exchange(s):
    """A grouped sum whose every row is its own group: the map-side
    combine merges nothing."""
    s.register("u", pa.table({
        "k": pa.array(np.arange(20_000), pa.int64()),
        "v": pa.array(np.arange(20_000) % 7, pa.int64())}))
    return (s.table("u").repartition(4)
            .group_by("k").agg(F.sum(col("v")).alias("sv")))


def _scalar_subquery(s):
    """The starved join again, its filter bound by a scalar subquery:
    ``ScalarSubqueryBinderOp`` plans the tree only once it runs."""
    from auron_tpu.frontend import scalar_subquery
    df = _starved_probe(s)
    low = scalar_subquery(s.table("r").group_by().agg(
        F.min(col("w")).alias("m")))
    return df.filter(col("z") >= low)


@pytest.fixture(scope="module")
def tpcds_tables():
    import tempfile
    from auron_tpu.it.tpcds import generate
    with tempfile.TemporaryDirectory(prefix="fusion_same_tree_") as d:
        yield generate(d, scale=0.02)


@pytest.mark.parametrize("case", [
    "starved_probe", "high_cardinality_exchange", "q65", "q96",
    "mesh_exchange", "scalar_subquery"])
def test_the_same_bytes_plan_the_same_tree(case, fusion_on, monkeypatch,
                                           request):
    """Plan and run the same task bytes three times in one process: the
    three operator trees — node for node, with every exchange's combine
    stamp and every stage's consumer fold — are one tree. (The parent
    planned the first run from a prior and the later ones from what the
    earlier runs had counted: a starved probe lost its consumer fold, a
    high-cardinality exchange its combine.)"""
    import jax
    from auron_tpu.frontend import session as session_mod
    if case == "mesh_exchange":
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        fusion_on.set(cfg.MESH_ENABLED, True)
        request.addfinalizer(lambda: fusion_on.unset(cfg.MESH_ENABLED))
    planned: dict = {}
    real = session_mod.plan_from_bytes

    def spy(data, ctx=None):
        op = real(data, ctx)
        planned.setdefault(data, []).append(op)
        return op

    monkeypatch.setattr(session_mod, "plan_from_bytes", spy)
    if case in ("q65", "q96"):
        from auron_tpu.it.tpcds_queries import QUERIES
        tables = request.getfixturevalue("tpcds_tables")
        q = next(q for q in QUERIES if q.name == case)
        for _ in range(3):
            q.run(Session(), tables)
    else:
        build = {"starved_probe": _starved_probe,
                 "high_cardinality_exchange": _high_cardinality_exchange,
                 "mesh_exchange": _high_cardinality_exchange,
                 "scalar_subquery": _scalar_subquery}[case]
        s = Session()
        df = build(s)
        for _ in range(3):
            _drain(s.plan_physical(df), df.num_partitions)
    assert planned
    for data, ops in planned.items():
        assert len(ops) == 3, "each run plans the task's bytes once"
        trees = [_describe(op) for op in ops]
        assert trees[0] == trees[1] == trees[2], \
            f"{case}: the same {len(data)} bytes planned differently:\n" \
            + "\n--- then ---\n".join(trees)
    described = "\n".join(_describe(ops[0]) for ops in planned.values())
    if case in ("high_cardinality_exchange", "mesh_exchange"):
        assert "combine='combine'" in described
    else:
        assert "consumer_fold=True" in described


def test_a_served_task_runs_the_same_programs_on_every_send(fusion_on,
                                                            tmp_path):
    """Through ``AuronServer``: the first and the third send of the same
    task bytes report the same ``program_calls`` by site — no send's
    programs depend on what an earlier send counted."""
    from auron_tpu.runtime.serving import AuronClient, AuronServer
    blob = _starved_probe(Session(), tmp_path).task_bytes(0)
    server = AuronServer()
    server.serve_background()
    try:
        client = AuronClient(*server.address)
        by_site = []
        for _ in range(3):
            _table, done = client.execute(blob)
            counts = done["cost_ledger"]["counts"]
            by_site.append(counts["program_calls_by_site"])
        assert by_site[0] and by_site[0] == by_site[2], by_site
    finally:
        server.shutdown()


def test_planning_reads_no_process_state():
    """The fusion pass takes the tree and the configuration, nothing
    else: no fingerprint parameter, and no file under ``auron_tpu/ir``
    asks the journal for one."""
    import inspect
    import pathlib

    import auron_tpu.ir as ir_pkg
    from auron_tpu.ir import planner
    assert list(inspect.signature(planner.fuse_stages).parameters) \
        == ["op", "config"]
    assert list(inspect.signature(planner._fold_combine).parameters) \
        == ["op"]
    assert "plan_fp" not in {f.name for f in
                             planner.PlannerContext.__dataclass_fields__
                             .values()}
    for path in pathlib.Path(ir_pkg.__file__).parent.glob("*.py"):
        text = path.read_text()
        assert "plan_fingerprint" not in text, path.name
        assert "plan_fp" not in text, path.name
    assert not (pathlib.Path(ir_pkg.__file__).parent / "cost.py").exists()


def test_combine_stage_has_one_form(fusion_on):
    """The folded combine stage has one form: its builder and its trace
    signature take no mode, two plannings of one aggregate give one
    signature, and the second run of a high-cardinality aggregate — the
    site the parent re-planned as a pass-through — builds no program."""
    import inspect
    from auron_tpu.ops.agg import AggOp
    from auron_tpu.parallel.exchange import ShuffleExchangeOp
    assert list(inspect.signature(AggOp.combine_signature).parameters) \
        == ["self"]
    assert list(inspect.signature(AggOp.build_combine_stage).parameters) \
        == ["self"]
    s = Session()
    df = _high_cardinality_exchange(s)
    sigs = []

    def run():
        op = s.plan_physical(df)
        (ex,) = [o for o in _walk(op) if isinstance(o, ShuffleExchangeOp)
                 and o.combine_mode == "combine"]
        sigs.append(ex.child.combine_signature())
        _drain(op, df.num_partitions)

    run()
    p0 = programs.totals()
    run()
    assert sigs[0] == sigs[1]
    assert programs.delta(p0).builds == 0

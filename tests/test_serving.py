"""Serving boundary: the callNative/nextBatch/finalizeNative lifecycle
over a real socket, including a genuinely separate engine PROCESS
(VERDICT r3 directive 5; reference: JniBridge.java:49-55,
AuronCallNativeWrapper.java:78-190, rt.rs:76-300)."""

import os
import subprocess
import sys
import tempfile

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from auron_tpu.ir import pb
from auron_tpu.runtime.serving import AuronClient, AuronServer


def _dataset(tmp):
    rng = np.random.default_rng(3)
    n = 20_000
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 50, n), pa.int64()),
        "v": pa.array(rng.normal(size=n), pa.float64())})
    path = os.path.join(tmp, "t.parquet")
    pq.write_table(tbl, path)
    return path, tbl


def _task(path, partition_id=0, num_partitions=1):
    col = lambda i: pb.ExprNode(column=pb.ColumnRefE(index=i))
    plan = pb.PlanNode(agg=pb.AggNode(
        child=pb.PlanNode(parquet_scan=pb.ParquetScanNode(files=[path])),
        mode="complete", group_exprs=[col(0)],
        aggs=[pb.AggFunctionP(fn="sum", arg=col(1)),
              pb.AggFunctionP(fn="count", arg=col(1))]))
    return pb.TaskDefinition(plan=plan, partition_id=partition_id,
                             num_partitions=num_partitions,
                             task_id=7).SerializeToString()


def _check(table, metrics, tbl):
    got = table.to_pandas().set_index("k0").sort_index()
    exp = tbl.to_pandas().groupby("k")["v"].agg(["sum", "count"])
    assert len(got) == len(exp)
    assert np.allclose(got["a0"].values, exp["sum"].values)
    assert np.array_equal(got["a1"].values, exp["count"].values)
    assert metrics is not None and isinstance(metrics, dict)


def test_in_process_server_roundtrip(tmp_path):
    path, tbl = _dataset(str(tmp_path))
    srv = AuronServer()
    srv.serve_background()
    try:
        client = AuronClient(*srv.address)
        table, metrics = client.execute(_task(path))
        _check(table, metrics, tbl)
        # second task over the same server (per-task lifecycle)
        table2, _ = client.execute(_task(path))
        assert table2.num_rows == table.num_rows
    finally:
        srv.shutdown()


def test_error_propagates_with_traceback(tmp_path):
    srv = AuronServer()
    srv.serve_background()
    try:
        client = AuronClient(*srv.address)
        with pytest.raises(RuntimeError, match="engine error"):
            client.execute(_task(str(tmp_path / "missing.parquet")))
    finally:
        srv.shutdown()


def test_stats_frame_returns_live_table(tmp_path):
    """ISSUE 14 satellite: a first-frame STATS request answers the
    /queries live table + admission counters as JSON over the EXISTING
    wire protocol (no HTTP port needed), via AuronClient.stats()."""
    import json as _json
    import threading

    from conftest import spin_until

    path, tbl = _dataset(str(tmp_path))
    srv = AuronServer()
    srv.serve_background()
    try:
        client = AuronClient(*srv.address)
        # idle shape first
        st = client.stats()
        assert st["queries"] == []
        assert st["admission"]["admitted"] == 0
        assert "batches_sent" in st["server"]
        # now sample it WHILE a task executes: the live table must show
        # the serving query with its progress columns
        seen: list = []
        done = threading.Event()

        def run_task():
            try:
                client.execute(_task(path))
            finally:
                done.set()

        t = threading.Thread(target=run_task, daemon=True)
        t.start()

        def saw_live_row():
            if done.is_set():
                return True   # too fast — the post-run checks still run
            rows = [r for r in client.stats()["queries"]
                    if r["query"].startswith("serving-")]
            if rows:
                seen.extend(rows)
            return bool(rows)

        spin_until(saw_live_row, what="a live serving row on STATS")
        done.wait(60)
        t.join(10)
        if seen:   # raced-to-done is legal; a seen row must be sane
            row = seen[0]
            assert row["state"] in ("running", "queued")
            assert row["scheduler"] == "serving"
            assert row["tasks_total"] in (0, 1)
        st = client.stats()
        assert st["admission"]["admitted"] >= 1
        assert st["queries"] == []   # nothing left seated
        # the frame is plain JSON on the wire (firewalled clients can
        # speak it without this helper)
        from auron_tpu.runtime.serving import (KIND_DONE, KIND_STATS,
                                               read_frame, write_frame)
        import socket
        with socket.create_connection(srv.address, timeout=10) as s:
            write_frame(s, KIND_STATS, b"")
            kind, payload = read_frame(s)
        assert kind == KIND_DONE
        assert _json.loads(payload.decode())["admission"]["admitted"] >= 1
    finally:
        srv.shutdown()


def test_cache_hit_flag_and_stats(tmp_path):
    """PR 16 satellite: a repeated identical task is served from the
    warm-path result cache — the DONE frame carries ``cache_hit``, the
    streamed result is bit-identical to the fresh run, and
    ``AuronClient.stats()`` reports the cache totals."""
    from auron_tpu import config as cfg
    from auron_tpu.cache.result_cache import get_cache

    path, tbl = _dataset(str(tmp_path))
    conf = cfg.get_config()
    conf.set(cfg.CACHE_ENABLED, True)
    cache = get_cache()
    cache.clear(reset_counters=True)
    srv = AuronServer()
    srv.serve_background()
    try:
        client = AuronClient(*srv.address)
        fresh, m1 = client.execute(_task(path))
        _check(fresh, m1, tbl)
        assert not m1.get("cache_hit")
        cached, m2 = client.execute(_task(path))
        assert m2.get("cache_hit") is True
        assert cached.equals(fresh)          # bit-identical replay
        st = client.stats()
        assert st["cache"]["enabled"]
        assert st["cache"]["hits"] >= 1
        assert st["cache"]["entries"] >= 1
        assert "aot" in st
    finally:
        srv.shutdown()
        conf.unset(cfg.CACHE_ENABLED)
        cache.clear(reset_counters=True)


def test_two_process_serving(tmp_path):
    """The VERDICT gate: a fixture client in THIS process drives an
    engine server in a SEPARATE python process over TCP."""
    from auron_tpu.utils.envsafe import cpu_child_env
    path, tbl = _dataset(str(tmp_path))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = cpu_child_env(n_devices=2)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "auron_tpu.runtime.serving"],
        stdout=subprocess.PIPE, text=True, env=env, cwd=repo)
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("AURON_SERVING "), line
        host, port = line.split()[1].split(":")
        client = AuronClient(host, int(port), timeout_s=180)
        table, metrics = client.execute(_task(path))
        _check(table, metrics, tbl)
    finally:
        proc.terminate()
        proc.wait(timeout=30)


def test_empty_result_returns_typed_table(tmp_path):
    """DONE carries the output schema, so zero-row tasks produce a typed
    empty table instead of None (round-5 directive: executor-grade
    serving)."""
    path, _tbl = _dataset(str(tmp_path))
    col = lambda i: pb.ExprNode(column=pb.ColumnRefE(index=i))
    lit = pb.ExprNode(literal=pb.LiteralE(dtype=pb.DT_FLOAT64, f64=1e9))
    plan = pb.PlanNode(filter=pb.FilterNode(
        child=pb.PlanNode(parquet_scan=pb.ParquetScanNode(files=[path])),
        predicates=[pb.ExprNode(binary=pb.BinaryE(
            op=">", left=col(1), right=lit))]))
    task = pb.TaskDefinition(plan=plan, task_id=1).SerializeToString()
    srv = AuronServer()
    srv.serve_background()
    try:
        client = AuronClient(*srv.address)
        table, metrics = client.execute(task)
        assert table is not None and table.num_rows == 0
        assert table.column_names == ["k", "v"]
        assert table.schema.field("v").type == pa.float64()
        assert isinstance(metrics, dict)
    finally:
        srv.shutdown()


def test_client_disconnect_cancels_task(tmp_path):
    """A client that walks away mid-stream stops engine compute within
    one batch (reference: is_task_running checks, rt.rs:208-238); the
    flow-control window also bounds in-flight frames while it lived."""
    import socket as socketmod
    import time

    from auron_tpu.runtime.serving import (KIND_BATCH, KIND_SUBMIT,
                                           read_frame, write_frame)
    path, _tbl = _dataset(str(tmp_path))
    col = lambda i: pb.ExprNode(column=pb.ColumnRefE(index=i))
    # small batches -> many BATCH frames for one task
    plan = pb.PlanNode(project=pb.ProjectNode(
        child=pb.PlanNode(parquet_scan=pb.ParquetScanNode(
            files=[path], batch_rows=512)),
        exprs=[col(0), col(1)], names=["k", "v"]))
    task = pb.TaskDefinition(plan=plan, task_id=2).SerializeToString()
    srv = AuronServer(window=2)
    srv.serve_background()
    try:
        s = socketmod.create_connection(srv.address, timeout=60)
        write_frame(s, KIND_SUBMIT, task)
        kind, _payload = read_frame(s)
        assert kind == KIND_BATCH
        s.close()           # walk away mid-stream, no CANCEL frame
        deadline = time.time() + 30
        while time.time() < deadline and not srv.stats["cancelled"]:
            time.sleep(0.1)
        assert srv.stats["cancelled"] == 1
        # without ACKs the window bounded the stream: 2 in flight max
        assert srv.stats["batches_sent"] <= 2
        sent_after_cancel = srv.stats["batches_sent"]
        time.sleep(1.0)
        assert srv.stats["batches_sent"] == sent_after_cancel
    finally:
        srv.shutdown()


def _blocked_task(path):
    """A many-batch task (512-row scan batches) the producer cannot
    finish while the client withholds ACKs — a deterministic way to
    keep one serving slot occupied without faults or sleeps."""
    col = lambda i: pb.ExprNode(column=pb.ColumnRefE(index=i))
    plan = pb.PlanNode(project=pb.ProjectNode(
        child=pb.PlanNode(parquet_scan=pb.ParquetScanNode(
            files=[path], batch_rows=512)),
        exprs=[col(0), col(1)], names=["k", "v"]))
    return pb.TaskDefinition(plan=plan, task_id=3).SerializeToString()


def _sched_knobs(max_concurrent, queue_depth):
    from auron_tpu import config as cfg
    conf = cfg.get_config()
    conf.set(cfg.SCHED_MAX_CONCURRENT, max_concurrent)
    conf.set(cfg.SCHED_QUEUE_DEPTH, queue_depth)

    def restore():
        conf.unset(cfg.SCHED_MAX_CONCURRENT)
        conf.unset(cfg.SCHED_QUEUE_DEPTH)
    return restore


from conftest import spin_until as _spin


def test_cancel_while_queued_dequeues_without_starting(tmp_path):
    """Satellite regression (PR 7 mapping): a serving client that sends
    CANCEL — or disconnects — while its query is still QUEUED behind a
    full scheduler is dequeued cleanly: silent teardown, no executor
    spin-up, no consumer/spill ledger entry, no admission counted."""
    import socket as socketmod

    from auron_tpu.runtime.serving import (KIND_BATCH, KIND_CANCEL,
                                           KIND_SUBMIT, read_frame,
                                           write_frame)
    path, _tbl = _dataset(str(tmp_path))
    restore = _sched_knobs(1, 2)
    srv = AuronServer(window=2)
    srv.serve_background()
    try:
        # A occupies the ONLY slot: unACKed window blocks its producer
        sa = socketmod.create_connection(srv.address, timeout=60)
        write_frame(sa, KIND_SUBMIT, _blocked_task(path))
        kind, _ = read_frame(sa)
        assert kind == KIND_BATCH
        _spin(lambda: srv.scheduler.running_count() == 1,
              what="A running")
        # B queues, then CANCELs while queued
        sb = socketmod.create_connection(srv.address, timeout=60)
        write_frame(sb, KIND_SUBMIT, _blocked_task(path))
        _spin(lambda: srv.scheduler.queued_count() == 1, what="B queued")
        write_frame(sb, KIND_CANCEL, b"")
        _spin(lambda: srv.scheduler.stats()["dequeued"] == 1,
              what="B dequeued")
        # C queues, then DISCONNECTS while queued (same mechanism)
        sc = socketmod.create_connection(srv.address, timeout=60)
        write_frame(sc, KIND_SUBMIT, _blocked_task(path))
        _spin(lambda: srv.scheduler.queued_count() == 1, what="C queued")
        sc.close()
        _spin(lambda: srv.scheduler.stats()["dequeued"] == 2,
              what="C dequeued")
        st = srv.scheduler.stats()
        # only A was ever ADMITTED; B and C never started an executor
        assert st["admitted"] == 1
        assert st["dequeued_by_reason"].get("cancelled") == 2
        # teardown is the silent-cancel mapping, no ERROR frames owed
        write_frame(sa, KIND_CANCEL, b"")
        sa.close()
        sb.close()
        _spin(lambda: srv.scheduler.running_count() == 0,
              what="A released")
        assert srv.stats["cancelled"] >= 3
    finally:
        restore()
        srv.shutdown()


def test_overload_sheds_with_structured_admission_error(tmp_path):
    """Past the bounded queue the server rejects FAST with a structured
    AdmissionRejected ERROR frame (reason + retry_after_s on the first
    line) instead of stalling the client or crashing."""
    import socket as socketmod

    from auron_tpu.runtime.serving import (KIND_BATCH, KIND_CANCEL,
                                           KIND_ERROR, KIND_SUBMIT,
                                           read_frame, write_frame)
    path, _tbl = _dataset(str(tmp_path))
    restore = _sched_knobs(1, 0)          # no queue at all
    srv = AuronServer(window=2)
    srv.serve_background()
    try:
        sa = socketmod.create_connection(srv.address, timeout=60)
        write_frame(sa, KIND_SUBMIT, _blocked_task(path))
        kind, _ = read_frame(sa)
        assert kind == KIND_BATCH
        _spin(lambda: srv.scheduler.running_count() == 1,
              what="A running")
        sb = socketmod.create_connection(srv.address, timeout=60)
        write_frame(sb, KIND_SUBMIT, _blocked_task(path))
        kind, payload = read_frame(sb)
        assert kind == KIND_ERROR
        first = payload.decode().splitlines()[0]
        assert first.startswith("AdmissionRejected ")
        assert "reason=queue_full" in first
        assert "retry_after_s=" in first
        assert srv.stats["rejected"] == 1
        assert srv.scheduler.stats()["rejected_by_reason"] == \
            {"queue_full": 1}
        sb.close()
        write_frame(sa, KIND_CANCEL, b"")
        sa.close()
        _spin(lambda: srv.scheduler.running_count() == 0,
              what="A released")
    finally:
        restore()
        srv.shutdown()


@pytest.fixture(scope="module")
def spark_fixture_env(tmp_path_factory):
    """Small TPC-DS dataset + fixture plans + path rewrites, shared by the
    live-attach tests."""
    import json

    from auron_tpu.it.tpcds_data import generate, load_pandas
    root = tmp_path_factory.mktemp("serving_attach")
    tables = generate(str(root), scale=0.2)
    by_basename = {os.path.basename(f): f
                   for files in tables.values() for f in files}
    fixtures = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "fixtures")

    def fixture(name):
        with open(os.path.join(fixtures, name)) as f:
            return json.load(f)

    return fixture, by_basename, load_pandas(tables)


def test_two_process_live_attach_all_fixtures(spark_fixture_env):
    """Round-5 directive 3: an external process submits UNCONVERTED Spark
    plan.toJSON trees over the socket; the engine converts, sources
    fallback boundaries from the client, executes, and returns batches +
    the conversion report. All three recorded fixtures, including the
    fallback one."""
    from auron_tpu.integration.spark_converter import SparkPlanConverter
    from auron_tpu.ir.planner import PlannerContext, plan_from_bytes
    from auron_tpu.runtime.executor import ExecContext
    from auron_tpu.utils.envsafe import cpu_child_env
    fixture, by_basename, pd_tables = spark_fixture_env

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = cpu_child_env(n_devices=2)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "auron_tpu.runtime.serving"],
        stdout=subprocess.PIPE, text=True, env=env, cwd=repo)

    def fallback_provider(_table, exec_cls, columns):
        assert exec_cls == "BatchEvalPythonExec"
        ss = pd_tables["store_sales"]
        sub = ss[ss.ss_store_sk.notna()][["ss_store_sk",
                                          "ss_quantity"]].copy()
        sub["py_bucket"] = sub.ss_quantity % 3
        assert list(sub.columns) == columns
        return pa.Table.from_pandas(sub.reset_index(drop=True),
                                    preserve_index=False)

    def oracle(name):
        """In-process conversion + execution of the same fixture —
        engine-vs-engine equality proves the serving composition."""
        rewrite = lambda p: by_basename.get(os.path.basename(p), p)
        conv = SparkPlanConverter(path_rewrite=rewrite)
        node, report = conv.convert(fixture(name))
        ctx = PlannerContext()
        for table, cls, _attrs in report.boundaries:
            ctx.catalog[table] = fallback_provider(
                table, cls, [a.name for a in _attrs])
        op = plan_from_bytes(
            pb.TaskDefinition(plan=node).SerializeToString(), ctx)
        from auron_tpu.columnar.arrow_bridge import to_arrow
        out = [pa.Table.from_batches([to_arrow(b, op.schema())])
               for b in op.execute(0, ExecContext()) if int(b.num_rows)]
        return pa.concat_tables(out) if out else None

    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("AURON_SERVING "), line
        host, port = line.split()[1].split(":")
        client = AuronClient(host, int(port), timeout_s=300)

        for name, expect_fallbacks in (("spark_plan_q03.json", 0),
                                       ("spark_plan_q04_smj.json", 0),
                                       ("spark_plan_fallback.json", 1)):
            table, done = client.execute_plan(
                fixture(name), path_rewrites=by_basename,
                fallback_provider=fallback_provider)
            assert "report" in done, name
            assert len(done["report"]["fallbacks"]) == expect_fallbacks, \
                (name, done["report"])
            exp = oracle(name)
            assert table is not None, name
            if exp is None:      # genuinely empty result: typed, 0 rows
                assert table.num_rows == 0, name
                continue
            assert table.num_rows > 0, name
            se = exp.to_pandas().sort_values(exp.column_names) \
                .reset_index(drop=True)
            sg = table.to_pandas().sort_values(table.column_names) \
                .reset_index(drop=True)
            assert sg.shape == se.shape, name
            import pandas.testing as pdt
            pdt.assert_frame_equal(sg, se, check_exact=False, rtol=1e-9)
    finally:
        proc.terminate()
        proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# crash-safe journal serving surface (ISSUE 13): RESUME frame,
# CANCEL-by-id, structured unknown-query verdicts
# ---------------------------------------------------------------------------

def _arm_journal(d):
    from auron_tpu import config as cfg
    conf = cfg.get_config()
    conf.set(cfg.JOURNAL_DIR, d)

    def restore():
        conf.unset(cfg.JOURNAL_DIR)
    return restore


def test_cancel_by_id_unknown_is_structured():
    """A first-frame CANCEL naming an id the server never saw gets the
    STRUCTURED verdict (UnknownQuery reason=unknown_query_id ...) on
    the ERROR frame's first line — not a generic traceback."""
    srv = AuronServer()
    srv.serve_background()
    try:
        client = AuronClient(*srv.address)
        with pytest.raises(RuntimeError) as ei:
            client.cancel_query("serving-999999")
        first = str(ei.value).splitlines()[1]   # after "engine error:"
        assert first.startswith("UnknownQuery reason=unknown_query_id")
        assert "serving-999999" in first
    finally:
        srv.shutdown()


def test_cancel_by_id_cancels_live_query(tmp_path):
    """CANCEL over a FRESH connection (reconnect/admin path) stops a
    query another socket is driving."""
    import socket as socketmod

    from auron_tpu.runtime.serving import KIND_BATCH, KIND_SUBMIT, \
        read_frame, write_frame
    path, _tbl = _dataset(str(tmp_path))
    srv = AuronServer(window=2)
    srv.serve_background()
    try:
        s = socketmod.create_connection(srv.address, timeout=60)
        write_frame(s, KIND_SUBMIT, _blocked_task(path))
        kind, _ = read_frame(s)
        assert kind == KIND_BATCH          # producer now parked un-ACKed
        _spin(lambda: srv._live_queries, what="query registration")
        qid = next(iter(srv._live_queries))
        client = AuronClient(*srv.address)
        assert client.cancel_query(qid) is True
        _spin(lambda: srv.stats["cancelled"] == 1,
              what="cancel teardown")
        s.close()
    finally:
        srv.shutdown()


def test_resume_unknown_query_is_structured(tmp_path):
    """RESUME for an id with no journal behind it: the structured
    ResumeUnavailable verdict names WHY (journaling_disabled with the
    plane disarmed, no_journal with it armed)."""
    srv = AuronServer()
    srv.serve_background()
    try:
        client = AuronClient(*srv.address)
        with pytest.raises(RuntimeError) as ei:
            client.resume("serving-31337")
        assert "ResumeUnavailable reason=journaling_disabled" \
            in str(ei.value)
        restore = _arm_journal(str(tmp_path / "journal"))
        try:
            with pytest.raises(RuntimeError) as ei:
                client.resume("serving-31337")
            assert "ResumeUnavailable reason=no_journal" in str(ei.value)
        finally:
            restore()
        assert srv.stats["resume_refused"] == 2
    finally:
        srv.shutdown()


def test_reconnect_after_server_restart_resumes(tmp_path):
    """The RESUME regression gate: a journaled task dies mid-run on
    server A (injected non-transient fault — the in-process stand-in
    for the server process being killed), server A goes away, and a
    client reconnecting to a FRESH server B continues the query by id:
    same rows a clean SUBMIT would have produced."""
    import glob as globmod

    from auron_tpu import config as cfg
    from auron_tpu.runtime import faults
    from auron_tpu.runtime import journal as jrn

    path, tbl = _dataset(str(tmp_path))
    jdir = str(tmp_path / "journal")
    restore = _arm_journal(jdir)
    conf = cfg.get_config()
    try:
        srv_a = AuronServer()
        srv_a.serve_background()
        try:
            client = AuronClient(*srv_a.address)
            conf.set(cfg.FAULTS_PLAN, "device.compute:fatal@1.0")
            conf.set(cfg.FAULTS_SEED, 2)
            faults.reset()
            try:
                with pytest.raises(RuntimeError, match="engine error"):
                    client.execute(_task(path))
            finally:
                conf.unset(cfg.FAULTS_PLAN)
                conf.unset(cfg.FAULTS_SEED)
                faults.reset()
        finally:
            srv_a.shutdown()
        # the failed task's journal survived the server: the RESUME
        # inventory (simulate full process death for the stem ledger)
        journals = globmod.glob(os.path.join(jdir, "*.journal"))
        assert len(journals) == 1
        stem = os.path.splitext(os.path.basename(journals[0]))[0]
        jrn._forget_open_stems()

        srv_b = AuronServer()
        srv_b.serve_background()
        try:
            client = AuronClient(*srv_b.address)
            table, metrics = client.resume(stem)
            _check(table, metrics, tbl)
            # the resumed journal completed: inventory consumed
            assert not globmod.glob(os.path.join(jdir, "*.journal"))
            # and a second RESUME of the same id is now the structured
            # unknown verdict (journals are deleted at completion)
            with pytest.raises(RuntimeError) as ei:
                client.resume(stem)
            assert "ResumeUnavailable reason=no_journal" in str(ei.value)
        finally:
            srv_b.shutdown()
    finally:
        restore()


def test_wire_resume_collect_scope_streams_every_partition(tmp_path):
    """Regression (caught by the e2e crash drive): a SESSION-journaled
    query is "collect"-scoped — the dead driver owned the fan-out over
    num_partitions partitions — so the RESUME frame must stream ALL of
    them, not just partition 0 of the journaled TaskDefinition.  The
    reassembled stream is bit-identical (order included) to the fresh
    Session run; a serving-journaled task stays at task scope (the
    host engine still owns the other partitions)."""
    import glob as globmod

    from auron_tpu import errors
    from auron_tpu.frontend.dataframe import col, functions as F
    from auron_tpu.frontend.session import Session
    from auron_tpu.runtime import journal as jrn

    path, _tbl = _dataset(str(tmp_path))

    def _df(s):
        return (s.read_parquet([path], partitions=2)
                .repartition(2, "k")
                .group_by("k")
                .agg(F.sum(col("v")).alias("sv"),
                     F.count(col("v")).alias("n")))

    s0 = Session()
    fresh = s0.execute(_df(s0))

    jdir = str(tmp_path / "journal")
    restore = _arm_journal(jdir)
    try:
        s1 = Session()
        orig = jrn.QueryJournal.record_shuffle_commit

        def hook(self, *a, **kw):
            orig(self, *a, **kw)
            raise errors.InjectedFatalError(
                "simulated crash after first shuffle commit",
                site="test.crash")

        jrn.QueryJournal.record_shuffle_commit = hook
        try:
            with pytest.raises(errors.AuronError):
                s1.execute(_df(s1))
        finally:
            jrn.QueryJournal.record_shuffle_commit = orig
        journals = globmod.glob(os.path.join(jdir, "*.journal"))
        assert len(journals) == 1
        stem = os.path.splitext(os.path.basename(journals[0]))[0]
        # simulate the driver process dying (SIGKILL loses the open-
        # stem ledger with the process)
        s1._journals = []
        jrn._forget_open_stems()

        srv = AuronServer()
        srv.serve_background()
        try:
            client = AuronClient(*srv.address)
            table, metrics = client.resume(stem)
            # every driver partition streamed, bit-identical order
            # included — NOT just partition 0's prefix
            assert table.equals(fresh)
            assert metrics.get("num_partitions") == 2
            assert not globmod.glob(os.path.join(jdir, "*.journal"))
        finally:
            srv.shutdown()
    finally:
        restore()


@pytest.mark.parametrize("ending", ["done", "error"])
def test_a_slow_reader_of_a_finished_engine_gets_every_frame(ending,
                                                             monkeypatch):
    """The engine sends what its window allows, then DONE (or ERROR), and
    closes; a reader the machine keeps waiting acknowledges its first
    batch after that, the engine's host answers with a reset, and the
    second ACK fails to write. The ACK is flow control for an engine that
    no longer waits: the frames already sent are the answer (PR 40: under
    the tier-1 workers this lost ``test_mesh_deployment.py``'s shared
    stage answers to a ``BrokenPipeError``, and would have hidden an
    ERROR frame the same way)."""
    import json
    import socket
    import threading
    import time

    from auron_tpu import errors
    from auron_tpu.runtime import serving

    rb = pa.record_batch({"x": pa.array([1, 2, 3])})
    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)

    def engine():
        conn, _ = listener.accept()
        with conn:
            serving.read_frame(conn)                       # SUBMIT
            for _ in range(3):
                serving.write_frame(conn, serving.KIND_BATCH,
                                    serving._ipc_bytes(rb))
            if ending == "done":
                serving.write_frame(conn, serving.KIND_DONE,
                                    json.dumps({"rows": 9}).encode())
            else:
                serving.write_frame(conn, serving.KIND_ERROR, b"boom")

    th = threading.Thread(target=engine, daemon=True)
    th.start()
    decode = serving._ipc_batch

    def slow_decode(payload):
        time.sleep(0.2)
        return decode(payload)

    monkeypatch.setattr(serving, "_ipc_batch", slow_decode)
    client = AuronClient("127.0.0.1", listener.getsockname()[1],
                         timeout_s=30)
    try:
        if ending == "done":
            table, done = client.execute(b"task")
            assert table.num_rows == 9 and done == {"rows": 9}
        else:
            with pytest.raises(errors.RemoteEngineError, match="boom"):
                client.execute(b"task")
    finally:
        th.join(timeout=30)
        listener.close()
    assert not th.is_alive()

"""The hash join's launch budget (PR 29; tier-1, on the CPU: counts only).

Every section of ``HashJoinOp`` that touches a device array runs inside a
program handed out by ``runtime/programs.py``: one ``ops.joins.build_side``
a build, one candidate search and one ``ops.joins.match`` a probe batch
(plus ``ops.joins.unmatched_build`` once, for right / full). Before PR 29
the join launched ~220 eager single-primitive programs a probe batch and
240 a build (2,528 a task of the benchmark's q3); this file is the guard
that keeps such glue from growing back.

A launch is a ``PjitFunction(<name>)`` event of the profiler's host plane
whose innermost operator span is ``auron:op/hash_join``; it is *eager*
when ``<name>`` is no ``auron_<site>`` program. (jax writes two events a
call, so every count here is in events.)
"""

import glob
import os
import sys

import pyarrow as pa
import pytest

from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.obs import trace
from auron_tpu.ops.joins import HashJoinOp
from auron_tpu.runtime.executor import collect

C = ir.ColumnRef
_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")

JOIN_TYPES = ("inner", "left", "right", "full", "semi", "anti", "existence")
PROBE_BATCHES = 3


def _launches(trace_dir, spans=("auron:op/hash_join",)) -> tuple:
    """(eager, engine) launch names whose innermost operator span is one
    of ``spans``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    eager, engine = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns,
                    e.name.split("#")[0]) for e in line.events]
            ops = [ev for ev in evs if ev[2].startswith("auron:op/")
                   and ev[2] != "auron:op/readback"]
            for s, e, name in evs:
                if not name.startswith("PjitFunction("):
                    continue
                over = [op for op in ops if op[0] <= s and e <= op[1]]
                if not over or max(over)[2] not in spans:
                    continue
                prog = name[len("PjitFunction("):-1]
                (engine if prog.startswith("auron_") else eager).append(prog)
    return eager, engine


def _traced(fn, trace_dir, spans=("auron:op/hash_join",)):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, _launches(trace_dir, spans)


def _join(join_type):
    """Three probe batches against one build batch: duplicate build keys,
    null keys on both sides, a probe batch with no candidate at all."""
    probe = [pa.record_batch({
        "lk": pa.array(keys, pa.int64()),
        "lv": pa.array([f"p{i}{j}" for j in range(len(keys))], pa.string()),
    }) for i, keys in enumerate(([1, 2, 3, None, 2], [7, 8, 9, 7, None],
                                 [4, 4, 2, 5, 6]))]
    build = pa.record_batch({
        "rk": pa.array([2, 2, 4, None, 6], pa.int64()),
        "rv": pa.array([20, 21, 40, 99, 60], pa.int64()),
    })
    scan = MemoryScanOp([probe], schema_from_arrow(probe[0].schema),
                        capacity=8)
    bscan = MemoryScanOp([[build]], schema_from_arrow(build.schema),
                         capacity=8)
    return HashJoinOp(scan, bscan, [C(0)], [C(0)], join_type=join_type)


@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_a_join_launches_programs_only(join_type, tmp_path):
    collect(_join(join_type))     # warm: compiles stay out of the trace

    def run():
        with trace.task_scope("q-" + join_type) as acc:
            table = collect(_join(join_type))
        return table, dict(acc.calls_by_site)

    (table, sites), (eager, engine) = _traced(run, tmp_path)
    assert eager == []
    # batch 2 ([7, 8, 9, 7, None]) has no candidate: inner and right skip
    # its match program, the others still owe its probe rows an answer
    matches = PROBE_BATCHES - (join_type in ("inner", "right"))
    want = {"ops.joins.build_side": 1, "hashtable.join_index": 1,
            "ops.joins.probe_count": PROBE_BATCHES,
            "ops.joins.match": matches}
    if join_type in ("right", "full"):
        want["ops.joins.unmatched_build"] = 1
    assert {s: n for s, n in sites.items() if s in want} == want
    assert sorted(set(engine)) == sorted(
        "auron_" + s.replace(".", "_") for s in want)
    assert table.num_rows > 0


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server in this process, the benchmark's TPC-DS tables at scale
    0.02 and its q3 over one split of two scan batches."""
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    from harness import cell, datagen

    from auron_tpu.frontend.session import Session
    from auron_tpu.runtime.serving import AuronClient, AuronServer
    root = str(tmp_path_factory.mktemp("tpcds"))
    arrow = datagen.generate(seed=2_147_483_659, scale=0.02)
    splits = datagen.write_splits(root, "store_sales",
                                  arrow["store_sales"], 16_384)
    dims = {name: datagen.write_whole(root, name, arrow[name])
            for name in arrow if name != "store_sales"}
    session = Session()
    task = cell.load_module("plans", "q3").build(
        session, dims, [splits[0][0]], 1).task_bytes(0)
    # the guard on live programs clears every cache at a task's end once
    # this worker's earlier modules have piled enough up: the traced task
    # would then trace its programs again, and tracing dispatches eagerly
    from auron_tpu import config as cfg
    conf = cfg.get_config()
    guard = conf.get(cfg.MAX_LIVE_PROGRAMS)
    conf.set(cfg.MAX_LIVE_PROGRAMS, 0)
    server = AuronServer()
    server.serve_background()
    host, port = server.address

    def run():
        table, metrics = AuronClient(host, port,
                                     timeout_s=600).execute(task)
        assert table.num_rows > 0
        return metrics["cost_ledger"]

    run()                       # warm: every program of q3 is built
    yield run
    server.shutdown()
    server.server_close()
    session.close()
    conf.set(cfg.MAX_LIVE_PROGRAMS, guard)


def test_q3_of_the_benchmark_launches_programs_only(served, tmp_path):
    """2,528 eager events a task before PR 29 (1,498 over this split)."""
    led, (eager, engine) = _traced(served, tmp_path)
    assert eager == []
    sites = led["counts"]["program_calls_by_site"]
    batches = sites["ops.joins.fused_probe"]
    assert batches >= 2, sites      # two joins, a probe batch each
    assert sites["ops.joins.build_side"] == 2
    assert sites["hashtable.join_index"] == 2
    assert sites["ops.joins.match"] == batches
    assert "ops.joins.expand" not in sites
    # one sync a build (the index's overflow flag) and one a probe batch
    # (the candidate total), both counted
    assert led["counts"]["readbacks"] >= 2 + batches

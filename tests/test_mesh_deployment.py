"""The served 4-device mesh path against the plain reference (PR 28).

TPC-DS as 4-partition stages over a 4-device mesh at a small scale, on 4
of the 8 virtual CPU devices: each of the four star-join plans the
benchmark serves (``it/tpcds_queries.py``'s q3 / q42 / q52 / q55) as ONE
task — a 4-partition stage over 4 input splits — through ``AuronServer``
/ ``AuronClient`` with ``auron.mesh.enabled``.

- every answer equals the plan's own Acero oracle over exactly the
  task's rows (integers, strings and decimal money exact) and the
  mesh-off answer bit for bit;
- the DONE frame of a mesh stage carries the exchange layer's three
  spans inside ``layers_s.exchange`` (``gang_wait``, ``mesh_stack``,
  ``mesh_round``), ``layers_s`` still summing to ``wall_s``, the four
  ``counts`` of the mesh route, and the recorded ``all_to_all`` route;
- a one-chip task's frame has none of them non-zero.
"""

import time

import pytest

import jax

from auron_tpu import config as cfg
from auron_tpu.obs import trace

PLANS = ("q3", "q42", "q52", "q55")
MESH_COUNTS = ("mesh_rounds", "mesh_escalations", "mesh_bytes",
               "mesh_slot_bytes")
MESH_SPANS = ("exchange.gang_wait", "exchange.mesh_stack",
              "exchange.mesh_round")
SCALE = 0.2                  # 100,000 fact rows in the task: every plan answers
SPLITS_PER_TASK = 4          # of the generator's 8 ``store_sales`` files

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 virtual devices")


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
    from auron_tpu.it.tpcds_queries import QUERIES
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
    session = PlanOnly()
    tasks = {p: queries[p].run(session, tables) for p in PLANS}
    arrow = tpcds.load_arrow({name: tables[name] for name in
                              ("store_sales", "date_dim", "item")})
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
    # money is decimal(7,2) summed as decimal: compared exactly
    res = QueryResultComparator().compare(plan, table, stage.oracle(plan))
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
    counts = led["counts"]
    assert counts["mesh_rounds"] >= 1
    assert counts["mesh_escalations"] >= 0
    assert 0 < counts["mesh_bytes"] <= counts["mesh_slot_bytes"]
    # one number under two names: the version-1 key and the new count
    assert counts["mesh_bytes"] == led["mesh_bytes"]
    assert _leaf_sum(done, "exchange_route_all_to_all") >= 1
    assert _leaf_sum(done, "exchange_route_demoted") == 0
    # the stage's program calls are counted like every other program's
    assert counts["program_calls"] > 0 and counts["readbacks"] > 0


@pytest.mark.parametrize("plan", PLANS)
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


def _recorded_spans(run):
    """``run()`` with ``auron.trace.enabled`` and the spans it left."""
    conf = cfg.get_config()
    conf.set(cfg.TRACE_ENABLED, True)
    try:
        result = run()
        # the root span closes on the server thread after the DONE frame
        deadline = time.monotonic() + 5.0
        while True:
            spans = list(trace.tracer().spans())
            if any(s.name == "serve.task" for s in spans) \
                    or time.monotonic() > deadline:
                return result, spans
            time.sleep(0.01)
    finally:
        conf.unset(cfg.TRACE_ENABLED)
        trace.reset()


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

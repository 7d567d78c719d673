"""Layer spans, the version-2 cost ledger and program names (PR 25).

- ``obs/trace.layer_span``: spans nest on the module's one stack, their
  self times (duration less child layer spans and compile seconds) land
  in the task's accumulator and sum to the root's duration, on one
  thread and with a scan worker beside it;
- a profiler session on the CPU backend holds the ``auron:`` annotations
  and ``PjitFunction(auron_<site>)`` and nothing called ``kernel``;
- a task through ``AuronServer`` / ``AuronClient`` returns a version-2
  ledger whose ``layers_s`` sums to ``wall_s``, whose ``ops_s`` sums to
  the operators' part, whose ``counts`` are exact, and which keeps every
  version-1 key;
- what was taken out (``auron.profile``, the hotspot tool) is gone.

Since PR 40 a layer span reads the thread's CPU clock beside the wall
clock: ``layers_cpu_s``, ``ops_s.<op>.cpu_s`` and ``scan_worker_cpu_s``
say what of a self time was computing, and a batch's row count is read
through ``obs/profile.row_count`` — one ``auron:op/readback`` and one
``counts.row_syncs`` where the count is still on the device.
"""

import ast
import glob
import os
import sys
import threading
import time

import pytest

from auron_tpu import config as cfg
from auron_tpu.obs import ledger, trace

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "benchmark")

#: every key of the version-1 ledger (PR 20), which version 2 keeps
V1_KEYS = {"version", "query_id", "outcome", "device", "wall_s",
           "device_s", "host_s", "host_total_s", "shuffle", "spill",
           "mesh_bytes", "journal_bytes_reused", "compile", "rows",
           "batches", "partitions", "cache_hit", "served_from", "retries",
           "fleet"}
V1_COMPILE_KEYS = {"xla_compiles", "seconds", "program_builds",
                   "program_hits"}
COUNTS = ("program_calls", "readbacks", "row_syncs", "d2h_bytes",
          "h2d_transfers", "h2d_bytes", "encode_pyloop_values",
          "layer_spans")
#: the layers a span charges CPU to: ``layers_s`` less ``compile`` (its
#: CPU is not known apart) and ``other`` (what no span covers)
CPU_LAYERS = {"plan", "scan_wait", "op_host", "op_device_wait", "exchange",
              "to_arrow", "send"}


def _busy(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _burn(cpu_seconds: float) -> None:
    """Spin until this thread has SPENT that much CPU, however long the
    machine or another thread keeps it waiting."""
    end = time.thread_time() + cpu_seconds
    while time.thread_time() < end:
        pass


# ---------------------------------------------------------------------------
# the primitive, by hand
# ---------------------------------------------------------------------------

class TestLayerSpanAccounting:
    def _root(self, acc):
        acc.start()
        return trace.layer_span("serve", "task", query_id=acc.query_id)

    def test_self_times_sum_to_the_roots_duration(self):
        with trace.task_scope("q-sum") as acc:
            t0 = time.monotonic()
            with self._root(acc):
                with trace.layer_span("plan", "decode"):
                    _busy(0.004)
                with trace.layer_span("op", "agg"):
                    _busy(0.003)
                    with trace.layer_span("op", "hash_join"):
                        _busy(0.005)
                        with trace.layer_span("op", "readback"):
                            _busy(0.002)
                with trace.layer_span("serve", "send"):
                    _busy(0.001)
                _busy(0.002)          # the root's own: `other`
                wall = time.monotonic() - t0
                v2 = acc.sealed(wall)
        layers = v2["layers_s"]
        assert sum(layers.values()) == pytest.approx(wall, abs=2e-6)
        assert layers["plan"] >= 0.004 and layers["send"] >= 0.001
        # exclusive: the parent's 3 ms do not hold the child's 5 + 2
        assert 0.003 <= v2["ops_s"]["agg"]["host_s"] < 0.005
        assert 0.005 <= v2["ops_s"]["hash_join"]["host_s"] < 0.007
        assert v2["ops_s"]["hash_join"]["device_wait_s"] >= 0.002
        assert 0.002 <= layers["other"] < 0.004
        assert v2["counts"]["layer_spans"] == 5   # the root is still open

    def test_ops_sum_to_the_operators_part(self):
        with trace.task_scope("q-ops") as acc:
            with self._root(acc):
                for name in ("scan", "agg", "scan"):
                    with trace.layer_span("op", name):
                        _busy(0.001)
                        with trace.layer_span("op", "readback"):
                            _busy(0.001)
                v2 = acc.sealed(1.0)
        ops = v2["ops_s"]
        assert ops["scan"]["batches"] == 2 and ops["agg"]["batches"] == 1
        host = sum(o["host_s"] for o in ops.values())
        wait = sum(o["device_wait_s"] for o in ops.values())
        assert host == pytest.approx(v2["layers_s"]["op_host"], abs=2e-6)
        assert wait == pytest.approx(v2["layers_s"]["op_device_wait"],
                                     abs=2e-6)

    def test_exchange_self_times_split_by_span(self):
        """``exchange_s``: the exchange layer's self time by span, the
        mesh route's three inside ``materialize``, an operator the
        exchange drives outside it; it sums to ``layers_s.exchange``."""
        with trace.task_scope("q-exchange") as acc:
            with self._root(acc):
                with trace.layer_span("exchange", "materialize"):
                    _busy(0.002)
                    with trace.layer_span("exchange", "gang_wait"):
                        _busy(0.004)
                    with trace.layer_span("op", "fused_stage"):
                        _busy(0.003)
                    with trace.layer_span("exchange", "mesh_round"):
                        _busy(0.001)
                        with trace.layer_span("op", "readback"):
                            _busy(0.002)
                with trace.layer_span("exchange", "map_write"):
                    _busy(0.001)
                v2 = acc.sealed(1.0)
        split = v2["exchange_s"]
        assert set(split) == set(trace.EXCHANGE_KEYS)
        # (six values, each rounded to the microsecond)
        assert sum(split.values()) == pytest.approx(
            v2["layers_s"]["exchange"], abs=4e-6)
        assert 0.002 <= split["materialize"] < 0.004
        assert 0.004 <= split["gang_wait"] < 0.006
        # the round's one fence is no operator's: it stays in the span
        # that made it (1 ms of launch + 2 ms of fence)
        assert 0.003 <= split["mesh_round"] < 0.005
        assert split["map_write"] >= 0.001
        assert split["mesh_stack"] == 0 and split["broadcast_collect"] == 0
        assert set(v2["ops_s"]) == {"fused_stage"}
        assert v2["layers_s"]["op_device_wait"] == 0.0

    def test_a_task_without_an_exchange_has_an_all_zero_split(self):
        with trace.task_scope("q-none") as acc:
            with self._root(acc):
                with trace.layer_span("op", "agg"):
                    _busy(0.001)
                v2 = acc.sealed(1.0)
        assert v2["exchange_s"] == dict.fromkeys(trace.EXCHANGE_KEYS, 0.0)
        assert all(v2["counts"][k] == 0 for k in (
            "mesh_read_batches", "mesh_read_rows", "mesh_read_slices",
            "mesh_home_bytes", "mesh_slot_bytes"))

    def test_readback_outside_an_operator_stays_in_its_layer(self):
        with trace.task_scope("q-fence") as acc:
            with self._root(acc):
                with trace.layer_span("convert", "to_arrow"):
                    with trace.layer_span("op", "readback"):
                        _busy(0.003)
                v2 = acc.sealed(1.0)
        assert v2["layers_s"]["to_arrow"] >= 0.003
        assert v2["layers_s"]["op_device_wait"] == 0.0
        assert v2["ops_s"] == {}
        assert v2["counts"]["layer_spans"] == 2

    def test_compile_seconds_come_out_of_the_span_they_fell_in(self):
        with trace.task_scope("q-compile") as acc:
            with self._root(acc):
                with trace.layer_span("op", "sort"):
                    _busy(0.006)
                    trace.on_compile(0.004)
                v2 = acc.sealed(1.0)
        assert v2["layers_s"]["compile"] == pytest.approx(0.004)
        assert 0.002 <= v2["ops_s"]["sort"]["host_s"] < 0.004
        assert v2["compile"] == {"task_xla_compiles": 1,
                                 "task_seconds": 0.004}

    def test_scan_worker_is_beside_the_task_thread(self):
        def worker(acc):
            with trace.worker_scope(acc):
                for key in ("decode", "encode", "h2d"):
                    with trace.layer_span("scan", key):
                        _burn(0.004)
                trace.count("h2d_transfers", 7)
                trace.count("h2d_bytes", 4096)

        with trace.task_scope("q-worker") as acc:
            t0 = time.monotonic()
            with self._root(acc):
                th = threading.Thread(target=worker, args=(acc,))
                th.start()
                with trace.layer_span("scan", "wait"):
                    th.join()
                wall = time.monotonic() - t0
                v2 = acc.sealed(wall)
        for key in ("decode", "encode", "h2d"):
            assert v2["scan_worker_s"][key] >= 0.004
        # the worker's 12 ms overlap the wait: they are not in layers_s
        assert sum(v2["layers_s"].values()) == pytest.approx(wall,
                                                             abs=2e-6)
        # (the worker may hold the GIL before the wait span opens)
        assert v2["layers_s"]["scan_wait"] >= 0.004
        assert v2["counts"]["h2d_transfers"] == 7
        assert v2["counts"]["h2d_bytes"] == 4096
        assert v2["counts"]["layer_spans"] == 4
        assert v2["cpu_s"] >= 0.012        # the worker's CPU is counted

    def test_queue_wait_is_outside_the_layers(self):
        with trace.task_scope("q-queue") as acc:
            with trace.layer_span("serve", "queue"):
                _busy(0.003)
            with self._root(acc):
                v2 = acc.sealed(0.0)
        assert v2["queue_s"] >= 0.003
        assert sum(v2["layers_s"].values()) == pytest.approx(0.0, abs=2e-6)

    def test_count_output_closes_its_span_before_the_yield(self):
        from auron_tpu.ops.base import MetricsSet, count_output

        class Batch:
            num_rows = 3

        def source():
            for _ in range(2):
                _busy(0.002)
                yield Batch()

        with trace.task_scope("q-gen") as acc:
            with self._root(acc):
                ms = MetricsSet(name="gen_op")
                for _b in count_output(source(), ms, timed=True):
                    _busy(0.01)         # the consumer's, not the op's
                v2 = acc.sealed(1.0)
        op = v2["ops_s"]["gen_op"]
        assert 0.004 <= op["host_s"] < 0.012
        assert op["batches"] == 3           # two batches and the end
        assert ms.snapshot()["output_rows"] == 6

    def test_out_of_order_exit_leaves_the_stack_clean(self):
        with trace.task_scope("q-ooo") as acc:
            with self._root(acc):
                a = trace.layer_span("op", "a")
                b = trace.layer_span("op", "b")
                a.__enter__()
                b.__enter__()
                a.__exit__(None, None, None)
                b.__exit__(None, None, None)
                with trace.layer_span("op", "c"):
                    pass
                acc.sealed(1.0)
        assert trace.tracer()._stack() == []

    def test_no_task_no_charge_and_a_plain_span_is_transparent(self):
        assert trace.current_task() is None
        with trace.layer_span("op", "orphan"):
            pass                              # nothing bound: no error
        conf = cfg.get_config()
        conf.set(cfg.TRACE_ENABLED, True)
        trace.reset()
        try:
            with trace.task_scope("q-plain") as acc:
                with self._root(acc):
                    with trace.layer_span("op", "outer"):
                        with trace.span("task", "task.attempt"):
                            with trace.layer_span("op", "inner"):
                                _busy(0.003)
                    v2 = acc.sealed(1.0)
            spans = {s.name: s for s in trace.tracer().spans()}
        finally:
            conf.unset(cfg.TRACE_ENABLED)
            trace.reset()
        assert v2["ops_s"]["inner"]["host_s"] >= 0.003
        assert v2["ops_s"]["outer"]["host_s"] < 0.003
        # recorded as any other span, in the `layer` category, linked
        assert spans["op.inner"].cat == "layer"
        assert spans["op.inner"].parent_id == spans["task.attempt"].span_id
        assert spans["task.attempt"].parent_id == spans["op.outer"].span_id

    def test_an_unrecorded_layer_span_passes_its_parents_id_through(self):
        conf = cfg.get_config()
        conf.set(cfg.TRACE_ENABLED, True)
        conf.set(cfg.TRACE_EVENTS, "task")
        trace.reset()
        try:
            with trace.span("task", "outer"):
                with trace.layer_span("op", "filtered"):
                    trace.event("task", "inside")
            spans = {s.name: s for s in trace.tracer().spans()}
        finally:
            conf.unset(cfg.TRACE_EVENTS)
            conf.unset(cfg.TRACE_ENABLED)
            trace.reset()
        assert set(spans) == {"outer", "inside"}
        assert spans["inside"].parent_id == spans["outer"].span_id


class TestCpuSelfTime:
    """A span's CPU self time (``time.thread_time_ns`` beside the wall
    clock) is charged where its wall self time is, in a task that is
    CPU-timed."""

    def _root(self, acc):
        acc.cpu_timed = True
        acc.start()
        return trace.layer_span("serve", "task", query_id=acc.query_id)

    def test_one_task_in_seven_is_cpu_timed(self, monkeypatch):
        """The thread CPU clock is a system call, 6 µs on the chip's host
        with the interpreter held: one task in ``CPU_TIMED_EVERY`` reads
        it round its spans, the others never, and their sealed ledgers
        say None where the CPU would stand."""
        reads = []
        clock = time.thread_time_ns

        def counted():
            reads.append(1)
            return clock()

        monkeypatch.setattr(trace.time, "thread_time_ns", counted)
        timed, sealed = [], []
        for _ in range(2 * trace.CPU_TIMED_EVERY):
            with trace.task_scope("q-sampled") as acc:
                timed.append(acc.cpu_timed)
                acc.start()
                before = len(reads)
                with trace.layer_span("serve", "task"):
                    with trace.layer_span("op", "agg"):
                        pass
                assert len(reads) - before == (4 if acc.cpu_timed else 0)
                sealed.append(acc.sealed(1.0))
        assert sum(timed) == 2
        for was_timed, v2 in zip(timed, sealed):
            assert (v2["layers_cpu_s"] is not None) == was_timed
            assert (v2["scan_worker_cpu_s"] is not None) == was_timed
            assert ("cpu_s" in v2["ops_s"]["agg"]) == was_timed
            assert v2["ops_s"]["agg"]["batches"] == 1   # wall: always

    def test_a_sleeping_span_books_wall_and_a_spinning_one_both(self):
        with trace.task_scope("q-cpu") as acc:
            with self._root(acc):
                with trace.layer_span("op", "sleeper"):
                    time.sleep(0.04)
                with trace.layer_span("op", "spinner"):
                    _burn(0.02)
                v2 = acc.sealed(1.0)
        sleeper, spinner = v2["ops_s"]["sleeper"], v2["ops_s"]["spinner"]
        # (a collection of the interpreter's may fall into the sleep)
        assert sleeper["host_s"] >= 0.04 > 2 * sleeper["cpu_s"]
        assert 0.02 <= spinner["cpu_s"] <= spinner["host_s"]
        assert v2["layers_cpu_s"]["op_host"] == pytest.approx(
            sleeper["cpu_s"] + spinner["cpu_s"], abs=2e-6)

    def test_a_childs_cpu_is_not_its_parents(self):
        with trace.task_scope("q-child") as acc:
            with self._root(acc):
                with trace.layer_span("op", "parent"):
                    time.sleep(0.04)
                    with trace.layer_span("op", "child"):
                        _burn(0.03)
                    with trace.layer_span("op", "readback"):
                        time.sleep(0.01)
                v2 = acc.sealed(1.0)
        parent, child = v2["ops_s"]["parent"], v2["ops_s"]["child"]
        assert child["cpu_s"] >= 0.03
        # the parent slept: its child's spin is not its own
        assert parent["host_s"] >= 0.04 > 2 * parent["cpu_s"]
        assert parent["device_wait_s"] >= 0.01
        assert v2["layers_cpu_s"]["op_host"] == pytest.approx(
            parent["cpu_s"] + child["cpu_s"], abs=2e-6)

    def test_a_declared_wait_leaves_the_cpu_clock_alone(self, monkeypatch):
        """The thread CPU clock is a system call (6 µs on the chip's host,
        the interpreter held): the spans that wait by declaration — for
        the device, the scan worker, the gang door, a slot — book no CPU
        and do not read it; every other span reads it twice."""
        reads = []
        clock = time.thread_time_ns

        def counted():
            reads.append(1)
            return clock()

        with trace.task_scope("q-waits") as acc:
            acc.cpu_timed = True
            with trace.layer_span("serve", "queue"):
                pass
            with self._root(acc):
                with trace.layer_span("op", "agg"):
                    monkeypatch.setattr(trace.time, "thread_time_ns",
                                        counted)
                    for layer, key in sorted(trace.DECLARED_WAITS):
                        with trace.layer_span(layer, key):
                            pass
                    in_waits = len(reads)
                    with trace.layer_span("op", "hash_join"):
                        pass
                    in_all = len(reads)
                    monkeypatch.undo()
                v2 = acc.sealed(1.0)
        assert trace.DECLARED_WAITS == {
            ("op", "readback"), ("scan", "wait"),
            ("exchange", "gang_wait"), ("serve", "queue")}
        assert in_waits == 0 and in_all == 2
        assert v2["layers_cpu_s"]["op_device_wait"] == 0.0
        assert v2["layers_cpu_s"]["scan_wait"] == 0.0

    def test_cpu_is_within_the_wall_layer_by_layer(self):
        cpu0 = time.thread_time()
        with trace.task_scope("q-within") as acc:
            t0 = time.monotonic()
            with self._root(acc):
                with trace.layer_span("plan", "decode"):
                    _burn(0.003)
                with trace.layer_span("scan", "wait"):
                    time.sleep(0.03)
                with trace.layer_span("op", "agg"):
                    _burn(0.004)
                    with trace.layer_span("op", "readback"):
                        time.sleep(0.003)
                with trace.layer_span("exchange", "materialize"):
                    _burn(0.002)
                    with trace.layer_span("exchange", "gang_wait"):
                        time.sleep(0.002)
                with trace.layer_span("convert", "to_arrow"):
                    _burn(0.002)
                with trace.layer_span("serve", "send"):
                    _burn(0.001)
                v2 = acc.sealed(time.monotonic() - t0)
        thread_cpu = time.thread_time() - cpu0
        layers, cpu = v2["layers_s"], v2["layers_cpu_s"]
        assert set(cpu) == CPU_LAYERS == set(layers) - {"compile", "other"}
        for key in cpu:
            assert 0 <= cpu[key] <= layers[key] + 1e-6, key
        assert sum(cpu.values()) <= thread_cpu + 1e-6
        assert sum(cpu.values()) <= v2["cpu_s"] + 1e-6
        assert cpu["scan_wait"] == 0.0 and cpu["plan"] >= 0.003
        assert cpu["op_host"] >= 0.004 and cpu["exchange"] >= 0.002

    def test_a_compile_in_a_span_leaves_its_cpu_within_the_wall(self):
        """The compile's seconds leave the span's wall (``on_compile``);
        its CPU is not known apart, so the operator's CPU stops at what
        is left of its wall (the cut is made key by key when the ledger
        is sealed: a coarse CPU clock's tick may be larger than a span)."""
        with trace.task_scope("q-compile-cpu") as acc:
            with self._root(acc):
                with trace.layer_span("op", "sort"):
                    t0 = time.perf_counter()
                    _burn(0.01)
                    spun = time.perf_counter() - t0
                    trace.on_compile(spun - 0.002)
                v2 = acc.sealed(1.0)
        sort = v2["ops_s"]["sort"]
        # 10 ms of CPU in the span, 2 ms of wall left to it
        assert 0.002 <= sort["host_s"] < 0.01
        assert sort["cpu_s"] == sort["host_s"]

    def test_a_thread_held_off_the_interpreter_is_off_cpu(self):
        """A spinner thread that never leaves the interpreter takes the
        lock in turns with the task's thread: the operator's span books
        the whole wall and only its own turns as CPU."""
        stop = threading.Event()

        def spin():
            while not stop.is_set():
                pass

        holder = threading.Thread(target=spin, daemon=True)
        with trace.task_scope("q-lock") as acc:
            with self._root(acc):
                holder.start()
                try:
                    with trace.layer_span("op", "agg"):
                        # 40 ms of CPU take at least seven turns of the
                        # interpreter's 5 ms, each handed back for one
                        _burn(0.04)
                finally:
                    stop.set()
                    holder.join(timeout=30)
                v2 = acc.sealed(1.0)
        assert not holder.is_alive()
        agg = v2["ops_s"]["agg"]
        assert agg["cpu_s"] >= 0.04
        assert agg["host_s"] - agg["cpu_s"] > 0.01
        assert v2["layers_s"]["op_host"] - v2["layers_cpu_s"]["op_host"] \
            == pytest.approx(agg["host_s"] - agg["cpu_s"], abs=2e-6)

    def test_a_workers_cpu_is_split_by_span(self):
        def worker(acc):
            with trace.worker_scope(acc):
                with trace.layer_span("scan", "decode"):
                    time.sleep(0.03)
                with trace.layer_span("scan", "encode"):
                    _burn(0.01)
                with trace.layer_span("scan", "h2d"):
                    _burn(0.004)

        with trace.task_scope("q-worker-cpu") as acc:
            with self._root(acc):
                th = threading.Thread(target=worker, args=(acc,))
                th.start()
                th.join(timeout=30)
                v2 = acc.sealed(1.0)
        assert not th.is_alive()
        wall, cpu = v2["scan_worker_s"], v2["scan_worker_cpu_s"]
        assert set(cpu) == set(wall) == {"decode", "encode", "h2d"}
        for key in cpu:
            assert 0 <= cpu[key] <= wall[key] + 1e-6, key
        assert 2 * cpu["decode"] < 0.03 <= wall["decode"]
        assert cpu["encode"] >= 0.01 and cpu["h2d"] >= 0.004
        # a worker's spans are beside the task's thread: not in layers_s
        assert v2["layers_cpu_s"] == dict.fromkeys(CPU_LAYERS, 0.0)


class TestRowCount:
    """``obs/profile.row_count``: the one way an operator reads a
    batch's row count."""

    def _task(self, body):
        with trace.task_scope("q-rows") as acc:
            acc.start()
            with trace.layer_span("serve", "task", query_id=acc.query_id):
                with trace.layer_span("op", "hash_join"):
                    got = body()
            return got, acc.sealed(1.0)

    def test_a_device_count_is_one_readback_and_one_row_sync(self):
        import jax.numpy as jnp

        from auron_tpu.columnar.batch import DeviceBatch
        from auron_tpu.obs import profile
        batch = DeviceBatch((), jnp.asarray(7, jnp.int32))
        conf = cfg.get_config()
        conf.set(cfg.TRACE_ENABLED, True)
        trace.reset()
        try:
            got, v2 = self._task(lambda: profile.row_count(batch))
            names = [s.name for s in trace.tracer().spans()]
        finally:
            conf.unset(cfg.TRACE_ENABLED)
            trace.reset()
        assert got == 7 and type(got) is int
        assert names.count("op.readback") == 1
        assert v2["counts"]["row_syncs"] == 1
        assert v2["counts"]["readbacks"] == 0
        assert v2["counts"]["d2h_bytes"] == 0
        # the read's wait is the operator's device wait, not its host time
        assert v2["ops_s"]["hash_join"]["device_wait_s"] > 0
        assert v2["layers_s"]["op_device_wait"] \
            == v2["ops_s"]["hash_join"]["device_wait_s"]

    @pytest.mark.parametrize("count", [5, "numpy"])
    def test_a_host_count_opens_no_span(self, count):
        import numpy as np

        from auron_tpu.obs import profile

        class Batch:
            num_rows = np.int32(5) if count == "numpy" else count

        got, v2 = self._task(lambda: profile.row_count(Batch()))
        assert got == 5 and type(got) is int
        assert v2["counts"]["row_syncs"] == 0
        assert v2["counts"]["readbacks"] == 0
        # the root's span and the operator's: the read opened none
        assert v2["counts"]["layer_spans"] == 2
        assert v2["layers_s"]["op_device_wait"] == 0.0

    def test_a_count_handed_in_itself_is_read_the_same_way(self):
        import jax.numpy as jnp

        from auron_tpu.obs import profile
        got, v2 = self._task(
            lambda: profile.row_count(jnp.asarray(11, jnp.int32)))
        assert got == 11
        assert v2["counts"]["row_syncs"] == 1

    def test_the_split_adds_no_time_to_the_operator(self):
        """What ``count_output`` booked as the operator's host time (the
        span round ``next()`` and the read of the row count) is now its
        host time plus its device wait: the same interval, split."""
        import jax.numpy as jnp

        from auron_tpu.columnar.batch import DeviceBatch
        from auron_tpu.ops.base import MetricsSet, count_output

        def source():
            for n in (3, 4):
                _busy(0.002)
                yield DeviceBatch((), jnp.asarray(n, jnp.int32))

        with trace.task_scope("q-split") as acc:
            acc.start()
            with trace.layer_span("serve", "task", query_id=acc.query_id):
                ms = MetricsSet(name="gen_op")
                it = count_output(source(), ms)
                inside = 0.0
                while True:
                    t0 = time.perf_counter()
                    b = next(it, None)
                    inside += time.perf_counter() - t0
                    if b is None:
                        break
                    _busy(0.003)        # the consumer's, not the op's
                v2 = acc.sealed(1.0)
        op = v2["ops_s"]["gen_op"]
        assert ms.snapshot()["output_rows"] == 7
        assert v2["counts"]["row_syncs"] == 2
        assert op["device_wait_s"] > 0 and op["host_s"] >= 0.004
        # three next() calls: the spans lie inside them, and what they
        # leave out is the generator's resume and the span's own
        # enter / exit (some tens of microseconds each)
        assert op["host_s"] + op["device_wait_s"] <= inside
        assert op["host_s"] + op["device_wait_s"] == pytest.approx(
            inside, abs=3e-3)


def _encode_on_a_scan_worker(rb) -> dict:
    """``to_device(rb)`` on a prefetch worker of one task; the sealed
    ``counts`` of that task."""
    from auron_tpu.columnar.arrow_bridge import to_device

    def worker(acc):
        with trace.worker_scope(acc):
            to_device(rb)

    with trace.task_scope("q-encode") as acc:
        th = threading.Thread(target=worker, args=(acc,))
        th.start()
        th.join(timeout=60)
        assert not th.is_alive()
        return acc.sealed(1.0)["counts"]


def test_a_store_sales_shaped_batch_encodes_with_no_python_loop():
    """int64 + decimal(7,2) + nullable keys, sliced as the scan slices a
    row group: every column is a view or a native copy of its Arrow
    buffers, so the count of values sent through a per-row Python encode
    is 0 (before PR 26 each decimal value was one Python object)."""
    import decimal

    import pyarrow as pa
    n = 1000
    money = pa.array([None if i % 11 == 0
                      else decimal.Decimal(i * 37 - 9999).scaleb(-2)
                      for i in range(n)], pa.decimal128(7, 2))
    rb = pa.record_batch({
        "ss_sold_date_sk": pa.array(
            [None if i % 13 == 0 else 2450816 + i for i in range(n)],
            pa.int64()),
        "ss_item_sk": pa.array(range(n), pa.int64()),
        "ss_quantity": pa.array(range(n), pa.int64()),
        "ss_sales_price": money,
        "ss_ext_sales_price": money,
        "ss_net_profit": money,
    }).slice(100, 700)
    counts = _encode_on_a_scan_worker(rb)
    assert counts["encode_pyloop_values"] == 0
    assert counts["h2d_transfers"] == 13    # 6 x (data, validity) + rows


def test_a_list_of_strings_column_is_counted_as_a_python_loop():
    import pyarrow as pa
    rows = [["a", "bb"], None, [], ["ccc", None, "d"]] * 5
    rb = pa.record_batch({
        "k": pa.array(range(len(rows)), pa.int64()),
        "tags": pa.array(rows, pa.list_(pa.string())),
    })
    counts = _encode_on_a_scan_worker(rb)
    assert counts["encode_pyloop_values"] == len(rows)


@pytest.mark.parametrize("fmt", ["parquet", "orc"])
def test_a_file_scan_always_runs_on_the_prefetch_worker(fmt, tmp_path):
    """There is one scan: decode, encode and transfer happen on the
    prefetch worker (``scan_worker_s``, beside the task thread) and the
    task thread books only its wait for them."""
    import pyarrow as pa
    from auron_tpu.io.orc import OrcScanOp
    from auron_tpu.io.parquet import ParquetScanOp
    from auron_tpu.ops.base import ExecContext
    table = pa.table({"k": pa.array(range(3000), pa.int64()),
                      "s": pa.array([f"r{i % 7}" for i in range(3000)])})
    path = str(tmp_path / f"t.{fmt}")
    if fmt == "orc":
        import pyarrow.orc as orc
        orc.write_table(table, path)
        op = OrcScanOp([path], batch_rows=1024)
    else:
        import pyarrow.parquet as pq
        pq.write_table(table, path, row_group_size=1024)
        op = ParquetScanOp([path], batch_rows=1024)
    with trace.task_scope("q-scan") as acc:
        acc.start()
        with trace.layer_span("serve", "task", query_id=acc.query_id):
            rows = sum(int(b.num_rows) for b in op.execute(0, ExecContext()))
            v2 = acc.sealed(1.0)
    assert rows == 3000
    assert set(v2["scan_worker_s"]) == {"decode", "encode", "h2d"}
    assert all(v > 0 for v in v2["scan_worker_s"].values())
    assert v2["layers_s"]["scan_wait"] > 0
    assert v2["counts"]["h2d_transfers"] > 0


def test_an_operator_timer_waits_for_nothing_and_opens_no_readback():
    """``ops/base.timer`` books host time only: ``track`` hands its
    value back without waiting on it (a leaf whose wait raises passes
    through), and no ``auron:op/readback`` span opens — the device wait
    belongs to ``device_fence`` / ``timed_get``."""
    from auron_tpu.ops.base import MetricsSet, timer

    class DeadLeaf:
        def block_until_ready(self):
            raise RuntimeError("device halted")

    metrics = MetricsSet(name="agg")
    leaf = DeadLeaf()
    with trace.task_scope("q-timer") as acc:
        acc.start()
        with trace.layer_span("serve", "task", query_id=acc.query_id):
            with timer(metrics.counter("elapsed_compute")) as t:
                assert t.track({"out": leaf})["out"] is leaf
            v2 = acc.sealed(1.0)
    assert metrics.snapshot()["elapsed_compute"] > 0
    assert v2["counts"]["readbacks"] == 0
    assert v2["ops_s"]["agg"]["batches"] == 1
    assert v2["ops_s"]["agg"]["device_wait_s"] == 0.0


def test_many_scan_workers_lose_no_update():
    """Several prefetch workers of one task write its worker fields at
    once (a join's dimension scans beside the fact scan): with more
    threads than cores and a shortened switch interval, no span, count
    or compile is lost."""
    workers, rounds = 16, 200
    acc = trace.TaskAccumulator("q-stress")

    def worker():
        with trace.worker_scope(acc):
            for _ in range(rounds):
                with trace.layer_span("scan", "h2d"):
                    trace.count("h2d_transfers", 3)
                    trace.count("h2d_bytes", 10)
                trace.on_compile(0.001)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker) for _ in range(workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    v2 = acc.sealed(1.0)
    assert v2["counts"]["layer_spans"] == workers * rounds
    assert v2["counts"]["h2d_transfers"] == 3 * workers * rounds
    assert v2["counts"]["h2d_bytes"] == 10 * workers * rounds
    assert v2["compile"]["task_xla_compiles"] == workers * rounds
    assert v2["scan_worker_s"]["h2d"] > 0
    # a worker's compiles are the task's, but not its thread's seconds
    assert v2["layers_s"]["compile"] == 0.0


def test_ledger_without_an_accumulator_is_a_zeroed_version_2():
    led = ledger.build([], query_id="q", wall_s=1.5)
    assert led["version"] == 2
    assert V1_KEYS <= set(led)
    assert led["layers_s"]["other"] == 1.5
    assert sum(led["layers_s"].values()) == pytest.approx(1.5)
    assert led["ops_s"] == {} and led["queue_s"] == 0.0
    assert all(led["counts"][k] == 0 for k in COUNTS)
    assert led["compile"]["task_xla_compiles"] == 0


# ---------------------------------------------------------------------------
# the served path: q3 at the benchmark's rehearsal scale
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server in this process, the benchmark's TPC-DS tables at scale
    0.02 and its star-join plans over one split (a task of the small
    cells' shape)."""
    for path in (_BENCH,):
        if path not in sys.path:
            sys.path.insert(0, path)
    from harness import cell, datagen

    from auron_tpu.frontend.session import Session
    from auron_tpu.runtime.serving import AuronClient, AuronServer
    root = str(tmp_path_factory.mktemp("tpcds"))
    arrow = datagen.generate(seed=2_147_483_659, scale=0.02)
    splits = datagen.write_splits(root, "store_sales",
                                  arrow["store_sales"], 2622)
    dims = {name: datagen.write_whole(root, name, arrow[name])
            for name in arrow if name != "store_sales"}
    session = Session()
    tasks = {}
    for plan in ("q3", "q42", "q52"):
        df = cell.load_module("plans", plan).build(
            session, dims, [splits[0][0]], 1)
        tasks[plan] = df.task_bytes(0)
    server = AuronServer()
    server.serve_background()
    host, port = server.address

    def run(plan):
        table, metrics = AuronClient(host, port,
                                     timeout_s=600).execute(tasks[plan])
        assert table.num_rows > 0
        return metrics["cost_ledger"]

    run("q3")                   # warm: every program of q3 is built
    yield run
    server.shutdown()
    server.server_close()
    session.close()


class TestServedLedger:
    def test_a_one_chip_task_reads_no_exchange(self, served):
        """A task of the one-chip cells is one scan partition: its plan
        holds no exchange, so the read program's site is not in it."""
        counts = served("q3")["counts"]
        assert counts["program_calls"] > 0
        assert "parallel.exchange.read_cut" not in \
            counts["program_calls_by_site"]
        assert counts["mesh_read_batches"] == counts["mesh_read_rows"] \
            == counts["mesh_read_slices"] == 0

    def test_version_2_keeps_every_version_1_key(self, served):
        led = served("q3")
        assert led["version"] == 2
        assert V1_KEYS <= set(led)
        assert V1_COMPILE_KEYS <= set(led["compile"])
        assert {"queue_s", "layers_s", "layers_cpu_s", "ops_s",
                "scan_worker_s", "scan_worker_cpu_s", "cpu_s",
                "counts"} <= set(led)
        assert {"task_xla_compiles", "task_seconds"} <= set(led["compile"])
        # the old inclusive numbers keep their meaning beside the new
        assert led["device_s"] > 0 and led["host_s"]["convert"] >= 0

    def test_layers_sum_to_the_wall(self, served):
        led = served("q3")
        layers = led["layers_s"]
        assert set(layers) == {"plan", "compile", "scan_wait", "op_host",
                               "op_device_wait", "exchange", "to_arrow",
                               "send", "other"}
        assert sum(layers.values()) == pytest.approx(led["wall_s"],
                                                     abs=1e-5)
        for key in ("plan", "scan_wait", "op_host", "to_arrow", "send"):
            assert layers[key] > 0, key
        assert layers["other"] > -1e-4
        # the spans cover the task: what is left over is small
        assert layers["other"] < 0.10 * led["wall_s"]

    def test_ops_sum_to_the_operators_part(self, served):
        led = served("q3")
        ops = led["ops_s"]
        assert {"parquet_scan", "hash_join", "agg"} <= set(ops)
        host = sum(o["host_s"] for o in ops.values())
        wait = sum(o["device_wait_s"] for o in ops.values())
        assert host == pytest.approx(led["layers_s"]["op_host"], abs=1e-4)
        assert wait == pytest.approx(led["layers_s"]["op_device_wait"],
                                     abs=1e-4)
        assert all(o["batches"] > 0 for o in ops.values())

    def test_cpu_self_times_lie_within_the_wall(self, served):
        # one task in CPU_TIMED_EVERY carries them, whichever thread or
        # test ran the ones before it
        runs = [served("q3") for _ in range(trace.CPU_TIMED_EVERY)]
        timed = [led for led in runs if led["layers_cpu_s"] is not None]
        assert len(timed) == 1
        assert all("cpu_s" not in op for led in runs if led is not timed[0]
                   for op in led["ops_s"].values())
        led = timed[0]
        layers, cpu = led["layers_s"], led["layers_cpu_s"]
        assert set(cpu) == CPU_LAYERS
        for key in cpu:
            assert 0 <= cpu[key] <= layers[key] + 1e-3, key
        assert cpu["op_host"] > 0 and cpu["plan"] > 0
        # the task thread's CPU and its workers' make cpu_s
        worker = led["scan_worker_cpu_s"]
        assert set(worker) == {"decode", "encode", "h2d"}
        for key in worker:
            assert 0 < worker[key] <= led["scan_worker_s"][key] + 1e-3
        assert sum(cpu.values()) <= led["cpu_s"] - sum(worker.values()) \
            + 1e-3
        ops = led["ops_s"]
        # (each is cut at its own wall: a read's own CPU, which the
        # readback span does not book, is its operator's)
        assert sum(o["cpu_s"] for o in ops.values()) == pytest.approx(
            cpu["op_host"], abs=5e-3)
        assert all(0 <= o["cpu_s"] <= o["host_s"] + 1e-3
                   for o in ops.values())

    def test_every_row_count_read_is_a_row_sync(self, served):
        """The counts of a q3 task over one split: the operators read
        their batches' row counts through the one helper, and the
        control readbacks are what they were."""
        counts = served("q3")["counts"]
        assert counts["row_syncs"] > counts["readbacks"] > 0
        led = served("q3")
        assert led["ops_s"]["hash_join"]["device_wait_s"] > 0

    def test_scan_worker_and_cpu(self, served):
        led = served("q3")
        worker = led["scan_worker_s"]
        assert set(worker) == {"decode", "encode", "h2d"}
        assert all(v > 0 for v in worker.values())
        assert 0 < led["cpu_s"] < 8 * led["wall_s"]
        assert led["queue_s"] >= 0

    def test_counts_are_equal_run_to_run(self, served):
        a, b = served("q3"), served("q3")
        for key in COUNTS:
            assert a["counts"][key] == b["counts"][key], key
            # the star-join scans (int64, decimal(7,2), strings) send no
            # value through a per-row Python encode; all else is counted
            assert (a["counts"][key] > 0) \
                == (key != "encode_pyloop_values"), key
        assert a["counts"]["program_calls_by_site"] \
            == b["counts"]["program_calls_by_site"]
        assert sum(a["counts"]["program_calls_by_site"].values()) \
            == a["counts"]["program_calls"]

    def test_counts_do_not_depend_on_auron_trace_enabled(self, served):
        conf = cfg.get_config()
        off = served("q3")
        conf.set(cfg.TRACE_ENABLED, True)
        try:
            on = served("q3")
            # the root span closes on the server thread after the DONE
            # frame has gone out: give it a moment to be recorded
            deadline = time.monotonic() + 5.0
            while True:
                recorded = {s.name for s in trace.tracer().spans()}
                if "serve.task" in recorded \
                        or time.monotonic() > deadline:
                    break
                time.sleep(0.01)
        finally:
            conf.unset(cfg.TRACE_ENABLED)
            trace.reset()
        assert on["counts"] == off["counts"]
        # ... and with it on the layer spans are recorded as spans
        assert {"serve.task", "serve.queue", "serve.send", "plan.decode",
                "scan.wait", "scan.decode", "scan.encode", "scan.h2d",
                "convert.to_arrow", "op.hash_join",
                "op.readback"} <= recorded

    def test_a_q3_task_opens_fewer_than_2000_layer_spans(self, served):
        assert 20 < served("q3")["counts"]["layer_spans"] < 2000

    def test_concurrent_tasks_each_report_their_own_compiles(self, served):
        """q42 and q52 have not run in this process: each compiles (or
        fetches) programs of its own, on its own threads. The two
        ledgers' thread-attributed counts sum to the process's, where
        the old process-wide deltas each hold both."""
        from auron_tpu.utils import compile_stats
        before = compile_stats.snapshot()
        out = {}

        def go(plan):
            out[plan] = served(plan)

        threads = [threading.Thread(target=go, args=(p,))
                   for p in ("q42", "q52")]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        process = compile_stats.delta(before)
        mine = [out[p]["compile"]["task_xla_compiles"]
                for p in ("q42", "q52")]
        assert all(n > 0 for n in mine), mine
        assert sum(mine) == process.count
        secs = sum(out[p]["compile"]["task_seconds"] for p in ("q42", "q52"))
        assert secs == pytest.approx(process.seconds, abs=1e-3)
        for p in ("q42", "q52"):
            led = out[p]
            assert led["layers_s"]["compile"] > 0
            assert sum(led["layers_s"].values()) == pytest.approx(
                led["wall_s"], abs=1e-5)

    def test_a_profiler_session_holds_the_programs_layers(self, served,
                                                         tmp_path):
        """The harness's own profiler options on the CPU backend: the
        annotations land on /host:CPU beside XLA's host events."""
        import jax
        from jax.profiler import ProfileData
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            led = served("q3")
            # the handler closes its root span after the DONE frame: a
            # second task sees the first one's root closed for certain
            served("q3")
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(str(tmp_path), "**",
                                         "*.xplane.pb"), recursive=True)
        names = set()
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                names.update(ev.name.split("#")[0] for ev in line.events)
        for want in ("auron:serve/queue", "auron:serve/task",
                     "auron:serve/send", "auron:plan/decode",
                     "auron:scan/wait", "auron:scan/decode",
                     "auron:scan/encode", "auron:scan/h2d",
                     "auron:convert/to_arrow", "auron:op/readback"):
            assert want in names, want
        for op in led["ops_s"]:
            assert "auron:op/" + op in names, op
        pjit = {n for n in names if n.startswith("PjitFunction(")}
        assert "PjitFunction(kernel)" not in pjit
        for site in led["counts"]["program_calls_by_site"]:
            want = "PjitFunction(auron_" + site.replace(".", "_") + ")"
            assert want in pjit, (want, sorted(pjit))


# ---------------------------------------------------------------------------
# names a trace can read
# ---------------------------------------------------------------------------

def _builder_sites():
    """(site, file, [names of the functions jitted inside the builder])
    for every ``@program_cache`` builder and every ``ProgramCache(...)``
    registered by hand, read from the source."""
    out = []
    for path in sorted(glob.glob(os.path.join(_REPO, "auron_tpu", "**",
                                              "*.py"), recursive=True)):
        with open(path) as f:
            src = f.read()
        if "program_cache(" not in src and "ProgramCache(" not in src:
            continue
        tree = ast.parse(src)
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call) and getattr(
                            dec.func, "id", "") == "program_cache":
                        inner = [n.name for n in ast.walk(node)
                                 if isinstance(n, ast.FunctionDef)
                                 and n is not node]
                        out.append((dec.args[0].value,
                                    os.path.relpath(path, _REPO), inner))
            elif isinstance(node, ast.Call) and getattr(
                    node.func, "attr", "") == "ProgramCache" \
                    and node.args and isinstance(node.args[0], ast.Constant):
                site = node.args[0].value
                want = "auron_" + site.replace(".", "_")
                inner = [n.name for n in ast.walk(tree)
                         if isinstance(n, ast.FunctionDef)
                         and n.name == want]
                out.append((site, os.path.relpath(path, _REPO), inner))
    return out


_SITES = [s for s in _builder_sites()
          if not s[1].endswith(os.path.join("runtime", "programs.py"))]


def test_the_source_scan_finds_every_registered_site():
    import auron_tpu.exprs.bloom        # noqa: F401  (registers its site)
    import auron_tpu.ops.generate       # noqa: F401
    import auron_tpu.ops.smj            # noqa: F401
    import auron_tpu.ops.window         # noqa: F401
    import auron_tpu.parallel.exchange  # noqa: F401
    import auron_tpu.parallel.mesh_exchange  # noqa: F401
    from auron_tpu.runtime import programs
    registered = set(programs.snapshot())
    found = {s[0] for s in _SITES}
    assert len(found) >= 30
    assert registered <= found, registered - found


@pytest.mark.parametrize("site,path,inner", _SITES,
                         ids=[s[0] for s in _SITES])
def test_site_jits_a_function_named_for_the_site(site, path, inner):
    from auron_tpu.runtime import programs
    want = programs.site_function_name(site)
    assert want == "auron_" + site.replace(".", "_")
    assert want in inner, f"{path}: {site} jits {inner}, not {want}"
    assert "kernel" not in inner


def test_no_live_program_is_called_kernel(served):
    """Whatever the served tasks built: the callables the registry holds
    carry their site's name (``programs.jit``'s donation wrapper too)."""
    served("q3")
    from auron_tpu.runtime import programs
    seen = 0
    for site_name in programs.snapshot():
        cache = programs.site(site_name)
        with cache._lock:
            values = list(cache._memo.values())
        for value in values:
            if not callable(value):
                continue
            seen += 1
            name = getattr(value, "__name__", "")
            assert name == programs.site_function_name(site_name), \
                (site_name, name)
    assert seen >= 5


@pytest.mark.parametrize("fn,name", [
    ("pallas_sum_count", "auron_kernels_pallas_sum_count"),
    ("dense_matmul_sum_count", "auron_kernels_dense_matmul_sum_count")])
def test_module_level_kernels_carry_an_auron_name(fn, name):
    import jax.numpy as jnp

    from auron_tpu.kernels import grouped_agg
    jitted = getattr(grouped_agg, fn)
    assert jitted.__name__ == name
    k = jnp.zeros(256, jnp.int32)
    v = jnp.ones(256, jnp.float32)
    kwargs = {"interpret": True} if fn == "pallas_sum_count" else {}
    text = jitted.lower(k, v, v, key_domain=256, **kwargs).as_text()
    assert "@jit_" + name in text


# ---------------------------------------------------------------------------
# what went
# ---------------------------------------------------------------------------

def test_auron_profile_and_the_hotspot_tool_are_gone():
    # the names are spelled in pieces so that a grep for what went finds
    # nothing under auron_tpu/, tools/ and tests/
    knob, tool, export = "auron." + "profile", "hotspot" + "_report.py", \
        "export" + "_task"
    assert not hasattr(cfg, "PROFILE")
    assert not hasattr(cfg, "PROFILE" + "_DIR")
    assert hasattr(cfg, "PROFILE_ENABLED")      # the attribution knob stays
    with open(os.path.join(_REPO, "CONFIG.md")) as f:
        doc = f.read()
    rows = [l for l in doc.splitlines() if l.startswith("| `auron.")]
    names = {l.split("`")[1] for l in rows}
    assert knob not in names and knob + ".dir" not in names
    assert knob + ".enabled" in names
    assert len(rows) == 77
    assert not os.path.exists(os.path.join(_REPO, "tools", tool))
    from auron_tpu.obs import profile
    assert not hasattr(profile, export)


def test_exchange_spans_charge_the_exchange_layer():
    """The shuffle's existing spans are layer spans now: a repartitioned
    plan run under a task books ``layers_s.exchange`` and still records
    ``shuffle.materialize`` under its old category and name."""
    import numpy as np
    import pyarrow as pa

    from auron_tpu.frontend.dataframe import col, functions as F
    from auron_tpu.frontend.session import Session
    rng = np.random.default_rng(5)
    table = pa.table({"k": rng.integers(0, 16, 4096),
                      "v": rng.integers(0, 100, 4096)})
    conf = cfg.get_config()
    conf.set(cfg.TRACE_ENABLED, True)
    trace.reset()
    s = Session()
    try:
        df = (s.from_arrow(table).repartition(2, "k")
              .group_by("k").agg(F.sum(col("v")).alias("sv")))
        with trace.task_scope("q-exchange") as acc:
            acc.start()
            t0 = time.monotonic()
            with trace.layer_span("serve", "task"):
                assert df.collect().num_rows == 16
                v2 = acc.sealed(time.monotonic() - t0)
        recorded = {(sp.cat, sp.name) for sp in trace.tracer().spans()}
    finally:
        conf.unset(cfg.TRACE_ENABLED)
        trace.reset()
        s.close()
    assert v2["layers_s"]["exchange"] > 0
    assert ("shuffle", "shuffle.materialize") in recorded

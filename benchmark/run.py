"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run makes the cell's TPC-DS tables from ``--seed``, starts ONE child
(``harness/server_main.py``: the only process that touches the chip,
holding ``AuronServer``), has it build every task's plan bytes, warms up
the shapes the window uses through the served path, then drives the
server for ``--seconds`` with the traffic mix's closed-loop
``AuronClient`` threads over loopback, and afterwards compares every
answer the window completed with the plan's own Acero oracle over exactly
that task's rows. The last line of stdout is the result object.

This process never initialises a jax backend: it pins itself to the CPU
platform after it has handed the child the environment it was given.
A run that finds no TPU exits non-zero and prints no result; an explicit
``JAX_PLATFORMS=cpu`` is the rehearsal path and its line says ``cpu``.
``--scale`` (default 1.0 = SF1) shrinks tables and splits together for
rehearsals and tests; a line made at another scale says so.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import select
import shutil
import struct
import subprocess
import sys
import tempfile
import threading
import time

_T0 = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from harness import cell as cellmod        # noqa: E402
from harness import compare, datagen, metrics        # noqa: E402

#: what marks a task as failed in its DONE frame (the served smoke's rule)
FAULT_NEEDLES = ("retr", "recompute", "demot", "interpret")
#: the traced slice: it starts this share into the window and lasts at
#: most this many seconds (traces are large and tracing slows the host)
TRACE_START_SHARE = 0.25
TRACE_SLICE_S = 5.0
#: how long the child may take to find its devices and start serving
CHILD_READY_S = 300.0
#: a warm-up task's read budget: the first run of a cell in a checkout
#: may take 1,200 s in all
COLD_TIMEOUT_S = 1000.0


def log(msg: str) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


def nonzero_leaves(tree, needles, path="") -> dict:
    """Every nonzero numeric leaf of a metrics tree whose key mentions
    one of ``needles``."""
    found = {}
    if isinstance(tree, dict):
        for key, val in tree.items():
            where = f"{path}.{key}" if path else str(key)
            if isinstance(val, (dict, list)):
                found.update(nonzero_leaves(val, needles, where))
            elif isinstance(val, (int, float)) and val \
                    and any(n in str(key) for n in needles):
                found[where] = val
    elif isinstance(tree, list):
        for i, val in enumerate(tree):
            found.update(nonzero_leaves(val, needles, f"{path}[{i}]"))
    return found


class Child:
    """The serving process and the two pipes to it."""

    def __init__(self, cell, log_path: str):
        read_fd, write_fd = os.pipe()
        self.log_path = log_path
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "harness", "server_main.py"),
             "--config", cell.config_file, "--chips", str(cell.chips),
             "--reply-fd", str(write_fd)],
            stdin=subprocess.PIPE, stdout=self._log,
            stderr=subprocess.STDOUT, pass_fds=(write_fd,),
            cwd=os.path.dirname(HERE))
        os.close(write_fd)
        self._replies = os.fdopen(read_fd, "r")
        self._lock = threading.Lock()

    def read_reply(self, timeout_s: float | None = None) -> dict:
        if timeout_s is not None and not select.select(
                [self._replies], [], [], timeout_s)[0]:
            raise RuntimeError(
                f"the serving process said nothing for {timeout_s:.0f}s; "
                f"the end of its log:\n{self.log_tail()}")
        line = self._replies.readline()
        if not line:
            raise RuntimeError(
                f"the serving process ended (exit {self.proc.wait()}); "
                f"the end of its log:\n{self.log_tail()}")
        return json.loads(line)

    def ask(self, **cmd) -> dict:
        with self._lock:
            self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
            self.proc.stdin.flush()
            return self.read_reply()

    def log_tail(self, n: int = 4000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")

    def stop(self) -> None:
        """Ask it to leave, then make sure: nothing of a run outlives it."""
        if self.proc.poll() is None:
            try:
                self.ask(cmd="exit")
            except (RuntimeError, OSError, ValueError):
                pass
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self.proc.wait()
        self._replies.close()
        self._log.close()


def make_data(cell, plan_modules, seed, scale, workdir) -> dict:
    """Tables from the seed, the fact table as parquet splits, the
    dimensions one file each. Returns what the run needs of them."""
    names = cell.tables(plan_modules)
    arrow = datagen.generate(seed, scale, names)
    fact = names[0]
    data_dir = os.path.join(workdir, "data")
    splits = datagen.write_splits(
        data_dir, fact, arrow[fact],
        cellmod.scaled_split_rows(cell.config, scale))
    dims = {name: datagen.write_whole(data_dir, name, arrow[name])
            for name in names[1:]}
    return {"arrow": arrow, "fact": fact, "splits": splits, "dims": dims,
            "groups": cellmod.split_groups(
                splits, int(cell.config["splits_per_task"]))}


def read_task_bytes(path: str, n: int) -> list:
    out = []
    with open(path, "rb") as f:
        for _ in range(n):
            (size,) = struct.unpack("<I", f.read(4))
            out.append(f.read(size))
    return out


def leaf_sum(tree, key) -> float:
    """Sum of the numeric leaves called ``key`` anywhere in a tree."""
    if isinstance(tree, dict):
        return sum(v if k == key and isinstance(v, (int, float))
                   else leaf_sum(v, key) for k, v in tree.items())
    if isinstance(tree, list):
        return sum(leaf_sum(v, key) for v in tree)
    return 0


def run_task(client, blob, require=None) -> dict:
    """One task through the served path, judged by its DONE frame:
    ``require`` {counter: least count} is what the configuration's
    guarantees say every task's frame must show."""
    t_submit = time.monotonic()
    rec = {"ok": False, "error": "", "table": None, "done": None}
    try:
        table, done = client.execute(blob)
    except Exception as e:   # a failed task is a result, not a crash
        rec["error"] = f"{type(e).__name__}: {str(e)[-400:]}"
    else:
        rec["table"], rec["done"] = table, done
        ledger = (done or {}).get("cost_ledger")
        bad = nonzero_leaves(done, FAULT_NEEDLES)
        seen = {k: leaf_sum(done, k) for k in require or {}}
        short = {k: n for k, n in seen.items() if n < require[k]}
        if not isinstance(ledger, dict):
            rec["error"] = "the DONE frame carries no cost_ledger"
        elif ledger.get("outcome") != "ok":
            rec["error"] = f"outcome {ledger.get('outcome')!r}"
        elif bad:
            rec["error"] = f"retries / demotions / interpreted: {bad}"
        elif short:
            rec["error"] = f"counters under what the config requires: {short}"
        else:
            rec["ok"] = True
    rec["t_submit"], rec["t_done"] = t_submit, time.monotonic()
    return rec


def drive(client_factory, n_clients, tasks, until=None, require=None,
          stop_on_failure=False) -> list:
    """``n_clients`` closed-loop clients, each submitting its next task
    when the last returned. ``tasks`` is an iterator of (label, blob)
    shared by the clients; with ``until`` no task is submitted after it
    and none is waited for beyond it. Returns the records so far."""
    records, lock = [], threading.Lock()
    failed = threading.Event()

    def loop():
        client = client_factory()
        while (until is None or time.monotonic() < until) \
                and not (stop_on_failure and failed.is_set()):
            with lock:
                nxt = next(tasks, None)
            if nxt is None:
                return
            label, blob = nxt
            rec = run_task(client, blob, require)
            rec.update(label)
            if not rec["ok"]:
                failed.set()
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=loop, daemon=True)
               for _ in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        # the window closes on time: a task still in flight is not
        # waited for, it is cut with the serving process
        t.join(None if until is None
               else max(0.0, until - time.monotonic()))
    with lock:
        return list(records)


def check_answers(cell, data, plan_modules, tasks) -> dict:
    """Every completed task against its oracle over its own rows."""
    arrow, fact = data["arrow"], data["fact"]
    results = []
    for t in tasks:
        _g, _files, lo, rows = data["groups"][t["group"]]
        view = dict(arrow)
        view[fact] = arrow[fact].slice(lo, rows)
        want = plan_modules[t["plan"]].oracle(view)
        res = compare.compare_tables(t["table"], want)
        if not compare.answer_ok(res):
            log(f"compare: {t['plan']} over group {t['group']} differs: "
                f"{res['error'] or res['examples']}")
        results.append(res)
    return compare.decide(results)


def layer_metrics(cell, ctx) -> dict:
    out = {}
    for name, entry in cell.per_layer().items():
        value = cellmod.load_module("layer_metrics", name).read(ctx)
        if value is not None:
            out[name] = {"value": value, "unit": entry["unit"]}
    return out


def run(args, client_wrapper=None) -> dict:
    """The whole run; returns the result object. ``client_wrapper`` is
    for the tests under ``benchmark/tests``, which break the timed path
    underneath the harness to see ``correct`` come out false."""
    cell = cellmod.Cell(args.workload, args.benchmark_json)
    plan_modules = {p: cellmod.load_module("plans", p) for p in cell.plans}
    workdir = tempfile.mkdtemp(prefix="auron_bench_")
    child = Child(cell, os.path.join(workdir, "server.log"))
    # whatever jax this process might yet import stays off the chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        return _run(args, cell, plan_modules, workdir, child,
                    client_wrapper)
    except Exception:
        log("the end of the serving process's log:\n" + child.log_tail())
        raise
    finally:
        child.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def _run(args, cell, plan_modules, workdir, child, client_wrapper) -> dict:
    from auron_tpu.runtime.serving import AuronClient

    seconds = float(args.seconds)
    log(f"cell {cell.name}: config {cell.config['name']}, traffic "
        f"{cell.traffic['name']}, seed {args.seed}, scale {args.scale}")
    data = make_data(cell, plan_modules, args.seed, args.scale, workdir)
    groups = data["groups"]
    log(f"data: {data['arrow'][data['fact']].num_rows} {data['fact']} rows "
        f"in {len(data['splits'])} splits, {len(groups)} tasks a plan")

    ready = child.read_reply(CHILD_READY_S)
    device = ready["device"]
    log(f"device: {device['count']} x {device['kind']} "
        f"({device['platform']}), found in {ready['device_init_s']:.1f}s; "
        f"compile cache {ready['cache_dir']} holds "
        f"{ready['cache_entries']} programs")

    order = cellmod.task_order(cell.plans, len(groups), args.seed)
    warm = cellmod.warmup_pairs(cell.plans, groups,
                                int(cell.config.get("warmup_groups", 1)))
    pairs = sorted(set(order) | set(warm))
    index = {pair: i for i, pair in enumerate(pairs)}
    tasks_path = os.path.join(workdir, "tasks.bin")
    child.ask(cmd="build", out=tasks_path, tables=data["dims"],
              scan_partitions=int(cell.config["scan_partitions"]),
              tasks=[[plan, groups[g][1]] for plan, g in pairs])
    blobs = read_task_bytes(tasks_path, len(pairs))
    log(f"plans: {len(blobs)} tasks' bytes built by the serving process")

    n_clients = int(cell.traffic["clients"])

    def client_factory(timeout_s=float(cell.traffic["task_timeout_s"])):
        client = AuronClient(ready["host"], ready["port"],
                             timeout_s=timeout_s)
        return client_wrapper(client) if client_wrapper else client

    def tasks_of(pairs):
        return ((({"plan": plan, "group": g, "rows": groups[g][3]}),
                 blobs[index[(plan, g)]]) for plan, g in pairs)

    # -- warm-up: every shape of the window, through the served path -----
    require = cell.config.get("require_counters")
    # a checkout's first run compiles here, and the server sends no
    # frame while it does: warm-up waits as long as a cold run may take
    warm_records = drive(lambda: client_factory(COLD_TIMEOUT_S), n_clients,
                         tasks_of(warm), require=require,
                         stop_on_failure=True)
    for rec in warm_records:
        if not rec["ok"]:
            raise RuntimeError(f"warm-up task {rec['plan']} over group "
                               f"{rec['group']} failed: {rec['error']}")
    # process-wide counts: concurrent tasks' own ledgers each see the
    # others' compiles, so their sum counts a program several times
    warm_stats = child.ask(cmd="stats")
    log(f"warm-up: {len(warm_records)} tasks; {warm_stats['xla_programs']} "
        f"XLA programs compiled or fetched in "
        f"{warm_stats['xla_compile_s']:.1f}s so far, persistent cache "
        f"{warm_stats['persistent_cache']}")

    # -- the window ---------------------------------------------------------
    trace_info = {}

    def trace_slice(t_start):
        time.sleep(max(0.0, t_start + TRACE_START_SHARE * seconds
                       - time.monotonic()))
        child.ask(cmd="trace_start", dir=os.path.join(workdir, "trace"))
        trace_info["start"] = time.monotonic() - t_start
        time.sleep(min(TRACE_SLICE_S, 0.5 * seconds))
        child.ask(cmd="trace_stop")
        trace_info["stop"] = time.monotonic() - t_start

    setup_s = time.monotonic() - _T0
    t_start = time.monotonic()
    tracer = None
    if args.trace:
        tracer = threading.Thread(target=trace_slice, args=(t_start,),
                                  daemon=True)
        tracer.start()
    records = drive(client_factory, n_clients,
                    tasks_of(itertools.cycle(order)),
                    until=t_start + seconds, require=require)
    t_close = time.monotonic()
    if tracer is not None:
        tracer.join()
    window = [r for r in records if r["t_done"] <= t_start + seconds]
    for r in window + warm_records:
        r["t_submit"] -= t_start
        r["t_done"] -= t_start
    done_ok = [r for r in window if r["ok"]]
    failed = [r for r in window if not r["ok"]]
    log(f"window: {seconds:.0f}s, {n_clients} closed-loop client(s); "
        f"{len(done_ok)} tasks completed, {len(failed)} failed, "
        f"the tasks in flight at the close are cut "
        f"(closed {t_close - t_start - seconds:+.2f}s off)")
    for r in failed[:5]:
        log(f"failed: {r['plan']} over group {r['group']}: {r['error']}")
    for plan in cell.plans:
        lat = metrics.latencies_ms([r for r in done_ok if r["plan"] == plan])
        if lat:
            log(f"  {plan}: {len(lat)} tasks, latency median "
                f"{metrics.percentile(lat, 50):.0f} ms, max {max(lat):.0f} ms")
    if args.tasks_out:
        with open(args.tasks_out, "w", encoding="utf-8") as f:
            json.dump([{k: r[k] for k in ("plan", "group", "rows", "ok",
                                          "error", "t_submit", "t_done",
                                          "done")}
                       for r in warm_records + window], f)

    stats = child.ask(cmd="stats")
    child.stop()
    trace = None
    if args.trace:
        # read here, once the serving process is gone: this process is
        # pinned off the chip and has its interpreter to itself
        from harness import trace_reduce
        trace = trace_reduce.reduce_dir(os.path.join(workdir, "trace"),
                                        cell.chips)
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(trace_reduce.find_xplane(
                os.path.join(workdir, "trace")), args.keep_trace)

    interp = {k: v.get("interpret", 0) for k, v in stats["kernels"].items()}
    if any(interp.values()):
        log(f"interpreted kernels ran: {interp}")
    n_failed = len(failed) + (1 if any(interp.values()) else 0)

    # -- correctness: after the window, outside set-up ----------------------
    t_cmp = time.monotonic()
    verdict = check_answers(cell, data, plan_modules, done_ok)
    log(f"compare: {verdict['tasks_compared']} answers, "
        f"{verdict['cells_compared']} cells; differing exact cells "
        f"{verdict['exact_mismatches']} (limit {verdict['exact_limit']}); "
        f"widest double gap {verdict['max_double_rel']:.3g} (limit "
        f"{verdict['double_rel_limit']:g}); answers of the wrong shape "
        f"{verdict['shape_errors']} (limit 0); "
        f"took {time.monotonic() - t_cmp:.1f}s")

    e2e = metrics.end_to_end(window, setup_s)
    lat = metrics.latencies_ms(window)
    log(f"latency samples: {len(lat)}")
    device_out = dict(device, memory_peak_bytes=stats["memory_peak_bytes"])
    result = {"correct": verdict["correct"],
              "attempted": len(window), "failed": n_failed,
              "device": device_out}
    if args.trace:
        ctx = {"tasks": done_ok, "warmup": warm_records, "trace": trace,
               "trace_slice": trace_info, "stats": stats,
               "warm_stats": warm_stats,
               "seconds": seconds, "config": cell.config,
               "traffic": cell.traffic, "device": device}
        result["metrics"] = layer_metrics(cell, ctx)
        device_out["busy_s"] = trace["busy_s"]
        device_out["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        log(f"trace: {trace['window_s']:.2f}s traced, device busy "
            f"{trace['busy_s']:.3f}s averaged over {cell.chips} chip(s) "
            f"({trace['devices']} with a plane in the trace), "
            f"{trace['modules']} programs run")
    else:
        units = cell.end_to_end()
        result["metrics"] = {
            name: {"value": e2e[name], "unit": units[name]["unit"]}
            for name in units if name in e2e}
    result["compile"] = {"xla_programs": stats["xla_programs"],
                         "xla_compile_s": stats["xla_compile_s"],
                         "persistent_cache": stats["persistent_cache"]}
    if args.scale != 1.0:
        result["rehearsal_scale"] = args.scale
    # last in the line: each number the verdict compared, beside its limit
    result["compared"] = {
        "answers": verdict["tasks_compared"],
        "exact_mismatches": {"value": verdict["exact_mismatches"],
                             "limit": verdict["exact_limit"]},
        "max_double_rel": {"value": verdict["max_double_rel"],
                           "limit": verdict["double_rel_limit"]},
        "shape_errors": {"value": verdict["shape_errors"], "limit": 0}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="1.0 = SF1; smaller only for rehearsals")
    ap.add_argument("--tasks-out", default=None,
                    help="write every task's record (times, DONE frame) "
                         "here as JSON")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced slice's .xplane.pb here")
    ap.add_argument("--benchmark-json", default=None,
                    help="another BENCHMARK.json (tests)")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except Exception as e:   # no result line: the exit code says it
        log(f"the run failed: {type(e).__name__}: {e}")
        return 1
    print(json.dumps(result), flush=True)
    for name, num in result["compared"].items():
        print(f"compared {name}: {json.dumps(num)}", file=sys.stderr,
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

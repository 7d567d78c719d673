"""chip_smoke.py — does the engine still start on the chip?

Drives the main path once, on one TPU, through the entry points a user
calls, and checks every answer by the repo's own means:

  device   jax.devices() must be a TPU (no TPU -> exit 1, nothing else
           runs, also under JAX_PLATFORMS=cpu); versions and HBM limit
  native   the host library: built / loaded / numpy, and why
  kernels  the Pallas VMEM grouped-agg compiled by Mosaic
           (interpret=False) at the flagship shape (gh=256) and a small
           domain (gh=8) vs the one-hot matmul kernel and numpy;
           jit(entry()) with the dispatch decision recorded as
           pallas_vmem; the sort and hash general kernels vs numpy
  served   TPC-DS q3/q42/q52/q55 over the real-schema generator at
           --scale (2.88 = the SF1 fact cardinality): client -> socket ->
           AuronServer -> plan_from_bytes -> scan -> device programs ->
           exchange -> Arrow BATCH frames back, each compared with its
           independent Acero oracle; device seconds > 0, zero retries,
           zero demotions, zero interpreted kernels — read from the DONE
           frame and the process registries
  mesh     with >= 4 devices: q01 and a TPC-DS join query with the
           exchange on the 4-chip all_to_all, bit-identical to mesh-off,
           shards on 4 distinct devices; else "mesh: skipped (N devices)"
  cache    where the compile cache is, entries before/after, programs
           built, compile seconds, persistent-cache hits

One process holds the chip: server and client share it over loopback.
Any failed check raises; nothing is caught and carried on from. The last
stdout line of a green run is one JSON object naming the device.

    python chip_smoke.py [--seed 7] [--scale 2.88]

The script sets no platform variable: it runs where jax runs.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time

_T0 = time.monotonic()

#: TPC-DS store_sales rows at scale 1.0 of it/tpcds.py; 2.88 is the SF1
#: (1 GB) fact cardinality the reference's own CI gates on
_SF1_SCALE = 2.88
_QUERIES = ("q3", "q42", "q52", "q55")


def say(stage: str, msg: str) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s] {stage}: {msg}", flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def stage_device():
    from importlib import metadata

    import jax
    import jaxlib

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        say("device", f"FAIL — no TPU: jax found {len(devs)} "
                      f"{dev.platform} device(s)")
        sys.exit(1)
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    check(limit, "the TPU reports no HBM bytes_limit")
    say("device", f"ok — {len(devs)} x {dev.device_kind} (tpu), HBM "
                  f"bytes_limit {limit} ({limit / 2**30:.2f} GiB); jax "
                  f"{jax.__version__}, jaxlib {jaxlib.__version__}, "
                  f"libtpu {metadata.version('libtpu')}")
    return dev


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _groups(keys, valid, sums, counts) -> dict:
    """{key: (sum, count)} of a kernel's dense group state."""
    import numpy as np
    keys, valid = np.asarray(keys), np.asarray(valid)
    sums, counts = np.asarray(sums), np.asarray(counts)
    return {int(k): (float(s), int(c))
            for k, s, c in zip(keys[valid], sums[valid], counts[valid])}


def _groups_agree(got: dict, want: dict, what: str, rtol: float) -> None:
    import math
    check(got.keys() == want.keys(),
          f"{what}: {len(got)} groups vs {len(want)} in the reference")
    for k, (s, c) in want.items():
        gs, gc = got[k]
        check(gc == c, f"{what}: key {k} count {gc} != {c}")
        check(math.isclose(gs, s, rel_tol=rtol, abs_tol=rtol * 64),
              f"{what}: key {k} sum {gs} != {s}")


def stage_kernels(seed: int) -> None:
    import numpy as np

    import jax
    import jax.numpy as jnp

    import __graft_entry__ as graft
    from auron_tpu.columnar.batch import DeviceBatch, PrimitiveColumn
    from auron_tpu.columnar.schema import DataType
    from auron_tpu.kernels import dispatch
    from auron_tpu.kernels import grouped_agg as gagg
    from auron_tpu.kernels import registry as kregistry

    rng = np.random.default_rng(seed)
    n = 1 << 20

    # -- the Pallas kernel, native, against two references ------------------
    for domain in (1 << 16, 1 << 11):
        gh, _gl = gagg.grid_dims(domain)
        k = rng.integers(0, domain, n).astype(np.int32)
        c = (rng.random(n) > 0.05).astype(np.float32)
        v = rng.normal(size=n).astype(np.float32) * c
        t0 = time.monotonic()
        ps, pc = gagg.pallas_sum_count(jnp.asarray(k), jnp.asarray(v),
                                       jnp.asarray(c), domain,
                                       interpret=False)
        ps, pc = np.asarray(ps), np.asarray(pc)
        took = time.monotonic() - t0
        ds, dc = gagg.dense_matmul_sum_count(
            jnp.asarray(k), jnp.asarray(v), jnp.asarray(c), domain)
        want_s = np.zeros(domain)
        np.add.at(want_s, k, v.astype(np.float64))
        want_c = np.zeros(domain)
        np.add.at(want_c, k, c)
        check(np.array_equal(pc, want_c), f"pallas gh={gh}: counts differ")
        check(np.array_equal(pc, np.asarray(dc)),
              f"pallas gh={gh}: counts differ from dense_matmul")
        # f32 accumulation: eps x rows per key x the largest value
        atol = 2.0 ** -23 * (n / domain) * float(np.abs(v).max())
        err = float(np.abs(ps - want_s).max())
        err_d = float(np.abs(ps - np.asarray(ds)).max())
        check(err <= atol, f"pallas gh={gh}: |sum - numpy| {err} > {atol}")
        check(err_d <= atol,
              f"pallas gh={gh}: |sum - dense_matmul| {err_d} > {atol}")
        say("kernels", f"pallas_vmem interpret=False gh={gh} blk=2048 "
                       f"n=2^20: ok — max|sum-numpy| {err:.2e}, "
                       f"|sum-dense_matmul| {err_d:.2e}, counts exact "
                       f"({took:.1f}s incl. compile)")

    # -- entry(): the dispatch decision, recorded ----------------------------
    decision = dispatch.select_grouped_agg(
        key_domain=1 << 16, key_dtypes=(DataType.INT64,),
        agg_fns=("sum", "count", "avg"),
        value_dtypes=(DataType.FLOAT64,), record=False)
    check((decision.kernel, decision.interpret) == ("pallas_vmem", False),
          f"dispatch on a TPU chose {decision}")
    before = kregistry.snapshot()["pallas_vmem"]
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    after = kregistry.snapshot()["pallas_vmem"]
    check(after["selected"] == before["selected"] + 1
          and after["interpret"] == before["interpret"] == 0,
          f"entry() did not record pallas_vmem native: {before} -> {after}")
    say("kernels", "jit(entry()[0]): ok — dispatch recorded pallas_vmem, "
                   "interpret=False")

    # -- the three q01 kernels over one 2^20-row batch vs numpy -------------
    # (the entry file's schema: k int64 in [0, 2^16), v float64 with
    # nulls, f int32; WHERE f > 10 AND v IS NOT NULL GROUP BY k)
    hk = rng.integers(0, 1 << 16, n).astype(np.int64)
    hv = rng.normal(size=n)
    hf = rng.integers(0, 40, n).astype(np.int32)
    hv_valid = rng.random(n) > 0.05
    ones = jnp.ones(n, jnp.bool_)
    batch = DeviceBatch(
        columns=(PrimitiveColumn(jnp.asarray(hk), ones),
                 PrimitiveColumn(jnp.asarray(hv), jnp.asarray(hv_valid)),
                 PrimitiveColumn(jnp.asarray(hf), ones)),
        num_rows=jnp.asarray(n, jnp.int32))
    keep = (hf > 10) & hv_valid
    sums = np.zeros(1 << 16)
    np.add.at(sums, hk[keep], hv[keep])
    cnts = np.bincount(hk[keep], minlength=1 << 16)
    want = {int(k): (float(sums[k]), int(cnts[k]))
            for k in np.nonzero(cnts)[0]}
    for name, kernel, rtol in (
            ("flagship(pallas_vmem)", graft.flagship_kernel(), 1e-5),
            ("_q01_kernel_sort", graft._q01_kernel_sort, 1e-9),
            ("_q01_kernel_hash", graft._q01_kernel_hash, 1e-9)):
        t0 = time.monotonic()
        keys, valid, ksums, kcounts, _avg = jax.jit(kernel)(batch)
        got = _groups(keys, valid, ksums, kcounts)
        _groups_agree(got, want, name, rtol)
        say("kernels", f"{name} n=2^20 keys=2^16: ok — {len(got)} groups "
                       f"match numpy ({time.monotonic() - t0:.1f}s incl. "
                       "compile)")
    interp = {k: s["interpret"] for k, s in kregistry.snapshot().items()}
    check(not any(interp.values()), f"interpreted kernels ran: {interp}")


# ---------------------------------------------------------------------------
# served
# ---------------------------------------------------------------------------

def _nonzero(tree, needles, path="") -> dict:
    """Every nonzero numeric leaf of a metrics tree whose key mentions
    one of ``needles`` — how 'zero retries, zero demotions' is read off
    a DONE frame without naming each operator."""
    found = {}
    if isinstance(tree, dict):
        for key, val in tree.items():
            where = f"{path}.{key}" if path else str(key)
            if isinstance(val, (dict, list)):
                found.update(_nonzero(val, needles, where))
            elif isinstance(val, (int, float)) and val \
                    and any(n in str(key) for n in needles):
                found[where] = val
    elif isinstance(tree, list):
        for i, val in enumerate(tree):
            found.update(_nonzero(val, needles, f"{path}[{i}]"))
    return found


def stage_served(seed: int, scale: float, workdir: str) -> dict:
    import pyarrow as pa

    from auron_tpu.frontend.session import Session
    from auron_tpu.it import runner, tpcds, tpcds_queries
    from auron_tpu.it.comparator import QueryResultComparator
    from auron_tpu.kernels import registry as kregistry
    from auron_tpu.runtime.serving import AuronClient, AuronServer

    if scale != _SF1_SCALE:
        say("served", f"scale CUT: {scale} instead of the SF1 "
                      f"{_SF1_SCALE}")
    t0 = time.monotonic()
    tables = tpcds.generate(workdir, scale=scale, seed=seed)
    arrow = tpcds.load_arrow(
        {name: tables[name] for name in ("store_sales", "date_dim", "item")})
    n_fact = arrow["store_sales"].num_rows
    say("served", f"data: seed {seed}, scale {scale} -> store_sales "
                  f"{n_fact} rows, generated + loaded for the oracle in "
                  f"{time.monotonic() - t0:.1f}s")

    class ServedSession(Session):
        """The client side: ``execute`` — where every Query.run's
        ``.collect()`` lands — ships each partition's TaskDefinition
        over the socket and reassembles the BATCH frames."""

        def __init__(self, client):
            super().__init__()
            self.client = client
            self.dones: list = []

        def execute(self, df, timeout_s=None):
            parts = []
            for p in range(df.num_partitions):
                table, metrics = self.client.execute(df.task_bytes(p))
                self.dones.append(metrics)
                parts.append(table)
            return pa.concat_tables(parts)

    comparator = QueryResultComparator(double_rel_tol=1e-7,
                                       double_abs_tol=1e-6)
    server = AuronServer()
    server.serve_background()
    try:
        client = AuronClient(*server.address, timeout_s=900)
        for q in tpcds_queries.QUERIES:
            if q.name not in _QUERIES:
                continue
            session = ServedSession(client)
            t0 = time.monotonic()
            got = q.run(session, tables)
            wall = time.monotonic() - t0
            session.close()
            res = comparator.compare(
                q.name, runner.defloat_decimals(got),
                runner.defloat_decimals(q.oracle(arrow)))
            check(res.ok, f"served {q.name} differs from its oracle: "
                          f"{res.report()}")
            check(got.num_rows > 0, f"served {q.name} returned no rows")
            check(session.dones, f"served {q.name}: no DONE frame seen")
            device_s = compiles = compile_s = batches = 0
            for done in session.dones:
                ledger = done.get("cost_ledger")
                check(isinstance(ledger, dict),
                      f"served {q.name}: DONE carries no cost_ledger")
                check(ledger["outcome"] == "ok", f"{q.name}: {ledger}")
                check(ledger["device"]["platform"] == "tpu",
                      f"served {q.name} computed on {ledger['device']}")
                check(ledger["device_s"] > 0,
                      f"served {q.name}: device seconds "
                      f"{ledger['device_s']} — nothing ran on the device")
                bad = _nonzero(done, ("retr", "recompute", "demot",
                                      "interpret"))
                check(not bad, f"served {q.name}: retries / demotions / "
                               f"interpreted kernels recorded: {bad}")
                device_s += ledger["device_s"]
                batches += ledger["batches"]
                compiles += ledger["compile"]["xla_compiles"]
                compile_s += ledger["compile"]["seconds"]
            check(batches >= 1, f"served {q.name}: no BATCH frame crossed "
                                "the socket")
            say("served", f"{q.name}: ok — {got.num_rows} rows in "
                          f"{batches} BATCH frame(s) == oracle; wall "
                          f"{wall:.1f}s, device_s {device_s:.2f}, "
                          f"{compiles} compiles {compile_s:.1f}s, "
                          "0 retries/demotions/interpreted")
    finally:
        server.shutdown()
    interp = {k: s["interpret"] for k, s in kregistry.snapshot().items()}
    check(not any(interp.values()), f"interpreted kernels ran: {interp}")
    say("served", f"kernels registry: {kregistry.snapshot()}")
    return tables


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------

def stage_mesh(tables: dict, workdir: str) -> None:
    import os

    import jax

    n_dev = len(jax.devices())
    if n_dev < 4:
        say("mesh", f"skipped ({n_dev} device{'s' if n_dev != 1 else ''})")
        return

    import __graft_entry__ as graft
    from auron_tpu import config as cfg
    from auron_tpu.frontend.session import Session
    from auron_tpu.it import tpcds_data, tpcds_queries
    from auron_tpu.it.queries import q01_dataframe
    from auron_tpu.obs import metric_tree as mt
    from auron_tpu.parallel import mesh as mesh_mod

    class TreeSession(Session):
        """``execute`` through EXPLAIN ANALYZE, keeping the mirrored
        metric tree: the exchange's route counters are read, never
        inferred."""

        tree = None

        def execute(self, df, timeout_s=None):
            op = self.plan_physical(df)
            self.tree, table = mt.explain_analyze(
                op, num_partitions=df.num_partitions,
                mem_manager=self.mem_manager, config=self.config)
            return table

    def counters(tree) -> dict:
        out: dict = {}
        for node in tree.walk():
            for key, val in node.metrics.items():
                if key.startswith(("exchange_route_", "mesh_")):
                    out[key] = out.get(key, 0) + val
        return out

    conf = cfg.get_config()
    star = tpcds_data.generate(os.path.join(workdir, "star"), scale=2.0)
    q3 = next(q for q in tpcds_queries.QUERIES if q.name == "q3")
    runs = (("q01_dataframe(partitions=4)",
             lambda s: s.execute(q01_dataframe(s, star, partitions=4))),
            ("tpcds q3", lambda s: q3.run(s, tables)))
    for name, run in runs:
        off = run(TreeSession())
        conf.set(cfg.MESH_ENABLED, True)
        conf.set(cfg.MESH_DEVICES, 4)
        try:
            session = TreeSession()
            t0 = time.monotonic()
            on = run(session)
            wall = time.monotonic() - t0
            plane = mesh_mod.current_plane()
            check(plane is not None and plane.num_devices == 4,
                  f"mesh plane: {plane}")
            check(not plane.quarantined(),
                  f"mesh {name}: quarantined {plane.quarantined()}")
        finally:
            conf.unset(cfg.MESH_ENABLED)
            conf.unset(cfg.MESH_DEVICES)
        got = counters(session.tree)
        routed = got.get("exchange_route_all_to_all", 0)
        check(routed >= 1, f"mesh {name}: no all_to_all route: {got}")
        check(got.get("exchange_route_demoted", 0) == 0,
              f"mesh {name}: demoted: {got}")
        check(got.get("mesh_shard_devices", 0) == 4 * routed,
              f"mesh {name}: exchange output shards on "
              f"{got.get('mesh_shard_devices')} devices over {routed} "
              "exchange(s), expected 4 each")
        check(on.equals(off), f"mesh {name}: differs from mesh-off")
        say("mesh", f"{name}: ok — {routed} exchange(s) on all_to_all, 0 "
                    f"demoted, shards on 4 distinct devices, "
                    f"{got.get('mesh_bytes_moved', 0)} bytes moved, "
                    f"Table.equals(mesh-off); wall {wall:.1f}s")
    live: dict = {}
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            live[shard.device.id] = live.get(shard.device.id, 0) \
                + shard.data.nbytes
    say("mesh", "live device buffers after the runs, bytes by device id: "
                f"{dict(sorted(live.items()))}")
    graft.dryrun_multichip(4)


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def stage_cache(path: str, entries_before: int) -> None:
    from auron_tpu.runtime import programs
    from auron_tpu.utils import compile_stats, xla_cache

    snap = compile_stats.snapshot()
    cache = compile_stats.persistent_cache()
    built = programs.totals()
    say("cache", f"dir {path}: {entries_before} entries before, "
                 f"{xla_cache.entries(path)} after; {snap.count} XLA "
                 f"programs, {snap.seconds:.1f}s compiling; persistent "
                 f"cache {cache['hits']} hits / {cache['misses']} misses; "
                 f"program registry {built.builds} builds / "
                 f"{built.hits} hits")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--scale", type=float, default=_SF1_SCALE,
                    help="it/tpcds.py scale of the served stage "
                         "(1.0 = 1M store_sales rows)")
    args = ap.parse_args(argv)

    dev = stage_device()

    from auron_tpu import native
    from auron_tpu.utils import compile_stats, xla_cache
    say("native", "%s (%s)" % native.status())
    compile_stats.install()
    cache_path = xla_cache.bind()
    entries_before = xla_cache.entries(cache_path)

    import jax
    workdir = tempfile.mkdtemp(prefix="auron_chip_smoke_")
    try:
        stage_kernels(args.seed)
        tables = stage_served(args.seed, args.scale, workdir)
        stage_mesh(tables, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    stage_cache(cache_path, entries_before)
    say("done", f"all stages green in {time.monotonic() - _T0:.0f}s")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

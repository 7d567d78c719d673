"""Perf-forensics plane contracts (PR 6):

- tools/perf_gate.py pass/fail/unusable mechanics on synthetic bench
  records + the checked-in baseline's shape;
- the baseline carries CPU floors only: no platform alias table, no
  device floor inherited from a record the tree no longer holds.
"""

import json
import os
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))

import perf_gate  # noqa: E402  (tools/ is not a package)


def _baseline():
    return perf_gate.load_baseline(
        os.path.join(_REPO, "tools", "perf_baseline.json"))


def _healthy_profile(base):
    """A profile section at the cpu pipeline baseline — synthetic cpu
    records need one now that a MISSING pipeline number fails the gate
    loudly (the silent-skip fix)."""
    pipe = base["platforms"]["cpu"]["pipeline"]
    return {"scale": pipe["scale"],
            "pipeline_rows_per_sec": pipe["rows_per_sec"]}


class TestPerfGate:
    def test_baseline_shape(self):
        base = _baseline()
        assert base["metric"] == "q01_pipeline_rows_per_sec_per_chip"
        assert "cpu" in base["platforms"]
        assert base["platforms"]["cpu"]["rows_per_sec"] > 0
        # CPU floors only: a platform is looked up by its own name, and
        # no device floor survives the records it was distilled from
        assert "platform_aliases" not in base
        assert "tpu" not in base["platforms"]

    def test_pass_at_head_level(self):
        base = _baseline()
        rec = {"value": base["platforms"]["cpu"]["rows_per_sec"] * 1.2,
               "platform": "cpu", "profile": _healthy_profile(base)}
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["perf_gate"] == "pass"
        assert v["floor_rows_per_sec"] < v["value_rows_per_sec"]

    def test_fail_on_simulated_q01_regression(self):
        """The r03→r05 trajectory (−61%) must fail the default
        tolerance."""
        base = _baseline()
        cpu = base["platforms"]["cpu"]["rows_per_sec"]
        rec = {"value": cpu * 0.39, "platform": "cpu"}
        v = perf_gate.evaluate(rec, base,
                               tolerance_pct=base["default_tolerance_pct"])
        assert v["perf_gate"] == "fail"
        assert v["delta_vs_baseline_pct"] < -50

    def test_tolerance_boundary(self):
        base = _baseline()
        cpu = base["platforms"]["cpu"]["rows_per_sec"]
        at_floor = {"value": cpu * 0.5, "platform": "cpu",
                    "profile": _healthy_profile(base)}
        just_below = {"value": cpu * 0.5 - 1, "platform": "cpu",
                      "profile": _healthy_profile(base)}
        # pinned = the CLI path: the platform entry's tighter tolerance
        # must NOT override an explicit --tolerance-pct
        assert perf_gate.evaluate(at_floor, base, 50.0,
                                  tolerance_pinned=True)["perf_gate"] \
            == "pass"
        assert perf_gate.evaluate(just_below, base, 50.0,
                                  tolerance_pinned=True)["perf_gate"] \
            == "fail"

    def test_platform_entry_tolerance_overrides_default(self):
        """The tightened CPU floor: the cpu entry's tolerance_pct (30)
        beats the resolved default (50) unless the caller pinned one."""
        base = _baseline()
        cpu = base["platforms"]["cpu"]["rows_per_sec"]
        entry_tol = base["platforms"]["cpu"]["tolerance_pct"]
        assert entry_tol < 50.0
        rec = {"value": cpu * (1 - (entry_tol + 5) / 100),
               "platform": "cpu", "profile": _healthy_profile(base)}
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["tolerance_pct"] == entry_tol
        assert v["perf_gate"] == "fail"
        # pinned CLI tolerance still wins
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0,
                               tolerance_pinned=True)
        assert v["perf_gate"] == "pass"

    def test_pipeline_floor_fails_seeded_minus_20pct(self):
        """The PR 8 satellite's acceptance test: a synthetic −20%
        regression of the q01 OPERATOR-pipeline throughput must fail
        the gate (the pipeline entry's tolerance is 15%), even when the
        kernel headline is healthy."""
        base = _baseline()
        cpu = base["platforms"]["cpu"]
        pipe = cpu["pipeline"]
        rec = {"value": cpu["rows_per_sec"] * 1.2, "platform": "cpu",
               "profile": {"scale": pipe["scale"],
                           "pipeline_rows_per_sec":
                               pipe["rows_per_sec"] * 0.8}}
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["pipeline"]["verdict"] == "fail"
        assert v["perf_gate"] == "fail"
        assert v["pipeline"]["delta_vs_baseline_pct"] == -20.0
        # at-baseline pipeline passes
        rec["profile"]["pipeline_rows_per_sec"] = pipe["rows_per_sec"]
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["perf_gate"] == "pass"
        assert v["pipeline"]["verdict"] == "pass"

    def test_pipeline_floor_skipped_on_scale_mismatch(self):
        """Batch-size / scale experiments (a different profile scale)
        must not trip the pipeline floor — but the skip is RECORDED in
        the verdict, never silent."""
        base = _baseline()
        cpu = base["platforms"]["cpu"]
        pipe = cpu["pipeline"]
        rec = {"value": cpu["rows_per_sec"], "platform": "cpu",
               "profile": {"scale": pipe["scale"] * 8,
                           "pipeline_rows_per_sec": 1.0}}
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["pipeline"]["verdict"] == "skipped"
        assert "scale" in v["pipeline"]["reason"]
        assert v["perf_gate"] == "pass"

    def test_pipeline_floor_missing_fails_loudly(self):
        """A cpu record WITHOUT a usable pipeline number (bench profile
        errored, or throughput collapsed to 0) must FAIL the gate —
        exactly the silent-decay mode the floor exists to catch."""
        base = _baseline()
        cpu = base["platforms"]["cpu"]["rows_per_sec"]
        for rec in (
            {"value": cpu, "platform": "cpu"},
            {"value": cpu, "platform": "cpu",
             "profile_error": "boom at scale 4"},
            {"value": cpu, "platform": "cpu",
             "profile": {"scale": 4.0, "pipeline_rows_per_sec": 0}},
        ):
            v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
            assert v["pipeline"]["verdict"] == "missing", rec
            assert v["perf_gate"] == "fail", rec

    def _healthy_mesh(self, base):
        m = base["platforms"]["mesh"]
        return {"mesh_rows_per_sec": m["rows_per_sec"],
                "devices": m["devices"], "scale": m["scale"],
                "scaling_factor": 0.9,
                "route_all_to_all_by_devices": {"8": 1}}

    def test_mesh_baseline_shape(self):
        """The ISSUE 11 satellite: a 'mesh' platform entry (virtual
        8-device CPU mesh q01 floor) exists and is well-formed."""
        base = _baseline()
        m = base["platforms"]["mesh"]
        assert m["rows_per_sec"] > 0
        assert m["devices"] == 8
        assert m["tolerance_pct"] > 0

    def test_mesh_floor_fails_seeded_regression(self):
        """A seeded mesh-path throughput decay past the tolerance must
        fail the gate even when every other floor is healthy — the
        acceptance criterion's 'mesh perf_gate entry that fails on a
        seeded regression'."""
        base = _baseline()
        cpu = base["platforms"]["cpu"]
        m = base["platforms"]["mesh"]
        mesh_rec = self._healthy_mesh(base)
        mesh_rec["mesh_rows_per_sec"] = m["rows_per_sec"] \
            * (1 - (m["tolerance_pct"] + 10) / 100)
        rec = {"value": cpu["rows_per_sec"] * 1.2, "platform": "cpu",
               "profile": _healthy_profile(base), "mesh": mesh_rec}
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["mesh"]["verdict"] == "fail"
        assert v["perf_gate"] == "fail"
        # at-baseline mesh passes
        rec["mesh"] = self._healthy_mesh(base)
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["mesh"]["verdict"] == "pass"
        assert v["perf_gate"] == "pass"

    def test_mesh_demoted_run_never_miscounted(self):
        """ISSUE 12 satellite: a bench run whose mesh rounds demoted to
        host measured the RECOVERY path — it must neither fail the mesh
        floor (even at host-tier throughput far below it) nor pass it
        (even at or above baseline); the demotion is recorded in the
        verdict instead."""
        base = _baseline()
        cpu = base["platforms"]["cpu"]["rows_per_sec"]
        m = base["platforms"]["mesh"]
        # far below the floor, but demoted: skipped, not failed
        demoted = self._healthy_mesh(base)
        demoted["mesh_rows_per_sec"] = m["rows_per_sec"] * 0.1
        demoted["mesh_demoted"] = True
        demoted["route_demoted_by_devices"] = {"8": 1}
        rec = {"value": cpu * 1.2, "platform": "cpu",
               "profile": _healthy_profile(base), "mesh": demoted}
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["mesh"]["verdict"] == "skipped"
        assert "demoted" in v["mesh"]["reason"]
        assert v["perf_gate"] == "pass"
        # at-baseline but demoted: still skipped (never counts TOWARD)
        healthy_but_demoted = self._healthy_mesh(base)
        healthy_but_demoted["route_demoted_by_devices"] = {"8": 2}
        rec["mesh"] = healthy_but_demoted
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["mesh"]["verdict"] == "skipped"
        # an un-demoted run still gates normally
        rec["mesh"] = self._healthy_mesh(base)
        rec["mesh"]["route_demoted_by_devices"] = {"8": 0}
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["mesh"]["verdict"] == "pass"

    def test_mesh_errored_bench_fails_loudly(self):
        """A bench that TRIED the mesh measurement and failed records
        mesh_error — the gate fails (the silent-decay hole stays
        closed); records predating the mesh bench skip, recorded."""
        base = _baseline()
        cpu = base["platforms"]["cpu"]["rows_per_sec"]
        errored = {"value": cpu * 1.2, "platform": "cpu",
                   "profile": _healthy_profile(base),
                   "mesh_error": "no all_to_all route recorded"}
        v = perf_gate.evaluate(errored, base, tolerance_pct=50.0)
        assert v["mesh"]["verdict"] == "missing"
        assert v["perf_gate"] == "fail"
        legacy = {"value": cpu * 1.2, "platform": "cpu",
                  "profile": _healthy_profile(base)}
        v = perf_gate.evaluate(legacy, base, tolerance_pct=50.0)
        assert v["mesh"]["verdict"] == "skipped"
        assert v["perf_gate"] == "pass"
        # a mesh section WITHOUT a usable value (interrupted child,
        # renamed key) is the silent-decay mode — fail, not skip
        hollow = {"value": cpu * 1.2, "platform": "cpu",
                  "profile": _healthy_profile(base),
                  "mesh": {"devices": 8, "scale": 2.0}}
        v = perf_gate.evaluate(hollow, base, tolerance_pct=50.0)
        assert v["mesh"]["verdict"] == "missing"
        assert v["perf_gate"] == "fail"

    def test_mesh_scale_or_devices_mismatch_skips_recorded(self):
        base = _baseline()
        cpu = base["platforms"]["cpu"]["rows_per_sec"]
        mesh_rec = self._healthy_mesh(base)
        mesh_rec["scale"] = mesh_rec["scale"] * 4
        rec = {"value": cpu * 1.2, "platform": "cpu",
               "profile": _healthy_profile(base), "mesh": mesh_rec}
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["mesh"]["verdict"] == "skipped"
        assert "scale" in v["mesh"]["reason"]
        mesh_rec = self._healthy_mesh(base)
        mesh_rec["devices"] = 4
        rec["mesh"] = mesh_rec
        v = perf_gate.evaluate(rec, base, tolerance_pct=50.0)
        assert v["mesh"]["verdict"] == "skipped"
        assert "devices" in v["mesh"]["reason"]

    def test_smoke_mode(self, capsys):
        """tools/perf_gate.py --smoke from tier-1: the in-process q01
        pipeline at tiny scale clears the generous smoke floor, the
        scheduler's solo-query tax clears the <2% concurrency-tax gate,
        and the last stdout line is one JSON verdict (driver
        contract)."""
        rc = perf_gate.main(["--smoke"])
        out = capsys.readouterr().out
        last = json.loads(out.strip().splitlines()[-1])
        assert last["mode"] == "smoke"
        assert rc == 0, out
        assert last["perf_gate"] == "pass"
        assert last["value_rows_per_sec"] > last["floor_rows_per_sec"]
        # the concurrency-tax gate: every query now passes through the
        # scheduler; its bookkeeping must stay invisible on a solo run
        assert last["sched_tax_limit_pct"] == 2.0
        assert 0.0 <= last["sched_tax_pct"] < last["sched_tax_limit_pct"]
        # the journal-overhead gate (crash-safe query journal): the
        # journaled q01 run ENGAGED (records > 0 — an idle journal
        # would be a vacuous measurement and fails the gate) and its
        # hot-path ledger stays under the 2% limit
        assert last["journal_overhead_limit_pct"] == 2.0
        assert last["journal_records"] > 0
        assert last["journal_commits"] >= 1
        assert 0.0 <= last["journal_overhead_pct"] \
            < last["journal_overhead_limit_pct"]
        # the warm-path cache gate (PR 16): the repeated q01 was served
        # from the result cache bit-identically, past the speedup
        # floor, and the AOT warmer replayed the recorded plan cleanly
        assert last["cache_gate"] == "pass"
        assert last["cache_hits"] >= 1
        assert last["cache_speedup_x"] >= last["cache_speedup_floor_x"]
        assert last["aot_warmed"] >= 1
        assert last["aot_errors"] == 0
        # the ops-plane gate (ISSUE 14): the live endpoint answered
        # parseable /metrics scrapes mid-q01, SLO family present
        assert last["ops_gate"] == "pass"
        assert last["ops_scrapes"] >= 1
        # the Fusion 2.0 gate (PR 17): map-side combine engaged (the
        # combine stage shipped strictly fewer rows than it took) and
        # the exchange's live-bytes ledger is lit
        assert last["fusion_gate"] == "pass"
        assert last["combine_shuffle_bytes"] > 0
        assert last["combine_rows_in"] > last["combine_rows_out"] > 0
        # the fleet-observability gate (ISSUE 20): trace propagation +
        # the cost ledger engaged on every on-arm query, disengaged
        # off-arm, and cost under the overhead limit
        assert last["obs_fleet_gate"] == "pass"
        assert last["obs_fleet_ledgers"] == last["obs_fleet_queries"]
        assert last["obs_fleet_overhead_pct"] \
            < last["obs_fleet_overhead_pct_max"] == 2.0

    def test_ops_gate_scrape_rejects_seeded_regressions(
            self, monkeypatch):
        """Seeded regressions for the smoke ops arm: a live endpoint
        whose exposition is unparseable (duplicate TYPE — the torn-
        exposition shape) or whose ``auron_query_duration_seconds``
        family vanished must fail the scrape LOUDLY, not pass a
        vacuous gate."""
        from auron_tpu import config as cfg
        from auron_tpu.obs import ops_server
        from auron_tpu.obs import registry as obs_registry
        conf = cfg.get_config()
        conf.set(cfg.OPS_ENABLED, True)
        conf.set(cfg.OPS_PORT, 0)
        try:
            srv = ops_server.ensure_started()
            assert srv is not None
            port = srv.port
            # healthy exposition passes (the family exists process-wide
            # once any query was observed)
            obs_registry.observe_query(0.01, "ok")
            fams = perf_gate.scrape_ops_metrics(port)
            assert "auron_query_duration_seconds" in fams
            real = obs_registry.MetricsRegistry.render_prometheus
            monkeypatch.setattr(
                obs_registry.MetricsRegistry, "render_prometheus",
                lambda self: real(self)
                + "# TYPE auron_info gauge\nauron_info 1\n")
            with pytest.raises(ValueError, match="duplicate TYPE"):
                perf_gate.scrape_ops_metrics(port)
            monkeypatch.setattr(
                obs_registry.MetricsRegistry, "render_prometheus",
                lambda self: "# HELP up x\n# TYPE up gauge\nup 1\n")
            with pytest.raises(ValueError,
                               match="auron_query_duration_seconds"):
                perf_gate.scrape_ops_metrics(port)
        finally:
            ops_server.release()
            conf.unset(cfg.OPS_ENABLED)
            conf.unset(cfg.OPS_PORT)

    def test_smoke_journal_overhead_regression_fails(
            self, monkeypatch, capsys):
        """A journal hot-path cost regression FAILS the smoke gate
        instead of hiding: seed a synthetic ledger an order of
        magnitude past the limit. The cache/ops/lint arms are stubbed
        to passing verdicts — each has its own seeded regression test,
        and this one must stay cheap enough for the bounded tier-1
        window."""
        monkeypatch.setenv("AURON_PERF_SMOKE_SCALE", "0.2")
        from auron_tpu.runtime import journal as jrn
        monkeypatch.setattr(
            jrn, "last_stats",
            lambda: {"hot_ns": int(1e12), "records": 6, "commits": 1})
        monkeypatch.setattr(perf_gate, "run_cache_gate",
                            lambda tables, smoke: {
                                "cache_gate": "pass",
                                "cache_speedup_x": 99.0,
                                "cache_speedup_floor_x": 5.0,
                                "aot_warmed": 1})
        monkeypatch.setattr(perf_gate, "run_ops_gate",
                            lambda tables: {"ops_gate": "pass",
                                            "ops_scrapes": 1})
        monkeypatch.setattr(perf_gate, "run_lint_gate",
                            lambda: {"lint_gate": "pass", "lint_new": 0})
        monkeypatch.setattr(perf_gate, "run_fusion_gate",
                            lambda: {"fusion_gate": "pass"})
        monkeypatch.setattr(perf_gate, "run_obs_fleet_gate",
                            lambda smoke: {"obs_fleet_gate": "pass",
                                           "obs_fleet_overhead_pct": 0.1,
                                           "obs_fleet_overhead_pct_max":
                                               2.0})
        rc = perf_gate.main(["--smoke"])
        out = capsys.readouterr().out
        last = json.loads(out.strip().splitlines()[-1])
        assert rc == 1
        assert last["perf_gate"] == "fail"
        assert "journal hot-path overhead" in last["reason"]

    def test_smoke_cache_gate_fails_on_silent_aot_errors(
            self, monkeypatch, capsys):
        """The cache arm's reason to exist: an AOT warmer that
        collected errors (it never raises by contract) must FAIL the
        smoke gate instead of passing vacuously. The ops/lint arms are
        stubbed to passing verdicts — each has its own seeded
        regression test, and this one must stay cheap enough for the
        bounded tier-1 window."""
        monkeypatch.setenv("AURON_PERF_SMOKE_SCALE", "0.2")
        from auron_tpu.cache import aot as _aot
        monkeypatch.setattr(
            _aot, "last_stats",
            lambda: {"warmed": 0, "skipped": 0,
                     "errors": ["deadbeef: ValueError: boom"]})
        monkeypatch.setattr(perf_gate, "run_ops_gate",
                            lambda tables: {"ops_gate": "pass",
                                            "ops_scrapes": 1})
        monkeypatch.setattr(perf_gate, "run_lint_gate",
                            lambda: {"lint_gate": "pass", "lint_new": 0})
        monkeypatch.setattr(perf_gate, "run_fusion_gate",
                            lambda: {"fusion_gate": "pass"})
        monkeypatch.setattr(perf_gate, "run_obs_fleet_gate",
                            lambda smoke: {"obs_fleet_gate": "pass",
                                           "obs_fleet_overhead_pct": 0.1,
                                           "obs_fleet_overhead_pct_max":
                                               2.0})
        rc = perf_gate.main(["--smoke"])
        out = capsys.readouterr().out
        last = json.loads(out.strip().splitlines()[-1])
        assert rc == 1
        assert last["perf_gate"] == "fail"
        assert last["cache_gate"] == "fail"
        assert "AOT warmer errored" in last["reason"]

    def test_fusion_gate_fails_on_disengaged_combine(self, monkeypatch):
        """The fusion arm's seeded regression: a map-side combine that
        SILENTLY disengaged (the stage ships as many rows as it took —
        exactly what a broken eligibility check or a dead fold would
        measure) must fail the arm loudly, a combine stage that never
        ran (zero rows in and out) likewise, and a dark byte ledger
        (zero counter) must fail rather than pass on rows alone. Runs
        the arm directly on stubbed bench numbers — the engagement
        checks are pure verdict logic."""
        import bench
        monkeypatch.setattr(bench, "bench_fusion2", lambda: {
            "combine_shuffle_bytes": 9_400_000,
            "combine_rows_in": 200_000, "combine_rows_out": 200_000,
            "fusion2_rows_per_sec": 1.0})
        out = perf_gate.run_fusion_gate()
        assert out["fusion_gate"] == "fail"
        assert "silently disengaged" in out["fusion_error"]
        monkeypatch.setattr(bench, "bench_fusion2", lambda: {
            "combine_shuffle_bytes": 9_400_000,
            "combine_rows_in": 0, "combine_rows_out": 0,
            "fusion2_rows_per_sec": 1.0})
        out = perf_gate.run_fusion_gate()
        assert out["fusion_gate"] == "fail"
        assert "silently disengaged" in out["fusion_error"]
        monkeypatch.setattr(bench, "bench_fusion2", lambda: {
            "combine_shuffle_bytes": 0,
            "combine_rows_in": 200_000, "combine_rows_out": 800,
            "fusion2_rows_per_sec": 1.0})
        out = perf_gate.run_fusion_gate()
        assert out["fusion_gate"] == "fail"
        assert "ledger went dark" in out["fusion_error"]
        # an engaged fold passes, with the measured rows in the verdict
        monkeypatch.setattr(bench, "bench_fusion2", lambda: {
            "combine_shuffle_bytes": 3_700_000,
            "combine_rows_in": 200_000, "combine_rows_out": 800,
            "fusion2_rows_per_sec": 1.0})
        out = perf_gate.run_fusion_gate()
        assert out["fusion_gate"] == "pass"
        assert out["combine_rows_out"] == 800

    def test_obs_fleet_gate_rejects_seeded_regressions(self):
        """The ISSUE 20 satellite: a seeded +10% trace-propagation /
        cost-ledger overhead must fail the obs-fleet arm, and a vacuous
        A/B — an on-arm whose ledger never engaged, or an off-arm that
        still produced ledgers (the knob no longer disengages) — must
        fail regardless of the measured overhead. Pure verdict
        mechanics on synthetic walls (obs_fleet_verdict)."""
        smoke = {"obs_fleet_overhead_pct_max": 2.0}
        honest = dict(ledgers_on=4, ledgers_off=0, queries=4)
        v = perf_gate.obs_fleet_verdict(1.0, 1.10, smoke, **honest)
        assert v["obs_fleet_gate"] == "fail"
        assert v["obs_fleet_overhead_pct"] == 10.0
        assert "fleet-observability gate" in v["obs_fleet_error"]
        # within-noise overhead passes
        v = perf_gate.obs_fleet_verdict(1.0, 1.01, smoke, **honest)
        assert v["obs_fleet_gate"] == "pass"
        assert v["obs_fleet_overhead_pct"] < 2.0
        # an idle on-arm ledger is a vacuous measurement — fail even
        # though the walls are identical
        v = perf_gate.obs_fleet_verdict(1.0, 1.0, smoke, ledgers_on=0,
                                        ledgers_off=0, queries=4)
        assert v["obs_fleet_gate"] == "fail"
        assert "idle ledger" in v["obs_fleet_error"]
        # an off-arm that still ledgers measured the feature against
        # itself — fail even at 0% overhead
        v = perf_gate.obs_fleet_verdict(1.0, 1.0, smoke, ledgers_on=4,
                                        ledgers_off=3, queries=4)
        assert v["obs_fleet_gate"] == "fail"
        assert "no longer disengages" in v["obs_fleet_error"]
        # a dark wall (measurement never ran) can't gate anything
        v = perf_gate.obs_fleet_verdict(0.0, 1.0, smoke, **honest)
        assert v["obs_fleet_gate"] == "fail"
        assert "went dark" in v["obs_fleet_error"]

    def test_unusable_records(self):
        base = _baseline()
        assert perf_gate.evaluate({"error": "boom"}, base, 50.0)[
            "perf_gate"] == "unusable"
        assert perf_gate.evaluate({"value": 1.0, "platform": "quantum"},
                                  base, 50.0)["perf_gate"] == "unusable"

    def test_tpu_record_has_no_floor_yet(self):
        """Nothing on the chip is measured until the ledger exists: a
        tpu record is unusable for gating, never compared to a CPU (or
        an inherited) number."""
        base = _baseline()
        v = perf_gate.evaluate({"value": 9e7, "platform": "tpu"}, base,
                               50.0)
        assert v["perf_gate"] == "unusable"
        assert "no baseline for platform 'tpu'" in v["reason"]

    def test_single_device_mesh_skip_is_not_a_failure(self):
        """bench.py on one device says in words that it skipped the mesh
        sweep; the gate records the skip instead of failing a 'mesh
        section without a value'."""
        base = _baseline()
        rec = {"value": base["platforms"]["cpu"]["rows_per_sec"],
               "platform": "cpu", "profile": _healthy_profile(base),
               "mesh": {"skipped": "1 device visible"}}
        v = perf_gate.evaluate(rec, base, 50.0)
        assert v["perf_gate"] == "pass"
        assert v["mesh"] == {"verdict": "skipped",
                             "reason": "1 device visible"}

    def test_probe_report_no_longer_rides_the_verdict(self):
        base = _baseline()
        rec = {"value": 1.0, "platform": "cpu",
               "probe_report": {"ok": False, "steps": []}}
        v = perf_gate.evaluate(rec, base, 50.0)
        assert not [k for k in v if k.startswith("probe")]

    def test_cli_exit_codes(self, tmp_path, capsys):
        base = _baseline()
        good = tmp_path / "good.json"
        good.write_text(json.dumps(
            {"value": base["platforms"]["cpu"]["rows_per_sec"],
             "platform": "cpu", "profile": _healthy_profile(base)}))
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"value": 1.0, "platform": "cpu"}))
        assert perf_gate.main(["--bench-json", str(good)]) == 0
        assert perf_gate.main(["--bench-json", str(bad)]) == 1
        err = tmp_path / "err.json"
        err.write_text(json.dumps({"error": "no measurement"}))
        assert perf_gate.main(["--bench-json", str(err)]) == 2
        out = capsys.readouterr().out
        # every run ends with a parseable JSON line (driver contract)
        for block in out.strip().split("\n"):
            pass
        last = out.strip().splitlines()[-1]
        assert json.loads(last)["perf_gate"] == "unusable"


class TestBenchRunsInOneProcess:
    """bench.py's contract as the gate consumes it: one process, the
    chip or an error, every record stamped with its device."""

    def test_fresh_bench_record_parses_last_line(self, monkeypatch):
        import subprocess as sp

        seen = {}

        def fake_run(args, **kw):
            seen["args"], seen["env"] = args, kw.get("env")

            class P:
                returncode = 1
                stdout = ('noise\n{"metric": "q01_pipeline_rows_per_sec'
                          '_per_chip", "error": "RuntimeError: no TPU"}\n')
                stderr = ""
            return P()

        monkeypatch.setattr(sp, "run", fake_run)
        rec = perf_gate.fresh_bench_record()
        assert rec["error"].endswith("no TPU")
        assert seen["args"][-1].endswith("bench.py")
        # the child inherits this process's environment untouched: no
        # platform is pinned for it
        assert seen["env"] is None
        base = _baseline()
        assert perf_gate.evaluate(rec, base, 50.0)["perf_gate"] \
            == "unusable"

    def test_device_or_fail_policy(self, monkeypatch):
        """TPU: yes. CPU: only when JAX_PLATFORMS says so by name.
        Anything else: an error, never a rate."""
        import jax

        import bench

        class Dev:
            device_kind = "fake"

            def __init__(self, platform):
                self.platform = platform

        def visible(platform):
            monkeypatch.setattr(jax, "devices", lambda: [Dev(platform)])

        visible("tpu")
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        assert bench._device_or_fail().platform == "tpu"
        visible("cpu")
        with pytest.raises(RuntimeError, match="no TPU"):
            bench._device_or_fail()
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert bench._device_or_fail().platform == "cpu"
        visible("gpu")
        with pytest.raises(RuntimeError, match="no TPU"):
            bench._device_or_fail()

    def test_bench_module_has_no_probe_or_fallback_left(self):
        import bench
        for gone in ("_probe_accelerator", "_run_bench_child",
                     "_condense_error", "_snapshot_partial",
                     "_child_main", "_mesh_child_main",
                     "_bench_mesh_record"):
            assert not hasattr(bench, gone), gone
        with open(bench.__file__) as f:
            src = f.read()
        assert "subprocess" not in src
        assert "git" not in src.replace("digit", "")

    def test_mesh_section_skips_in_words_on_one_device(self, monkeypatch):
        import jax

        import bench
        monkeypatch.setattr(jax, "devices", lambda: [object()])
        assert bench.bench_mesh() == {"skipped": "1 device visible"}

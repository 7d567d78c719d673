"""BENCHMARK.json against the benchmark's contract and against the files
it names: what a later PR that only adds data files must keep true."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    # 2 + 14 runs a cell, with the full 24 cells, inside 43,200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines(bench):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200, (entry["name"], key)
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_configs_and_cells_point_at_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert set(c["reduced"]) == set(body["reduced"])
        assert body["guarantees"] and body["assumed"]
    used, pairs = set(), set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert w["chips"] == json.load(open(os.path.join(
            ROOT, configs[w["config"]]["file"])))["chips"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        traffic = json.load(open(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json")))
        for plan in traffic["plans"]:
            assert os.path.isfile(os.path.join(BENCH, "plans", plan + ".py"))
    assert used == set(configs)
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_metrics_cover_every_cell(bench):
    from harness.cell import Cell
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert "bound" not in m and m["moves"] in e2e
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    layers = {m["layer"] for m in bench["per_layer"]}
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = Cell(w["name"])
        mine = cell.end_to_end()
        assert "setup_s" in mine and len(mine) >= 2
        layer = cell.per_layer()
        assert layer
        for m in layer.values():
            assert m["moves"] in mine
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cells


#: the readers every cell runs, and what each cell reads besides: held
#: by name, so that no edit of a `workloads` list or of a `moves` drops
#: a reader from a cell unseen (a metric with no `workloads` list is
#: read only where the end-to-end metric it moves is reported): 21 in
#: every cell, so 22 / 21 / 28 in the three
IN_EVERY_CELL = {
    "compile.task_ms", "compile.warm_s", "compile.xla_in_window",
    "convert.to_arrow_ms", "ops.device_wait_ms", "ops.dispatch_ms",
    "ops.host_ms", "ops.program_calls", "ops.readbacks", "ops.sync_wait_ms",
    "plan.decode_ms", "scan.convert_ms", "scan.decode_ms", "scan.encode_ms",
    "scan.h2d_ms", "scan.h2d_transfers", "serve.cpu_share",
    "serve.overhead_ms", "serve.queue_wait_ms", "serve.send_ms",
    "serve.unattributed_ms"}
READERS = {
    "tpcds_sf1.star_join": (
        {"fact_rows_per_s", "task_p50_ms", "setup_s"},
        {"serve.task_p90_ms.thin"}),
    "tpcds_sf1_smallfiles.star_join": (
        {"fact_rows_per_s", "task_p50_ms", "task_p90_ms", "setup_s"},
        set()),
    "tpcds_sf1_mesh2x2.star_join_serial": (
        {"fact_rows_per_s", "setup_s"},
        {"serve.task_p50_ms.serial", "exchange.all_to_all_ms",
         "exchange.mesh_bytes", "exchange.host_ms", "exchange.rounds",
         "exchange.ici_share", "device.busy_min_share"}),
}


@pytest.mark.parametrize("workload", sorted(READERS))
def test_a_cell_reports_these_metrics_and_runs_these_readers(workload):
    from harness.cell import Cell
    end_to_end, own = READERS[workload]
    cell = Cell(workload)
    assert set(cell.end_to_end()) == end_to_end
    assert set(cell.per_layer()) == IN_EVERY_CELL | own


def test_a_reader_for_every_cell_moves_what_every_cell_reports(bench):
    # ... cells added later included: it lists no cell, and the metric
    # it moves has no list either
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in IN_EVERY_CELL:
        assert "workloads" not in by_name[name], name
        assert "workloads" not in e2e[by_name[name]["moves"]], name

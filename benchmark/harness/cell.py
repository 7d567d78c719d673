"""What a cell is made of, read from data: ``BENCHMARK.json`` names the
workload's configuration file and traffic mix, the traffic mix names its
plans, and every one of them is a file found by that name. Nothing here
knows a cell, a configuration, a mix or a plan by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import random
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(directory: str, name: str):
    """The module in ``<benchmark>/<directory>/<name>.py``. ``name`` may
    hold dots (a metric's name), so this goes by path, not by import."""
    folder = os.path.join(BENCH_DIR, directory)
    path = os.path.join(folder, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {directory} file for {name!r}: {path}")
    if folder not in sys.path:
        sys.path.insert(0, folder)      # a plan imports planlib beside it
    mod_name = "bench_" + directory + "_" + name.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its files loaded."""

    def __init__(self, workload: str, benchmark_json: str | None = None):
        self.bench = load_json(benchmark_json
                               or os.path.join(ROOT, "BENCHMARK.json"))
        entry = next((w for w in self.bench["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            raise SystemExit(
                f"no workload {workload!r} in BENCHMARK.json; it has "
                f"{[w['name'] for w in self.bench['workloads']]}")
        self.name = workload
        self.chips = int(entry["chips"])
        conf_entry = next(c for c in self.bench["configs"]
                          if c["name"] == entry["config"])
        self.config_file = os.path.join(ROOT, conf_entry["file"])
        self.config = load_json(self.config_file)
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", entry["traffic"] + ".json"))
        self.plans = list(self.traffic["plans"])

    def _reports(self, metric: dict, moved=None) -> bool:
        cells = metric.get("workloads")
        if cells is not None:
            return self.name in cells
        return moved is None or moved in self.end_to_end()

    def end_to_end(self) -> dict:
        """{name: entry} of the end-to-end metrics this cell reports."""
        return {m["name"]: m for m in self.bench["end_to_end"]
                if self._reports(m)}

    def per_layer(self) -> dict:
        """{name: entry} of the per-layer metrics whose readers run in
        this cell: those that list it, and those that list no cell and
        move an end-to-end metric this cell reports."""
        return {m["name"]: m for m in self.bench["per_layer"]
                if self._reports(m, moved=m["moves"])}

    def tables(self, plan_modules: dict) -> tuple:
        """The tables the cell's plans read, the fact table first."""
        names: list = []
        for mod in plan_modules.values():
            for t in mod.TABLES:
                if t not in names:
                    names.append(t)
        return tuple(names)


def scaled_split_rows(config: dict, scale: float) -> int:
    """Rows of one split. A rehearsal (``scale`` < 1) shrinks the split
    with the table, so that it has as many splits and tasks as SF1."""
    return max(int(round(config["split_rows"] * scale)), 16)


def split_groups(splits: list, per_task: int) -> list:
    """Consecutive splits in groups of ``per_task``: one group is one
    task's input. [(group_index, [paths], first_row, rows)]"""
    groups = []
    for g, i in enumerate(range(0, len(splits), per_task)):
        part = splits[i:i + per_task]
        groups.append((g, [p for p, _lo, _n in part], part[0][1],
                       sum(n for _p, _lo, n in part)))
    return groups


def task_order(plans: list, n_groups: int, seed: int) -> list:
    """The endless order in which clients draw (plan, group) pairs, as a
    list of one whole pass: plans round-robin from a seeded rotation,
    each over its own seeded shuffle of the groups. Every seed draws the
    same set of pairs in another order, and any four consecutive draws
    hold each of four plans once."""
    rng = random.Random(seed)
    rot = rng.randrange(len(plans))
    rotated = plans[rot:] + plans[:rot]
    orders = {}
    for plan in sorted(plans):
        order = list(range(n_groups))
        rng.shuffle(order)
        orders[plan] = order
    return [(plan, orders[plan][i]) for i in range(n_groups)
            for plan in rotated]


def warmup_pairs(plans: list, groups: list, n_full: int = 1) -> list:
    """Each plan over the first ``n_full`` full groups and over the short
    last one: the input shapes the window has. Sizes inside a plan
    (a join's output, the groups of an aggregate) are bucketed to powers
    of two and move with the rows, so a configuration whose tasks are
    short asks for several groups (``warmup_groups``) to see both sides
    of a bucket's edge before the window does."""
    full = [g for g in groups if g[3] == groups[0][3]][:max(n_full, 1)]
    pairs = [(plan, g[0]) for g in full for plan in plans]
    if groups[-1][3] != groups[0][3]:
        pairs += [(plan, groups[-1][0]) for plan in plans]
    return pairs

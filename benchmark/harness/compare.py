"""The comparison that decides ``correct``.

Every task the window completed is compared with the plan's oracle over
exactly that task's rows. Rows are put in a canonical order on both
sides (the plans' sort keys are total orders, but the comparison does not
lean on that). Integers, strings, dates and decimal money compare
exactly: the limit on differing cells is 0. Doubles compare to 1e-7
relative — a double sum over 131,072 rows reassociated by a parallel
reduction moves by about 1e-12, while the same sum carried in float32
moves by 1e-4 to 1e-3, so 1e-7 sits five orders above the first and three
under the second (the value the repo's own served smoke uses).

The benchmark's copy of ``auron_tpu/it/comparator.py``, with decimals
compared as decimals and not through float64.
"""

from __future__ import annotations

import decimal
import math

import pyarrow as pa

DOUBLE_REL_TOL = 1e-7
#: under this a double is compared absolutely (a sum that is 0)
DOUBLE_ABS_TOL = 1e-9


def _cell_key(v):
    """None < numbers by value < strings < the rest."""
    if v is None:
        return (0, 0, "")
    if isinstance(v, (bool, int, float, decimal.Decimal)):
        f = float(v)
        return (1, 2, math.inf if math.isnan(f) else f)
    if isinstance(v, str):
        return (1, 3, v)
    return (1, 4, str(v))


def _canon_rows(table: pa.Table) -> list:
    rows = [tuple(r[c] for c in table.column_names)
            for r in table.to_pylist()]
    return sorted(rows, key=lambda row: tuple(_cell_key(v) for v in row))


def _double_gap(a: float, b: float) -> float:
    """|a - b| relative to the larger magnitude; 0 for two NaNs."""
    if math.isnan(a) or math.isnan(b):
        return 0.0 if math.isnan(a) and math.isnan(b) else math.inf
    if abs(a - b) <= DOUBLE_ABS_TOL:
        return 0.0
    return abs(a - b) / max(abs(a), abs(b))


def compare_tables(got: pa.Table, want: pa.Table) -> dict:
    """{"exact_mismatches": cells that differ and must not,
        "max_double_rel": the widest relative gap of a double cell,
        "cells": cells compared, "error": a shape fault or ""}"""
    out = {"exact_mismatches": 0, "max_double_rel": 0.0, "cells": 0,
           "error": "", "examples": []}
    if got is None:
        out["error"] = "no table came back"
        return out
    if set(got.column_names) != set(want.column_names):
        out["error"] = (f"columns differ: {got.column_names} vs "
                        f"{want.column_names}")
        return out
    want = want.select(got.column_names)
    if got.num_rows != want.num_rows:
        out["error"] = f"rows differ: {got.num_rows} vs {want.num_rows}"
        return out
    for i, (gr, wr) in enumerate(zip(_canon_rows(got), _canon_rows(want))):
        for name, g, w in zip(got.column_names, gr, wr):
            out["cells"] += 1
            if g is None or w is None:
                bad = not (g is None and w is None)
            elif isinstance(g, float) or isinstance(w, float):
                gap = _double_gap(float(g), float(w))
                out["max_double_rel"] = max(out["max_double_rel"], gap)
                bad = False
                if gap > DOUBLE_REL_TOL and len(out["examples"]) < 5:
                    out["examples"].append((i, name, g, w))
            else:
                bad = g != w
            if bad:
                out["exact_mismatches"] += 1
                if len(out["examples"]) < 5:
                    out["examples"].append((i, name, g, w))
    return out


def answer_ok(res: dict) -> bool:
    return (not res["error"] and res["exact_mismatches"] == 0
            and res["max_double_rel"] <= DOUBLE_REL_TOL)


def decide(results: list) -> dict:
    """Fold per-task comparisons into the run's verdict and the numbers
    printed beside their limits."""
    shape = [r for r in results if r["error"]]
    return {
        "tasks_compared": len(results),
        "cells_compared": sum(r["cells"] for r in results),
        "tasks_wrong": sum(1 for r in results if not answer_ok(r)),
        "shape_errors": len(shape),
        "exact_mismatches": sum(r["exact_mismatches"] for r in results),
        "exact_limit": 0,
        "max_double_rel": max((r["max_double_rel"] for r in results),
                              default=0.0),
        "double_rel_limit": DOUBLE_REL_TOL,
        "correct": bool(results) and all(answer_ok(r) for r in results),
    }


def float32_money(arrow: dict) -> dict:
    """The control's input: every decimal column carried in float32, the
    precision below the decimal(7,2) the configurations state."""
    out = {}
    for name, table in arrow.items():
        cols = [c.cast(pa.float64()).cast(pa.float32())
                if pa.types.is_decimal(f.type) else c
                for f, c in zip(table.schema, table.columns)]
        out[name] = pa.table(dict(zip(table.column_names, cols)))
    return out


def float32_answer(answer: pa.Table, like: pa.Table) -> pa.Table:
    """The control's answer: float columns rounded through float32 and
    put back into the type the sound answer has (cents for money)."""
    cols = []
    for name in like.column_names:
        col, want = answer[name], like.schema.field(name).type
        if pa.types.is_floating(col.type):
            col = col.cast(pa.float32()).cast(pa.float64())
            if pa.types.is_decimal(want):
                col = pa.array(
                    [None if v is None else
                     decimal.Decimal(repr(v)).quantize(
                         decimal.Decimal(1).scaleb(-want.scale),
                         rounding=decimal.ROUND_HALF_EVEN)
                     for v in col.to_pylist()], want)
        cols.append(col)
    return pa.table(dict(zip(like.column_names, cols)))

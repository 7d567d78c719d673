"""The control of "How `correct` is decided", at the cells' own size.

The configurations state decimal(7,2) money, summed exactly. The control
is the reference put in the program's place with money carried in
float32 (the step that would tempt a later PR: decimals as floats on the
device): inputs cast to float32, the oracle run over them, its sums
rounded through float32 and back to cents. For each cell's layout and
each seed this prints how many of the tasks a window would complete come
out wrong; the comparison's limit on differing exact cells is 0, so one
is enough to turn ``correct`` false. Needs no chip: both sides are the
plain reference.

    python benchmark/tests/control_full_size.py [--scale 1.0] [--seeds 3] [--tasks 40]
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import cell as cellmod        # noqa: E402
from harness import compare, datagen       # noqa: E402


def control_verdict(cell, arrow, scale, seed, n_tasks) -> dict:
    """Sound and control verdicts over the first ``n_tasks`` tasks of the
    seed's order, as a window would complete them."""
    plans = {p: cellmod.load_module("plans", p) for p in cell.plans}
    fact = cell.tables(plans)[0]
    split = cellmod.scaled_split_rows(cell.config, scale)
    per_task = int(cell.config["splits_per_task"])
    n_rows = arrow[fact].num_rows
    splits = [(None, lo, min(split, n_rows - lo))
              for lo in range(0, n_rows, split)]
    groups = cellmod.split_groups(splits, per_task)
    order = cellmod.task_order(cell.plans, len(groups), seed)[:n_tasks]
    low = compare.float32_money(arrow)
    sound, control = [], []
    for plan, g in order:
        _g, _files, lo, rows = groups[g]
        view = dict(arrow, **{fact: arrow[fact].slice(lo, rows)})
        low_view = dict(low, **{fact: low[fact].slice(lo, rows)})
        want = plans[plan].oracle(view)
        sound.append(compare.compare_tables(plans[plan].oracle(view), want))
        control.append(compare.compare_tables(
            compare.float32_answer(plans[plan].oracle(low_view), want), want))
    return {"sound": compare.decide(sound), "control": compare.decide(control)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--tasks", type=int, default=40)
    args = ap.parse_args(argv)
    bench = cellmod.load_json(os.path.join(cellmod.ROOT, "BENCHMARK.json"))
    ok = True
    for seed in range(2_200_000_001, 2_200_000_001 + args.seeds):
        arrow = datagen.generate(seed, args.scale)
        for w in bench["workloads"]:
            cell = cellmod.Cell(w["name"])
            v = control_verdict(cell, arrow, args.scale, seed, args.tasks)
            s, c = v["sound"], v["control"]
            print(f"seed {seed} {cell.name}: {s['tasks_compared']} tasks, "
                  f"{s['cells_compared']} cells; sound differing cells "
                  f"{s['exact_mismatches']} correct={s['correct']}; "
                  f"float32-money control differing cells "
                  f"{c['exact_mismatches']} in {c['tasks_wrong']} tasks "
                  f"correct={c['correct']} (limit 0)", flush=True)
            ok = ok and s["correct"] and not c["correct"]
    print("control fails everywhere, sound passes everywhere" if ok
          else "NO LIMIT HOLDS somewhere: see above")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Exchange layer: what the map-side combine merges — the sum of
combine_rows_out over the sum of combine_rows_in, both over the leaves of
the window's DONE frames (the counters ShuffleExchangeOp books for every
hash exchange whose partial aggregate is folded into its split: rows that
entered the combine, groups that left it for the collective). A ratio of
sums, so a leaf that a frame's tree holds twice cancels. 0.003-0.27 where
the group keys repeat within a partition; near 1 where the key is nearly
unique there (q28's key is the price itself) and the combine's sort
merges nothing. None where no frame counts a combine."""


def _leaf_sum(tree, key):
    if isinstance(tree, dict):
        return sum(v if k == key and isinstance(v, (int, float))
                   and not isinstance(v, bool) else _leaf_sum(v, key)
                   for k, v in tree.items())
    if isinstance(tree, list):
        return sum(_leaf_sum(v, key) for v in tree)
    return 0


def read(ctx):
    rows_in = rows_out = 0
    for task in ctx["tasks"]:
        done = task.get("done")
        rows_in += _leaf_sum(done, "combine_rows_in")
        rows_out += _leaf_sum(done, "combine_rows_out")
    return rows_out / rows_in if rows_in else None

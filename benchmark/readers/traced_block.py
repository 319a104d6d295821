"""A ratio over the work of the trees the trace holds, from the row counts
the program wrote into its model (``internal_count``, ``leaf_count``), walked
by ``reference/model_text.split_rows`` as ``roofline.py`` walks them:

- ``row_visits``: every split's parent rows, what the partition moved;
- ``hist_rows``: every split's smaller child's rows, the histograms that
  were built (the sibling's comes by subtraction);
- ``root_rows``: the rows at every tree's root, what a tree was grown on.

``numerator``: ``{"work": name}``, one of these, or ``{"scope_ns": scope}``,
the device nanoseconds under that scope in the traced call. ``denominator``:
names of these, or ``features`` for the configuration's ``shape.features``,
multiplied together. ``None`` without a trace's trees, or where the scope
took no time. (The program sums the same two counts a block on its own, in
its ``fused_block`` records; ``tests/test_timeline_readers.py`` holds them
equal to this walk, exactly.)"""
import numpy as np

from reference import model_text


def work(trees):
    sums = {"row_visits": 0, "hist_rows": 0, "root_rows": 0}
    for t in trees:
        if t["num_leaves"] < 2:
            continue
        parent, left, right = model_text.split_rows(t)
        sums["row_visits"] += int(parent.sum())
        sums["hist_rows"] += int(np.minimum(left, right).sum())
        sums["root_rows"] += int(parent[0])
    return sums


def read(args, facts):
    trees = facts.get("trace_trees")
    if not trees:
        return None
    sums = dict(work(trees), features=facts["features"])
    over = 1.0
    for name in args["denominator"]:
        over *= sums[name]
    if not over:
        return None
    top = args["numerator"]
    if "work" in top:
        return sums[top["work"]] / over
    seconds = facts["trace"]["by_scope"].get(top["scope_ns"], 0.0) \
        if facts.get("trace") else 0.0
    return 1e9 * seconds / over if seconds else None

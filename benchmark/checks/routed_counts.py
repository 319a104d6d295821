"""Recorded leaf counts (which the program takes from histograms over the
PARTITIONED rows) against counts from routing every row through the finished
tree in plain numpy, which never sees the partition: exactly equal. A
partition that drops, duplicates or misplaces a row breaks the equality."""
import numpy as np


def run(args, c):
    from reference import model_text
    which = {"first": 0, "last": len(c["trees"]) - 1}
    worst, done = 0, []
    for name in args["trees"]:
        t = c["trees"][which[name]]
        routed = model_text.leaf_counts(t, c["X"])
        worst = max(worst, int(np.abs(routed - t["leaf_count"]).max()))
        done.append(which[name])
    return worst == 0, "trees %s: all %d rows routed in numpy; max |recorded - routed| leaf count %d" % (
        done, c["rows"], worst)

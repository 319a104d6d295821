"""Quality as a FLOOR only: the plain metric of the program's raw scores on
the first ``rows`` rows (whole queries for a ranking job) is at least the
floor in the configuration, which was set below the lowest value seen on the
chip."""
import numpy as np


def run(args, c):
    from reference import quality
    n = min(int(args["rows"]), c["rows"])
    group = None
    if c["group"] is not None:
        ends = np.cumsum(c["group"])
        q = int(np.searchsorted(ends, n, side="right"))
        group, n = c["group"][:q], int(ends[q - 1])
    score = np.asarray(c["booster"].predict(c["X"][:n], raw_score=True), np.float64)
    label = c["label"][:n]
    if args["metric"] == "auc":
        value = quality.auc(label, score)
    elif args["metric"].startswith("ndcg@"):
        value = quality.ndcg_at(label, score, group, int(args["metric"][5:]))
    else:
        raise KeyError(args["metric"])
    floor = float(args["floor"])
    return value >= floor, "%s %.6f on the first %d rows after %d trees, floor %s" % (
        args["metric"], value, n, len(c["trees"]), floor)

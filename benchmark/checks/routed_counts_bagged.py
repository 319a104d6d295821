"""``routed_counts`` for a job that bags its rows: the recorded leaf counts
of the chosen trees against a plain numpy walk of the tree over the rows
of that iteration's bag (``reference/tweedie.py`` ``bag``), exactly. A
partition that drops, duplicates or misplaces a row, or a bag that is not
the seed's, breaks the equality."""
import numpy as np


def run(args, c):
    from reference import model_text, tweedie
    which = {"first": 0, "last": len(c["trees"]) - 1}
    worst, done, rows = 0, [], []
    for name in args["trees"]:
        i = which[name]
        t = c["trees"][i]
        inbag = np.flatnonzero(tweedie.bag(c["rows"], c["params"], i))
        routed = model_text.leaf_counts(t, c["X"][inbag])
        worst = max(worst, int(np.abs(routed - t["leaf_count"]).max()))
        done.append(i)
        rows.append(len(inbag))
    return worst == 0, ("trees %s: their bags' %s rows routed in numpy; max |recorded - routed| "
                        "leaf count %d" % (done, rows, worst))

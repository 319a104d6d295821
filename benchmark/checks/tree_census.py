"""Every tree has at least ``min_leaves`` leaves (and no more than the
configured ``num_leaves``) and its recorded leaf counts sum to N (from the
model text alone)."""


def run(args, c):
    most = int(c["params"]["num_leaves"])
    least = min(most, int(args["min_leaves"]))
    leaves = [t["num_leaves"] for t in c["trees"]]
    sums_ok = all(int(t["leaf_count"].sum()) == c["rows"] for t in c["trees"])
    ok = bool(leaves) and least <= min(leaves) and max(leaves) <= most and sums_ok
    return ok, "%d trees, leaves %s..%s (want %d..%d), every leaf_count sum == %d: %s" % (
        len(leaves), min(leaves, default=None), max(leaves, default=None),
        least, most, c["rows"], sums_ok)

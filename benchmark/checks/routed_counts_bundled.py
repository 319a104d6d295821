"""Recorded leaf counts (which the program takes from histograms over the
PARTITIONED bundled rows) against two plain walks of the finished tree that
never see the partition: over the reference's own bundled matrix, exactly
equal (the table the program trained on, a conflict row holding what the
stated rule leaves it); and over the raw CSC columns, where only a conflict
row can land elsewhere, so no leaf may differ by more than their number."""
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def run(args, c):
    from reference import efb
    gm = efb.group_map_of(c["binned"])
    bundled, conflict_rows = efb.bundle_of(c["X"], gm)
    Xc = efb.csc_of(c["X"])
    which = {"first": 0, "last": len(c["trees"]) - 1}
    trees = [c["trees"][which[name]] for name in args["trees"]]

    def counts(job):
        t, raw = job
        leaf = efb.walk_raw(t, Xc) if raw else efb.walk_bundled(t, bundled, gm)
        return int(np.abs(efb.leaf_counts(leaf, t) - t["leaf_count"]).max())

    # the walks are independent and numpy's gathers release the GIL
    with ThreadPoolExecutor(4) as pool:
        worst = list(pool.map(counts, [(t, raw) for t in trees for raw in (False, True)]))
    worst_b, worst_r = max(worst[0::2]), max(worst[1::2])
    done = [which[name] for name in args["trees"]]
    ok = worst_b == 0 and worst_r <= len(conflict_rows)
    return ok, ("trees %s: all %d rows walked; max |recorded - walked| leaf count: bundled matrix %d "
                "(must be 0), raw columns %d (at most the %d conflict rows)" % (
                    done, c["rows"], worst_b, worst_r, len(conflict_rows)))
